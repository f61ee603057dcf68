"""The mapping function Phi: LID -> pruning parameter alpha (paper §3.2).

    z(u)   = (LID_hat(u) - mu) / sigma                       (Eq. 7)
    Phi(u) = alpha_min + (alpha_max - alpha_min) / (1 + e^z)  (Eq. 8)

Monotonicity (Prop. 3.5) and boundedness (Prop. 3.6) hold by construction and
are property-tested in ``tests/test_mapping.py``.

The same module also hosts the *routing-side* budget law of Prop. 4.2
(L(q) ∝ exp(lambda * LID(q))), which the paper derives but deliberately does
not deploy per-query (fixed L at serve time, §4.1); we expose it for the
beyond-paper adaptive-beam experiments.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

Array = jax.Array

# Defaults from the paper's evaluation (§3.2 / Table 2).
ALPHA_MIN = 1.0
ALPHA_MAX = 1.5


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AlphaMapping:
    """Frozen Phi parameters: population stats + operational range."""

    mu: Array
    sigma: Array
    alpha_min: float = dataclasses.field(metadata=dict(static=True), default=ALPHA_MIN)
    alpha_max: float = dataclasses.field(metadata=dict(static=True), default=ALPHA_MAX)

    def __call__(self, lid: Array) -> Array:
        return phi(lid, self.mu, self.sigma, self.alpha_min, self.alpha_max)


def phi(
    lid: Array,
    mu: Array,
    sigma: Array,
    alpha_min: float = ALPHA_MIN,
    alpha_max: float = ALPHA_MAX,
) -> Array:
    """Eq. 8. Vectorised over ``lid``.

    ``sigma`` is clamped away from zero: a dataset with no geometric variance
    degenerates to the constant mapping alpha = (alpha_min + alpha_max) / 2
    at z = 0, matching the paper's "average complexity" behaviour.
    """
    z = (lid - mu) / jnp.maximum(sigma, 1e-6)
    # Clip z for float safety; exp(±40) already saturates the logistic in f32.
    z = jnp.clip(z, -40.0, 40.0)
    return alpha_min + (alpha_max - alpha_min) / (1.0 + jnp.exp(z))


def constant_alpha(n: int, alpha: float) -> Array:
    """Static-alpha per-node array — the DiskANN/Vamana baseline (alpha=1.2
    conventionally).  MCGI with this mapping *is* Vamana, which is how the
    framework isolates the paper's contribution."""
    return jnp.full((n,), alpha, dtype=jnp.float32)


OUTLIER_MADS = 5.0


def budget_center(lid: Array) -> Array:
    """The batch's LID center for the budget law: the mean of the estimates
    within ``OUTLIER_MADS`` robust standard deviations (1.4826 * MAD) of
    the median.

    A probe beam that is still far from the query's cluster holds nearly
    equidistant candidates, and Eq. 5's MLE then reads an LID in the
    hundreds (or inf); a plain batch mean lets a few such estimates push
    every other query of the batch down to ``l_min``.  The center skips
    them, so it equals the plain mean on a batch without such estimates.
    Skipping rather than capping them at the band's edge (their share of
    the batch would still set the others' grants) or centering on the
    median (which shifts every grant of a skewed but clean batch) keeps
    the center where the batch's ordinary queries put it.
    """
    med = jnp.median(lid)
    scale = 1.4826 * jnp.median(jnp.abs(lid - med))
    keep = jnp.abs(lid - med) <= OUTLIER_MADS * scale
    return (jnp.sum(jnp.where(keep, lid, 0.0))
            / jnp.sum(keep).astype(lid.dtype))


def adaptive_beam_budget(
    lid: Array,
    lam: float | Array,
    l_min: int | Array,
    l_max: int,
    mu: Array | None = None,
) -> Array:
    """Prop. 4.2's iso-recall budget  L(q) = C * exp(lambda * LID(q)).

    Normalised so a query of the batch's typical complexity
    (:func:`budget_center`, unless ``mu`` pins the center) gets the
    geometric mean of [l_min, l_max]; clipped to the operational range.
    Integer-valued.

    ``lam`` and ``l_min`` may be traced scalars: the distributed serving path
    threads *per-shard* calibrated budget laws through as runtime arrays
    (shard geometry differs), so neither knob may be baked into the compiled
    program as a python constant. ``l_max`` stays static — it is the physical
    beam shape.

    This is the beyond-paper knob (the paper fixes L for SIMD alignment and
    compensates in the topology); on TPU a *grouped* adaptive beam is feasible
    because queries are batched — see ``repro/core/search.py`` early-exit.
    """
    center = budget_center(lid) if mu is None else mu
    l_mid = jnp.sqrt(jnp.asarray(l_min, jnp.float32)
                     * jnp.asarray(l_max, jnp.float32))
    budget = l_mid * jnp.exp(lam * (lid - center))
    return jnp.clip(jnp.round(budget), l_min, l_max).astype(jnp.int32)
