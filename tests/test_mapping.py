"""Mapping function Phi (Eq. 8) — Props 3.5/3.6 as executable properties."""
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core import mapping

finite_f = st.floats(
    min_value=-1e4, max_value=1e4, allow_nan=False, allow_infinity=False
)


@given(
    lid=st.lists(finite_f, min_size=2, max_size=32),
    mu=finite_f,
    sigma=st.floats(min_value=1e-3, max_value=1e3),
)
@settings(max_examples=200, deadline=None)
def test_boundedness(lid, mu, sigma):
    """Prop 3.6: alpha strictly inside (alpha_min, alpha_max) for finite LID."""
    a = mapping.phi(jnp.asarray(lid), jnp.float32(mu), jnp.float32(sigma))
    assert bool((a >= mapping.ALPHA_MIN).all())
    assert bool((a <= mapping.ALPHA_MAX).all())


@given(
    l1=finite_f, l2=finite_f, mu=finite_f,
    sigma=st.floats(min_value=1e-3, max_value=1e3),
)
@settings(max_examples=200, deadline=None)
def test_monotonicity(l1, l2, mu, sigma):
    """Prop 3.5: Phi strictly decreasing in LID (weakly under f32/clipping)."""
    lo, hi = min(l1, l2), max(l1, l2)
    a_lo = float(mapping.phi(jnp.float32(lo), jnp.float32(mu), jnp.float32(sigma)))
    a_hi = float(mapping.phi(jnp.float32(hi), jnp.float32(mu), jnp.float32(sigma)))
    assert a_hi <= a_lo + 1e-6


def test_midpoint():
    """z = 0 maps to the midpoint alpha ~= 1.25 (paper §3.2)."""
    a = float(mapping.phi(jnp.float32(5.0), jnp.float32(5.0), jnp.float32(1.0)))
    np.testing.assert_allclose(a, 1.25, atol=1e-6)


def test_constant_alpha_is_vamana():
    a = mapping.constant_alpha(10, 1.2)
    assert a.shape == (10,)
    np.testing.assert_allclose(float(a[0]), 1.2, rtol=1e-6)


@given(
    lam=st.floats(min_value=0.0, max_value=1.0),
    lids=st.lists(st.floats(min_value=0.5, max_value=64.0), min_size=2,
                  max_size=16),
)
@settings(max_examples=100, deadline=None)
def test_adaptive_budget_bounds_and_monotone(lam, lids):
    l = mapping.adaptive_beam_budget(jnp.asarray(lids), lam, 8, 128)
    assert bool((l >= 8).all()) and bool((l <= 128).all())
    order = np.argsort(np.asarray(lids))
    budgets = np.asarray(l)[order]
    assert (np.diff(budgets) >= 0).all()


def _probe_beams(rng, lids, width=32, degenerate=()):
    """Synthetic finished probe states: each ordinary query's candidate
    distances are those of a uniform ball of its dimension (Eq. 5 reads
    about ``lid`` back); a degenerate query's candidates are nearly
    equidistant, as when the probe beam is still far from the query's
    cluster."""
    ranks = np.arange(1, width + 1, dtype=np.float64) / width
    d = np.stack([ranks ** (2.0 / m) * (1 + 0.01 * rng.random(width))
                  for m in lids])
    for i in degenerate:
        d[i] = 100.0 * (1 + 1e-4 * rng.random(width))
    ids = np.tile(np.arange(width, dtype=np.int32), (len(lids), 1))
    return jnp.asarray(ids), jnp.asarray(d, jnp.float32)


def test_degenerate_probe_beams_do_not_move_the_budget_center():
    """A few queries whose probe beams hold nearly equidistant candidates
    read LIDs in the hundreds.  With a plain batch mean as the budget
    law's center they lift the center and push every other query of the
    batch toward ``l_min`` — how many such queries a batch holds then
    sets the work of the whole batch.  Six of 128 (under 5%) must move the
    center by under 0.05 and no other query's grant by more than one
    rounding step."""
    from repro.core import search

    rng = np.random.default_rng(0)
    lids = rng.uniform(10.0, 20.0, 128)
    bad = rng.choice(128, 6, replace=False)
    cfg = search.AdaptiveBeamBudget(l_min=8, l_max=128, lam=0.25)
    ok = np.setdiff1d(np.arange(128), bad)

    clean_ids, clean_d = _probe_beams(np.random.default_rng(1), lids)
    mixed_ids, mixed_d = _probe_beams(np.random.default_rng(1), lids,
                                      degenerate=bad)
    clean_b, _, clean_lid = search.grant_budgets((clean_ids[ok],
                                                  clean_d[ok]), cfg)
    mixed_b, _, mixed_lid = search.grant_budgets((mixed_ids, mixed_d), cfg)
    assert float(np.asarray(mixed_lid)[bad].min()) > 100.0
    center_clean = float(mapping.budget_center(clean_lid))
    center_mixed = float(mapping.budget_center(mixed_lid))
    assert abs(center_mixed - center_clean) < 0.05
    assert np.abs(np.asarray(mixed_b)[ok].astype(int)
                  - np.asarray(clean_b).astype(int)).max() <= 1
    # The plain mean fails the same batch.
    assert float(jnp.mean(mixed_lid)) - float(jnp.mean(clean_lid)) > 5.0


def test_budget_center_is_the_mean_without_outliers():
    """On a batch with no estimate beyond the outlier band the center is
    the plain batch mean, so a deployment without degenerate probe beams
    keeps its budgets."""
    lid = jnp.asarray(np.random.default_rng(3).uniform(12.0, 26.0, 256),
                      jnp.float32)
    assert float(mapping.budget_center(lid)) == float(jnp.mean(lid))
    np.testing.assert_array_equal(
        np.asarray(mapping.adaptive_beam_budget(lid, 0.25, 8, 128)),
        np.asarray(mapping.adaptive_beam_budget(lid, 0.25, 8, 128,
                                                mu=jnp.mean(lid))))
