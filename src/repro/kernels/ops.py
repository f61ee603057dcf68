"""Jit'd public wrappers for the Pallas kernels.

Dispatch policy (one shared :func:`resolve_impl`, used by every wrapper):

1. ``REPRO_PALLAS_INTERPRET=1`` -> ``"interpret"`` — the Pallas kernel body
   runs in interpret mode, bit-faithful to the compiled kernel, on *any*
   backend.  The env var wins everywhere, TPU included, so a suspect kernel
   can be pinned to interpret semantics in production triage.
2. TPU backend -> ``"pallas"`` — the kernel runs compiled.
3. otherwise -> ``"ref"`` — the jnp oracle in :mod:`repro.kernels.ref`
   (fast on CPU, same semantics).

Libraries call these wrappers only — never pallas_call directly — so the
integration point is uniform across hardware.  :func:`beam_walk` is the one
exception to rule 3: the step-kernel layer calls it only when the fused
kernel was asked for, so off-TPU it runs interpret mode (bit-identical to
the compiled kernel) instead of silently handing back the reference walk.
"""
from __future__ import annotations

import os

import jax

from repro.kernels import beam_step as _beam
from repro.kernels import decode_attention as _da
from repro.kernels import l2_distance as _l2
from repro.kernels import lid_kernel as _lid
from repro.kernels import pq_scan as _pq
from repro.kernels import ref as _ref
from repro.kernels import topk as _topk

Array = jax.Array


def resolve_impl() -> str:
    """Resolve the kernel implementation for this process.

    Returns ``"interpret"`` | ``"pallas"`` | ``"ref"``; precedence is
    interpret-env-var > TPU-compiled > oracle (the env var must win on TPU
    too — it is the triage/CI switch for running kernel bodies bit-faithfully
    without the hardware fast path).
    """
    if os.environ.get("REPRO_PALLAS_INTERPRET", "0") == "1":
        return "interpret"
    if jax.default_backend() == "tpu":
        return "pallas"
    return "ref"


def bulk_l2(q: Array, x: Array) -> Array:
    """(Q, D) x (N, D) -> (Q, N) squared L2 (MXU-tiled on TPU)."""
    impl = resolve_impl()
    if impl == "ref":
        return _ref.l2_distance_ref(q, x)
    return _l2.l2_distance(q, x, interpret=impl == "interpret")


def pq_bulk_scan(luts: Array, codes: Array) -> Array:
    """(Q, M, K) x (N, M) -> (Q, N) ADC distances (one-hot-MXU on TPU)."""
    impl = resolve_impl()
    if impl == "ref":
        return jax.vmap(lambda lut: _ref.pq_scan_ref(lut, codes))(luts)
    return _pq.pq_scan(luts, codes, interpret=impl == "interpret")


def topk(d: Array, k: int) -> tuple[Array, Array]:
    """(Q, N) -> ascending (vals, ids) (tile-select + merge on TPU)."""
    impl = resolve_impl()
    if impl == "ref":
        return _ref.topk_ref(d, k)
    return _topk.topk(d, k, interpret=impl == "interpret")


def lid_estimate(knn_d2: Array) -> Array:
    """(B, k) sorted squared k-NN dists -> (B,) Hill LID."""
    impl = resolve_impl()
    if impl == "ref":
        return _ref.lid_ref(knn_d2)
    return _lid.lid_estimate(knn_d2, interpret=impl == "interpret")


def decode_attention(q: Array, k: Array, v: Array, kv_len: Array) -> Array:
    """Flash-decoding attention; see :mod:`repro.kernels.decode_attention`.

    The non-TPU path uses the grouped-einsum reference (no KV expansion) so
    a sequence-sharded cache lowers to partial-softmax collectives, not a
    full cache all-gather."""
    impl = resolve_impl()
    if impl == "ref":
        return _ref.decode_attention_gqa_ref(q, k, v, kv_len)
    return _da.decode_attention(q, k, v, kv_len, interpret=impl == "interpret")


def beam_walk(states, ctxs: Array, adj: Array, table: Array, budgets: Array,
              hop_limits: Array, *, kind: str):
    """Run a batch of walk lanes to convergence, one fused hop per launch;
    see :func:`repro.kernels.beam_step.beam_walk`.

    Always the fused kernel: compiled on TPU, interpret mode elsewhere (the
    caller asked for the kernel by name, so the oracle is never substituted).
    """
    return _beam.beam_walk(states, ctxs, adj, table, budgets, hop_limits,
                           kind=kind, interpret=resolve_impl() != "pallas")
