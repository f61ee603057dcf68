"""Compile the serving path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  Each test lowers one kernel (or
the fused walk program) at the SIFT1M smoke's widths — N = 1M, D = 128,
R = 64, beam 128, 64 queries, PQ M = 16 x K = 256 — or at GIST1M's D = 960
(R = 96), and checks that the compiled text carries the Mosaic kernel
(``tpu_custom_call``).  What the TPU compiler refuses — block tiling,
primitives Mosaic cannot lower, VMEM over-use — would otherwise only show on
the chip; interpret mode accepts all of it.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around these
compiles — an entry written for a described chip cannot be read back
without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N = 1_000_000          # SIFT1M / GIST1M base size
Q, L, K_TOP = 64, 128, 10
M_PQ, K_PQ = 16, 256
WIDTHS = {"sift1m": (128, 64), "gist1m": (960, 96)}   # (D, R)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    """jit + lower + compile for the described chip; returns HLO text."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _walk_state(spec, n, beam=L, q=Q):
    nw = (n + 31) // 32
    return (spec((q, beam), jnp.int32), spec((q, beam), jnp.float32),
            spec((q, beam), jnp.bool_), spec((q, nw), jnp.uint32),
            spec((q,), jnp.int32), spec((q,), jnp.int32))


def _spec(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_l2_distance_compiles(one_chip, width):
    from repro.kernels.l2_distance import l2_distance

    s, d = _spec(one_chip), WIDTHS[width][0]
    text = _compile(l2_distance, s((Q, d), jnp.float32), s((N, d), jnp.float32))
    assert "tpu_custom_call" in text


def test_lid_estimate_compiles(one_chip):
    from repro.kernels.lid_kernel import lid_estimate

    s = _spec(one_chip)
    assert "tpu_custom_call" in _compile(lid_estimate, s((N, 16), jnp.float32))


def test_pq_scan_compiles(one_chip):
    from repro.kernels.pq_scan import pq_scan

    s = _spec(one_chip)
    text = _compile(pq_scan, s((Q, M_PQ, K_PQ), jnp.float32),
                    s((N, M_PQ), jnp.uint8))
    assert "tpu_custom_call" in text


def test_topk_compiles(one_chip):
    from repro.kernels.topk import topk

    s = _spec(one_chip)
    text = _compile(lambda d: topk(d, K_TOP), s((Q, N), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kind,width", [("exact", "sift1m"),
                                        ("exact", "gist1m"),
                                        ("pq", "sift1m")])
def test_beam_step_compiles(one_chip, kind, width):
    from repro.kernels.beam_step import beam_step

    s = _spec(one_chip)
    d, r = WIDTHS[width]
    if kind == "exact":
        ctx, table = s((Q, d), jnp.float32), s((N, d), jnp.float32)
    else:
        ctx, table = (s((Q, M_PQ, K_PQ), jnp.float32),
                      s((N, M_PQ), jnp.uint8))
    text = _compile(lambda st, c, a, t, b, h: beam_step(st, c, a, t, b, h,
                                                         kind=kind),
                    _walk_state(s, N), ctx, s((N, r), jnp.int32), table,
                    s((Q,), jnp.int32), s((Q,), jnp.int32))
    assert "tpu_custom_call" in text


def test_fused_walk_program_compiles(one_chip, monkeypatch):
    """The batch-level while loop the engine's probe/continue programs run
    (``PallasBeamStep.run_batch`` over the PQ evaluator): the compiled
    program must hold the fused kernel, not the reference hop chain.  The
    dispatch policy asks ``jax.default_backend()``, which is the CPU here,
    so the test steers it to the chip's answer."""
    from repro.core import search
    from repro.kernels import ops

    monkeypatch.setattr(ops, "resolve_impl", lambda: "pallas")
    s = _spec(one_chip)
    d, r = WIDTHS["sift1m"]

    def walk(states, luts, adj, codes, budgets, hop_limits):
        return search.PALLAS_STEP.run_batch(
            states, luts, adj, search._pq_eval(codes), L, hop_limits,
            budgets)

    text = _compile(walk, _walk_state(s, N), s((Q, M_PQ, K_PQ), jnp.float32),
                    s((N, r), jnp.int32), s((N, M_PQ), jnp.uint8),
                    s((Q,), jnp.int32), s((Q,), jnp.int32))
    assert "tpu_custom_call" in text and "while" in text
