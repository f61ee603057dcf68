"""Pallas kernels vs jnp oracles — interpret-mode shape/dtype sweeps."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.beam_step import beam_step
from repro.kernels.decode_attention import decode_attention
from repro.kernels.l2_distance import l2_distance
from repro.kernels.lid_kernel import lid_estimate
from repro.kernels.pq_scan import pq_scan
from repro.kernels.topk import topk


@pytest.mark.parametrize("q_n,x_n,d", [(8, 64, 32), (130, 300, 96), (1, 129, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_l2_distance_sweep(q_n, x_n, d, dtype):
    key = jax.random.PRNGKey(q_n + x_n + d)
    q = jax.random.normal(key, (q_n, d), dtype)
    x = jax.random.normal(jax.random.fold_in(key, 1), (x_n, d), dtype)
    out = l2_distance(q, x, interpret=True)
    expect = ref.l2_distance_ref(q, x)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("n,m,k,q", [(200, 8, 16, 2), (513, 16, 256, 3), (64, 4, 64, 1)])
def test_pq_scan_sweep(n, m, k, q):
    key = jax.random.PRNGKey(n)
    codes = jax.random.randint(key, (n, m), 0, k).astype(jnp.uint8)
    luts = jax.random.uniform(jax.random.fold_in(key, 1), (q, m, k))
    out = pq_scan(luts, codes, interpret=True)
    expect = jax.vmap(lambda l: ref.pq_scan_ref(l, codes))(luts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,k,q", [(1500, 10, 4), (5000, 32, 2), (1000, 1, 1)])
def test_topk_sweep(n, k, q):
    key = jax.random.PRNGKey(k)
    d = jax.random.uniform(key, (q, n))
    vals, ids = topk(d, k, interpret=True)
    evals, eids = ref.topk_ref(d, k)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(evals), rtol=1e-6)
    assert (np.asarray(ids) == np.asarray(eids)).all()


@pytest.mark.parametrize("b,k", [(100, 8), (700, 16), (512, 32)])
def test_lid_kernel_sweep(b, k):
    key = jax.random.PRNGKey(b)
    d2 = jnp.sort(jax.random.uniform(key, (b, k)) + 0.01, axis=1)
    out = lid_estimate(d2, interpret=True)
    expect = ref.lid_ref(d2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-4)


@pytest.mark.parametrize("b,hq,hkv,s,d", [(2, 8, 2, 700, 64), (1, 4, 4, 512, 32),
                                          (3, 6, 1, 130, 16)])
def test_decode_attention_sweep(b, hq, hkv, s, d):
    key = jax.random.PRNGKey(s)
    q = jax.random.normal(key, (b, hq, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d))
    lens = jax.random.randint(jax.random.fold_in(key, 3), (b,), 1, s + 1)
    out = decode_attention(q, k, v, lens, interpret=True)
    g = hq // hkv
    expect = ref.decode_attention_ref(
        q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2), lens
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=3e-4, atol=3e-4)


def test_ops_dispatch_cpu_fallback():
    """On CPU the ops layer must route to the oracle and stay numerically
    identical to it."""
    from repro.kernels import ops

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (4, 16))
    x = jax.random.normal(jax.random.fold_in(key, 1), (32, 16))
    np.testing.assert_allclose(
        np.asarray(ops.bulk_l2(q, x)), np.asarray(ref.l2_distance_ref(q, x)),
        rtol=1e-6,
    )


def _walk_problem(kind, n, r, beam, q, seed):
    """A random fused-walk problem: dup-free adjacency, per-query entry
    seeded in beam slot 0 (visited bit set), ragged budgets/hop limits."""
    rng = np.random.default_rng(seed)
    adj = jnp.asarray(np.stack(
        [rng.choice(n, size=r, replace=False) for _ in range(n)]
    ).astype(np.int32))
    if kind == "pq":
        m, k = 8, 16
        table = jnp.asarray(rng.integers(0, k, (n, m)).astype(np.uint8))
        ctxs = jnp.asarray(rng.random((q, m, k), dtype=np.float32))
        d0 = np.asarray(ctxs)[
            np.arange(q)[:, None], np.arange(m), np.asarray(table)[:q].astype(int)
        ].sum(axis=1)
    else:
        d = 24
        table = jnp.asarray(rng.standard_normal((n, d), dtype=np.float32))
        ctxs = jnp.asarray(rng.standard_normal((q, d), dtype=np.float32))
        d0 = ((np.asarray(table)[:q] - np.asarray(ctxs)) ** 2).sum(axis=1)
    entries = np.arange(q, dtype=np.int32)       # query i enters at node i
    beam_ids = np.full((q, beam), -1, np.int32)
    beam_d = np.full((q, beam), np.inf, np.float32)
    beam_ids[:, 0], beam_d[:, 0] = entries, d0
    visited = np.zeros((q, (n + 31) // 32), np.uint32)
    visited[np.arange(q), entries // 32] = np.uint32(1) << (entries % 32)
    state = (jnp.asarray(beam_ids), jnp.asarray(beam_d),
             jnp.zeros((q, beam), bool), jnp.asarray(visited),
             jnp.zeros((q,), jnp.int32), jnp.ones((q,), jnp.int32))
    budgets = jnp.asarray(
        rng.integers(max(2, beam // 2), beam + 1, q).astype(np.int32))
    hop_limits = jnp.asarray(rng.integers(2, 7, q).astype(np.int32))
    return state, ctxs, adj, table, budgets, hop_limits


@pytest.mark.parametrize("kind", ["exact", "pq"])
@pytest.mark.parametrize("n,r,beam,q", [(200, 8, 16, 3), (64, 4, 8, 1),
                                        (130, 6, 12, 2)])
def test_beam_step_sweep(kind, n, r, beam, q):
    """Multi-hop fused walk (interpret) vs the jitted oracle, bit-identical
    at every hop — ids, distances, visited words, hop/eval counters.  The
    oracle is jitted so both sides share XLA's reduction order; that is the
    same discipline the step-kernel layer relies on for engine parity."""
    st_k, ctxs, adj, table, budgets, hop_limits = _walk_problem(
        kind, n, r, beam, q, seed=n + beam)
    st_r = st_k
    step_r = jax.jit(functools.partial(ref.beam_step_ref, kind=kind))
    for _ in range(6):
        st_k = beam_step(st_k, ctxs, adj, table, budgets, hop_limits,
                         kind=kind, interpret=True)
        st_r = step_r(st_r, ctxs, adj, table, budgets, hop_limits)
        for got, want in zip(st_k, st_r):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # hop_limit <= 6 for every lane, so every lane is terminal (limit hit
    # or frontier exhausted): one more step must be the identity.
    assert (np.asarray(st_k[4]) <= np.asarray(hop_limits)).all()
    st_fix = beam_step(st_k, ctxs, adj, table, budgets, hop_limits,
                       kind=kind, interpret=True)
    for got, want in zip(st_fix, st_k):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_beam_step_respects_budget():
    """The per-lane budget gates frontier selection: budget=1 is the greedy
    walk, it diverges from the full-beam walk on the same problem, and it
    stays bit-identical to the jitted oracle at the same budget."""
    st0, ctxs, adj, table, _, _ = _walk_problem("exact", 200, 8, 16, 4, seed=7)
    hop_limits = jnp.full((4,), jnp.int32(6))
    step_r = jax.jit(functools.partial(ref.beam_step_ref, kind="exact"))
    runs = {}
    for b in (1, 16):
        budgets = jnp.full((4,), jnp.int32(b))
        st = st0
        for _ in range(6):
            st = beam_step(st, ctxs, adj, table, budgets, hop_limits,
                           kind="exact", interpret=True)
        runs[b] = st
        want = st0
        for _ in range(6):
            want = step_r(want, ctxs, adj, table, budgets, hop_limits)
        for got, exp in zip(st, want):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    assert not np.array_equal(np.asarray(runs[1][1]), np.asarray(runs[16][1]))


def test_resolve_impl_policy(monkeypatch):
    """interpret-env > TPU > oracle — and the env var must win *on* TPU."""
    from repro.kernels import ops

    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops.resolve_impl() == "ref"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.resolve_impl() == "pallas"
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert ops.resolve_impl() == "interpret"      # env wins over TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops.resolve_impl() == "interpret"
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ops.resolve_impl() == "ref"            # "0" is not opted in


def test_ops_beam_step_request_routing(monkeypatch):
    """The fused walk the step-kernel layer requests is never the oracle:
    compiled on TPU, interpret mode on the CPU and under the env switch."""
    from repro.kernels import ops

    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    calls = []
    monkeypatch.setattr(
        ops._beam, "beam_walk",
        lambda *a, **kw: calls.append(("kernel", kw["interpret"])))
    monkeypatch.setattr(
        ops._ref, "beam_step_ref", lambda *a, **kw: calls.append(("oracle",)))
    args = (None,) * 6
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    ops.beam_walk(*args, kind="exact")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ops.beam_walk(*args, kind="exact")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    ops.beam_walk(*args, kind="exact")
    assert calls == [("kernel", True), ("kernel", False), ("kernel", True)]
