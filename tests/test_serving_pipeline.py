"""Engine-specific tests that are not cross-backend parity properties: the
auto-picked bucket family (a pure function of the granted-budget histogram),
the compiled lane gather of a bucket (bit-identical to eager ``a[sel]``,
its lane family compiled on the first bucketed batch) and the live
recalibration hook.  The pipelining / bucketing / permutation
identity properties formerly here are consolidated in
``tests/test_engine_parity.py`` (shared fixtures:
``tests/_backend_fixtures.py``), where every backend — including the staged
distributed path — is pinned to them together."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serving
from repro.core import distance
from repro.core import search as search_mod
from repro.serving import pipeline as pipe
from tests._backend_fixtures import BUDGET, built
from tests._hypothesis_compat import given, settings, st


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 96))
def test_auto_bucket_ceilings_deterministic(seed, n):
    """The auto-picked family is a pure function of the budget histogram:
    deterministic, permutation-invariant, covering, and tight (every ceiling
    is an occupied budget value)."""
    rng = np.random.default_rng(seed)
    budgets = rng.integers(BUDGET.l_min, BUDGET.l_max + 1, size=n)
    cs = pipe.auto_bucket_ceilings(budgets, BUDGET)
    assert cs == pipe.auto_bucket_ceilings(budgets, BUDGET)
    perm = rng.permutation(n)
    assert cs == pipe.auto_bucket_ceilings(budgets[perm], BUDGET)
    assert list(cs) == sorted(set(cs))           # ascending, distinct
    assert cs[-1] == budgets.max()               # covers every budget
    occupied = set(np.unique(budgets).tolist())
    assert all(int(c) in occupied for c in cs)   # tight ceilings
    # Partition covers each query exactly once, inside its ceiling.
    parts = pipe.partition_by_bucket(budgets, cs)
    seen = np.concatenate([m for _, m, _ in parts])
    assert sorted(seen.tolist()) == list(range(n))
    for bi, members, _ in parts:
        assert (budgets[members] <= cs[bi]).all()


def test_auto_bucket_single_value_degenerates_to_one_bucket():
    """lam=0-style batches (every budget equal) must not be split: the
    launch cost makes one bucket optimal."""
    budgets = np.full(32, 24)
    assert pipe.auto_bucket_ceilings(budgets, BUDGET) == (24,)


def test_recalibrate_updates_live_engine():
    """The recalibration hook refits the budget law in place (lam moves, the
    engine object and backend survive), and the joint variant fits l_min
    too — the Online-MCGI refresh path."""
    x, q, gt_i, idx, _ = built()
    eng = serving.SearchEngine(
        serving.ExactBackend(x, idx.adj, idx.entry),
        dataclasses.replace(BUDGET, center=None), k=10)
    backend = eng.backend
    result = eng.recalibrate(q, gt_i, recall_target=0.9, sample=32)
    assert eng.backend is backend            # engine not rebuilt
    assert eng.budget_cfg.lam == result.lam  # fitted knob is live
    res = eng.search(q)
    assert float(distance.recall_at_k(jnp.asarray(res.ids), gt_i)) > 0.5

    joint = eng.recalibrate(q, gt_i, recall_target=0.9, joint=True,
                            sample=32)
    assert joint.l_min is not None
    assert eng.budget_cfg.l_min == joint.l_min
    assert eng.budget_cfg.lam == joint.lam


def test_recalibrate_rejected_for_distributed_engines():
    """A staged distributed engine must not recalibrate in place: swapping
    budget_cfg would desync it from the backend's compiled beam_budget and
    brick every later search on the probe consistency check. The hook
    rejects cleanly and points at the per-shard pass."""
    import pytest

    class FakeDistributed:
        staged = True
        beam_budget = BUDGET

    eng = serving.SearchEngine(FakeDistributed(), BUDGET, k=10)
    with pytest.raises(NotImplementedError, match="per shard"):
        eng.recalibrate(eval_recall=lambda cfg: 1.0)


def _probed(kind: str):
    """(backend, probe_state, ctxs, budgets, hop_limits) of the fixture
    queries on the exact or the PQ (tiered, no rerank) backend; with
    ``exact-host`` the queries stay a host array, as the core
    ``num_buckets=`` entry points pass them."""
    x, q, _, idx, tiered = built()
    backend = (serving.TieredBackend(tiered, rerank=False) if kind == "pq"
               else serving.ExactBackend(x, idx.adj, idx.entry))
    ctxs = q if kind == "exact-host" else backend.admit(q)
    state, budgets, hop_limits, _ = backend.probe(ctxs, BUDGET)
    return backend, state, ctxs, budgets, hop_limits


def _eager_gather(state, ctxs, budgets, hop_limits, padded):
    """The eager per-leaf gather the compiled one replaces (the pinned
    reference)."""
    sel = jnp.asarray(padded)
    return (jax.tree_util.tree_map(lambda a: a[sel], state), ctxs[sel],
            budgets[sel], hop_limits[sel])


def _eager_bucketed_continue(cont, state, ctxs, budgets, hop_limits,
                             ceilings):
    """Pinned eager reference of :func:`pipe.bucketed_continue`."""
    q = ctxs.shape[0]
    out = None
    for _bi, members, padded in pipe.partition_by_bucket(
            np.asarray(budgets), ceilings):
        handles = cont(*_eager_gather(state, ctxs, budgets, hop_limits,
                                      padded))
        if out is None:
            out = tuple(np.empty((q,) + h.shape[1:], np.dtype(h.dtype))
                        for h in handles)
        for buf, h in zip(out, handles):
            buf[members] = np.asarray(h)[:members.size]
    return out


@pytest.mark.parametrize("kind", ["exact", "pq", "exact-host"])
@pytest.mark.parametrize("lanes", ["bucket", "padded", "zero"])
def test_lane_gather_bit_identical_to_eager(kind, lanes):
    """The compiled bucket gather returns, leaf for leaf, what eager
    ``a[sel]`` returns: same values, shapes, dtypes, weak types, shardings
    and commitment (a host array for host queries) — for a whole bucket, a
    padded one (a member row repeated) and the zero-lane bucket."""
    _, state, ctxs, budgets, hop_limits = _probed(kind)
    padded = {"bucket": np.array([1, 4, 5, 9, 17, 22, 30, 31]),
              "padded": np.array([3, 7, 11, 12, 26, 3, 3, 3]),
              "zero": np.empty((0,), np.int64)}[lanes]
    got = pipe.gather_lanes(state, ctxs, budgets, hop_limits, padded)
    want = _eager_gather(state, ctxs, budgets, hop_limits, padded)
    got_l, got_t = jax.tree_util.tree_flatten(got)
    want_l, want_t = jax.tree_util.tree_flatten(want)
    assert got_t == want_t
    assert len(got_l) == 9        # six probe-state leaves + three inputs
    for g, w in zip(got_l, want_l):
        assert type(g) is type(w)
        assert g.shape == w.shape == (padded.size,) + g.shape[1:]
        assert g.dtype == w.dtype
        if isinstance(w, jax.Array):
            assert g.weak_type == w.weak_type
            assert g.sharding == w.sharding and g.committed == w.committed
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("kind", ["exact", "pq"])
def test_bucketed_continue_matches_pinned_eager_reference(kind):
    """``bucketed_continue`` over the compiled gather gives the eager
    gather's results bit for bit, and feeds the continue program inputs of
    the same signature: the continue's jit cache gains no entry."""
    backend, state, ctxs, budgets, hop_limits = _probed(kind)
    cont = backend.continue_fn(BUDGET)
    jitted = (search_mod._continue_exact_jit if kind == "exact"
              else search_mod._continue_pq_jit)
    ceilings = search_mod.budget_bucket_ceilings(BUDGET.l_min, BUDGET.l_max,
                                                 4)
    want = _eager_bucketed_continue(cont, state, ctxs, budgets, hop_limits,
                                    ceilings)
    compiled = jitted._cache_size()
    got = pipe.bucketed_continue(cont, state, ctxs, budgets, hop_limits,
                                 ceilings)
    assert jitted._cache_size() == compiled
    assert len(got) == len(want) == 4
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


def test_lane_gather_compiles_nothing_after_first_batch():
    """The first probe of a shape compiles the gather for every padded lane
    count; later batches of that size, with other bucket partitions,
    compile no gather program."""
    x, q, _, idx, _ = built()
    eng = serving.SearchEngine(serving.ExactBackend(x, idx.adj, idx.entry),
                               BUDGET, k=10, num_buckets=4)
    batch = 16
    first = eng.search(q[:batch])
    assert eng.backend.lane_gathers.warmed == batch // pipe.LANE_QUANTUM
    compiled = pipe._gather_lanes._cache_size()
    partitions = set()
    rng = np.random.default_rng(0)
    for _ in range(4):
        res = eng.search(q[rng.choice(q.shape[0], batch, replace=False)])
        partitions.add(tuple(
            p.size for _, _, p in pipe.partition_by_bucket(
                res.astats.budget, res.ceilings, eng.pad_quantum)))
    partitions.add(tuple(
        p.size for _, _, p in pipe.partition_by_bucket(
            first.astats.budget, first.ceilings, eng.pad_quantum)))
    assert len(partitions) > 1          # the partitions did differ
    assert pipe._gather_lanes._cache_size() == compiled
    assert eng.backend.lane_gathers.warmed == batch // pipe.LANE_QUANTUM
