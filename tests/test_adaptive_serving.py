"""Per-query adaptive-beam serving engine + regression tests for the fixes
that shipped with it (online-LID recording, disk-model queue depth, per-shard
entry points)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build, distance, mapping, online, search
from repro.distributed import sharded_search as ss
from repro.index import build_tiered_index
from repro.index.disk import DiskTierModel, search_tiered_adaptive

CFG = build.BuildConfig(degree=24, beam_width=48, iters=2, batch=256,
                        max_hops=96)


@pytest.fixture(scope="module")
def built(tiny_dataset):
    x, q = tiny_dataset
    gt_d, gt_i = distance.brute_force_topk(q, x, k=10)
    idx = build.build_mcgi(x, CFG)
    return x, q, gt_i, idx


# ------------------------------------------------------- adaptive beam engine

def test_budgets_monotone_in_query_lid(built):
    """Prop. 4.2 in the engine: harder queries (higher LID) get larger beam
    budgets; the law's bounds are respected."""
    x, q, _, idx = built
    cfg = search.AdaptiveBeamBudget(l_min=8, l_max=48, lam=0.3)
    _, _, _, astats = search.beam_search_exact_adaptive(
        x, idx.adj, q, idx.entry, cfg, k=10)
    lid = np.asarray(astats.q_lid)
    budget = np.asarray(astats.budget)
    assert (budget >= 8).all() and (budget <= 48).all()
    order = np.argsort(lid)
    assert (np.diff(budget[order]) >= 0).all()
    # Adaptivity actually happened: the batch isn't all one budget.
    assert budget.min() < budget.max()


def test_adaptive_matches_fixed_recall_at_equal_mean_budget(built):
    """Iso-recall: adaptive at mean budget ~L matches fixed-L recall - eps on
    tiny-mixture."""
    x, q, gt_i, idx = built
    cfg = search.AdaptiveBeamBudget(l_min=8, l_max=48, lam=0.3)
    ids_a, _, stats_a, astats = search.beam_search_exact_adaptive(
        x, idx.adj, q, idx.entry, cfg, k=10)
    r_adapt = float(distance.recall_at_k(ids_a, gt_i))

    mean_budget = int(round(float(astats.budget.mean())))
    ids_f, _, stats_f = search.beam_search_exact(
        x, idx.adj, q, idx.entry, beam_width=mean_budget,
        max_hops=4 * mean_budget, k=10)
    r_fixed = float(distance.recall_at_k(ids_f, gt_i))
    assert r_adapt >= r_fixed - 0.05, (r_adapt, r_fixed, mean_budget)


def test_adaptive_retires_easy_queries_early(built):
    """Per-query early exit: hop counts vary with the granted budget, and
    small-budget queries pay fewer hops than the fixed-l_max baseline."""
    x, q, _, idx = built
    cfg = search.AdaptiveBeamBudget(l_min=8, l_max=48, lam=0.3)
    _, _, stats_a, astats = search.beam_search_exact_adaptive(
        x, idx.adj, q, idx.entry, cfg, k=10)
    _, _, stats_f = search.beam_search_exact(
        x, idx.adj, q, idx.entry, beam_width=48, max_hops=192, k=10)
    assert float(stats_a.hops.mean()) < float(stats_f.hops.mean())
    hops = np.asarray(stats_a.hops)
    budget = np.asarray(astats.budget)
    lo, hi = budget <= np.median(budget), budget > np.median(budget)
    if lo.any() and hi.any():
        assert hops[lo].mean() <= hops[hi].mean()


def test_adaptive_tiered_path(built):
    """The deployed two-tier path: PQ-routed adaptive walk + slow-tier
    rerank returns sane results and diagnostics."""
    x, q, gt_i, idx = built
    tiered = build_tiered_index(x, idx, m_pq=8)
    cfg = search.AdaptiveBeamBudget(l_min=8, l_max=48, lam=0.3)
    ids, d2, stats, astats = search_tiered_adaptive(tiered, q, cfg, k=10)
    r = float(distance.recall_at_k(ids, gt_i))
    assert r >= 0.85, r
    assert astats.budget.shape == (q.shape[0],)
    assert (np.asarray(d2)[:, :-1] <= np.asarray(d2)[:, 1:] + 1e-6).all()


# ------------------------------------------------------------- satellite fixes

def test_online_mcgi_records_lid(tiny_dataset):
    """build_online_mcgi returns the per-node online-LID estimates its alphas
    were computed from (regression: it used to return zeros)."""
    x, _ = tiny_dataset
    x = x[:1000]
    idx = online.build_online_mcgi(
        x, dataclasses.replace(CFG, iters=1), sample=256)
    lid = np.asarray(idx.lid)
    assert float(lid.std()) > 1e-3  # non-constant
    # Consistent with the returned alphas: alpha == Phi(lid) exactly.
    expect = np.asarray(mapping.phi(idx.lid, idx.mu, idx.sigma))
    np.testing.assert_allclose(np.asarray(idx.alpha), expect, atol=1e-5)


def test_disk_model_queue_depth():
    """Rerank batch is issued queue_depth-parallel (regression: queue_depth
    was ignored)."""
    m = DiskTierModel(read_latency_us=100.0, queue_depth=8)
    # 10 serial reads + ceil(48/8)=6 rounds of rerank.
    lat = float(m.latency_us(jnp.float32(10), rerank_reads=48))
    assert lat == pytest.approx((10 + 6) * 100.0)
    # Deeper queue, fewer rounds — strictly faster for the same work.
    deeper = DiskTierModel(read_latency_us=100.0, queue_depth=16)
    assert float(deeper.latency_us(jnp.float32(10), rerank_reads=48)) < lat
    # No rerank term when there is no rerank batch.
    assert float(m.latency_us(jnp.float32(10))) == pytest.approx(1000.0)


def test_disk_model_overlapped_pipeline_mode():
    """overlapped=True models the double-buffered engine: per-batch cost is
    max(dependent chain, rerank rounds), not their sum — and degrades to the
    serial model when either stage is absent."""
    m = DiskTierModel(read_latency_us=100.0, queue_depth=8)
    # Chain 10 reads (1000us) vs 48-read rerank = 6 rounds (600us): the
    # rerank hides behind the next batch's chain entirely.
    assert float(m.latency_us(jnp.float32(10), rerank_reads=48,
                              overlapped=True)) == pytest.approx(1000.0)
    # Rerank-bound regime: 2-read chain (200us) under a 600us rerank.
    assert float(m.latency_us(jnp.float32(2), rerank_reads=48,
                              overlapped=True)) == pytest.approx(600.0)
    # Overlap never exceeds the serial model, and equals it with no rerank.
    serial = float(m.latency_us(jnp.float32(10), rerank_reads=48))
    assert float(m.latency_us(jnp.float32(10), rerank_reads=48,
                              overlapped=True)) < serial
    assert float(m.latency_us(jnp.float32(10), overlapped=True)) == \
        pytest.approx(float(m.latency_us(jnp.float32(10))))


def test_local_search_uses_given_entry():
    """_local_search starts at the supplied per-shard entry (regression: it
    hardcoded local row 0). A disconnected graph makes the entry decisive:
    with no out-edges the walk can only ever see its entry point."""
    n, d = 16, 4
    x = jnp.asarray(np.random.default_rng(0).normal(size=(n, d)), jnp.float32)
    adj = jnp.full((n, 4), -1, jnp.int32)  # no edges at all
    q = x[:4]
    for entry in (0, 7):
        d2, ids = ss._local_search(
            adj, None, x, None, q, jnp.int32(entry),
            beam_width=4, max_hops=8, k=1, query_chunk=4, use_pq=False)
        assert (np.asarray(ids) == entry).all()


def test_shard_medoids_matches_per_block_medoid():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(64, 8)), jnp.float32)
    ents = ss.shard_medoids(x, 4)
    assert ents.shape == (4,)
    for s in range(4):
        block = x[s * 16:(s + 1) * 16]
        assert int(ents[s]) == int(search.medoid(block))


def test_shard_entry_points_cover_every_cluster():
    """Each shard's entry candidates (sqrt(rows) of them, its medoid first)
    put one in every tight cluster of the shard, whatever the seed: the
    k-means++ seeding reaches a far cluster that uniformly drawn initial
    rows would often miss."""
    rng = np.random.default_rng(5)
    n_shards, clusters, size, d = 4, 16, 25, 8
    shards, labels = [], []
    for _ in range(n_shards):
        centers = rng.normal(size=(clusters, d)) * 50.0
        lab = np.repeat(np.arange(clusters), size)
        shards.append(centers[lab] + rng.normal(size=(lab.size, d)) * 0.1)
        labels.append(lab)
    x = jnp.asarray(np.concatenate(shards), jnp.float32)
    for seed in (0, 1, 2):
        ents = np.asarray(ss.shard_entry_points(x, n_shards, seed=seed))
        assert ents.shape == (n_shards, ss.entry_count(clusters * size))
        assert ents.shape[1] == 20
        np.testing.assert_array_equal(ents[:, 0],
                                      np.asarray(ss.shard_medoids(x, 4)))
        for s in range(n_shards):
            assert set(labels[s][ents[s, 1:]]) == set(range(clusters))
