"""End-to-end index quality: MCGI vs Vamana vs Online-MCGI, recall + I/O."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build, distance, online, search
from repro.core.ivf import build_ivf, search_ivf
from repro.core.hnsw import build_hnsw, search_hnsw

CFG = build.BuildConfig(degree=24, beam_width=48, iters=2, batch=256,
                        max_hops=96)


@pytest.fixture(scope="module")
def built(tiny_dataset):
    x, q = tiny_dataset
    gt_d, gt_i = distance.brute_force_topk(q, x, k=10)
    idx = build.build_mcgi(x, CFG)
    return x, q, gt_i, idx


def test_mcgi_recall(built):
    x, q, gt_i, idx = built
    ids, _, stats = search.beam_search_exact(
        x, idx.adj, q, idx.entry, beam_width=48, k=10
    )
    r = float(distance.recall_at_k(ids, gt_i))
    assert r >= 0.95, r
    assert float(stats.hops.mean()) < 96


def test_alpha_tracks_lid(built):
    """The paper's core mechanism: high-LID nodes get small alpha."""
    _, _, _, idx = built
    lid = np.asarray(idx.lid)
    alpha = np.asarray(idx.alpha)
    corr = np.corrcoef(lid, alpha)[0, 1]
    assert corr < -0.9, corr  # logistic of z-score: strongly anti-monotone
    assert alpha.min() >= 1.0 and alpha.max() <= 1.5


def test_recall_increases_with_beam(built):
    """Fig. 2b trend: recall(L) monotone-ish in L."""
    x, q, gt_i, idx = built
    recalls = []
    for L in (8, 24, 64):
        ids, _, _ = search.beam_search_exact(
            x, idx.adj, q, idx.entry, beam_width=L, k=10
        )
        recalls.append(float(distance.recall_at_k(ids, gt_i)))
    assert recalls[0] <= recalls[1] + 0.02
    assert recalls[1] <= recalls[2] + 0.02
    assert recalls[-1] > 0.9


def test_vamana_baseline_recall(tiny_dataset):
    x, q = tiny_dataset
    gt_d, gt_i = distance.brute_force_topk(q, x, k=10)
    idx = build.build_vamana(x, alpha=1.2, cfg=CFG)
    ids, _, _ = search.beam_search_exact(
        x, idx.adj, q, idx.entry, beam_width=48, k=10
    )
    assert float(distance.recall_at_k(ids, gt_i)) >= 0.9
    assert float(idx.alpha[0]) == pytest.approx(1.2)


def test_online_mcgi_recall(tiny_dataset):
    x, q = tiny_dataset
    x = x[:1000]
    gt_d, gt_i = distance.brute_force_topk(q, x, k=10)
    idx = online.build_online_mcgi(
        x, dataclasses.replace(CFG, iters=2), sample=256
    )
    ids, _, _ = search.beam_search_exact(
        x, idx.adj, q, idx.entry, beam_width=48, k=10
    )
    assert float(distance.recall_at_k(ids, gt_i)) >= 0.9
    # Online alpha must actually vary across nodes (adaptivity happened).
    assert float(jnp.std(idx.alpha)) > 1e-3


def test_ivf_baseline(tiny_dataset):
    x, q = tiny_dataset
    gt_d, gt_i = distance.brute_force_topk(q, x, k=10)
    idx = build_ivf(x, nlist=32, iters=5)
    ids, _, scanned = search_ivf(idx, x, q, nprobe=8, k=10)
    r = float(distance.recall_at_k(ids, gt_i))
    assert r >= 0.9, r
    assert float(scanned.mean()) < x.shape[0]  # sub-linear scan


def test_hnsw_baseline(tiny_dataset):
    x, q = tiny_dataset
    x, q = x[:800], q[:20]
    gt_d, gt_i = distance.brute_force_topk(q, x, k=10)
    idx = build_hnsw(x, m=12, ef_construction=64)
    ids, _, _ = search_hnsw(idx, x, q, ef=48, k=10)
    r = float(distance.recall_at_k(ids, gt_i))
    assert r >= 0.9, r


def test_search_stats_io_accounting(built):
    """Hops == slow-tier reads: bounded by max_hops, > 0, and dist_evals
    <= hops * degree."""
    x, q, _, idx = built
    _, _, stats = search.beam_search_exact(
        x, idx.adj, q, idx.entry, beam_width=16, max_hops=50, k=10
    )
    hops = np.asarray(stats.hops)
    evals = np.asarray(stats.dist_evals)
    assert (hops > 0).all() and (hops <= 50).all()
    assert (evals <= hops * idx.degree_cap).all()


@pytest.mark.parametrize("seed,cap", [(0, 4), (1, 16)])
def test_reverse_pairs_device_matches_host(seed, cap):
    """The build step's on-device reverse-edge grouping yields the host
    grouping's destinations (ascending) and candidate rows, in order, with
    duplicates, INVALID slots and over-cap groups in the input."""
    rng = np.random.default_rng(seed)
    n, b, r = 50, 16, 8
    node_ids = rng.permutation(n)[:b].astype(np.int32)
    rows = rng.integers(0, n, (b, r)).astype(np.int32)
    rows[rng.random((b, r)) < 0.25] = build.INVALID
    want_d, want_c = build._reverse_pairs(node_ids, rows, cap)
    dest, cand, count = build._reverse_pairs_device(
        jnp.asarray(node_ids), jnp.asarray(rows), cap, n)
    count = int(count)
    assert count == want_d.size
    np.testing.assert_array_equal(np.asarray(dest)[:count], want_d)
    np.testing.assert_array_equal(np.asarray(cand)[:count], want_c)
    assert (np.asarray(cand)[count:] == build.INVALID).all()
