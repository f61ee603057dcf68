"""Multi-device integration tests (8 virtual host devices, subprocess-
isolated so unit tests keep the default single-device backend)."""
import functools
import json
import pathlib
import subprocess
import sys

import pytest

WORKER = pathlib.Path(__file__).parent / "_distributed_worker.py"
SRC = str(pathlib.Path(__file__).parents[1] / "src")


def _run(scenario: str, *extra: str, timeout=2400) -> dict:
    env = {"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"}
    import os

    env.update({k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS",)})
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, str(WORKER), scenario, *extra],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_search_recall_and_hedging():
    r = _run("sharded_search")
    assert r["recall"] >= 0.85, r
    # Dropping 1 of 8 shards loses at most that shard's data fraction (plus
    # noise) and returns nothing from the dead shard.
    assert r["recall_dropped_shard"] >= r["recall"] - 0.2
    assert r["results_from_dead_shard"] == 0


def test_checkpoint_elastic_reshard(tmp_path):
    r = _run("checkpoint_reshard", str(tmp_path))
    assert r["identical"] and r["resharded"] and r["step"] == 5


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    r = _run("train_match")
    # f32 reduction order differs across the 8-way mesh; ~2e-3 absolute on a
    # ~6.25 loss is numerics, not a sharding bug.
    assert abs(r["loss_single"] - r["loss_mesh"]) < 5e-3, r


@pytest.mark.slow
def test_smoke_cells_lower_on_mesh():
    r = _run("cells_lower")
    assert all(r.values()), r


def test_moe_expert_parallel_matches_reference():
    r = _run("moe_ep")
    assert r["max_err"] < 1e-5, r
    assert r["aux_err"] < 1e-5, r


@pytest.mark.slow
def test_merge_modes_agree():
    r = _run("merge_modes")
    assert r["ids_match"] and r["d2_match"], r


@functools.lru_cache(maxsize=1)
def _staged():
    """One worker run shared by the staged-engine assertions below (the
    scenario builds 8 sub-graphs and compiles the mesh programs once)."""
    return _run("staged_engine")


@pytest.mark.slow
def test_staged_engine_parity():
    """Staged distributed serving at engine parity: the probe/continue
    split is bit-identical to the monolithic step, pipelining and
    coalescing are result-transparent (ragged tails included), scheduling
    is permutation-invariant, and identity per-shard laws are pure
    plumbing."""
    r = _staged()
    for key in ("staged_eq_mono_ids", "staged_eq_mono_d2",
                "pipelined_eq_eager", "permutation_invariant",
                "coalesce_count", "coalesce_identical",
                "identity_laws_bitwise", "zero_query_ok"):
        assert r[key], (key, r)


@pytest.mark.slow
def test_staged_bucket_gather_matches_eager():
    """The compiled lane gather of a bucket over the sharded probe state:
    the continue program receives what the eager per-leaf gather gave it
    (values, shapes, dtypes, weak types, shardings) and compiles nothing
    new for it."""
    r = _staged()
    for key in ("gather_eq_eager", "gather_no_continue_recompile"):
        assert r[key], (key, r)


@pytest.mark.slow
def test_front_door_over_distributed_backend():
    """The async front door serving the staged distributed backend under a
    virtual clock: served lanes bit-identical to direct dispatch, a shard
    lost between dispatches vanishes from later served results, and a
    wedged mesh dispatch degrades to timeout (no host probe view, so no
    partial support) with the open-lane bound shedding overload."""
    r = _run("front_door")
    assert not r["supports_partial"], r
    for key in ("served_ok", "bit_identical", "post_flip_ok",
                "post_flip_no_dead", "wedge_timeout_no_partials",
                "wedge_shed_at_bound", "wedge_all_futures_done"):
        assert r[key], (key, r)


@pytest.mark.slow
def test_staged_fault_injection_mid_stream():
    """set_shard_ok flipped between batches of a pipelined stream: later
    batches exclude the dead shard, recall loss is bounded by its data
    fraction, results stay best-so-far finite under the bucket hop
    deadlines, and the jit caches are pinned (no recompilation)."""
    r = _staged()
    for key in ("fault_no_dead_results", "fault_best_so_far_finite",
                "fault_recall_bounded", "fault_no_recompile"):
        assert r[key], (key, r)


@pytest.mark.slow
def test_sharded_serving_matches_brute_force():
    """The staged mesh walk served by ``SearchEngine`` on four devices
    against a plain float32 brute force over the whole set: every served
    distance is the true distance of its global id (shard * rows + local
    id), recall@10 meets its floor, and the per (query, shard) counters
    the continue program returns sum to the per-query totals.  Each shard
    holds sqrt(rows) entry candidates, its medoid first, and the staged walk
    from them equals the monolithic step's."""
    r = _run("sharded_serving")
    assert r["rows_per_shard"] == 512 and r["ids_in_range"], r
    assert r["d2_max_rel_err"] <= 1e-5, r
    assert r["recall_at_10"] >= 0.9, r
    assert r["counters_sum_to_totals"] and r["budget_in_range"], r
    assert r["entries_shape"] == [4, 22] and r["medoid_first"], r
    assert r["staged_eq_mono"], r
