"""Smoke run of the MCGI serving path on a TPU: the quickest proof that the
system still starts on the chip.

    python3 chip_smoke.py [--seed 0] [--n N]        # one chip
    python3 chip_smoke.py --chips 4 [--seed 0]      # the sharded path only

One chip, one process, at the ``mcgi-sift1m`` configuration (d = 128,
R = 64, L_build = 100, l_search = 128, adaptive law lam = 0.25, l_min = 8):
generate the ``sift1m-proxy`` data from ``--seed`` on the chip, build the
MCGI graph and the PQ fast tier there, then serve the same batches through
``SearchEngine`` over three backends of the one graph — (a) the exact
full-precision walk, (b) the PQ-steered walk with exact rerank, (c) (b) with
its slow tier read from a block store in a temporary directory — and about
200 requests through the ``FrontDoor`` over (b).  Every backend runs the hop
the launcher would pick (``step_kernel="auto"``: the fused Pallas step on a
TPU).  It fails on any of: recall@10 under the floor against the chip's
brute force, (c) != (b) in any id, a front-door error/shed or failed hedge,
a continue program without the fused kernel, or fused and reference recall
more than 0.01 apart.  The per-batch times it prints are smoke timings, not
benchmark figures.

``--chips 4`` runs only the sharded scatter-gather path: the staged
adaptive ``DistributedBackend`` on a mesh of four chips over
``build_sharded_arrays`` at ``mcgi-sift1b``'s widths (d = 128, R = 32,
L_build = 50, PQ M = 16) and 4x the one-chip N, the same batches through
the monolithic distributed step (ids must match the staged step), and
recall@10 against brute force.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every check passed.  Without a TPU (or with
``REPRO_PALLAS_INTERPRET`` set) it exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# The one-chip N: SIFT1M's 1,000,000 points cut to 250,000.  On one v5e a
# cold build at 1M projects to ~40 min (exact k-NN calibration ~330 s, two
# refinement rounds ~2060 s) and at 500k to ~17 min; at 250k it is ~9 min.
N_DEFAULT = 250_000
N_REASON = ("cut from SIFT1M's 1,000,000: a cold one-chip build takes "
            "~40 min at 1M, ~17 min at 500k")
N_QUERIES = 1000
BATCH, N_BATCHES = 64, 4
# recall@10 floor against brute force, from a CPU rehearsal at reduced N
# (see CHANGES.md); a correct walk on this data clears it easily.
RECALL_FLOOR = 0.85
FUSED_VS_REFERENCE_TOL = 0.01
FRONT_DOOR_REQUESTS = 200


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def fail(msg: str):
    raise SystemExit(f"[smoke] FAIL: {msg}")


@contextlib.contextmanager
def phase(name: str, times: dict):
    t0 = time.perf_counter()
    yield
    times[name] = time.perf_counter() - t0
    log(f"{name}: {times[name]:.1f}s")


def require_chip(chips: int):
    """Refuse any other device: a TPU backend, the compiled Pallas dispatch,
    no interpret override, and at least ``chips`` chips."""
    if os.environ.get("REPRO_PALLAS_INTERPRET"):
        fail("REPRO_PALLAS_INTERPRET is set: the smoke runs compiled kernels")
    import jax

    devices = jax.devices()
    log(f"platform={devices[0].platform} device_kind={devices[0].device_kind}"
        f" devices={len(devices)}")
    if jax.default_backend() != "tpu":
        fail(f"no TPU: the default backend is {jax.default_backend()!r}")
    from repro.kernels import ops

    if ops.resolve_impl() != "pallas":
        fail(f"kernel dispatch resolves to {ops.resolve_impl()!r}")
    if len(devices) < chips:
        fail(f"--chips {chips} needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def dataset(name: str, n: int, seed: int):
    from repro.data import synthetic

    spec = dataclasses.replace(synthetic.REGISTRY[name], n=n,
                               n_queries=N_QUERIES)
    x, q = synthetic.make_dataset(spec, seed=seed)
    return x.block_until_ready(), q


def recall(ids, gt) -> float:
    import numpy as np

    ids, gt = np.asarray(ids), np.asarray(gt)
    return float(np.mean([np.isin(a, b).mean() for a, b in zip(ids, gt)]))


def serve_batches(engine, batches, label: str, timed: bool = True):
    """Warm every shape on the batches, then serve them again; prints the
    per-batch wall time of the second pass.  Returns the stacked ids."""
    import numpy as np

    for b in batches:
        engine.search(b)
    out = []
    for i, b in enumerate(batches):
        t0 = time.perf_counter()
        res = engine.search(b)
        dt = (time.perf_counter() - t0) * 1e3
        if timed:
            log(f"{label} batch {i}: {dt:.1f} ms (smoke timing, not a "
                "benchmark figure)")
        out.append(np.asarray(res.ids))
    return np.concatenate(out)


def continue_program_text(backend, budget, queries) -> str:
    """Compiled text of the backend's continue program at the batch shape
    (the program the engine runs after the probe)."""
    ctxs = backend.admit(queries)
    probe_state, budgets, hop_limits, _ = backend.probe(ctxs, budget)
    cont = backend.continue_fn(budget)
    return cont.func.lower(*cont.args, probe_state, ctxs, budgets,
                           hop_limits, **cont.keywords).compile().as_text()


def front_door(engine, queries, gt) -> None:
    """~200 requests through the wall-clock front door; nothing may error,
    shed or fail a hedge."""
    import numpy as np

    from repro.serving import server as sv

    lanes = 32
    cls = sv.QoSClass("interactive", deadline_s=300.0, batch_window_s=0.005,
                      max_lanes=lanes, lane_quantum=lanes)
    qn = np.asarray(queries)
    engine.search(qn[:lanes])                 # warm the padded shape
    rows = np.random.default_rng(0).integers(0, qn.shape[0],
                                             FRONT_DOOR_REQUESTS)
    door = sv.FrontDoor({"interactive": engine}, [cls])
    t0 = time.perf_counter()
    futs = [door.submit(qn[r], cls="interactive") for r in rows]
    door.close(wait=True, timeout=900)
    wall = time.perf_counter() - t0
    res = [f.result(timeout=0) for f in futs]
    st = door.stats()
    counts = {s: st[s] for s in ("ok", "partial", "timeout", "shed", "error")}
    ok = [(r, s) for r, s in zip(rows, res) if s.status == "ok"]
    rec = recall([s.ids for _, s in ok], [gt[r] for r, _ in ok]) if ok \
        else float("nan")
    log(f"front door: {len(rows)} requests in {wall:.1f}s {counts} "
        f"partial_errors={st['partial_errors']} recall@10={rec:.4f} "
        "(smoke timing)")
    if counts["error"] or counts["shed"] or st["partial_errors"]:
        notes = sorted({s.note for s in res if s.note})[:3]
        fail(f"front door: {counts}, partial_errors="
             f"{st['partial_errors']}: {notes}")


def smoke_one_chip(args) -> None:
    import numpy as np

    from repro import serving
    from repro.configs import base
    from repro.core import build, distance, search
    from repro.index import build_tiered_index, open_or_build_slow_tier

    cfg = base.get("mcgi-sift1m").config
    n = args.n
    reason = N_REASON if n == N_DEFAULT else "set by --n"
    log(f"config={cfg.name} N={n} ({reason}) d={cfg.d} R={cfg.degree} "
        f"L_build={cfg.l_build} l_search={cfg.l_search} lam={cfg.lam} "
        f"l_min={cfg.l_min} queries={N_QUERIES} seed={args.seed}")
    hop = search.resolve_step_kernel("auto").name
    log(f"hop={hop}")
    times: dict = {}
    with phase("data", times):
        x, queries = dataset("sift1m-proxy", n, args.seed)
    t_build = time.perf_counter()

    def progress(msg):
        log(f"build +{time.perf_counter() - t_build:.1f}s {msg}")

    with phase("graph build (LID calibration + refinement)", times):
        graph = build.build_mcgi(
            x, build.BuildConfig(degree=cfg.degree, beam_width=cfg.l_build,
                                 seed=args.seed), progress=progress)
        graph.adj.block_until_ready()
    with phase("PQ training + encoding (M=16)", times):
        index = build_tiered_index(x, graph, m_pq=16, seed=args.seed)
        index.codes.block_until_ready()
    with phase("brute-force ground truth", times):
        _, gt = distance.brute_force_topk(queries, x, k=cfg.k)
        gt = np.asarray(gt)
    qn = np.asarray(queries)
    batches = [qn[i * BATCH:(i + 1) * BATCH] for i in range(N_BATCHES)]
    gt_b = gt[:BATCH * N_BATCHES]
    budget = cfg.beam_budget()
    store_dir = pathlib.Path(tempfile.mkdtemp(prefix="mcgi-smoke-"))
    try:
        with phase("block store write", times):
            slow = open_or_build_slow_tier(store_dir / "sift.blocks", index,
                                           log=log)
        backends = {
            "a-exact": serving.ExactBackend(x, graph.adj, graph.entry,
                                            step_kernel="auto"),
            "b-tiered": serving.TieredBackend(index, step_kernel="auto"),
            "c-tiered-disk": serving.TieredBackend(index, slow_tier=slow,
                                                   step_kernel="auto"),
        }
        engines = {k: serving.SearchEngine(b, budget, k=cfg.k,
                                           num_buckets="auto")
                   for k, b in backends.items()}
        ids, recalls = {}, {}
        for name, eng in engines.items():
            with phase(f"serve {name} (warm-up + timed pass)", times):
                ids[name] = serve_batches(eng, batches, name)
            recalls[name] = recall(ids[name], gt_b)
            log(f"{name}: recall@10={recalls[name]:.4f} "
                f"(floor {RECALL_FLOOR})")
        same = float((ids["c-tiered-disk"] == ids["b-tiered"]).mean())
        log(f"(c) == (b): identical id share {same:.6f}")

        customs = {}
        for name in ("a-exact", "b-tiered"):
            text = continue_program_text(backends[name], budget, batches[0])
            customs[name] = "tpu_custom_call" in text
        log(f"tpu_custom_call in continue programs: {customs}")

        ref_recalls, shares = {}, {}
        for name in ("a-exact", "b-tiered"):
            backends[name].set_step_kernel("reference")
            with phase(f"serve {name} with the reference hop", times):
                ref_ids = serve_batches(engines[name], batches,
                                        name + " reference hop")
            backends[name].set_step_kernel("auto")
            ref_recalls[name] = recall(ref_ids, gt_b)
            shares[name] = float((ref_ids == ids[name]).mean())
            log(f"{name}: fused recall@10={recalls[name]:.4f} reference "
                f"recall@10={ref_recalls[name]:.4f} identical id share "
                f"{shares[name]:.6f}")

        with phase("front door over (b)", times):
            front_door(engines["b-tiered"], queries, gt)
        for eng in engines.values():
            eng.close()
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)

    low = {k: v for k, v in recalls.items() if v < RECALL_FLOOR}
    if low:
        fail(f"recall@10 under the floor {RECALL_FLOOR}: {low}")
    if same != 1.0:
        fail(f"(c) differs from (b) in {1 - same:.6f} of ids")
    if hop != "pallas" or not all(customs.values()):
        fail(f"hop={hop}, tpu_custom_call in continue programs: {customs}")
    for name, r in ref_recalls.items():
        if abs(r - recalls[name]) > FUSED_VS_REFERENCE_TOL:
            fail(f"{name}: fused recall {recalls[name]:.4f} vs reference "
                 f"{r:.4f}")


def shard_devices(arrays) -> dict:
    """Array name -> number of distinct devices holding its shards."""
    return {k: len({s.device for s in v.addressable_shards})
            for k, v in arrays.items() if k in ("adj", "codes", "vectors")}


def smoke_four_chips(args, devices) -> None:
    import numpy as np

    from repro import compat, serving
    from repro.configs import base
    from repro.core import build, distance
    from repro.distributed import sharded_search as ss

    cfg = base.get("mcgi-sift1b").config
    chips = len(devices)
    n = chips * args.n
    per_chip = ("the one-chip N" if args.n == N_DEFAULT
                else f"{args.n}, set by --n")
    log(f"config={cfg.name} widths: d={cfg.d} R={cfg.degree} "
        f"L_build={cfg.l_build} PQ M={cfg.m_pq}; N={n} = {chips} x "
        f"{per_chip} ({cfg.n} in the config: cut to fit the run)")
    times: dict = {}
    with phase("data", times):
        x, queries = dataset("sift1b-proxy", n, args.seed)
    mesh = compat.make_mesh((chips,), ("data",), devices=devices)
    with phase("sharded build (one sub-graph per chip)", times):
        arrays, per = ss.build_sharded_arrays(
            x, mesh, build_cfg=build.BuildConfig(
                degree=cfg.degree, beam_width=cfg.l_build, seed=args.seed),
            m_pq=cfg.m_pq, seed=args.seed)
    spread = shard_devices(arrays)
    log(f"{per} points per shard; devices holding shards: {spread}")
    with phase("brute-force ground truth", times):
        _, gt = distance.brute_force_topk(queries, x, k=cfg.k)
        gt = np.asarray(gt)
    qn = np.asarray(queries)
    batches = [qn[i * BATCH:(i + 1) * BATCH] for i in range(N_BATCHES)]
    gt_b = gt[:BATCH * N_BATCHES]
    budget = cfg.beam_budget()
    backend = serving.DistributedBackend(
        mesh, arrays, beam_width=budget.l_max, max_hops=2048, k=cfg.k,
        query_chunk=BATCH, beam_budget=budget, budget_buckets=4,
        step_kernel="auto")
    staged = serving.SearchEngine(backend, budget, k=cfg.k,
                                  num_buckets="auto")
    mono = serving.SearchEngine(backend, None, k=cfg.k)
    with phase("serve staged (warm-up + timed pass)", times):
        ids_s = serve_batches(staged, batches, "staged")
    with phase("serve monolithic (warm-up + timed pass)", times):
        ids_m = serve_batches(mono, batches, "monolithic")
    same = float((ids_s == ids_m).mean())
    rec = recall(ids_s, gt_b)
    log(f"staged == monolithic: identical id share {same:.6f}")
    log(f"staged recall@10={rec:.4f} monolithic recall@10="
        f"{recall(ids_m, gt_b):.4f} (floor {RECALL_FLOOR})")
    if any(v != chips for v in spread.values()):
        fail(f"shards not spread over {chips} chips: {spread}")
    if same != 1.0:
        fail(f"staged differs from monolithic in {1 - same:.6f} of ids")
    if rec < RECALL_FLOOR:
        fail(f"recall@10 {rec:.4f} under the floor {RECALL_FLOOR}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=N_DEFAULT,
                    help="base points per chip")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded path on a mesh of 4 chips")
    args = ap.parse_args()
    devices = require_chip(args.chips)
    import jax

    from repro import runtime

    runtime.use_compile_cache()
    t0 = time.perf_counter()
    if args.chips == 1:
        smoke_one_chip(args)
    else:
        smoke_four_chips(args, devices)
    log(f"all checks passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
