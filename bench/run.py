"""Runs one cell of the benchmark once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a deployment
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  One run:

1. refuses anything but a TPU (no CPU fallback, no interpreted kernels);
2. makes the base set and the query pool on the device from ``--seed``;
3. builds the index, or loads it from ``bench/cache/`` when this seed,
   deployment and program were built before, and warms every shape the
   cell's traffic uses (all of this is ``setup_s``);
4. runs the traffic for ``--seconds`` (with ``--trace 1`` under the
   profiler);
5. checks every answer against the plain reference (:mod:`bench.reference`)
   and prints one JSON line: ``correct``, ``attempted``, ``failed``,
   ``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
   per-layer metrics with ``--trace 1``), ``device``, ``breakdown`` (traced
   runs) and, last, ``compared``: each number the check read, with its
   limit.  The same numbers are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parents[1]
if sys.path and pathlib.Path(sys.path[0]).resolve() == _ROOT / "bench":
    sys.path.pop(0)       # keep bench/ modules from shadowing the stdlib
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

import numpy as np  # noqa: E402

from bench import spec  # noqa: E402

JAX_CACHE = spec.BENCH_DIR / "cache" / "jax"
TRACE_DIR = spec.BENCH_DIR / "cache" / "trace"
TPU_LOGS = spec.BENCH_DIR / "cache" / "tpu_logs"


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T_START:.1f}s] {msg}",
          file=sys.stderr, flush=True)


class NoChip(SystemExit):
    pass


def require_chip(chips: int):
    """The devices to run on; exits non-zero on anything but a TPU with
    the compiled kernels and enough chips."""
    if os.environ.get("REPRO_PALLAS_INTERPRET"):
        raise NoChip("bench: REPRO_PALLAS_INTERPRET is set; the benchmark "
                     "runs compiled kernels only")
    import jax

    if jax.default_backend() != "tpu":
        raise NoChip(f"bench: no TPU (the default backend is "
                     f"{jax.default_backend()!r}); there is no CPU fallback")
    from repro.kernels import ops

    if ops.resolve_impl() != "pallas":
        raise NoChip(f"bench: kernel dispatch resolves to "
                     f"{ops.resolve_impl()!r}, not 'pallas'")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, found "
                     f"{len(devices)}")
    return devices


def compile_cache_dir(environ=os.environ) -> pathlib.Path:
    """``JAX_COMPILATION_CACHE_DIR`` when it is set, else a fixed path in
    the checkout (the path is part of the cache's key, so it never moves)."""
    return pathlib.Path(environ.get("JAX_COMPILATION_CACHE_DIR") or JAX_CACHE)


def use_compile_cache() -> None:
    """JAX's persistent compilation cache in :func:`compile_cache_dir`;
    every program is kept, however fast it compiled, so a warm run obtains
    the whole lane family from the cache."""
    import jax

    path = compile_cache_dir()
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def budget(cfg: dict):
    from repro.core.search import AdaptiveBeamBudget

    s = cfg["search"]
    return AdaptiveBeamBudget(
        l_min=int(s["l_min"]), l_max=int(s["l_search"]), lam=float(s["lam"]),
        probe_hops=int(s["probe_hops"]), hop_factor=int(s["hop_factor"]))


def percentile(x, q: float) -> float:
    x = np.asarray(x, np.float64)
    return float(np.percentile(x, q)) if x.size else float("nan")


def read_metrics(entries, rec: dict, *, required: bool) -> dict:
    """Each metric of ``entries`` by its reader (``bench/metrics/<name>.py``).
    A per-layer reader that finds nothing leaves its metric out; an
    end-to-end metric the cell has to report is an error when missing."""
    out = {}
    for m in entries:
        v = spec.metric_reader(m["name"])(rec)
        if v is None:
            if required:
                raise spec.SpecError(f"end-to-end metric {m['name']!r} has "
                                     f"nothing to read in this cell")
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def slow_tier_counts(slow) -> dict | None:
    if slow is None:
        return None
    st = slow.stats()
    return {"io_blocks": st["io_blocks"], "hits": st["cache_hits"],
            "misses": st["cache_misses"]}


def setup(cell, seed: int, config_file=None):
    """Data, index and engine for ``cell``, every shape warmed.  Returns
    (base, pool as numpy, served backend, engine, compile counter)."""
    from repro import serving

    from bench import data, drive

    counter = drive.CompileCounter().install()
    cfg, traffic = cell.config, cell.traffic
    base, pool = data.make_data(cfg, seed)
    pool_np = np.asarray(pool)
    log(f"data made: base {tuple(base.shape)}, pool {pool_np.shape}")
    served = spec.backend_module(cfg["backend"]).open_backend(
        cfg, base, seed, config_file or spec.config_path(cell.config_name),
        log)
    log(f"index {'built' if served.built else 'loaded'} in "
        f"{served.build_s:.1f}s")
    engine = serving.SearchEngine(served.backend, budget(cfg),
                                  k=int(cfg["k"]), num_buckets="auto")
    spec.driver_module(traffic["mode"]).warm(engine, pool_np, traffic, seed,
                                             counter, log)
    drive.annotate_engine(engine)
    # Set-up leaves a large heap (compiled programs, caches); freezing it
    # keeps the window's garbage collections from walking it again.
    gc.collect()
    gc.freeze()
    return base, pool_np, served, engine, counter


def run(cell, seed: int, seconds: float, trace: int, devices,
        peaks: dict | None = None, config_file=None) -> dict:
    """One run of ``cell`` on ``devices``; returns the result line's object.
    Prints the progress and the compared numbers on standard error."""
    from bench import reference
    from bench import trace as trace_mod

    cfg, mode = cell.config, cell.traffic["mode"]
    kind = devices[0].device_kind
    log(f"cell={cell.name} seed={seed} seconds={seconds} trace={trace} "
        f"device={devices[0].platform}/{kind} x{len(devices)}")
    base, pool_np, served, engine, counter = setup(cell, seed, config_file)
    setup_s = time.perf_counter() - T_START
    log(f"setup_s={setup_s:.3f}")

    st_before = slow_tier_counts(served.slow_tier)
    compiles0 = counter.count
    capture = trace_mod.Capture(TRACE_DIR) if trace else None
    with capture or contextlib.nullcontext():
        win = spec.driver_module(mode).run(engine, pool_np, cell.traffic,
                                           seed, seconds)
    gc.unfreeze()
    compiles = counter.count - compiles0
    st_after = slow_tier_counts(served.slow_tier)
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)

    engine.close()
    served.close()
    del engine, served
    gc.collect()

    numbers = reference.compare(base, pool_np, win.qidx, win.ids, win.d2,
                                int(cfg["k"]))
    correct, compared = reference.judge(numbers, cfg["correct"]["limits"])
    if win.never_done:
        correct = False
    failed = win.not_ok + numbers["malformed_rows"]

    log("numbers " + json.dumps(numbers))
    log(f"window {win.seconds:.3f}s attempted={win.attempted} "
        f"answered={win.ids.shape[0]} failed={failed} "
        f"never_done={win.never_done} compiles_in_window={compiles}")
    if win.lateness_s is not None:
        log(f"generator lateness p50={percentile(win.lateness_s, 50)*1e3:.3f}"
            f"ms p99={percentile(win.lateness_s, 99)*1e3:.3f}ms")

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    out = {"correct": bool(correct), "attempted": int(win.attempted),
           "failed": int(failed)}
    rec = {"mode": mode, "backend": cfg["backend"], "config": cfg,
           "window": win, "numbers": numbers, "setup_s": setup_s,
           "answered": int(win.ids.shape[0])}
    if trace:
        reduced = trace_mod.reduce(capture.xplane())
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        rec.update(peaks=peaks, trace=reduced, slow_tier=(
            None if st_before is None else
            {k: st_after[k] - st_before[k] for k in st_before}))
        out["metrics"] = read_metrics(cell.per_layer, rec, required=False)
        out["device"] = device
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
        log(f"kernel device time {reduced['kernel_s']}")
    else:
        out["metrics"] = read_metrics(cell.end_to_end, rec, required=True)
        out["device"] = device
    out["compared"] = compared
    for name, c in compared.items():
        log(f"compared {name}={c['value']!r} limit={c['limit']!r}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.find_cell(args.workload)
    # The TPU runtime logs to /tmp/tpu_logs unless told otherwise; a run
    # writes only inside its checkout.
    if "TPU_LOG_DIR" not in os.environ:
        TPU_LOGS.mkdir(parents=True, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(TPU_LOGS)
    devices = require_chip(cell.chips)
    from bench import roofline

    pk = roofline.peaks(devices[0].device_kind)
    use_compile_cache()
    out = run(cell, args.seed, args.seconds, args.trace, devices, peaks=pk)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except NoChip as e:
        print(e, file=sys.stderr)
        sys.exit(2)
