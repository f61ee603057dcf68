"""Finds an open-loop cell's knee once: the highest offered rate at which
nothing sheds or times out and the open-lane count does not grow over the
window.  The cell's traffic file then fixes its rate at about 0.8 x knee;
the benchmark's runs never search.

    python3 bench/sweep.py --workload <open-loop cell> --seed <n> \\
        --seconds 8 --rates 100,150,200,250

One process, one build: each rate gets a window of its own over the same
warmed engine, and one JSON line per rate is printed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[1]
if sys.path and pathlib.Path(sys.path[0]).resolve() == _ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

import numpy as np  # noqa: E402

from bench import spec  # noqa: E402
from bench import run as run_mod  # noqa: E402


def growing(open_lanes: np.ndarray, max_lanes: int) -> bool:
    """Open lanes in the last third above those in the first third by more
    than one dispatch's worth."""
    n = open_lanes.size // 3
    if n == 0:
        return False
    return float(open_lanes[-n:].mean()) > float(open_lanes[:n].mean()) \
        + max_lanes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    if cell.traffic["mode"] != "open":
        raise SystemExit("bench/sweep.py: only open-loop cells have a knee")
    run_mod.require_chip(cell.chips)
    run_mod.use_compile_cache()
    _, pool_np, served, engine, _ = run_mod.setup(cell, args.seed)
    max_lanes = int(cell.traffic["class"]["max_lanes"])
    for rate in (float(r) for r in args.rates.split(",")):
        win = spec.driver_module("open").run(
            engine, pool_np, cell.traffic, args.seed, args.seconds, rate=rate)
        lat = np.sort(win.latencies_s)
        row = {
            "rate": rate, "attempted": win.attempted, "not_ok": win.not_ok,
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3,
            "open_lanes_first_third": float(
                win.open_lanes[:win.open_lanes.size // 3].mean()),
            "open_lanes_last_third": float(
                win.open_lanes[-(win.open_lanes.size // 3):].mean()),
            "growing": growing(win.open_lanes, max_lanes),
            "lanes_per_dispatch": (
                sum(n for _, _, n in win.dispatches)
                / max(1, len(win.dispatches))),
            "lateness_p99_ms": float(np.percentile(win.lateness_s, 99))
            * 1e3,
        }
        row["sustained"] = row["not_ok"] == 0 and not row["growing"]
        print(json.dumps(row), flush=True)
    served.close()
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except run_mod.NoChip as e:
        print(e, file=sys.stderr)
        sys.exit(2)
