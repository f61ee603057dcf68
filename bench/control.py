"""Readings of the control: the plain reference put in the program's place,
one precision lower (bfloat16 inputs, float32 accumulation), at a cell's
own size, on several seeds.  The benchmark's runs do not run it; its
readings set the upper end of each limit (see ``PERF.md``).

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--answers N]

For each seed it makes the cell's data, draws as many pool rows as a window
answers from the seed, serves them with the control, and prints one JSON
line of the numbers the comparison reads.  It needs a TPU, as a run does.
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

from bench import data, reference, spec  # noqa: E402
from bench import run as run_mod  # noqa: E402


def readings(cell, seed: int, answers: int) -> dict:
    cfg = cell.config
    base, pool = data.make_data(cfg, seed)
    rng = np.random.default_rng(data.sub_seed(seed, "traffic"))
    qidx = rng.integers(0, pool.shape[0], answers)
    pool_np = np.asarray(pool)
    ids, d2 = reference.control_answers(base, pool_np[qidx], int(cfg["k"]))
    numbers = reference.compare(base, pool_np, qidx, ids, d2, int(cfg["k"]))
    ok, compared = reference.judge(numbers, cfg["correct"]["limits"])
    return {"cell": cell.name, "seed": seed, "control": "bfloat16",
            "correct": ok, **numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--answers", type=int, default=4096)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    run_mod.require_chip(cell.chips)
    run_mod.use_compile_cache()
    for s in args.seeds.split(","):
        print(json.dumps(readings(cell, int(s), args.answers)), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except run_mod.NoChip as e:
        print(e, file=sys.stderr)
        sys.exit(2)
