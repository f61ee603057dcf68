"""Runs the tiny sharded cell through ``bench.run.run`` on four virtual CPU
devices, in a process of its own (the device count is fixed before JAX is
imported):

    python bench/tests/_sharded_worker.py

Runs it twice on one seed (the second run loads the first's index): as it
is, then with a fault planted in the answers the program serves.  Prints
each run's result line object, with the readings of the per-shard metrics
and the run's ``fault``, as one JSON line.
"""
import os
import pathlib
import sys

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import json  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

from bench import index_cache, spec  # noqa: E402

TINY = spec.BENCH_DIR / "tests" / "data" / "tiny-sharded.json"
PER_SHARD = ("shard_skew.sharded", "granted_budget.sharded")


def tiny_cell() -> spec.Cell:
    e2e = tuple({"name": n, "unit": u} for n, u in
                (("qps", "queries/s"), ("recall_at_10", "ratio"),
                 ("setup_s", "s")))
    return spec.Cell(name="tiny-sharded.stream", chips=4,
                     config_name="tiny-sharded", traffic_name="stream",
                     config=json.loads(TINY.read_text()),
                     traffic={"mode": "stream", "batch": 32},
                     end_to_end=e2e, per_layer=())


def _plant_fault() -> None:
    from repro.serving import engine as eng

    finish = eng.DistributedBackend.finish

    def broken(self, *a, **kw):
        res = finish(self, *a, **kw)
        res.ids = np.array(res.ids)
        res.ids[:, 0] = (res.ids[:, 0] + 1) % 2000   # d2 left as scored
        return res

    eng.DistributedBackend.finish = broken


def main() -> None:
    import jax

    from bench import run as R

    # The per-shard readers read the window a traced run would hand them.
    readings = {}
    read_metrics = R.read_metrics

    def read_with_per_shard(entries, rec, *, required):
        for name in PER_SHARD:
            readings[name] = spec.metric_reader(name)(rec)
        return read_metrics(entries, rec, required=required)

    R.read_metrics = read_with_per_shard
    with tempfile.TemporaryDirectory() as d:
        index_cache.INDEX_DIR = pathlib.Path(d)
        for fault in (False, True):
            if fault:
                _plant_fault()
            out = R.run(tiny_cell(), 2**31 + 7, 0.5, 0, jax.devices()[:4],
                        config_file=TINY)
            out.update(fault=fault, readings=dict(readings),
                       devices=len(jax.devices()))
            print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
