"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic mix.
Each lives in a file of its own, found by name:

* ``bench/configs/<config>.json``  the deployment (sizes, build and budget
  parameters, backend kind, correctness limits);
* ``bench/traffic/<traffic>.json`` the traffic mix, a data file whose
  ``mode`` names its driver;
* ``bench/drivers/<mode>.py``       warms and runs a mode of traffic;
* ``bench/backends/<kind>.py``      builds the serving backend for a kind;
* ``bench/metrics/<metric>.py``     one reader per metric, end-to-end and
  per-layer alike.

So a later change adds a cell by adding files and entries, never by editing
a file that is already here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
BENCHMARK_JSON = ROOT / "BENCHMARK.json"


class SpecError(Exception):
    """A cell, configuration, traffic mix or metric that cannot be found."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: tuple      # metric entries (dicts) this cell reports, trace 0
    per_layer: tuple       # metric entries (dicts) this cell reports, trace 1


def load_benchmark(path: pathlib.Path = BENCHMARK_JSON) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_json(path: pathlib.Path, what: str) -> dict:
    if not path.is_file():
        raise SpecError(f"no {what} file {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def config_path(name: str) -> pathlib.Path:
    return BENCH_DIR / "configs" / f"{name}.json"


def traffic_path(name: str) -> pathlib.Path:
    return BENCH_DIR / "traffic" / f"{name}.json"


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric["workloads"] if "workloads" in metric else True


def find_cell(name: str, bench: dict | None = None) -> Cell:
    bench = load_benchmark() if bench is None else bench
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    w = entries[0]
    cfg_entries = [c for c in bench["configs"] if c["name"] == w["config"]]
    if not cfg_entries:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    cfg = _load_json(ROOT / cfg_entries[0]["file"], "config")
    traffic = _load_json(traffic_path(w["traffic"]), "traffic")
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, name))
    per_layer = tuple(m for m in bench["per_layer"] if _reports(m, name))
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=cfg, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)


def load_module(path: pathlib.Path, what: str):
    """Import a file by path (file names may hold '.' and '-')."""
    if not path.is_file():
        raise SpecError(f"no {what} file {path.relative_to(ROOT)}")
    mod_name = ".".join(("bench",) + path.relative_to(BENCH_DIR).with_suffix(
        "").parts)
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def backend_module(kind: str):
    return load_module(BENCH_DIR / "backends" / f"{kind}.py", "backend")


def driver_module(mode: str):
    """The driver of a traffic mode: ``warm(...)`` and ``run(...)``."""
    return load_module(BENCH_DIR / "drivers" / f"{mode}.py", "driver")


def metric_reader(name: str):
    """The ``read(rec) -> float | None`` function of a metric."""
    return load_module(BENCH_DIR / "metrics" / f"{name}.py", "metric").read
