"""BENCHMARK.json against the rules of its format, and every name in it
resolved to its file (no chip, no JAX)."""
from __future__ import annotations

import json
import re

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def _line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert spec.BENCHMARK_JSON.stat().st_size <= 64 * 1024
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line(w) for w in bench["command"])
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51


def test_entries_have_only_the_allowed_keys(bench):
    keys = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
    }
    for section, want in keys.items():
        for e in bench[section]:
            assert set(e) - {"workloads"} == want, (section, e["name"])
            if section in ("configs", "workloads"):
                assert "workloads" not in e


def test_names_units_and_lines(bench):
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[section]:
            assert NAME.match(e["name"]), e["name"]
            names.append((section if section in ("configs", "workloads")
                          else "metric", e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for key in ("why", "layer", "source"):
                if key in e and section != "end_to_end":
                    assert _line(e[key]), (e["name"], key)
    assert len(names) == len(set(names))
    for c in bench["configs"]:
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])


def test_cells_configs_and_metrics_fit_together(bench):
    cells = {w["name"] for w in bench["workloads"]}
    cfgs = {c["name"] for c in bench["configs"]}
    assert 1 <= len(cells) <= 24 and 1 <= len(cfgs) <= 24
    assert {w["config"] for w in bench["workloads"]} == cfgs
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert four <= max(1, len(cells) // 2)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in cells
            reports = e2e[m["moves"]].get("workloads")
            assert reports is None or w in reports
    for m in list(bench["end_to_end"]) + list(bench["per_layer"]):
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        mine = [m for m in bench["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert {"setup_s"} < {m["name"] for m in mine}
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_layers_are_named_alike(bench):
    by_layer = {}
    for m in bench["per_layer"]:
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_every_cell_resolves_by_name(cell):
    c = spec.find_cell(cell)
    assert c.config["name"] == c.config_name
    driver = spec.driver_module(c.traffic["mode"])
    assert callable(driver.warm) and callable(driver.run)
    assert callable(spec.backend_module(c.config["backend"]).open_backend)
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    assert c.config["correct"]["limits"]


@pytest.mark.parametrize("section", ["configs"])
def test_config_files_state_their_cut(bench, section):
    for c in bench[section]:
        path = spec.ROOT / c["file"]
        assert path.relative_to(spec.BENCH_DIR)
        cfg = json.loads(path.read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(cfg["source_values"]) == set(c["reduced"])
        assert cfg["assumed"]


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.find_cell("no-such.cell")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.backend_module("no-such-kind")
    with pytest.raises(spec.SpecError):
        spec.driver_module("no-such-mode")


@pytest.mark.parametrize("traffic", sorted(
    p.stem for p in (spec.BENCH_DIR / "traffic").glob("*.json")))
def test_every_traffic_mix_is_data_with_a_driver(traffic):
    mix = json.loads(spec.traffic_path(traffic).read_text())
    driver = spec.driver_module(mix["mode"])
    assert callable(driver.warm) and callable(driver.run)
    assert not list((spec.BENCH_DIR / "traffic").glob("*.py"))
