"""BENCHMARK.json keeps to its contract, and every cell resolves its
configuration, traffic and per-layer metric files by name."""
import json
import re

import pytest

from perfbench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
B = spec.load()
CELLS = [w["name"] for w in B["workloads"]]


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "perfbench/run.py"]
    assert 1 <= B["run_seconds"] <= 51
    for p in B["paths"]:
        assert (spec.ROOT / p).is_dir() and not p.startswith("/") \
            and ".." not in p
    assert len(json.dumps(B, indent=1)) < 64 * 1024


def test_entries_have_exactly_their_keys_and_valid_names():
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("perfbench/")
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files(cell):
    wl = spec.workload(B, cell)
    cfg = spec.config(B, wl)
    assert (spec.BENCH / "drivers" / f"{cfg['driver']}.py").is_file()
    assert (spec.BENCH / "reference" / f"{cfg['reference']}.py").is_file()
    entry = next(c for c in B["configs"] if c["name"] == wl["config"])
    assert entry["reduced"] == cfg["reduced"]
    assert spec.traffic(wl)["why"]
    for m in spec.per_layer(B, cell):
        assert callable(spec.reader(m["name"]).read)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    e2e = [m["name"] for m in spec.end_to_end(B, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = spec.per_layer(B, cell)
    assert layers and all(m["moves"] in e2e for m in layers)
