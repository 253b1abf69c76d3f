"""Resolve a workload of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:

* a configuration: the JSON file named by its ``file`` entry, whose
  ``driver`` names the module in ``perfbench/drivers`` that runs it and
  whose ``reference`` names its plain reference in ``perfbench/reference``;
* a traffic mix: ``perfbench/traffic/<traffic>.json``;
* a per-layer metric: the reader ``perfbench/metrics/<name>.py``.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


def load(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def workload(spec: Dict, name: str) -> Dict:
    for wl in spec["workloads"]:
        if wl["name"] == name:
            return wl
    known = ", ".join(w["name"] for w in spec["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")


def config(spec: Dict, wl: Dict, root: Path = ROOT) -> Dict:
    entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    with open(root / entry["file"]) as f:
        return json.load(f)


def config_named(name: str, root: Path = ROOT) -> Dict:
    """The configuration file ``perfbench/configs/<name>.json``, whether or
    not a cell of ``BENCHMARK.json`` uses it."""
    with open(root / "perfbench" / "configs" / f"{name}.json") as f:
        return json.load(f)


def driver(cfg: Dict) -> ModuleType:
    """The module ``perfbench.drivers.<driver>`` that runs configuration
    ``cfg``: its ``Cell`` and the ``VARIANTS`` its check can put in the
    program's place."""
    return importlib.import_module(f"perfbench.drivers.{cfg['driver']}")


def traffic(wl: Dict, root: Path = ROOT) -> Dict:
    with open(root / "perfbench" / "traffic" / f"{wl['traffic']}.json") as f:
        return json.load(f)


def applies(metric: Dict, wl_name: str) -> bool:
    return "workloads" not in metric or wl_name in metric["workloads"]


def end_to_end(spec: Dict, wl_name: str) -> List[Dict]:
    return [m for m in spec["end_to_end"] if applies(m, wl_name)]


def per_layer(spec: Dict, wl_name: str) -> List[Dict]:
    """Per-layer metrics this workload reports: those that list it, and
    those without a list whose end-to-end metric it reports."""
    e2e = {m["name"] for m in end_to_end(spec, wl_name)}
    return [m for m in spec["per_layer"]
            if (wl_name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def reader(name: str, root: Path = ROOT) -> ModuleType:
    """The reader module of per-layer metric ``name``."""
    path = root / "perfbench" / "metrics" / f"{name}.py"
    mod_name = "perfbench_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(f"no reader for per-layer metric {name!r}: "
                                f"{path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
