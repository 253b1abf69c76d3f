"""Declarative sweep grids → frozen configs → (optionally parallel) trials.

A *grid* is one config template plus axes of values; :func:`expand_grid`
freezes the cartesian product into :class:`Trial`\\ s (plain config dicts —
the only thing that crosses a process boundary).  :func:`run_suite` executes
them serially or across a ``ProcessPoolExecutor`` and merges the per-trial
:class:`~repro.core.telemetry.RunReport`\\ s into one JSON-able artifact.

Determinism is the whole point:

* every trial is keyed by the sha256 of its canonical ``{kind, config}``
  JSON (:func:`trial_key`) — that key names its result-cache entry, so a
  re-run only executes trials whose exact config changed;
* per-client RNG seeds derive from config *content* (``repro.exp.seeding``),
  never from submission order, and replicates get their seeds the same way
  (:func:`with_replicates`);
* the merged artifact is assembled in trial-definition order and carries no
  wall-clock fields, so any submission order — shuffled, sharded, parallel —
  produces a byte-identical file (timing travels separately).
"""
from __future__ import annotations

import hashlib
import json
import multiprocessing
import os
import tempfile
import time
from concurrent.futures import ProcessPoolExecutor, as_completed
from dataclasses import dataclass
from itertools import product
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.exp import (ExperimentConfig, TopologyConfig, TrafficConfig,
                       config_fingerprint, derive_seed, run_experiment,
                       run_topology_experiment)

from .common import experiment_config

TRIAL_KINDS = ("experiment", "topology")


@dataclass(frozen=True)
class Trial:
    """One frozen unit of work: a config dict plus which runner drives it."""

    name: str
    kind: str  # "experiment" (single-host) | "topology" (multi-host)
    config: Dict[str, Any]


def _canonical(obj: Any) -> str:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


def trial_key(trial: Trial) -> str:
    """Content address of one trial: sha256 over the exact ``{kind, config}``
    JSON — the config's seed and every physics knob included, so two trials
    share a key (and a cache entry) only when they are the same run."""
    return hashlib.sha256(
        _canonical({"kind": trial.kind, "config": trial.config})
        .encode("utf-8")).hexdigest()


def set_axis(cfg_dict: Dict[str, Any], path: str, value: Any) -> None:
    """Set ``path`` (dotted, e.g. ``"traffic.rate_gbps"``) in a nested config
    dict.  A missing segment raises KeyError — a typo'd axis must not
    silently sweep nothing."""
    parts = path.split(".")
    d = cfg_dict
    for p in parts[:-1]:
        if not isinstance(d, dict) or p not in d:
            raise KeyError(f"axis path {path!r}: no key {p!r} in config")
        d = d[p]
    if not isinstance(d, dict) or parts[-1] not in d:
        raise KeyError(f"axis path {path!r}: no key {parts[-1]!r} in config")
    d[parts[-1]] = value


Axis = Tuple[str, Sequence[Any]]  # (dotted path, values) [+ optional labels]


def expand_grid(name: str, kind: str, template: Dict[str, Any],
                axes: Sequence[Sequence[Any]]) -> List[Trial]:
    """Cartesian product of ``axes`` over one config template, in definition
    order (first axis slowest).  Each axis is ``(path, values)`` or
    ``(path, values, labels)``; labels name the trial when a value has no
    short repr (e.g. a whole ``ports`` list)."""
    if kind not in TRIAL_KINDS:
        raise ValueError(f"kind must be one of {TRIAL_KINDS}, got {kind!r}")
    paths, value_lists, label_lists = [], [], []
    for ax in axes:
        path, values = ax[0], list(ax[1])
        labels = list(ax[2]) if len(ax) > 2 else [str(v) for v in values]
        if len(labels) != len(values):
            raise ValueError(f"axis {path!r}: {len(values)} values but "
                             f"{len(labels)} labels")
        paths.append(path)
        value_lists.append(values)
        label_lists.append(labels)
    trials: List[Trial] = []
    for combo in product(*(range(len(v)) for v in value_lists)):
        cfg = json.loads(json.dumps(template))  # deep, JSON-clean copy
        tags = []
        for path, vi, values, labels in zip(paths, combo, value_lists,
                                            label_lists):
            set_axis(cfg, path, values[vi])
            tags.append(f"{path.rsplit('.', 1)[-1]}={labels[vi]}")
        trial_name = f"{name}/{','.join(tags)}" if tags else name
        if "name" in cfg:
            cfg["name"] = trial_name
        trials.append(Trial(name=trial_name, kind=kind, config=cfg))
    names = [t.name for t in trials]
    if len(set(names)) != len(names):
        raise ValueError(f"grid {name!r} produced duplicate trial names")
    return trials


def with_replicates(trials: Sequence[Trial], n: int) -> List[Trial]:
    """Each trial × ``n`` seed-replicates.  Replicate 0 is the trial itself;
    replicate r ≥ 1 re-seeds ``traffic.seed`` from the trial config's
    content fingerprint — stable under reordering, decorrelated across
    replicates and across distinct trials."""
    out: List[Trial] = []
    for t in trials:
        out.append(Trial(name=f"{t.name}@r0", kind=t.kind, config=t.config))
        fp = config_fingerprint(t.config)
        for r in range(1, n):
            cfg = json.loads(json.dumps(t.config))
            cfg.setdefault("traffic", {})
            cfg["traffic"]["seed"] = derive_seed(fp, r, "replicate")
            out.append(Trial(name=f"{t.name}@r{r}", kind=t.kind, config=cfg))
    return out


def _run_trial(payload: Tuple[str, str]) -> Dict[str, Any]:
    """Worker entry point (module-level: must pickle by reference).  Takes
    ``(kind, config_json)``, returns the RunReport as plain data."""
    kind, cfg_json = payload
    cfg_dict = json.loads(cfg_json)
    if kind == "topology":
        rep = run_topology_experiment(TopologyConfig.from_dict(cfg_dict))
    elif kind == "experiment":
        rep = run_experiment(ExperimentConfig.from_dict(cfg_dict))
    else:
        raise ValueError(f"unknown trial kind {kind!r}")
    return rep.to_dict()


def _cache_path(cache_dir: str, key: str) -> str:
    return os.path.join(cache_dir, f"{key}.json")


def _cache_load(cache_dir: str, key: str) -> Optional[Dict[str, Any]]:
    try:
        with open(_cache_path(cache_dir, key)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _cache_store(cache_dir: str, key: str, report: Dict[str, Any]) -> None:
    os.makedirs(cache_dir, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=cache_dir, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(report, f, sort_keys=True)
        os.replace(tmp, _cache_path(cache_dir, key))  # atomic vs. racers
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def run_suite(trials: Sequence[Trial], workers: int = 1,
              cache_dir: Optional[str] = None,
              submit_order: Optional[Sequence[int]] = None,
              ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Execute every trial; return ``(merged, timing)``.

    ``merged`` maps trial name → ``{kind, config, report}`` in *definition*
    order and contains nothing wall-clock-dependent: shuffling
    ``submit_order``, changing ``workers``, or re-running from a warm
    ``cache_dir`` all produce the identical object.  ``timing`` carries the
    wall-clock facts (workers, wall seconds, trials/s, cache hits) for
    benchmark artifacts."""
    trials = list(trials)
    names = [t.name for t in trials]
    if len(set(names)) != len(names):
        raise ValueError("duplicate trial names in suite")
    order = list(range(len(trials))) if submit_order is None \
        else list(submit_order)
    if sorted(order) != list(range(len(trials))):
        raise ValueError("submit_order must be a permutation of the trials")
    keys = [trial_key(t) for t in trials]
    results: Dict[int, Dict[str, Any]] = {}
    cache_hits = 0
    t0 = time.perf_counter()  # simlint: disable=SL001 -- bench wall timing
    todo: List[int] = []
    for i in order:
        cached = _cache_load(cache_dir, keys[i]) if cache_dir else None
        if cached is not None:
            results[i] = cached
            cache_hits += 1
        else:
            todo.append(i)
    payloads = {i: (trials[i].kind, _canonical(trials[i].config))
                for i in todo}
    if workers <= 1 or len(todo) <= 1:
        for i in todo:
            results[i] = _run_trial(payloads[i])
    else:
        # spawn, never fork: a parent that has initialized a JAX backend
        # holds threads (and possibly a chip) that a forked child inherits
        with ProcessPoolExecutor(
                max_workers=workers,
                mp_context=multiprocessing.get_context("spawn")) as ex:
            futs = {ex.submit(_run_trial, payloads[i]): i for i in todo}
            for fut in as_completed(futs):
                results[futs[fut]] = fut.result()
    if cache_dir:
        for i in todo:
            _cache_store(cache_dir, keys[i], results[i])
    wall_s = time.perf_counter() - t0  # simlint: disable=SL001 -- bench wall timing
    merged = {t.name: {"kind": t.kind, "config": t.config,
                       "report": results[i]}
              for i, t in enumerate(trials)}
    timing = {"workers": workers, "n_trials": len(trials),
              "n_cache_hits": cache_hits, "wall_s": wall_s,
              "trials_per_s": (len(trials) / wall_s) if wall_s > 0 else 0.0}
    return merged, timing


def write_suite_json(path: str, merged: Dict[str, Any]) -> None:
    """Serialize a merged suite byte-stably (sorted keys, fixed separators,
    trailing newline)."""
    with open(path, "w") as f:
        json.dump(merged, f, sort_keys=True, indent=2)
        f.write("\n")


# -- predefined grids ---------------------------------------------------------

def fig3a_grid(trial_s: float = 0.002) -> List[Trial]:
    """The Fig. 3(a) sweep as a parallel suite: MSB search over stack kind ×
    NIC-port count (the grid ``benchmarks/parallel_bench.py`` times)."""
    base = experiment_config(
        "bypass",
        traffic=TrafficConfig(mode="msb", trial_s=trial_s, refine_iters=2,
                              start_gbps=0.1),
        name="fig3a-grid").to_dict()
    port = base["ports"][0]
    return expand_grid("fig3a-grid", "experiment", base, [
        ("stack.kind", ["bypass", "kernel"]),
        ("ports", [[dict(port)] * n for n in (1, 2, 3, 4)],
         ["1", "2", "3", "4"]),
    ])


NAMED_GRIDS = {"fig3a-grid": fig3a_grid}


def named_grid(name: str, trial_s: float = 0.002) -> List[Trial]:
    if name not in NAMED_GRIDS:
        raise ValueError(
            f"unknown grid {name!r}; available: {sorted(NAMED_GRIDS)}")
    return NAMED_GRIDS[name](trial_s=trial_s)
