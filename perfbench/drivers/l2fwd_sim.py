"""Driver of the simulator cells: the trials of a zero-loss bandwidth
search, replayed from the traffic file's recorded schedule of offered rates.

Each trial is one open-loop l2fwd experiment through ``run_experiment``
with the configuration's engine (``epoch-jit``: the epoch planner on the
host, the wire scan on the device).  A trial that overloads its rings stays
on that engine: the planner drops the arrivals that find a ring full, as
the per-event loop would.  The window runs whole searches: the schedule,
in an order drawn from the seed for each search, again and again until
``--seconds`` have passed and the search under way is complete.

After the window every trial's ``RunReport`` is compared exactly with the
plain reference's report for its schedule entry.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from perfbench import generate
from perfbench.reference import l2fwd as ref


def experiment_config(cfg: Dict, traffic: Dict, rate_gbps: float, seed: int):
    from repro.exp import (CostConfig, ExperimentConfig, PoolConfig,
                           PortConfig, StackConfig, TrafficConfig)
    from repro.exp.config import LinkConfig, RssConfig
    port = PortConfig(
        n_queues=cfg["n_queues"], ring_size=cfg["ring_size"],
        writeback_threshold=cfg["writeback_threshold"],
        rss=RssConfig(table_size=cfg["rss_table_size"],
                      key_hex=cfg["rss_key_hex"]),
        link=LinkConfig(gbps=cfg["link_gbps"],
                        latency_ns=cfg["link_latency_ns"]))
    return ExperimentConfig(
        name="perfbench",
        pool=PoolConfig(n_slots=cfg["pool_slots"], slot_size=cfg["slot_size"]),
        ports=(port,) * cfg["ports"],
        stack=StackConfig(
            kind=cfg["stack"], burst_size=cfg["burst"],
            n_lcores=cfg["n_lcores"],
            cost=CostConfig(cpu_ghz=cfg["cpu_ghz"],
                            pmd_poll_cycles=cfg["pmd_poll_cycles"],
                            pmd_per_packet_cycles=cfg["pmd_per_packet_cycles"])),
        traffic=TrafficConfig(
            mode="open_loop", rate_gbps=rate_gbps,
            kind=traffic["arrivals"], packet_size=cfg["packet_size"],
            duration_s=traffic["trial_s"], seed=seed, engine=cfg["engine"],
            n_flows=cfg["n_flows"], max_tx_burst=cfg["max_tx_burst"]))


class Cell:
    """A driver for a variant of l2fwd subclasses this and overrides
    ``experiment_config``, which builds each trial's ``ExperimentConfig``
    from the configuration, the traffic, the trial's rate and its seed."""

    experiment_config = staticmethod(experiment_config)

    def __init__(self, cfg: Dict, traffic: Dict, seed: int,
                 rehearse: bool = False):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        if rehearse:  # the same schedule in far shorter trials
            self.traffic = dict(traffic, trial_s=min(traffic["trial_s"], 2e-4))
        self.rates: List[float] = list(self.traffic["rates_gbps"])
        self.trials: List[Dict] = []
        self._want: Dict = {}

    def _trial(self, i: int) -> Dict:
        from repro.core import EpochRunInfo
        from repro.exp import run_experiment
        info = EpochRunInfo()
        t0 = time.perf_counter()
        try:
            rep = run_experiment(self.experiment_config(
                self.cfg, self.traffic, self.rates[i],
                generate.trial_seed(self.seed, i)), info=info)
            error = None
        except Exception as exc:  # a failed trial is counted, not fatal
            rep, error = None, repr(exc)
        wall = time.perf_counter() - t0
        return {"entry": i, "wall_s": wall, "engine": info.engine,
                "used_jax": bool(info.used_jax),
                "report": None if rep is None else rep.to_dict(),
                "frames": 0 if rep is None else rep.sent, "error": error}

    def _search(self, c: int, annotate=None) -> List[Dict]:
        out = []
        for i in generate.search_order(self.seed, c, len(self.rates)):
            if annotate is None:
                out.append(self._trial(int(i)))
            else:
                with annotate("perfbench.trial"):
                    out.append(self._trial(int(i)))
        return out

    def setup(self) -> None:
        """One whole search: every shape the window's trials use."""
        bad = [t["error"] for t in self._search(0) if t["error"]]
        if bad:
            raise RuntimeError(f"warm-up trial failed: {bad[0]}")

    def window(self, seconds: float, annotate) -> Dict:
        t0 = time.perf_counter()
        c = 1
        while True:
            self.trials += self._search(c, annotate)
            c += 1
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
        window_s = t1 - t0
        walls = np.array([t["wall_s"] for t in self.trials])
        frames = sum(t["frames"] for t in self.trials)
        on_device = [t for t in self.trials
                     if t["engine"] == self.cfg["engine"] and t["used_jax"]]
        failed = sum(1 for t in self.trials if t["error"] is not None)
        return {
            "attempted": len(self.trials), "failed": failed,
            "window_s": window_s,
            "end_to_end": {
                "sim_pkts_per_s": frames / window_s,
                "trial_ms_p95": float(np.percentile(walls, 95)) * 1e3},
            "counters": {
                "searches": c - 1, "trials": len(self.trials),
                "frames": frames, "device_trials": len(on_device),
                "device_frames": sum(t["frames"] for t in on_device),
                "device_wall_s": sum(t["wall_s"] for t in on_device),
                "steered": self.cfg["n_queues"] > 1},
        }

    def release(self) -> None:
        """The trials hold no device state past their own run."""

    def _reference(self, i: int, dtype: Optional[str]) -> Dict:
        key = (i, dtype)
        if key not in self._want:
            self._want[key] = ref.simulate(
                self.cfg, self.traffic["arrivals"], self.rates[i],
                self.traffic["trial_s"], generate.trial_seed(self.seed, i),
                dtype=dtype)
        return self._want[key]

    def check(self, dtype: Optional[str] = None) -> List[List]:
        """Numbers compared with their limits, over every trial of the
        window.  ``dtype`` puts the control (the reference computed in that
        floating type) in the program's place."""
        mismatch = missing = 0
        for t in self.trials:
            if t["report"] is None:
                missing += 1
                continue
            got = (t["report"] if dtype is None
                   else self._reference(t["entry"], dtype))
            mismatch += ref.report_mismatches(got,
                                              self._reference(t["entry"], None))
        return [["report_mismatch", mismatch, 0],
                ["missing_reports", missing, 0]]


# what ``Cell.check`` can put in the program's place: the control, the
# plain reference computed in float32 (wire times and statistics)
VARIANTS = {"control": {"dtype": "float32"}}
