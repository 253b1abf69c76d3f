"""With the simulator's timed path broken underneath, a run of a sim cell
(at rehearsal size, past the harness's look for a chip) comes out as not
correct; so does the control put in the program's place.  Every cell runs
under the driver its configuration names, a variant of l2fwd's too."""
import contextlib
import json
import os
import sys
import types

import numpy as np
import pytest

from perfbench import spec
from perfbench.drivers import l2fwd_sim
from repro.core import fastpath
from repro.core.telemetry import LatencyRecorder

B = spec.load()
CELLS = [w["name"] for w in B["workloads"]]


def _unchanged(out, handed):
    return np.asarray(handed, np.int64), out[1], out[2]


def _half(out, handed):
    a = np.array(out[0])
    a[len(a) // 2:] = a[len(a) // 2 - 1]
    return a, out[1], out[2]


def _arrival_late(out, handed):
    a = np.array(out[0])
    a[-1] += 1
    return a, out[1], out[2]


PASS_FAULTS = {"state_unchanged": _unchanged, "half_batch": _half,
               "arrival_altered": _arrival_late}


def _run(cell_name, seed=2**31 + 7, cfg=None):
    wl = spec.workload(B, cell_name)
    cfg = spec.config(B, wl) if cfg is None else cfg
    cell = spec.driver(cfg).Cell(cfg, spec.traffic(wl), seed, rehearse=True)
    cell.setup()
    cell.window(0.3, lambda _n: contextlib.nullcontext())
    cell.release()
    return cell


def _not_correct(cell):
    checks = cell.check()
    return any(v > lim for _n, v, lim in checks), checks


@pytest.mark.parametrize("fault", sorted(PASS_FAULTS))
def test_broken_device_pass_is_not_correct(fault, monkeypatch):
    real = fastpath.epoch_pass_jax

    def broken(handed, *rest):
        return PASS_FAULTS[fault](real(handed, *rest), handed)

    monkeypatch.setattr(fastpath, "epoch_pass_jax", broken)
    bad, checks = _not_correct(_run("l2fwd-4port.msb"))
    assert bad, checks


def test_answer_altered_where_produced_is_not_correct(monkeypatch):
    real = LatencyRecorder.record_many

    def altered(self, rtts_ns):
        rtts = np.array(rtts_ns)
        rtts[len(rtts) // 2] += 1
        real(self, rtts)

    monkeypatch.setattr(LatencyRecorder, "record_many", altered)
    bad, checks = _not_correct(_run("l2fwd-1port.msb"))
    assert bad, checks


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_and_control(cell_name):
    cell = _run(cell_name)
    assert all(v <= lim for _n, v, lim in cell.check())
    if hasattr(cell, "trials"):
        assert cell.trials and all(t["used_jax"] for t in cell.trials)
    driver = spec.driver(spec.config(B, spec.workload(B, cell_name)))
    ctl = cell.check(**driver.VARIANTS["control"])
    assert any(v > lim for _n, v, lim in ctl), ctl


def _variant_driver(monkeypatch, calls):
    """A test-only l2fwd variant: a module that overrides nothing but
    ``experiment_config``, registered as ``perfbench.drivers.l2fwd_variant``
    for this test alone."""
    class Cell(l2fwd_sim.Cell):
        @staticmethod
        def experiment_config(cfg, traffic, rate_gbps, seed):
            calls.append(rate_gbps)
            return l2fwd_sim.experiment_config(cfg, traffic, rate_gbps, seed)

    mod = types.ModuleType("perfbench.drivers.l2fwd_variant")
    mod.Cell, mod.VARIANTS = Cell, l2fwd_sim.VARIANTS
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def test_a_cell_runs_under_the_driver_its_configuration_names(monkeypatch):
    calls = []
    mod = _variant_driver(monkeypatch, calls)
    cfg = dict(spec.config(B, spec.workload(B, "l2fwd-1port.msb")),
               driver="l2fwd_variant")
    assert spec.driver(cfg) is mod
    cell = _run("l2fwd-1port.msb", cfg=cfg)
    assert isinstance(cell, mod.Cell)
    assert len(calls) == len(cell.rates) + len(cell.trials)  # set-up, window
    assert all(v <= lim for _n, v, lim in cell.check())
    ctl = cell.check(**mod.VARIANTS["control"])
    assert any(v > lim for _n, v, lim in ctl), ctl


def test_control_script_runs_the_driver_its_configuration_names(
        monkeypatch, capsys):
    from perfbench import control
    calls = []
    _variant_driver(monkeypatch, calls)
    real = spec.config_named
    monkeypatch.setattr(spec, "config_named", lambda name: dict(
        real(name), driver="l2fwd_variant"))
    for key, default in (("JAX_COMPILATION_CACHE_DIR", ""),
                         ("JAX_PLATFORMS", "cpu")):  # restored afterwards
        monkeypatch.setenv(key, os.environ.get(key, default))
    assert control.main(["--config", "l2fwd-dpdk-1port-1518", "--traffic",
                         "fig3a-msb-1port", "--seeds", str(2**31 + 11),
                         "--seconds", "0.2", "--rehearse"]) == 0
    out = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert calls and out["program"] == {"report_mismatch": 0,
                                        "missing_reports": 0}
    assert out["control"]["report_mismatch"] > 0
