"""With the simulator's timed path broken underneath, a run of a sim cell
(at rehearsal size, past the harness's look for a chip) comes out as not
correct; so does the control put in the program's place."""
import contextlib

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


def _run(cell_name, seed=2**31 + 7):
    wl = spec.workload(B, cell_name)
    cell = l2fwd_sim.Cell(spec.config(B, wl), spec.traffic(wl), seed,
                          rehearse=True)
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
    assert cell.trials and all(t["used_jax"] for t in cell.trials)
    ctl = cell.check(**l2fwd_sim.VARIANTS["control"])
    assert any(v > lim for _n, v, lim in ctl), ctl
