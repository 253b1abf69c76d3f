"""The plain l2fwd reference against a second witness, the simulator's
per-event loop (``engine="event"``): identical reports on short trials, in
and out of the fast-path regime, on one port and on four, with RSS over
several queues too; and its control (the trial computed in float32) does
not agree."""
import numpy as np
import pytest

from perfbench import spec
from perfbench.drivers.l2fwd_sim import experiment_config
from perfbench.reference import l2fwd as ref
from repro.exp import run_experiment

CFG1 = spec.config_named("l2fwd-dpdk-1port-1518")
CFG4 = spec.config_named("l2fwd-dpdk-4port-1518")


def _event(cfg, traffic, rate, seed):
    return run_experiment(experiment_config(dict(cfg, engine="event"),
                                            traffic, rate, seed)).to_dict()


@pytest.mark.parametrize("cfg,kind,rate,trial_s,changes,drops", [
    (CFG1, "uniform", 24.8, 5e-4, {}, False),
    (CFG1, "uniform", 40.0, 2e-3, {}, True),
    (CFG4, "uniform", 99.2, 5e-4, {}, False),
    (CFG4, "uniform", 102.4, 1e-3, {"ring_size": 64}, True),
    (CFG1, "poisson", 90.0, 3e-4,
     {"n_queues": 4, "n_lcores": 2, "writeback_threshold": 8}, False),
])
def test_reference_equals_the_event_loop(cfg, kind, rate, trial_s, changes,
                                         drops):
    cfg = dict(cfg, **changes)
    traffic = {"arrivals": kind, "trial_s": trial_s}
    seed = 2**31 + 99
    want = _event(cfg, traffic, rate, seed)
    got = ref.simulate(cfg, kind, rate, trial_s, seed)
    assert ref.report_mismatches(got, want) == 0
    assert (want["dropped"] > 0) == drops


def test_control_in_float32_is_not_correct():
    exact = ref.simulate(CFG4, "uniform", 99.2, 4e-4, 0)
    ctl = ref.simulate(CFG4, "uniform", 99.2, 4e-4, 0, dtype="float32")
    assert ref.report_mismatches(ctl, exact) > 0
    # past 2**24 ns the float32 wire loses whole nanoseconds
    t = ref.emission_schedule("uniform", 99.2, 1518, 20_000_000, 0)[0]
    assert (t[t > 2**24].astype(np.float32).astype(np.int64)
            != t[t > 2**24]).any()


def test_toeplitz_matches_the_rss_spec_vector():
    # Microsoft RSS verification suite: 66.9.149.187:2794 -> 161.142.100.80:1766
    data = bytes([66, 9, 149, 187, 161, 142, 100, 80]) + \
        (2794).to_bytes(2, "big") + (1766).to_bytes(2, "big")
    assert ref.toeplitz(bytes.fromhex(CFG1["rss_key_hex"]), data) == 0x51CCC178
