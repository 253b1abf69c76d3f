"""The plain l2fwd reference against a second witness, the simulator's
per-event loop (``engine="event"``): identical reports on short trials, in
and out of the fast-path regime, on one port and on four, with RSS over
several queues too, with one frame size and with a frame-size mix; and its
control (the trial computed in float32) does not agree."""
import numpy as np
import pytest

from perfbench import spec
from perfbench.drivers.l2fwd_sim import experiment_config
from perfbench.reference import l2fwd as ref
from repro.core import TrafficPattern
from repro.exp import Testbed, run_experiment

CFG1 = spec.config_named("l2fwd-dpdk-1port-1518")
CFG4 = spec.config_named("l2fwd-dpdk-4port-1518")
# simple IMIX in Ethernet frame sizes (IP 40, 576 and 1,500 B), 7:4:1
IMIX = [[64, 7], [594, 4], [1518, 1]]


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


def _mixed(cfg, **changes):
    cfg = {**cfg, "frame_mix": IMIX, **changes}
    del cfg["packet_size"]
    return cfg


def _event_trace(cfg, kind, rate, trial_s, seed):
    """The per-event loop fed the reference's own times and sizes as a
    trace (the program cannot be configured with a frame-size mix); the
    configuration's ``packet_size`` is not read on the trace path."""
    times, sizes = ref.emission_schedule(kind, rate, None, int(trial_s * 1e9),
                                         seed, frame_mix=cfg["frame_mix"])
    tb = Testbed.build(experiment_config(
        dict(cfg, engine="event", packet_size=max(b for b, _w in IMIX)),
        {"arrivals": kind, "trial_s": trial_s}, rate, seed))
    pattern = TrafficPattern(trace=list(zip(times.tolist(), sizes.tolist())))
    return tb.loadgen.run_sim(tb.server, pattern, duration_s=trial_s,
                              clock=tb.clock, sched=tb.sched).to_dict()


@pytest.mark.parametrize("cfg,kind,rate,trial_s,changes,drops", [
    (CFG1, "uniform", 6.0, 1e-3, {}, False),
    (CFG4, "uniform", 23.0, 5e-4, {}, False),
    (CFG4, "uniform", 30.0, 1e-3, {"ring_size": 64}, True),
    (CFG4, "poisson", 20.0, 5e-4, {}, False),
    (CFG1, "poisson", 12.0, 3e-4,
     {"n_queues": 4, "n_lcores": 4, "writeback_threshold": 8}, False),
])
def test_mixed_reference_equals_the_event_loop(cfg, kind, rate, trial_s,
                                               changes, drops):
    cfg = _mixed(cfg, **changes)
    seed = 2**31 + 99
    want = _event_trace(cfg, kind, rate, trial_s, seed)
    got = ref.simulate(cfg, kind, rate, trial_s, seed)
    # a trace states no offered rate: the program reports 0 for it there
    assert want["offered_gbps"] == 0.0 and got["offered_gbps"] == rate
    assert ref.report_mismatches(got, dict(want, offered_gbps=rate)) == 0
    assert want["sent"] > 0 and (want["dropped"] > 0) == drops


def test_mixed_control_in_float32_is_not_correct():
    cfg = _mixed(CFG4)
    exact = ref.simulate(cfg, "uniform", 23.0, 4e-4, 0)
    ctl = ref.simulate(cfg, "uniform", 23.0, 4e-4, 0, dtype="float32")
    assert ref.report_mismatches(ctl, exact) > 0


def test_mix_draws_each_size_at_its_weight():
    mean, table = ref.frame_table(IMIX)
    assert mean == 4342 / 12 and table.dtype == np.int32
    assert table.tolist() == [64] * 7 + [594] * 4 + [1518]
    drawn = ref.emission_schedule("uniform", 23.0, None, 2_000_000,
                                  2**31 + 5, frame_mix=IMIX)[1]
    rule = np.random.default_rng(2**31 + 5).integers(0, 12, size=len(drawn))
    assert (drawn == table[rule]).all()
    sizes = drawn[:12_000]
    assert len(sizes) == 12_000
    for b, w in IMIX:
        assert abs(np.mean(sizes == b) - w / 12) <= 0.02


@pytest.mark.parametrize("bad", [
    dict(CFG4, frame_mix=IMIX),                       # with packet_size
    _mixed(CFG4, frame_mix=[[64, 0], [1518, 1]]),     # weight under 1
    _mixed(CFG4, frame_mix=[[64, 1.5], [1518, 1]]),   # weight not whole
    _mixed(CFG4, frame_mix=[[60, 1], [1518, 1]]),     # under 64 B
    _mixed(CFG4, frame_mix=[[64, 1], [4096, 1]]),     # over the slot
])
def test_invalid_mix_raises(bad):
    with pytest.raises(ValueError):
        ref.simulate(bad, "uniform", 23.0, 1e-4, 0)
