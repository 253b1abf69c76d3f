"""Telemetry edge cases: degenerate throughput windows and exhaustive
ServerStats merges — the counters every RunReport is assembled from."""
from dataclasses import dataclass

import numpy as np
import pytest

from repro.core import ServerStats, ThroughputMeter
from repro.core.kernel_stack import KernelStats


# -- ThroughputMeter ----------------------------------------------------------

def test_throughput_meter_degenerate_window_reports_nonzero():
    """Regression: a single completion landing in one terminal flush gives
    start_ns == end_ns; the meter used to report 0 Gbps (as if nothing
    moved).  It must measure over the 1 ns tick floor instead."""
    m = ThroughputMeter()
    m.on_packet(1518, 1_000)
    assert m.packets == 1
    assert m.gbps > 0
    assert m.mpps > 0


def test_throughput_meter_degenerate_merge_counts_window():
    m = ThroughputMeter()
    m.merge_counts(4, 4 * 512, 7_000, 7_000)  # burst at one instant
    assert m.gbps > 0 and m.mpps > 0


def test_throughput_meter_empty_still_zero():
    m = ThroughputMeter()
    assert m.elapsed_s == 0.0
    assert m.gbps == 0.0
    assert m.mpps == 0.0


def test_throughput_meter_normal_window_unchanged():
    m = ThroughputMeter()
    m.on_packet(1000, 0)
    m.on_packet(1000, 1_000_000)  # 2000 B over 1 ms
    assert m.elapsed_s == pytest.approx(1e-3)
    assert m.gbps == pytest.approx(2000 * 8 / 1e9 / 1e-3)
    assert m.mpps == pytest.approx(2 / 1e6 / 1e-3)


def test_throughput_meter_open_window_anchors_start():
    m = ThroughputMeter()
    m.open_window(100)
    m.on_packet(1518, 1_000_100)
    assert m.elapsed_s == pytest.approx(1e-3)


# -- ServerStats.merge_from ---------------------------------------------------

@dataclass
class _FloatStats(ServerStats):
    busy_frac: float = 0.0


@dataclass
class _BadStats(ServerStats):
    note: str = ""


def test_merge_from_is_exhaustive_over_numeric_fields():
    """Regression: merge_from silently dropped any non-int field a stats
    subclass added; float fields must accumulate like ints do."""
    a = _FloatStats(rx_packets=1, busy_frac=0.5)
    b = _FloatStats(rx_packets=2, busy_frac=0.25)
    a.merge_from(b)
    assert a.rx_packets == 3
    assert a.busy_frac == pytest.approx(0.75)


def test_merge_from_fails_loudly_on_unmergeable_field():
    with pytest.raises(TypeError, match="note"):
        _BadStats().merge_from(_BadStats())


def test_merge_from_still_aggregates_kernel_stats_and_buckets():
    a, b = KernelStats(), KernelStats()
    a.record_burst(4)
    b.record_burst(4)
    b.syscalls = 7
    a.merge_from(b)
    assert a.syscalls == 7
    assert a.burst_count == 2
    assert int(a.burst_buckets.sum()) == 2
    assert isinstance(a.burst_buckets, np.ndarray)


# -- host spans ---------------------------------------------------------------

def _span_cfg(rate_gbps, packet_size, pool_slots=8192):
    from repro.exp import (ExperimentConfig, PoolConfig, PortConfig,
                           StackConfig, TrafficConfig)
    return ExperimentConfig(
        pool=PoolConfig(n_slots=pool_slots, slot_size=2048),
        ports=(PortConfig(n_queues=4, ring_size=1024,
                          writeback_threshold=32),),
        stack=StackConfig(kind="bypass", burst_size=64, n_lcores=4),
        traffic=TrafficConfig(mode="open_loop", rate_gbps=rate_gbps,
                              packet_size=packet_size, duration_s=0.0005,
                              engine="epoch-jit"))


# (traffic, what ran) -> every span with the span it nests in
_FAST_TREE = {
    "repro.experiment": None,
    "repro.testbed.build": "repro.experiment",
    "repro.testbed.pool": "repro.testbed.build",
    "repro.testbed.port": "repro.testbed.build",
    "repro.epoch.plan": "repro.experiment",
    "repro.epoch.schedule": "repro.epoch.plan",
    "repro.epoch.wire": "repro.epoch.plan",
    "repro.epoch.pass": "repro.epoch.wire",
    "repro.epoch.cascade": "repro.epoch.plan",
    "repro.epoch.validate": "repro.epoch.plan",
    "repro.epoch.drain": "repro.epoch.plan",
    "repro.epoch.commit": "repro.experiment",
    "repro.report": "repro.epoch.commit",
}
_EVENT_TREE = dict(
    {k: v for k, v in _FAST_TREE.items()
     if k not in ("repro.epoch.drain", "repro.epoch.commit")},
    **{"repro.loadgen.event_loop": "repro.experiment",
       "repro.report": "repro.experiment"})


def _recorded_spans(tmp_path, fn):
    """``fn()`` under a profiler session on the CPU; its ``repro.*`` host
    spans as (name, start, end, stats), sorted by start."""
    import glob

    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:CPU")
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


def _parent(spans, i):
    """The innermost span that holds span ``i`` (None at the root)."""
    _n, a, b, _st = spans[i]
    holders = [s for j, s in enumerate(spans)
               if j != i and s[1] <= a and b <= s[2]]
    return min(holders, key=lambda s: s[2] - s[1])[0] if holders else None


def test_span_is_one_shared_no_op_without_a_profiler():
    from jax.profiler import TraceAnnotation

    from repro.core.telemetry import span
    assert not TraceAnnotation.is_enabled()
    s = span("repro.experiment")
    assert s is span("repro.report") and not isinstance(s, TraceAnnotation)
    with s as inner:
        inner.set_metadata(rounds=3)


@pytest.mark.parametrize("rate,size,slots,ran,tree", [
    (40.0, 1518, 8192, "epoch-jit", _FAST_TREE),
    # 64 B at 100 Gbit/s fills the rings: the plan drops, on the device path
    (100.0, 64, 8192, "epoch-jit", _FAST_TREE),
    # 4 buffers would exhaust: planned, then the event loop runs
    (40.0, 1518, 4, "event", _EVENT_TREE),
], ids=["device-path", "device-path-drops", "fallback"])
def test_a_traced_experiment_records_its_spans_nested(tmp_path, rate, size,
                                                      slots, ran, tree):
    from repro.core import EpochRunInfo
    from repro.exp import run_experiment
    cfg = _span_cfg(rate, size, slots)
    info = EpochRunInfo()
    rep, spans = _recorded_spans(tmp_path,
                                 lambda: run_experiment(cfg, info=info))
    assert info.engine == ran
    assert {s[0] for s in spans} == set(tree)
    for i, s in enumerate(spans):
        assert _parent(spans, i) == tree[s[0]], s[0]
    assert sum(s[0] == "repro.experiment" for s in spans) == 1
    loops = [s for s in spans if s[0] == "repro.loadgen.event_loop"]
    if ran == "event":
        assert len(loops) == 1 and loops[0][3]["rounds"] > 0
    else:
        cascade, = [s for s in spans if s[0] == "repro.epoch.cascade"]
        assert cascade[3]["dropped"] == info.n_dropped == rep.dropped
    # the spans touch no simulated state
    assert rep.to_dict() == run_experiment(cfg).to_dict()


def test_event_loop_span_counts_its_rounds(tmp_path):
    """``rounds`` is the number of rounds the loop ran: all of them when a
    cap cuts the run short."""
    from repro.core import TrafficPattern
    from repro.exp import Testbed
    tb = Testbed.build(_span_cfg(40.0, 1518))
    _rep, spans = _recorded_spans(tmp_path, lambda: tb.loadgen.run_sim(
        tb.server, TrafficPattern(rate_gbps=40.0, packet_size=1518),
        duration_s=0.0005, clock=tb.clock, sched=tb.sched, max_rounds=7))
    loop, = [s for s in spans if s[0] == "repro.loadgen.event_loop"]
    assert loop[3]["rounds"] == 7


def test_port_span_counts_the_key_tables_its_configure_built(tmp_path):
    """A key new to the process is built by the first port that uses it and
    by no other port, in this build or any later one."""
    from dataclasses import replace

    from repro.exp import PortConfig, RssConfig, Testbed
    port = PortConfig(rss=RssConfig(key_hex=bytes(range(90, 130)).hex()))
    cfg = replace(_span_cfg(40.0, 1518), ports=(port, port))
    _tbs, spans = _recorded_spans(
        tmp_path, lambda: [Testbed.build(cfg) for _ in range(2)])
    built = [s[3]["rss_tables_built"] for s in spans
             if s[0] == "repro.testbed.port"]
    assert built == [1, 0, 0, 0]
