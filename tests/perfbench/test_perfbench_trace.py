"""The trace reduction, on a small trace recorded on a TPU v5e: a window
span holding three ``perfbench.trial`` spans, each running two jitted
programs (``jit__lambda``)."""
from pathlib import Path

import pytest

from perfbench import spans
from perfbench import trace as tr

RECORDED = Path(__file__).parent / "data" / "tiny_v5e.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return tr.load(str(RECORDED))


def test_union_merges_overlaps_and_keeps_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == \
        [(0, 4), (5, 7), (8, 9)]
    assert tr.union([]) == []


def test_loads_device_ops_modules_and_benchmark_spans(trace):
    assert list(trace.device_ops) == ["/device:TPU:0"]
    names = sorted({s.name for s in trace.host_spans})
    assert names == ["perfbench.trial", "perfbench.window"]
    assert {m.name for m in trace.device_modules["/device:TPU:0"]} == \
        {"jit__lambda"}
    assert all(op.module == "jit__lambda" and op.name.startswith("%")
               for op in trace.device_ops["/device:TPU:0"])


def test_busy_is_the_union_of_ops_inside_the_window(trace):
    lo, hi = trace.window()
    ops = [(max(s.start_ns, lo), min(s.end_ns, hi))
           for s in trace.device_ops["/device:TPU:0"]
           if s.end_ns > lo and s.start_ns < hi]
    covered, last = 0.0, lo   # ops on one core run one after another
    for a, b in sorted(ops):
        covered += max(0.0, b - max(a, last))
        last = max(last, b)
    assert tr.busy_s(trace) == pytest.approx(covered / 1e9)
    assert 0 < tr.busy_s(trace) < tr.window_s(trace)
    assert tr.window_s(trace) == pytest.approx((hi - lo) / 1e9)


def test_kernel_time_by_stable_name(trace):
    secs, n = tr.module_s(trace, lambda s: s.name == "jit__lambda")
    lo, hi = trace.window()
    want = [s for s in trace.device_modules["/device:TPU:0"]
            if s.start_ns >= lo and s.end_ns <= hi]
    assert n == len(want) > 0
    assert secs == pytest.approx(sum(s.dur_ns for s in want) / 1e9)
    assert tr.module_s(trace, lambda s: s.name == "jit__other") == (0.0, 0)


def test_idle_gaps_name_the_host_span_and_add_up(trace):
    gaps = spans.idle_gaps(trace)
    assert gaps[0][0] == "perfbench.trial"
    idle = tr.window_s(trace) - tr.busy_s(trace)
    assert sum(v for _n, v in gaps) == pytest.approx(idle)
    top = tr.top_ops(trace, k=3)
    assert len(top) == 3 and top[0][0].startswith("jit__lambda:%")
    assert top[0][1] >= top[1][1] >= top[2][1]
