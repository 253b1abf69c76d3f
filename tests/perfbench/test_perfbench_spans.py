"""The program's spans in a trace: idle time charged to the innermost span
at any depth (on hand-built spans), and the readers of the ``repro.*``
spans on a small trace recorded on a TPU v5e: one window holding a
device-path trial and a trial that falls back to the event loop, each
under a ``perfbench.trial`` span (``data/record_spans.py``)."""
import random
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench import devices, run, spans, spec
from perfbench import trace as tr

DATA = Path(__file__).parent / "data"
RECORDED = DATA / "tiny_v5e_spans.xplane.pb"
OLD = DATA / "tiny_v5e.xplane.pb"
DEV = "/device:TPU:0"
NEW = ("testbed_build_ms.sim", "planner_self_ms.sim",
       "epoch_pass_host_us.sim")
B = spec.load()
_V5E = SimpleNamespace(device_kind="TPU v5 lite")
EXISTING = [m["name"] for m in B["per_layer"] if m["name"] not in NEW]
COUNTERS = {"searches": 1, "trials": 2, "frames": 8464, "device_trials": 1,
            "device_frames": 32, "device_wall_s": 0.01, "steered": False,
            "compiles_in_window": 0}


def _trace(host, ops=(), lo=0, hi=100):
    return tr.Trace(device_ops={DEV: [tr.Span("%op", a, b) for a, b in ops]},
                    host_spans=[tr.Span(tr.WINDOW_SPAN, lo, hi)]
                    + [tr.Span(n, a, b) for n, a, b in host])


def _gaps(trace):
    return {n: round(v * 1e9, 6) for n, v in spans.idle_gaps(trace)}


def test_a_gap_after_many_children_goes_to_their_parent():
    kids = [("repro.child", 10 * i, 10 * i + 10) for i in range(5)]
    trace = _trace([("repro.parent", 0, 90)] + kids,
                   ops=[(0, 55), (80, 100)])
    assert _gaps(trace) == {"repro.parent": 25.0}


def test_a_gap_over_two_siblings_is_cut_at_their_edges():
    trace = _trace([("repro.parent", 0, 100), ("repro.a", 10, 30),
                    ("repro.b", 35, 50)], ops=[(0, 20), (45, 100)])
    assert _gaps(trace) == {"repro.a": 10.0, "repro.parent": 5.0,
                            "repro.b": 10.0}


def test_idle_outside_every_span_is_the_windows():
    trace = _trace([("perfbench.trial", 20, 60), ("repro.x", 30, 40)],
                   ops=[(50, 55)])
    assert _gaps(trace) == {tr.WINDOW_SPAN: 60.0, "perfbench.trial": 25.0,
                            "repro.x": 10.0}


@pytest.mark.parametrize("seed", range(5))
def test_idle_totals_add_up_to_window_less_busy(seed):
    rnd = random.Random(seed)
    host = []

    def nest(lo, hi, depth):
        t = lo
        while depth < 6 and t < hi - 2:
            a = rnd.randint(t, hi - 2)
            b = rnd.randint(a + 1, hi)
            host.append((f"repro.d{depth}", a, b))
            nest(a, b, depth + 1)
            t = b
    nest(0, 10_000, 0)
    ops = sorted((a, a + rnd.randint(1, 50))
                 for a in rnd.sample(range(10_000), 200))
    trace = _trace(host, ops=ops, hi=10_000)
    got = spans.idle_gaps(trace)
    idle = tr.window_s(trace) - tr.busy_s(trace)
    assert sum(v for _n, v in got) == pytest.approx(idle, rel=1e-12)
    assert [v for _n, v in got] == sorted((v for _n, v in got), reverse=True)
    assert len(spans.idle_gaps(trace, k=2)) == 2


def test_self_time_leaves_out_what_children_cover():
    parent = tr.Span("repro.plan", 0, 100)
    kids = [tr.Span("repro.pass", 10, 30), tr.Span("repro.pass", 20, 40),
            tr.Span("repro.pass", 90, 120)]
    assert spans.self_ns(parent, kids) == 60
    assert spans.self_ns(parent, kids + [parent]) == 60


# -- the recorded trace ---------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return spans.load(str(RECORDED))


def _raw_host_spans(path):
    """(name, start, end, stats) of every ``repro.*`` host event, straight
    from the profile, for sums done by hand."""
    from jax.profiler import ProfileData
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def _ctx(path, trace_path=True):
    bench = spec.load()
    wl = spec.workload(bench, "l2fwd-1port.msb")
    ctx = {"trace": tr.load(str(path)), "window": {"counters": COUNTERS},
           "config": spec.config(bench, wl), "chips": 1,
           "peaks": devices.peaks("TPU v5 lite")}
    if trace_path:
        ctx["trace_path"] = str(path)
    return ctx


def test_load_adds_the_program_spans_with_their_args(recorded):
    names = {s.name for s in recorded.host_spans}
    assert {"perfbench.window", "perfbench.trial", "repro.experiment",
            "repro.testbed.build", "repro.testbed.pool",
            "repro.testbed.port", "repro.epoch.plan", "repro.epoch.pass",
            "repro.epoch.commit", "repro.loadgen.event_loop",
            "repro.report"} <= names
    loop, = [s for s in recorded.host_spans
             if s.name == "repro.loadgen.event_loop"]
    assert loop.arg("rounds") > 1000
    assert sum(s.name == "perfbench.trial" for s in recorded.host_spans) == 2
    assert sum(s.name == "repro.experiment" for s in recorded.host_spans) == 2


def test_breakdown_charges_idle_time_to_the_program_spans(recorded):
    """The traced result's breakdown: its idle gaps are the span sweep's ten
    largest totals, most of them ``repro.*`` spans; its ops the trace's."""
    got = run.breakdown(recorded)
    assert got["device_ops"] == tr.top_ops(recorded, k=10)
    assert got["idle_gaps"] == spans.idle_gaps(recorded)[:10]
    names = [n for n, _v in got["idle_gaps"]]
    assert names[0] == "repro.loadgen.event_loop"
    assert {"repro.testbed.port", "repro.epoch.pass"} <= set(names)
    idle = tr.window_s(recorded) - tr.busy_s(recorded)
    ours = sum(v for n, v in got["idle_gaps"] if n.startswith("repro."))
    assert ours > 0.9 * idle


@pytest.mark.parametrize("path", [RECORDED, OLD], ids=["spans", "old"])
def test_harness_reads_the_per_layer_metrics_as_before(path, tmp_path,
                                                       monkeypatch):
    """``run.per_layer`` given the trace with the program's spans and its
    path reads every metric of the cell as the readers did given the plain
    trace, finding the profile by its window."""
    d = tmp_path / "cell" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(path, d / "host.xplane.pb")
    monkeypatch.setattr(spans, "TRACE_ROOT", tmp_path)
    monkeypatch.setattr(spans, "_LOADED", {})
    before = _ctx(path, trace_path=False)
    wl = spec.workload(B, "l2fwd-1port.msb")
    want = {m["name"]: spec.reader(m["name"]).read(before)
            for m in spec.per_layer(B, wl["name"])}
    got = run.per_layer(B, wl, before["window"], spans.load(str(path)),
                        str(path), [_V5E], before["config"])
    assert {n: v["value"] for n, v in got.items()} == \
        {n: v for n, v in want.items() if v is not None}
    assert got and all(v["unit"] for v in got.values())


def test_idle_time_goes_to_the_program_spans(recorded):
    gaps = dict(spans.idle_gaps(recorded))
    idle = tr.window_s(recorded) - tr.busy_s(recorded)
    assert sum(gaps.values()) == pytest.approx(idle)
    ours = sum(v for n, v in gaps.items() if n.startswith("repro."))
    assert ours > 0.9 * idle
    assert max(gaps, key=gaps.get) == "repro.loadgen.event_loop"


def test_testbed_build_ms_by_hand():
    raw = [s for s in _raw_host_spans(RECORDED)
           if s[0] == "repro.testbed.build"]
    want = sum(b - a for _n, a, b, _s in raw) / len(raw) / 1e6
    got = spec.reader("testbed_build_ms.sim").read(_ctx(RECORDED))
    assert len(raw) == 2 and got == pytest.approx(want)


def test_planner_self_ms_by_hand():
    raw = _raw_host_spans(RECORDED)
    plans = [s for s in raw if s[0] == "repro.epoch.plan"]
    passes = [s for s in raw if s[0] == "repro.epoch.pass"]
    # the passes of a plan run one after another inside it
    want = (sum(b - a for _n, a, b, _s in plans)
            - sum(b - a for _n, a, b, _s in passes)) / len(plans) / 1e6
    got = spec.reader("planner_self_ms.sim").read(_ctx(RECORDED))
    assert len(plans) == 2 and got == pytest.approx(want)


def test_epoch_pass_host_us_by_hand():
    passes = [s for s in _raw_host_spans(RECORDED)
              if s[0] == "repro.epoch.pass"]
    want = sum(b - a for _n, a, b, _s in passes) / 1 / 1e3
    got = spec.reader("epoch_pass_host_us.sim").read(_ctx(RECORDED))
    assert len(passes) == 2 and got == pytest.approx(want)


def test_readers_find_the_run_profile_by_its_window(tmp_path, monkeypatch):
    """Without ``trace_path`` in the context, the readers take the profile
    under the trace directory whose window is the traced run's."""
    for name, src in (("a", OLD), ("b", RECORDED)):
        d = tmp_path / name / "plugins" / "profile" / "1"
        d.mkdir(parents=True)
        shutil.copy(src, d / "host.xplane.pb")
    monkeypatch.setattr(spans, "TRACE_ROOT", tmp_path)
    monkeypatch.setattr(spans, "_LOADED", {})
    ctx = _ctx(RECORDED, trace_path=False)
    assert spans.program_trace(ctx).window() == ctx["trace"].window()
    assert spec.reader("testbed_build_ms.sim").read(ctx) == \
        spec.reader("testbed_build_ms.sim").read(_ctx(RECORDED))


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_without_the_program_spans(name):
    """A trace of a program without spans (the old fixture), and a run
    without a trace, give no value and raise nothing."""
    reader = spec.reader(name)
    assert reader.read(_ctx(OLD)) is None
    assert reader.read(dict(_ctx(OLD), trace=None)) is None


@pytest.mark.parametrize("path", [RECORDED, OLD], ids=["spans", "old"])
@pytest.mark.parametrize("name", EXISTING)
def test_existing_readers_read_the_same_with_the_program_spans(path, name):
    plain = _ctx(path)
    extended = dict(plain, trace=spans.load(str(path)))
    assert spec.reader(name).read(extended) == spec.reader(name).read(plain)


@pytest.mark.parametrize("path", [RECORDED, OLD], ids=["spans", "old"])
def test_trace_reductions_read_the_same_with_the_program_spans(path):
    plain, extended = tr.load(str(path)), spans.load(str(path))
    assert tr.window_s(extended) == tr.window_s(plain)
    assert tr.busy_s(extended) == tr.busy_s(plain)
    assert tr.top_ops(extended) == tr.top_ops(plain)
    assert tr.module_s(extended, lambda s: True) == \
        tr.module_s(plain, lambda s: True)
    def key(s):
        return s.start_ns, s.end_ns, s.name
    assert sorted((s for s in extended.host_spans
                   if not s.name.startswith(spans.PREFIX)), key=key) == \
        sorted(plain.host_spans, key=key)
