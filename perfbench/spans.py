"""The program's own host spans in a traced window, beside the benchmark's.

The program names its work with ``jax.profiler.TraceAnnotation`` spans whose
names start ``repro.`` (``repro.core.telemetry.span``); they lie on the
host plane of the same trace as the device ops, on the same clock.
:func:`load` reads a trace as :func:`perfbench.trace.load` does and adds
those spans, each with its arguments (``rounds`` on the event loop), to
``host_spans``.  The busy, window, module and op reductions of
:mod:`perfbench.trace` read the same on the result: they look only at the
device planes and the window span.

On the host's single Python thread the spans nest five deep inside a
``perfbench.trial``.  :func:`idle_gaps` cuts each idle stretch of the
device at span edges and charges each piece to the innermost span that
covers it, at any depth; ``perfbench/run.py`` gives the largest totals
as the traced result's ``breakdown.idle_gaps``.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from perfbench import trace as tr

PREFIX = "repro."
# where perfbench/run.py writes a traced run's profile, one directory per cell
TRACE_ROOT = Path(__file__).resolve().parents[1] / ".perfbench" / "trace"


@dataclass(frozen=True)
class Span(tr.Span):
    args: Tuple[Tuple[str, float], ...] = ()

    def arg(self, key: str, default: float = 0.0) -> float:
        return next((v for k, v in self.args if k == key), default)


def load(path: str) -> tr.Trace:
    """:func:`perfbench.trace.load`, with the program's ``repro.*`` host
    spans added to ``host_spans`` (sorted by start)."""
    from jax.profiler import ProfileData
    out = tr.load(path)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            out.host_spans.extend(
                Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     args=tuple((k, float(v)) for k, v in e.stats))
                for e in line.events if e.name.startswith(PREFIX))
    out.host_spans.sort(key=lambda s: (s.start_ns, -s.end_ns))
    return out


_LOADED: Dict[str, tr.Trace] = {}


def program_trace(ctx) -> Optional[tr.Trace]:
    """The traced run's trace with the program's spans, for a per-layer
    reader: from ``ctx["trace_path"]`` when the harness gives it, else the
    newest profile under :data:`TRACE_ROOT` whose window is that of
    ``ctx["trace"]``.  None when the run was not traced."""
    if ctx.get("trace") is None:
        return None
    want = ctx["trace"].window()
    paths = ([ctx["trace_path"]] if ctx.get("trace_path") else
             sorted(glob.glob(str(TRACE_ROOT / "*" / "plugins" / "profile"
                                  / "*" / "*.xplane.pb")),
                    key=os.path.getmtime, reverse=True))
    for path in paths:
        if path not in _LOADED:
            _LOADED[path] = load(path)
        if _LOADED[path].window() == want:
            return _LOADED[path]
    return None


def named(trace: tr.Trace, name: str) -> List[Span]:
    """The host spans called ``name`` that lie inside the window."""
    lo, hi = trace.window()
    return [s for s in trace.host_spans
            if s.name == name and s.start_ns >= lo and s.end_ns <= hi]


def self_ns(parent: tr.Span, children: Sequence[tr.Span]) -> float:
    """``parent``'s duration less the part its ``children`` cover
    (``parent`` itself left out of them)."""
    covered = tr.union(tr.clip([s for s in children if s is not parent],
                               parent.start_ns, parent.end_ns))
    return parent.dur_ns - sum(b - a for a, b in covered)


def owners(spans: Sequence[tr.Span], lo: float,
           hi: float) -> List[Tuple[float, float, str]]:
    """``[lo, hi)`` cut at every span edge, each piece named by the
    innermost span that covers it (the window's name where none does).
    Spans from one thread nest, so the innermost one open is the last
    one opened: a stack sweep."""
    inside = sorted((s for s in spans if s.end_ns > lo and s.start_ns < hi),
                    key=lambda s: (s.start_ns, -s.end_ns))
    cuts = sorted({lo, hi} | {min(hi, max(lo, x)) for s in inside
                              for x in (s.start_ns, s.end_ns)})
    out: List[Tuple[float, float, str]] = []
    stack: List[tr.Span] = []
    j = 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(inside) and inside[j].start_ns <= a:
            stack.append(inside[j])
            j += 1
        while stack and stack[-1].end_ns <= a:
            stack.pop()
        out.append((a, b, stack[-1].name if stack else tr.WINDOW_SPAN))
    return out


def idle_gaps(trace: tr.Trace, k: Optional[int] = None) -> List[List]:
    """Idle device seconds inside the window by the innermost host span
    over each piece, the ``k`` largest totals (all when None), averaged
    over devices.  The totals add up to the window less the busy time."""
    lo, hi = trace.window()
    pieces = owners([s for s in trace.host_spans
                     if s.name != tr.WINDOW_SPAN], lo, hi)
    tot: Dict[str, float] = {}
    for ops in trace.device_ops.values():
        busy = tr.union(tr.clip(ops, lo, hi))
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        i = 0
        for a, b, name in pieces:
            while i < len(idle) and idle[i][1] <= a:
                i += 1
            m = i
            while m < len(idle) and idle[m][0] < b:
                part = min(b, idle[m][1]) - max(a, idle[m][0])
                tot[name] = tot.get(name, 0.0) + part / 1e9
                m += 1
    n_dev = max(1, len(trace.device_ops))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])
    return [[n, v / n_dev] for n, v in ranked[:k]]
