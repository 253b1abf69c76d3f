"""Reduction of a JAX profiler trace to device busy time and kernel time by
stable name (``perfbench/spans.py`` adds the program's spans and the idle
time charged to them).

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`load` reads it with ``jax.profiler.ProfileData`` into plain
:class:`Span` lists:

* device ops: the events of each ``/device:...`` plane's ``XLA Ops`` line,
  named by their HLO instruction (``%fusion.3``), each tagged with the
  program (``XLA Modules`` event) it ran in;
* device modules: the events of the ``XLA Modules`` line, one per program
  execution, named by the jitted function (``jit__scan_i32``);
* host spans: the benchmark's own ``jax.profiler.TraceAnnotation`` spans
  (names starting ``perfbench.``), from any host thread.

Busy time is the union of a device's op intervals inside the window,
averaged over the devices; the idle share is one minus busy over window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Sequence, Tuple

WINDOW_SPAN = "perfbench.window"
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float
    module: str = ""

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclass
class Trace:
    device_ops: Dict[str, List[Span]] = field(default_factory=dict)
    device_modules: Dict[str, List[Span]] = field(default_factory=dict)
    host_spans: List[Span] = field(default_factory=list)

    def window(self) -> Tuple[float, float]:
        """(start, end) of the benchmark's window span."""
        spans = [s for s in self.host_spans if s.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        return spans[0].start_ns, spans[0].end_ns


def options(jax):
    """Profiler options for a traced window: device and host activity and
    annotations, without the Python call tracer (which records every
    Python call and would slow a host-bound run many times over)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(hlo_text: str) -> str:
    """``%fusion.3 = s32[...] fusion(...)`` -> ``%fusion.3``."""
    return hlo_text.split(" = ", 1)[0]


def module_name(event_name: str) -> str:
    """``jit__gather(3296312864593532255)`` -> ``jit__gather``."""
    return _MODULE_ID.sub("", event_name)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = sorted((Span(module_name(e.name), e.start_ns,
                                e.start_ns + e.duration_ns)
                           for e in lines["XLA Modules"].events)
                          if "XLA Modules" in lines else [],
                          key=lambda s: s.start_ns)
            starts = [m.start_ns for m in mods]
            ops = []
            for e in lines["XLA Ops"].events:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i].name if i >= 0 and e.start_ns < mods[i].end_ns \
                    else ""
                ops.append(Span(op_name(e.name), e.start_ns,
                                e.start_ns + e.duration_ns, mod))
            tr.device_ops[plane.name] = sorted(ops, key=lambda s: s.start_ns)
            tr.device_modules[plane.name] = mods
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                tr.host_spans.extend(
                    Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith("perfbench."))
    return tr


def clip(spans: Sequence[Span], lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s.start_ns, lo), min(s.end_ns, hi)) for s in spans
            if s.end_ns > lo and s.start_ns < hi]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge intervals into disjoint ones, in order."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_s(tr: Trace) -> float:
    """Seconds in which an op ran on the device, averaged over devices."""
    lo, hi = tr.window()
    per_dev = [sum(b - a for a, b in union(clip(ops, lo, hi)))
               for ops in tr.device_ops.values()]
    return (sum(per_dev) / len(per_dev)) / 1e9 if per_dev else 0.0


def window_s(tr: Trace) -> float:
    lo, hi = tr.window()
    return (hi - lo) / 1e9


def _inside(spans: Dict[str, List[Span]], lo: float, hi: float):
    for per_dev in spans.values():
        for s in per_dev:
            if s.start_ns >= lo and s.end_ns <= hi:
                yield s


def module_s(tr: Trace, match: Callable[[Span], bool]) -> Tuple[float, int]:
    """(seconds, executions) of the programs ``match`` picks that ran
    inside the window, summed over devices."""
    lo, hi = tr.window()
    picked = [s for s in _inside(tr.device_modules, lo, hi) if match(s)]
    return sum(s.dur_ns for s in picked) / 1e9, len(picked)


def leaves(ops: List[Span]) -> List[Span]:
    """The ops that contain no other op (a ``while`` loop's event spans the
    events of its body); ``ops`` sorted by start."""
    return [s for s, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start_ns >= s.end_ns]


def top_ops(tr: Trace, k: int = 10) -> List[List]:
    """The ``k`` device ops (by program and op name, containers left out)
    that took most time, in seconds averaged over devices."""
    lo, hi = tr.window()
    tot: Dict[str, float] = {}
    for s in _inside({d: leaves(ops) for d, ops in tr.device_ops.items()},
                     lo, hi):
        key = f"{s.module}:{s.name}" if s.module else s.name
        tot[key] = tot.get(key, 0.0) + s.dur_ns / 1e9
    n_dev = max(1, len(tr.device_ops))
    return [[n, v / n_dev]
            for n, v in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

