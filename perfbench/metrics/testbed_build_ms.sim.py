"""Host milliseconds per testbed build, averaged over the builds in the
traced window: the program's ``repro.testbed.build`` span (pool, ports,
server, load generator), one per trial."""
from perfbench import spans


def read(ctx):
    trace = spans.program_trace(ctx)
    builds = [] if trace is None else spans.named(trace, "repro.testbed.build")
    if not builds:
        return None
    return sum(s.dur_ns for s in builds) / len(builds) / 1e6
