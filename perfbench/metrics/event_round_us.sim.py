"""Host microseconds per round of the per-event loop: the summed
``repro.loadgen.event_loop`` spans in the traced window over the sum of
their ``rounds`` arguments."""
from perfbench import spans


def read(ctx):
    trace = spans.program_trace(ctx)
    loops = [] if trace is None else spans.named(trace,
                                                 "repro.loadgen.event_loop")
    rounds = sum(s.arg("rounds") for s in loops)
    if not rounds:
        return None
    return sum(s.dur_ns for s in loops) / rounds / 1e3
