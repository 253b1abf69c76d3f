"""Host milliseconds of the epoch planner per plan, less its device
passes: each ``repro.epoch.plan`` span in the traced window less the part
its ``repro.epoch.pass`` spans cover, averaged over the plans (those of
trials that then fall back included)."""
from perfbench import spans


def read(ctx):
    trace = spans.program_trace(ctx)
    plans = [] if trace is None else spans.named(trace, "repro.epoch.plan")
    if not plans:
        return None
    passes = spans.named(trace, "repro.epoch.pass")
    return sum(spans.self_ns(p, passes) for p in plans) / len(plans) / 1e6
