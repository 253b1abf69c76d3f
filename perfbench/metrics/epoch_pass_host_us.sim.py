"""Host microseconds of the epoch pass per trial on the device path: the
summed ``repro.epoch.pass`` spans in the traced window (padding, the copy
to the device, dispatch, the wait and the copy back), over the window's
device-path trials, the base ``epoch_pass_device_us.sim`` divides by."""
from perfbench import spans


def read(ctx):
    trials = ctx["window"]["counters"].get("device_trials")
    trace = spans.program_trace(ctx)
    passes = [] if trace is None else spans.named(trace, "repro.epoch.pass")
    if not trials or not passes:
        return None
    return sum(s.dur_ns for s in passes) / trials / 1e3
