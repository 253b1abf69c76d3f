"""Device time of the epoch pass per trial that ran it, in microseconds:
the summed device durations of the wire scan's and the RSS gather's
executions (modules ``jit__scan_i32`` and ``jit__gather``) in the traced
window, over the window's trials on the device path."""
from perfbench import trace as tr

MODULES = ("jit__scan_i32", "jit__gather")


def is_epoch_pass(span) -> bool:
    return span.name in MODULES


def read(ctx):
    if ctx["trace"] is None or not ctx["window"]["counters"].get("device_trials"):
        return None
    secs, n = tr.module_s(ctx["trace"], is_epoch_pass)
    if n == 0:
        return None
    return secs / ctx["window"]["counters"]["device_trials"] * 1e6
