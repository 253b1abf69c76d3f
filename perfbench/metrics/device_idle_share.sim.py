"""Share of the traced window in which no operation ran on the device, in
percent: one minus the union of the device's op intervals over the
window, averaged over the chips used."""
from perfbench import trace as tr


def read(ctx):
    if ctx["trace"] is None:
        return None
    win = tr.window_s(ctx["trace"])
    if win <= 0 or not ctx["trace"].device_ops:
        return None
    return 100.0 * (1.0 - tr.busy_s(ctx["trace"]) / win)
