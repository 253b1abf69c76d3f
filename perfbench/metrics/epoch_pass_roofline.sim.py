"""The epoch pass's share of its roofline, in percent: the least time its
bytes need at the chip's peak HBM rate, counted from the unpadded frames
of the trials that ran it, over its device time in the traced window."""
from perfbench import counts
from perfbench import trace as tr
from perfbench import spec

_device_us = spec.reader("epoch_pass_device_us.sim")


def read(ctx):
    c = ctx["window"]["counters"]
    if ctx["trace"] is None or not c.get("device_frames"):
        return None
    secs, n = tr.module_s(ctx["trace"], _device_us.is_epoch_pass)
    if n == 0 or secs <= 0:
        return None
    least = counts.epoch_pass_bytes(c["device_frames"], c["steered"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
