"""Share of the window's trials that the epoch planner kept on the device
path (``EpochRunInfo.engine`` is the configured engine and the pass ran on
JAX), in percent."""


def read(ctx):
    c = ctx["window"]["counters"]
    if not c.get("trials"):
        return None
    return 100.0 * c["device_trials"] / c["trials"]
