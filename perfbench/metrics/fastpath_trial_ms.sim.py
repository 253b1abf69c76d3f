"""Host milliseconds per trial that the epoch planner kept on the device
path, averaged over those trials of the window: testbed build, planning,
the device pass and the commit."""


def read(ctx):
    c = ctx["window"]["counters"]
    if not c.get("device_trials"):
        return None
    return 1e3 * c["device_wall_s"] / c["device_trials"]
