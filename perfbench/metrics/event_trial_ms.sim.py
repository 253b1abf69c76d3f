"""Host milliseconds per trial that fell back to the per-event loop (a
ring would overflow), averaged over those trials of the window."""


def read(ctx):
    c = ctx["window"]["counters"]
    if not c.get("event_trials"):
        return None
    return 1e3 * c["event_wall_s"] / c["event_trials"]
