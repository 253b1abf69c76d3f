"""Backend compiles inside the measured window (JAX monitoring events)."""


def read(ctx):
    return ctx["window"]["counters"].get("compiles_in_window")
