"""Record ``tiny_v5e_spans.xplane.pb``: a traced window of two trials of the
1-port cell, the shortest one the epoch planner keeps on the device path
and the one that falls back to the event loop (its 4 ms trial fills a
ring; a rehearsal's shorter trials never do), each under a
``perfbench.trial`` span, as ``perfbench/run.py --trace 1`` records them.

    python3 tests/perfbench/data/record_spans.py <out.xplane.pb>

On a TPU; ``JAX_PLATFORMS=cpu`` records the same spans on the CPU.  The
fixture dates from a program whose dropping trial still fell back; the
planner now clips full rings and keeps every trial of this cell on the
device path, so the script finds no event-loop trial and stops.
"""
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from perfbench import spec  # noqa: E402
from perfbench import trace as tr  # noqa: E402

WORKLOAD = "l2fwd-1port.msb"


def main(out: str) -> int:
    bench = spec.load()
    wl = spec.workload(bench, WORKLOAD)
    cfg = spec.config(bench, wl)
    cell = spec.driver(cfg).Cell(cfg, spec.traffic(wl), seed=1)
    warm = [cell._trial(i) for i in range(len(cell.rates))]  # compiles too
    fast = min((t for t in warm if t["used_jax"]),
               key=lambda t: t["frames"])["entry"]
    slow = next((t["entry"] for t in warm if t["engine"] == "event"), None)
    if slow is None:
        raise SystemExit("no trial of the cell falls back to the event loop")
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=tr.options(jax))
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            for i in (fast, slow):
                with jax.profiler.TraceAnnotation("perfbench.trial"):
                    cell._trial(i)
        jax.profiler.stop_trace()
        shutil.copy(tr.find_xplane(tmp), out)
    print(f"entries {fast} (device path) and {slow} (event loop) -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
