"""Run one benchmark cell and print its result line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: load, warm up (``setup_s``), measure for ``--seconds``, read
the peak device memory, release the program's state, then compare what the
window produced with the plain reference.  With ``--trace 0`` the result
carries the cell's end-to-end metrics; with ``--trace 1`` the window runs
under the JAX profiler and the result carries its per-layer metrics, the
device's busy and window seconds and a breakdown.

The last line of stdout is one JSON object; the numbers compared, each with
its limit, are the last lines of stderr and the result's last key.  Without
a TPU (or with fewer chips than the cell asks for) the run exits 1 and
prints no result.  ``--rehearse`` runs the same path on the CPU at tiny
sizes, and prints no device metric.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import spec as specmod  # noqa: E402

# a fixed directory of the benchmark's own: entries that other tools left
# under the repo's `.jax_cache` (without the access-time files JAX's cache
# eviction reads) would make every write there fail
CACHE_DIR = ROOT / ".perfbench" / "jax_cache"
TRACE_DIR = ROOT / ".perfbench" / "trace"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny sizes, no device metric")
    return ap.parse_args(argv)


def _environment(rehearse: bool) -> None:
    """Before JAX loads: its persistent compile cache at a fixed path in
    this checkout; for a rehearsal, the CPU."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"


def per_layer(spec, wl, window, trace, trace_path, devs, cfg):
    """Each per-layer metric's reader, given what the run observed (the
    trace with the program's spans, and the profile it was read from); a
    reader that finds nothing to read returns None and is left out."""
    from perfbench import devices
    ctx = {"window": window, "trace": trace, "trace_path": trace_path,
           "config": cfg, "chips": len(devs),
           "peaks": devices.peaks(devs[0].device_kind)}
    out = {}
    for m in specmod.per_layer(spec, wl["name"]):
        value = specmod.reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(trace) -> dict:
    """The ten device ops that took most time, and the ten largest totals
    of idle device time by the innermost span over it, the program's
    ``repro.*`` spans included."""
    from perfbench import spans
    from perfbench import trace as tr
    return {"device_ops": tr.top_ops(trace, k=10),
            "idle_gaps": spans.idle_gaps(trace, k=10)}


def main(argv=None) -> int:
    args = parse(argv)
    spec = specmod.load()
    wl = specmod.workload(spec, args.workload)
    cfg = specmod.config(spec, wl)
    traffic = specmod.traffic(wl)
    _environment(args.rehearse)

    import jax
    from perfbench import devices
    from perfbench import spans
    from perfbench import trace as tr
    from perfbench.compiles import CompileCounter
    try:
        devs = devices.check(jax, wl["chips"], allow_cpu=args.rehearse)
    except devices.NoDevice as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return 1
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileCounter(jax)

    cell = specmod.driver(cfg).Cell(cfg, traffic, args.seed,
                                    rehearse=args.rehearse)
    cell.setup()
    setup_s = time.perf_counter() - T0

    tracing = bool(args.trace)
    trace_dir = TRACE_DIR / wl["name"]
    if tracing:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=tr.options(jax))
    annotate = (jax.profiler.TraceAnnotation if tracing
                else lambda _name: contextlib.nullcontext())
    c0 = compiles.n
    with annotate("perfbench.window"):
        window = cell.window(args.seconds, annotate)
    window["counters"]["compiles_in_window"] = compiles.n - c0
    if tracing:
        jax.profiler.stop_trace()
    peak = devices.memory_peak_bytes(devs)
    cell.release()
    t_check = time.perf_counter()
    checks = cell.check()
    correct = all(v <= lim for _n, v, lim in checks)

    phases = dict(setup_s=setup_s, window_s=window["window_s"],
                  check_s=time.perf_counter() - t_check)
    print(f"perfbench: phases {json.dumps(phases)}", file=sys.stderr)
    for name, value, limit in checks:
        print(f"check {name} {value} limit {limit}", file=sys.stderr)
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"]}
    check_line = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    if args.rehearse:
        result.update(rehearsal=True, checks=check_line)
        print(json.dumps(result), flush=True)
        return 0
    device = dict(devices.describe(devs), memory_peak_bytes=peak)
    if tracing:
        path = tr.find_xplane(str(trace_dir))
        reduced = spans.load(path)
        device.update(busy_s=tr.busy_s(reduced), window_s=tr.window_s(reduced))
        result["metrics"] = per_layer(spec, wl, window, reduced, path, devs,
                                      cfg)
        result["device"] = device
        result["breakdown"] = breakdown(reduced)
    else:
        metrics = {}
        for m in specmod.end_to_end(spec, wl["name"]):
            value = (setup_s if m["name"] == "setup_s"
                     else window["end_to_end"][m["name"]])
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    result["checks"] = check_line
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
