"""Benchmark driver: one section per paper table, plus suite sweeps.

CSV sections emit ``name,us_per_call,derived`` rows through
:data:`benchmarks.common.ROWS`; "text" sections (fastpath, parallel) deliver
primarily through their JSON artifacts.  The CSV header appears only when a
selected section is a CSV one — ``--only fastpath`` no longer prints a
stray header over a JSON-artifact run.  ``--out`` writes the structured
per-section report (every emitted row, grouped by section) as JSON.

``--suite <grid> --workers N`` bypasses the sections entirely and runs a
declarative sweep grid (:mod:`benchmarks.suite`) across a worker pool,
writing the merged trial artifact to ``--out``.
"""
from __future__ import annotations

import argparse
import json
from typing import List, Optional, Sequence, Tuple

from repro.launch.compile_cache import enable_compile_cache

Section = Tuple[str, str, object]  # (name, "csv" | "text", thunk)


def select_sections(sections: Sequence[Section],
                    only: Optional[str]) -> List[Section]:
    """The sections one invocation will run (all of them, or the ``--only``
    pick)."""
    if only is None:
        return list(sections)
    return [s for s in sections if s[0] == only]


def needs_csv_header(sections: Sequence[Section]) -> bool:
    """True iff any selected section emits CSV rows — the only case the
    ``name,us_per_call,derived`` header belongs in the output."""
    return any(fmt == "csv" for _name, fmt, _fn in sections)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=["fig3a", "fig3b", "fig4", "incast", "aqm",
                             "serving", "latency", "fastpath", "parallel"])
    # VIRTUAL seconds per MSB trial since the SimClock refactor: a few ms of
    # simulated traffic is statistically plenty and runs fast at any rate
    ap.add_argument("--trial-s", type=float, default=0.004)
    ap.add_argument("--out", default=None,
                    help="write the structured section report (or the suite "
                    "artifact with --suite) to this JSON path")
    ap.add_argument("--workers", type=int, default=4,
                    help="worker-pool size for --suite and the parallel "
                    "section")
    ap.add_argument("--suite", default=None,
                    help="run a named sweep grid (e.g. fig3a-grid) through "
                    "the parallel suite runner instead of the sections")
    ap.add_argument("--cache-dir", default=None,
                    help="per-trial result cache for --suite (content-keyed; "
                    "re-runs only changed configs)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.suite:
        from . import suite as suite_mod
        trials = suite_mod.named_grid(args.suite, trial_s=args.trial_s)
        merged, timing = suite_mod.run_suite(trials, workers=args.workers,
                                             cache_dir=args.cache_dir)
        out = args.out or f"SUITE_{args.suite}.json"
        suite_mod.write_suite_json(out, merged)
        print(f"# suite {args.suite}: {timing['n_trials']} trials "
              f"({timing['n_cache_hits']} cached) in {timing['wall_s']:.2f}s "
              f"= {timing['trials_per_s']:.2f} trials/s "
              f"[workers={timing['workers']}] -> {out}")
        return

    from . import (fastpath_bench, fig3a_scalability, fig3b_sensitivity,
                   fig4_dca_burst, fig_aqm, fig_incast, fig_serving,
                   parallel_bench, tbl_latency)
    from .common import ROWS

    sections: List[Section] = [
        ("fig3a", "csv", lambda: fig3a_scalability.run(trial_s=args.trial_s)),
        ("fig3b", "csv", lambda: fig3b_sensitivity.run(trial_s=args.trial_s)),
        ("fig4", "csv", lambda: fig4_dca_burst.run(duration_s=args.trial_s)),
        ("incast", "csv",
         lambda: fig_incast.run(trial_s=min(args.trial_s, 0.001))),
        ("aqm", "csv", lambda: fig_aqm.run(trial_s=min(args.trial_s, 0.005))),
        ("serving", "csv",
         lambda: fig_serving.run(trial_s=min(args.trial_s, 0.002))),
        ("latency", "csv", tbl_latency.run),
        ("fastpath", "text", lambda: fastpath_bench.run(quick=True)),
        ("parallel", "text",
         lambda: parallel_bench.run(quick=True, workers=args.workers)),
    ]
    selected = select_sections(sections, args.only)
    if needs_csv_header(selected):
        print("name,us_per_call,derived")
    report = {}
    for name, _fmt, fn in selected:
        before = len(ROWS)
        fn()
        report[name] = ROWS[before:]
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"sections": report}, f, indent=2, sort_keys=True)
            f.write("\n")


if __name__ == '__main__':
    main()
