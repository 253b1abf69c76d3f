"""Readings that the limits of ``correct`` are set from, several seeds in
one process (the benchmark's own runs do not run this).

    python3 perfbench/control.py --config <name> --traffic <name>
        --seeds 1,2,3 [--seconds 2] [--variants all|none|control,half_batch]

The configuration and the traffic are named by their files in
``perfbench/configs`` and ``perfbench/traffic``, so a cell that is not (or
not yet) in ``BENCHMARK.json`` can be read too; it runs on one chip.
For each seed: the cell's set-up and a short window at its own load, then
the numbers compared for the program (``program``) and for each variant of
the driver's ``VARIANTS`` put in the program's place: the control (the
reference in the next lower precision).  One JSON
line per seed.  ``--rehearse`` runs it on the CPU at tiny sizes.
"""
import argparse
import contextlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import spec as specmod  # noqa: E402
from perfbench.run import _environment  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--variants", default="all")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cfg = specmod.config_named(args.config)
    traffic = specmod.traffic({"traffic": args.traffic})
    _environment(args.rehearse)
    import jax
    from perfbench import devices
    devs = devices.check(jax, 1, allow_cpu=args.rehearse)
    driver = specmod.driver(cfg)
    names = {"all": list(driver.VARIANTS), "none": []}.get(
        args.variants, args.variants.split(","))
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = driver.Cell(cfg, traffic, seed, rehearse=args.rehearse)
        cell.setup()
        win = cell.window(args.seconds, lambda _n: contextlib.nullcontext())
        cell.release()
        out = {"seed": seed, "attempted": win["attempted"],
               "device": devices.describe(devs),
               "program": {n: v for n, v, _lim in cell.check()}}
        for name in names:
            out[name] = {n: v for n, v, _lim in
                         cell.check(**driver.VARIANTS[name])}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
