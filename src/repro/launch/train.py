"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
        --steps 50 --feed bypass --ports 2 --ckpt-dir /tmp/ckpt

``--smoke`` selects the reduced config (CPU-runnable); the full configs are
for real pods (and are exercised via the dry-run here).  ``--mesh`` attaches
the production mesh/rules when multiple devices exist.
"""
from __future__ import annotations

import argparse

import jax

from repro.launch.compile_cache import enable_compile_cache
from repro.data.pipeline import DataConfig
from repro.launch.mesh import make_production_mesh, make_smoke_mesh, rules_for
from repro.models.registry import ARCHS, get_config, get_smoke_config
from repro.optim import adamw
from repro.runtime.trainer import TrainerConfig, TrainerRuntime


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--feed", choices=["bypass", "kernel"], default="bypass")
    ap.add_argument("--ports", type=int, default=1)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["none", "single", "multi"],
                    default="none")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    dcfg = DataConfig(seq_len=args.seq_len, global_batch=args.global_batch,
                      seed=args.seed)
    tcfg = TrainerConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                         ckpt_dir=args.ckpt_dir, feed=args.feed,
                         feed_ports=args.ports, feed_depth=args.depth,
                         log_every=args.log_every, seed=args.seed)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
                                decay_steps=args.steps)

    mesh = rules = None
    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
        rules = rules_for(mesh)

    runtime = TrainerRuntime(cfg, dcfg, tcfg, opt_cfg, mesh=mesh, rules=rules)
    state = runtime.run()
    print(f"[train] finished at step {state.step}; "
          f"stragglers={runtime.straggler_events}")
    if runtime.metrics_log:
        first, last = runtime.metrics_log[0], runtime.metrics_log[-1]
        print(f"[train] loss {first['loss']:.4f} -> {last['loss']:.4f}")


if __name__ == "__main__":
    main()
