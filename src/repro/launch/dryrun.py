import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (architecture × shape) cell on the
production meshes and extract memory / cost / collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out results/dryrun.jsonl

Each record proves the cell compiles on (16,16)=256 chips (and (2,16,16)=512
for --mesh multi/both) and carries the §Roofline terms.
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.inputs import input_specs
from repro.launch.mesh import make_production_mesh, rules_for
from repro.models import lm
from repro.models.config import ModelConfig
from repro.models.registry import (ARCHS, SHAPES, STEP_KIND, all_cells,
                                   cell_status, get_config)
from repro.optim import adamw
from repro.parallel import hlo_analysis, hlo_counter
from repro.parallel.axes import axis_rules
from repro.parallel.specs import (make_batch_specs, make_cache_specs,
                                  make_param_specs, make_shardings)
from repro.runtime.steps import (make_decode_step, make_prefill_step,
                                 make_train_step)


def _abstract_params(cfg: ModelConfig):
    key = jax.random.PRNGKey(0)
    return jax.eval_shape(lambda: lm.init_params(cfg, key))


def _mem_dict(compiled) -> Dict[str, Any]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for f in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "temp_size_in_bytes"):
        v = getattr(ma, f, None)
        if v is not None:
            out[f] = int(v)
    return out


def run_cell(arch: str, shape: str, multi_pod: bool,
             opt_override=None, lower_only: bool = False) -> Dict[str, Any]:
    cfg = get_config(arch)
    ok, reason = cell_status(cfg, shape)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "step": STEP_KIND[shape],
    }
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    dims = SHAPES[shape]
    kind = STEP_KIND[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    # decode cells always use the tp layout (kv_seq context sharding);
    # train/prefill follow the arch's tuned layout — but pure-FSDP needs the
    # global batch to split across every chip (prefill_32k's batch=32 cannot
    # shard 256 ways; replicated activations would 8x the compute term)
    layout = cfg.parallel_layout
    if kind == "decode" or dims["global_batch"] % mesh.size != 0:
        layout = "tp"
    layout = os.environ.get("REPRO_FORCE_LAYOUT", layout)
    rules = rules_for(mesh, layout)
    rec["layout"] = layout
    n_dev = mesh.size

    t0 = time.time()
    with axis_rules(rules, mesh):
        params_s = _abstract_params(cfg)
        pspecs = make_param_specs(params_s, rules, mesh)
        pshard = make_shardings(pspecs, mesh)
        args = input_specs(cfg, shape)
        if kind == "train":
            opt_cfg = opt_override or adamw.AdamWConfig()
            opt_s = jax.eval_shape(lambda p: adamw.init(opt_cfg, p), params_s)
            ospecs = adamw.OptState(
                step=jax.sharding.PartitionSpec(),
                master=pspecs if opt_cfg.master_fp32 else (),
                m=pspecs, v=pspecs)
            oshard = make_shardings(ospecs, mesh)
            bshard = make_shardings(make_batch_specs(args[0], rules, mesh), mesh)
            step_fn = make_train_step(cfg, opt_cfg, grad_shardings=pshard)
            jitted = jax.jit(step_fn, in_shardings=(pshard, oshard, bshard),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_s, opt_s, args[0])
        elif kind == "prefill":
            bshard = make_shardings(make_batch_specs(args[0], rules, mesh), mesh)
            step_fn = make_prefill_step(cfg, dims["seq_len"])
            jitted = jax.jit(step_fn, in_shardings=(pshard, bshard))
            lowered = jitted.lower(params_s, args[0])
        else:  # decode
            cache_s, token_s, pos_s = args
            cspecs = make_cache_specs(cfg, cache_s, rules, mesh)
            cshard = make_shardings(cspecs, mesh)
            tshard = make_shardings(make_batch_specs(token_s, rules, mesh), mesh)
            qshard = make_shardings(make_batch_specs(pos_s, rules, mesh), mesh)
            step_fn = make_decode_step(cfg)
            jitted = jax.jit(step_fn,
                             in_shardings=(pshard, cshard, tshard, qshard),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_s, cache_s, token_s, pos_s)
        t_lower = time.time() - t0
        rec["lower_s"] = round(t_lower, 2)
        if lower_only:
            rec["status"] = "lowered"
            return rec
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    cost = compiled.cost_analysis() or {}
    hlo_text = compiled.as_text()
    # trip-count-aware static analysis (cost_analysis counts loop bodies once)
    counted = hlo_counter.analyze(hlo_text)
    model_flops = hlo_analysis.model_flops_for_step(
        cfg, kind, dims["seq_len"], dims["global_batch"])
    roof = hlo_analysis.Roofline(
        flops_per_device=counted.dot_flops,
        hbm_bytes_per_device=counted.hbm_bytes,
        wire_bytes_per_device=counted.total_wire_bytes,
        n_devices=n_dev,
        model_flops_total=model_flops,
    )
    rec.update(
        status="ok",
        n_devices=n_dev,
        params=cfg.param_count(),
        active_params=cfg.active_param_count(),
        memory=_mem_dict(compiled),
        xla_cost_analysis={"flops": float(cost.get("flops", 0.0)),
                           "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
        collective_counts=counted.collective_counts,
        collective_op_bytes={k: round(v) for k, v
                             in counted.collective_op_bytes.items()},
        collective_wire_bytes={k: round(v) for k, v
                               in counted.collective_wire_bytes.items()},
        roofline=roof.as_dict(),
    )
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch × shape) cell")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--lower-only", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.all:
        cells = all_cells()
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    out_f = open(args.out, "a") if args.out else None
    n_fail = 0
    for arch, shape in cells:
        for multi in meshes:
            try:
                rec = run_cell(arch, shape, multi, lower_only=args.lower_only)
            except Exception as e:  # noqa: BLE001 — a failed cell is a bug
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if multi else "16x16",
                       "status": "error", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                n_fail += 1
            line = json.dumps(rec)
            print(line if rec.get("status") != "error"
                  else json.dumps({k: rec[k] for k in
                                   ("arch", "shape", "mesh", "status", "error")}))
            if out_f:
                out_f.write(line + "\n")
                out_f.flush()
    if out_f:
        out_f.close()
    if n_fail:
        raise SystemExit(f"{n_fail} cell(s) failed")


if __name__ == "__main__":
    main()
