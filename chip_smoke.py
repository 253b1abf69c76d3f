#!/usr/bin/env python3
"""Smoke run of the device paths on a TPU, through the entry points a user
calls.  One process, no child processes.

    python chip_smoke.py                  # one chip: sim, ingest, gather
    python chip_smoke.py --four-chips     # ingest over a 4-device data mesh,
                                          # against the one-chip run
    python chip_smoke.py --interpret      # CPU rehearsal: tiny sizes, every
                                          # Pallas kernel in interpret mode

Phases (one JSON line each, then the result line):

* ``sim``    — the ``benchmarks/fastpath_bench.py`` shape (1 port, 8 RSS
  queues, 8 lcores, ring 1024, 100 Gbit/s, 1518 B, open loop, 0.02 s
  simulated) through ``run_experiment`` with ``engine="epoch-jit"``: it must
  stay on the fast path with the device pass, and its RunReport must be
  bit-identical to ``engine="event"``;
* ``ingest`` — ``TrainerRuntime`` on qwen3-1.7b at full width (depth cut to
  fit one v5e's 16 GB), 4 steps fed by the bypass dataplane, then 4 by the
  kernel-stack feed; every loss finite;
* ``gather`` — the compiled ``burst_gather_pallas`` kernel on a 4096 x
  2048-byte arena, exactly equal to ``kernels.ref.burst_gather``.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without
``--interpret`` the script fails unless JAX's first device is a TPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# qwen3-1.7b cut to fit one v5e: 28 layers with bf16 params and the fp32
# AdamW master, m and v need ~24 GB.  memory_analysis() of the train step
# compiled for v5e at global batch 4 x seq 2048: 4 layers take 6.68 GiB of
# arguments + 7.25 GiB of temporaries = 13.93 of 15.75 GiB (5 layers leave
# under 1.2 GiB); the temporaries are mostly the (4, 2048, 151936) f32
# logits of the loss and their gradient
FULL_LAYERS = 28
INGEST_LAYERS = 4
INGEST_BATCH = 4      # global batch: divides over the 4-device data mesh
INGEST_SEQ = 2048
INGEST_STEPS = 4
GATHER_SLOTS, GATHER_SLOT_BYTES, GATHER_BURST, GATHER_WIDTH = 4096, 2048, 256, 1518
SIM_DURATION_S = 0.02
# step-1 loss on 4 chips vs one chip: one bf16 ulp, relative
FOUR_CHIP_RTOL = 2.0 ** -8


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileCounter:
    """Counts backend compiles (each new executable, cache hit or not) and
    their seconds, through JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1
            self.secs += duration

    def snapshot(self):
        return self.n, self.secs


def sim_phase(compiles: CompileCounter) -> None:
    from benchmarks.fastpath_bench import _cfg, _report_key
    from repro.core import EpochRunInfo
    from repro.exp import run_experiment

    t0 = time.perf_counter()
    ref = run_experiment(_cfg("event", SIM_DURATION_S))
    event_wall = time.perf_counter() - t0

    walls, infos, reps, n_comp, comp_s = [], [], [], [], []
    for _ in range(2):  # cold (compiles), then warm
        c0, s0 = compiles.snapshot()
        info = EpochRunInfo()
        t0 = time.perf_counter()
        reps.append(run_experiment(_cfg("epoch-jit", SIM_DURATION_S),
                                   info=info))
        walls.append(time.perf_counter() - t0)
        infos.append(info)
        n_comp.append(compiles.n - c0)
        comp_s.append(compiles.secs - s0)
    for info in infos:
        assert info.fastpath and info.used_jax, info
        assert info.engine == "epoch-jit", info.engine
    identical = all(_report_key(r) == _report_key(ref) for r in reps)
    assert identical, "epoch-jit RunReport differs from the event loop"
    assert n_comp[1] == 0, f"warm run compiled {n_comp[1]} times"
    emit("sim", ran="run_experiment engine=epoch-jit vs engine=event",
         shape="1 port x 8 RSS queues, 8 lcores, ring 1024, 100 Gbit/s, "
               "1518 B, open loop", simulated_s=SIM_DURATION_S,
         frames=ref.sent, epoch_slices=infos[0].n_epochs,
         fastpath=infos[0].fastpath, used_jax=infos[0].used_jax,
         bit_identical=identical, compiles_cold=n_comp[0],
         compile_cold_s=comp_s[0], compiles_warm=n_comp[1],
         wall_cold_s=walls[0], wall_warm_s=walls[1], event_wall_s=event_wall)


def attention_rule(cfg, seq: int):
    """(forward, backward) attention implementations of the train step."""
    from repro.kernels import ops
    fwd = ops._auto_impl()
    bwd = ops.xla_attention_impl(seq, seq, causal=cfg.causal,
                                 window=cfg.window)
    if fwd == "pallas":
        bwd = f"{bwd} XLA via custom_vjp"
    return fwd, bwd


def train(cfg, dcfg, feed: str, steps: int, mesh=None, rules=None):
    """One TrainerRuntime run; returns (losses, feed stats, wall s, the
    per-step metrics log)."""
    from repro.optim import adamw
    from repro.runtime.trainer import TrainerConfig, TrainerRuntime
    tcfg = TrainerConfig(steps=steps, feed=feed, log_every=1, seed=0)
    rt = TrainerRuntime(cfg, dcfg, tcfg, adamw.AdamWConfig(),
                        mesh=mesh, rules=rules)
    t0 = time.perf_counter()
    state = rt.run()
    wall = time.perf_counter() - t0
    del state
    losses = [m["loss"] for m in rt.metrics_log]
    assert len(losses) == steps and all(map(math.isfinite, losses)), losses
    assert rt._feed.stats.batches == steps, rt._feed.stats
    return losses, rt._feed.stats, wall, rt.metrics_log


def ingest_cfg(tiny: bool):
    from repro.data.pipeline import DataConfig
    from repro.models.registry import get_config, get_smoke_config
    full = get_config("qwen3-1.7b")
    if tiny:
        smoke = get_smoke_config("qwen3-1.7b")
        return (smoke.replace(parallel_layout=full.parallel_layout),
                DataConfig(seq_len=128, global_batch=INGEST_BATCH, seed=0))
    return (full.replace(n_layers=INGEST_LAYERS),
            DataConfig(seq_len=INGEST_SEQ, global_batch=INGEST_BATCH, seed=0))


def ingest_phase(jax, compiles: CompileCounter, tiny: bool) -> None:
    cfg, dcfg = ingest_cfg(tiny)
    fwd, bwd = attention_rule(cfg, dcfg.seq_len)
    if fwd == "pallas":
        assert_pallas_in_step(jax, cfg, dcfg)
    for feed in ("bypass", "kernel"):
        c0, s0 = compiles.snapshot()
        losses, stats, wall, log = train(cfg, dcfg, feed, INGEST_STEPS)
        mem = jax.devices()[0].memory_stats() or {}
        emit("ingest", ran=f"TrainerRuntime feed={feed}", arch=cfg.arch_id,
             d_model=cfg.d_model, n_heads=cfg.n_heads,
             n_kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff,
             vocab=cfg.vocab_size, dtype=cfg.param_dtype,
             layers=cfg.n_layers, full_layers=FULL_LAYERS,
             cut="depth only",
             global_batch=dcfg.global_batch, seq_len=dcfg.seq_len,
             attention_forward=fwd, attention_backward=bwd,
             steps=len(losses), losses=losses, feed_batches=stats.batches,
             feed_devices=stats.devices, feed_wait_ns=stats.wait_ns,
             feed_put_ns=stats.put_ns, compiles=compiles.n - c0,
             compile_s=compiles.secs - s0, wall_s=wall,
             steps_2_to_4_s=log[-1]["wall_s"] - log[0]["wall_s"],
             peak_hbm_bytes=mem.get("peak_bytes_in_use"),
             hbm_limit_bytes=mem.get("bytes_limit"))


def assert_pallas_in_step(jax, cfg, dcfg) -> None:
    """The train step's gradient trace must hold the Pallas attention call
    (no silent reroute to the XLA path)."""
    import jax.numpy as jnp
    from repro.models import lm
    params = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    tok = jax.ShapeDtypeStruct((dcfg.global_batch, dcfg.seq_len), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, b: lm.train_loss(cfg, p, b)[0]))(
        params, {"tokens": tok, "labels": tok})
    assert "pallas_call" in str(jaxpr), "train step has no Pallas attention"


def four_chip_phase(jax, compiles: CompileCounter, tiny: bool) -> None:
    from repro.launch.mesh import make_auto_mesh, rules_for
    cfg, dcfg = ingest_cfg(tiny)
    fwd, bwd = attention_rule(cfg, dcfg.seq_len)
    # reference: the same global batch and seed on one chip, then freed
    one, _stats, one_wall, _ = train(cfg, dcfg, "bypass", 1)
    mesh = make_auto_mesh((4, 1), ("data", "model"))
    rules = rules_for(mesh, cfg.parallel_layout)
    for feed in ("bypass", "kernel"):
        c0, s0 = compiles.snapshot()
        losses, stats, wall, log = train(cfg, dcfg, feed, INGEST_STEPS,
                                         mesh=mesh, rules=rules)
        assert stats.devices == 4, f"batches landed on {stats.devices} devices"
        diff = abs(losses[0] - one[0])
        ok = diff <= FOUR_CHIP_RTOL * abs(one[0])
        emit("ingest-4chip", ran=f"TrainerRuntime feed={feed} on a "
             f"(data=4, model=1) mesh, rules={cfg.parallel_layout}",
             arch=cfg.arch_id, layers=cfg.n_layers,
             global_batch=dcfg.global_batch, seq_len=dcfg.seq_len,
             attention_forward=fwd, attention_backward=bwd,
             feed_devices=stats.devices, losses=losses,
             one_chip_step1_loss=one[0], step1_abs_diff=diff,
             rtol=FOUR_CHIP_RTOL, step1_matches=ok,
             compiles=compiles.n - c0, compile_s=compiles.secs - s0,
             wall_s=wall, one_chip_wall_s=one_wall,
             steps_2_to_4_s=log[-1]["wall_s"] - log[0]["wall_s"])
        assert ok, f"step-1 loss {losses[0]} vs one chip {one[0]}"


def gather_phase(jax, compiles: CompileCounter, tiny: bool) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    from repro.kernels.burst_gather import burst_gather_pallas
    n_slots, burst = (512, 64) if tiny else (GATHER_SLOTS, GATHER_BURST)
    rng = np.random.default_rng(0)
    arena = jnp.asarray(rng.integers(0, 256, size=(n_slots, GATHER_SLOT_BYTES),
                                     dtype=np.uint8))
    slots = jnp.asarray(rng.permutation(n_slots)[:burst], jnp.int32)
    lens = jnp.asarray(rng.integers(1, GATHER_SLOT_BYTES + 1, size=burst),
                       jnp.int32)
    kernel = jax.jit(functools.partial(
        burst_gather_pallas, out_width=GATHER_WIDTH,
        interpret=ops._interpret(False)))
    c0, _s = compiles.snapshot()
    t0 = time.perf_counter()
    compiled = kernel.lower(arena, slots, lens).compile()
    compile_wall = time.perf_counter() - t0
    n_compiles = compiles.n - c0
    custom_call = "tpu_custom_call" in compiled.as_text()
    assert custom_call or tiny, "the gather did not compile to a TPU kernel"
    got = jax.block_until_ready(compiled(arena, slots, lens))
    reps = 10
    t0 = time.perf_counter()
    for _ in range(reps):
        out = compiled(arena, slots, lens)
    jax.block_until_ready(out)
    per_call = (time.perf_counter() - t0) / reps
    want = ref.burst_gather(arena, slots, lens, GATHER_WIDTH)
    equal = bool((np.asarray(got) == np.asarray(want)).all())
    emit("gather", ran="burst_gather_pallas (compiled) vs ref.burst_gather",
         arena=f"{n_slots} x {GATHER_SLOT_BYTES} B", burst=burst,
         out_width=GATHER_WIDTH, tpu_custom_call=custom_call,
         exactly_equal=equal, compiles=n_compiles, compile_s=compile_wall,
         wall_per_call_s=per_call)
    assert equal, "burst_gather_pallas differs from ref.burst_gather"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the ingest phase over a 4-device data mesh, "
                    "with its one-chip comparison")
    ap.add_argument("--interpret", action="store_true",
                    help="CPU rehearsal: tiny sizes, Pallas interpret mode")
    args = ap.parse_args()

    if args.interpret:
        os.environ["REPRO_FORCE_IMPL"] = "pallas"
        os.environ["REPRO_PALLAS_INTERPRET"] = "1"
        if args.four_chips:
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                       " --xla_force_host_platform_device_count=4")
    else:
        for var in ("REPRO_FORCE_IMPL", "REPRO_PALLAS_INTERPRET"):
            if os.environ.get(var):
                print(f"chip_smoke: {var} is set; the chip run takes the "
                      "kernels' own dispatch", file=sys.stderr)
                return 2

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.interpret:
        print(f"chip_smoke: JAX found no TPU (first device: {platform}); "
              "run on the chip, or pass --interpret for the CPU rehearsal",
              file=sys.stderr)
        return 1
    n_need = 4 if args.four_chips else 1
    if len(devices) < n_need:
        print(f"chip_smoke: needs {n_need} devices, JAX has {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    compiles = CompileCounter(jax)
    if args.four_chips:
        four_chip_phase(jax, compiles, args.interpret)
    else:
        sim_phase(compiles)
        ingest_phase(jax, compiles, args.interpret)
        gather_phase(jax, compiles, args.interpret)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
