"""Serving launcher: prefill + decode loop driven by a request load generator.

The serving analogue of the paper's measurement setup: a LoadGen-style
request generator (Poisson/uniform arrivals) offers token-generation requests
to the model server; per-request latency (time-to-first-token for prefill,
per-token decode latency) is timestamped exactly like EtherLoadGen packets.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --requests 32 --prompt-len 64 --gen-len 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.core.telemetry import LatencyRecorder
from repro.models import lm
from repro.models.registry import ARCHS, get_config, get_smoke_config
from repro.runtime.steps import make_decode_step, make_prefill_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if not cfg.has_decode:
        raise SystemExit(f"{args.arch} is encoder-only: no decode serving")
    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))

    B = args.batch
    max_len = args.prompt_len + args.gen_len + (
        cfg.n_patches if cfg.frontend == "vision_patches" else 0)
    prefill = jax.jit(make_prefill_step(cfg, max_len))
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))

    rng = np.random.default_rng(args.seed)
    ttft = LatencyRecorder()
    tpot = LatencyRecorder()
    n_batches = (args.requests + B - 1) // B
    total_tokens = 0
    t_start = time.perf_counter_ns()
    for _ in range(n_batches):
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(B, args.prompt_len)),
            jnp.int32)}
        if cfg.frontend == "vision_patches":
            batch["patches"] = jnp.asarray(
                rng.standard_normal((B, cfg.n_patches, cfg.d_model)) * 0.02,
                jnp.dtype(cfg.compute_dtype))
        t0 = time.perf_counter_ns()
        logits, cache = prefill(params, batch)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        jax.block_until_ready(tok)
        ttft.record(time.perf_counter_ns() - t0)
        pos0 = args.prompt_len + (cfg.n_patches
                                  if cfg.frontend == "vision_patches" else 0)
        for i in range(args.gen_len):
            t1 = time.perf_counter_ns()
            pos = jnp.full((B,), pos0 + i, jnp.int32)
            tok, logits, cache = decode(params, cache, tok, pos)
            jax.block_until_ready(tok)
            tpot.record(time.perf_counter_ns() - t1)
            total_tokens += B
    wall_s = (time.perf_counter_ns() - t_start) / 1e9
    print(f"[serve] {args.requests} requests, {total_tokens} generated tokens "
          f"in {wall_s:.2f}s ({total_tokens / wall_s:.1f} tok/s)")
    print(f"[serve] TTFT: {ttft.stats()}")
    print(f"[serve] per-token: {tpot.stats()}")


if __name__ == "__main__":
    main()
