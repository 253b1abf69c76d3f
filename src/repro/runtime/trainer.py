"""Trainer runtime: bypass-fed step loop with fault tolerance.

Wires together the paper's dataplane (kernel-stack or bypass feed), the model
step functions, checkpoint/restart, and straggler mitigation:

* **feed choice** — ``feed="bypass"`` (polling, multi-port, pre-issued DMA) or
  ``feed="kernel"`` (blocking baseline); one flag, same loop.
* **checkpoint/restart** — async sharded checkpoints every N steps; on start,
  the trainer resumes from the latest valid checkpoint and fast-forwards the
  deterministic data stream (exact replay).
* **straggler mitigation** — the bypass feed's poll deadline bounds how long a
  slow producer port can stall a step; on timeout the runtime drops the
  stalled transfer and refills from the staging rings (drop-and-refill, the
  inverse of the loadgen's no-drop guarantee), and counts the event.
* **elastic scaling** — restore() re-shards the checkpoint onto whatever mesh
  the relaunch built (pod counts can change between runs).
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core.dataplane import BypassDataplane, KernelStackFeed, make_feed
from repro.data.pipeline import DataConfig, stream_factory
from repro.models import lm
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.parallel.axes import AxisRules, axis_rules
from repro.parallel.specs import (make_batch_specs, make_param_specs,
                                  make_shardings)
from repro.runtime.steps import make_train_step


@dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    feed: str = "bypass"             # bypass | kernel
    feed_ports: int = 1
    feed_depth: int = 3
    step_deadline_s: float = 120.0   # straggler watchdog
    log_every: int = 10
    seed: int = 0


@dataclass
class TrainerState:
    params: Any
    opt_state: adamw.OptState
    step: int = 0


class TrainerRuntime:
    def __init__(self, cfg: ModelConfig, dcfg: DataConfig,
                 tcfg: TrainerConfig,
                 opt_cfg: Optional[adamw.AdamWConfig] = None,
                 mesh=None, rules: Optional[AxisRules] = None):
        self.cfg = cfg
        self.dcfg = dcfg
        self.tcfg = tcfg
        self.opt_cfg = opt_cfg or adamw.AdamWConfig()
        self.mesh = mesh
        self.rules = rules
        self.ckpt = (CheckpointManager(tcfg.ckpt_dir)
                     if tcfg.ckpt_dir else None)
        self.metrics_log: list = []
        self.straggler_events = 0
        self._feed = None

    # -- setup ------------------------------------------------------------------
    def _ctx(self):
        if self.rules is not None:
            return axis_rules(self.rules, self.mesh)
        import contextlib
        return contextlib.nullcontext()

    def init_state(self) -> TrainerState:
        with self._ctx():
            params = lm.init_params(self.cfg, jax.random.PRNGKey(self.tcfg.seed))
            opt_state = adamw.init(self.opt_cfg, params)
        return TrainerState(params=params, opt_state=opt_state, step=0)

    def _shardings(self, params):
        if self.rules is None or self.mesh is None:
            return None, None
        pspecs = make_param_specs(params, self.rules, self.mesh)
        pshard = make_shardings(pspecs, self.mesh)
        ospecs = adamw.OptState(
            step=jax.sharding.PartitionSpec(),
            master=pspecs if self.opt_cfg.master_fp32 else (),
            m=pspecs, v=pspecs)
        oshard = make_shardings(ospecs, self.mesh)
        return pshard, oshard

    def maybe_restore(self, state: TrainerState) -> TrainerState:
        if self.ckpt is None:
            return state
        latest = self.ckpt.latest_step()
        if latest is None:
            return state
        pshard, oshard = self._shardings(state.params)
        tree = {"params": state.params, "opt": state.opt_state}
        shardings = ({"params": pshard, "opt": oshard}
                     if pshard is not None else None)
        restored, step, extra = self.ckpt.restore(latest, tree, shardings)
        print(f"[trainer] restored checkpoint @ step {step}")
        return TrainerState(params=restored["params"], opt_state=restored["opt"],
                            step=step)

    # -- run -------------------------------------------------------------------
    def run(self, state: Optional[TrainerState] = None) -> TrainerState:
        tcfg = self.tcfg
        with self._ctx():
            if state is None:
                state = self.init_state()
                state = self.maybe_restore(state)

            step_fn = make_train_step(self.cfg, self.opt_cfg)
            bshard = None
            if self.mesh is not None:
                pshard, oshard = self._shardings(state.params)
                # commit the state to the step's shardings up front: the
                # step's own outputs come back committed, and a change from
                # uncommitted inputs at step 1 would compile the step twice
                state = TrainerState(
                    params=jax.device_put(state.params, pshard),
                    opt_state=jax.device_put(state.opt_state, oshard),
                    step=state.step)
                probe = stream_factory(self.cfg, self.dcfg)(0, 1)
                bshard = make_shardings(
                    make_batch_specs(next(probe), self.rules, self.mesh),
                    self.mesh)
                jitted = jax.jit(step_fn, in_shardings=(pshard, oshard, bshard),
                                 donate_argnums=(0, 1))
            else:
                jitted = jax.jit(step_fn, donate_argnums=(0, 1))

            factory = stream_factory(self.cfg, self.dcfg,
                                     start_step=state.step,
                                     n_steps=tcfg.steps - state.step)
            # the feed transfers each batch straight into the step's batch
            # sharding (no single-device landing and reshard in the step)
            feed = make_feed(tcfg.feed, factory, depth=tcfg.feed_depth,
                             ports=tcfg.feed_ports, sharding=bshard)
            self._feed = feed
            t_start = time.perf_counter()
            try:
                while state.step < tcfg.steps:
                    try:
                        batch = feed.next_batch(
                            timeout_s=tcfg.step_deadline_s) if isinstance(
                                feed, BypassDataplane) else feed.next_batch()
                    except TimeoutError:
                        # straggler port: drop in-flight, refill, retry once
                        self.straggler_events += 1
                        feed._inflight.clear()
                        batch = feed.next_batch(timeout_s=tcfg.step_deadline_s)
                    if batch is None:
                        break
                    params, opt_state, metrics = jitted(
                        state.params, state.opt_state, batch)
                    state = TrainerState(params=params, opt_state=opt_state,
                                         step=state.step + 1)
                    if state.step % tcfg.log_every == 0 or state.step == 1:
                        m = {k: float(v) for k, v in metrics.items()}
                        m["step"] = state.step
                        m["wall_s"] = time.perf_counter() - t_start
                        self.metrics_log.append(m)
                        print(f"[trainer] step {state.step}: "
                              f"loss={m['loss']:.4f} gnorm={m['grad_norm']:.3f} "
                              f"({m['wall_s']:.2f}s)")
                    if (self.ckpt is not None
                            and state.step % tcfg.ckpt_every == 0):
                        self.ckpt.save(state.step,
                                       {"params": state.params,
                                        "opt": state.opt_state},
                                       extra={"step": state.step})
            finally:
                feed.stop()
                if self.ckpt is not None:
                    self.ckpt.wait()
            return state
