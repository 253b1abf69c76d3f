"""End-to-end behaviour tests for the paper's system.

(1) the kernel-bypass claim itself: the bypass stack sustains strictly more
    bandwidth than the kernel stack on identical hardware/budget;
(2) the DCA burst-size use case: large bursts build deeper queues;
(3) the full trainer: bypass-fed training with checkpoint/restart resumes
    deterministically;
(4) dataplane semantics: bypass and kernel feeds deliver identical batches.
"""
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BypassL2FwdServer, KernelStackServer, LoadGen,
                        PacketPool, Port, SimClock, TrafficPattern,
                        run_burst_experiment)
from repro.core.cost import HostCostModel
from repro.core.dataplane import BypassDataplane, KernelStackFeed
from repro.data.pipeline import DataConfig, stream_factory
from repro.models.registry import get_smoke_config
from repro.runtime.trainer import TrainerConfig, TrainerRuntime


def _mk(kind: str, nports: int = 1):
    pool = PacketPool(8192, 1518)
    # ring small enough that a saturated stack overflows it within the run
    # (in virtual time the tail is always fully drained, so a huge ring
    # would just absorb the backlog instead of dropping)
    ports = [Port.make(pool, ring_size=256, link_gbps=100.0,
                       link_latency_ns=1000) for _ in range(nports)]
    if kind == "bypass":
        server = BypassL2FwdServer(ports, burst_size=64)
    else:
        server = KernelStackServer(ports)
    server.attach_clock(SimClock(), HostCostModel())
    return server, ports


def test_bypass_beats_kernel_stack():
    """The paper's headline: same offered load, kernel stack saturates and
    drops while the bypass stack keeps up (or achieves strictly more)."""
    rate = 6.0  # Gbps — above the kernel stack's modeled capacity (~3.7)
    srv_b, ports_b = _mk("bypass")
    rep_b = LoadGen(ports_b).run_sim(srv_b, TrafficPattern(rate_gbps=rate,
                                                           packet_size=1518),
                                     duration_s=0.005)
    srv_k, ports_k = _mk("kernel")
    rep_k = LoadGen(ports_k).run_sim(srv_k, TrafficPattern(rate_gbps=rate,
                                                           packet_size=1518),
                                     duration_s=0.005)
    assert rep_b.achieved_gbps > rep_k.achieved_gbps
    assert rep_b.drop_pct <= rep_k.drop_pct
    assert rep_k.dropped > 0  # the kernel stack really saturated


def test_kernel_stack_does_more_work_per_packet():
    srv_b, ports_b = _mk("bypass")
    LoadGen(ports_b).run_sim(srv_b, TrafficPattern(rate_gbps=0.1,
                                                   packet_size=512),
                             duration_s=0.05)
    srv_k, ports_k = _mk("kernel")
    LoadGen(ports_k).run_sim(srv_k, TrafficPattern(rate_gbps=0.1,
                                                   packet_size=512),
                             duration_s=0.05)
    # bypass: zero copies & allocations; kernel: ≥3 copies per packet,
    # ≥1 syscall per packet (sendto) + batched read()s, ≥2 allocs per packet
    assert srv_k.stats.copies >= 3 * srv_k.stats.rx_packets
    assert srv_k.stats.syscalls >= srv_k.stats.rx_packets
    assert srv_k.stats.allocs >= 2 * srv_k.stats.rx_packets
    assert srv_k.stats.interrupts > 0
    assert srv_b.stats.rx_packets > 0  # and no copy counters even exist


def test_dca_burst_size_queue_pressure():
    """Paper Fig. 4: processing in bursts of 32 keeps the staging queue
    shallow; waiting for the whole 1024-packet train floods it."""
    tr32, d32 = run_burst_experiment(1024, 32)
    tr1024, d1024 = run_burst_experiment(1024, 1024)
    assert tr32.high_water < tr1024.high_water
    assert tr32.mean < tr1024.mean
    assert d32[d32 >= 0].mean() < d1024[d1024 >= 0].mean()


def test_feeds_deliver_identical_batches():
    cfg = get_smoke_config("qwen3-1.7b")
    dcfg = DataConfig(seq_len=16, global_batch=4, seed=9)
    kf = KernelStackFeed(stream_factory(cfg, dcfg, n_steps=3)(0, 1))
    bp = BypassDataplane(stream_factory(cfg, dcfg, n_steps=3), depth=2, ports=1)
    try:
        for _ in range(3):
            a = kf.next_batch()
            b = bp.next_batch()
            for ka in a:
                np.testing.assert_array_equal(np.asarray(a[ka]),
                                              np.asarray(b[ka]))
        assert bp.next_batch() is None  # clean end of stream
    finally:
        bp.stop()


def test_multiport_feed_covers_global_batch():
    cfg = get_smoke_config("qwen3-1.7b")
    dcfg = DataConfig(seq_len=16, global_batch=8, seed=4)
    bp = BypassDataplane(stream_factory(cfg, dcfg, n_steps=2), depth=2, ports=2)
    try:
        seen = [bp.next_batch() for _ in range(4)]  # 2 steps × 2 ports
        assert all(s is not None for s in seen)
        assert all(s["tokens"].shape == (4, 16) for s in seen)  # 8/2 ports
    finally:
        bp.stop()


@pytest.fixture
def no_jax_compilation_cache():
    """The persistent compilation cache aborts XLA:CPU on reloading the
    trainer's donated-buffer executables (jax 0.4.x limitation); compile
    fresh for this test and restore the cache afterwards."""
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", old)


@pytest.mark.slow  # wall-clock jax training loop (~10s); nightly/-m slow
def test_trainer_checkpoint_restart_determinism(tmp_path,
                                                no_jax_compilation_cache):
    cfg = get_smoke_config("qwen3-1.7b").replace(param_dtype="float32",
                                                 compute_dtype="float32")
    dcfg = DataConfig(seq_len=32, global_batch=2, seed=5)

    def losses_of(run_steps, ckpt_dir):
        t = TrainerRuntime(cfg, dcfg, TrainerConfig(
            steps=run_steps, ckpt_every=2, ckpt_dir=ckpt_dir, feed="bypass",
            log_every=1))
        t.run()
        return {m["step"]: m["loss"] for m in t.metrics_log}

    d1 = str(tmp_path / "a")
    full = losses_of(6, d1)
    # interrupted run: 4 steps, then resume to 6 in a fresh runtime
    d2 = str(tmp_path / "b")
    losses_of(4, d2)
    resumed = losses_of(6, d2)
    for s in (5, 6):
        assert abs(full[s] - resumed[s]) < 1e-4, \
            f"step {s}: {full[s]} vs {resumed[s]} — restart not deterministic"


@pytest.mark.parametrize("feed", ["bypass", "kernel"])
def test_trainer_on_a_mesh_compiles_the_step_once(feed):
    """Under a mesh the feed places every batch with the step's batch
    sharding, and the state starts committed to the step's shardings, so
    the step compiles once (uncommitted step-1 inputs compiled it twice)."""
    from repro.launch.mesh import make_smoke_mesh, rules_for
    cfg = get_smoke_config("qwen3-1.7b").replace(parallel_layout="fsdp")
    mesh = make_smoke_mesh(1)
    rt = TrainerRuntime(cfg, DataConfig(seq_len=32, global_batch=2),
                        TrainerConfig(steps=3, feed=feed, log_every=1),
                        mesh=mesh, rules=rules_for(mesh, "fsdp"))
    compiles = []

    def on_event(event, duration, **kw):
        if (event == "/jax/core/compile/backend_compile_duration"
                and "train_step" in str(kw.get("fun_name"))):
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        rt.run()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert len(compiles) == 1
    assert rt._feed.stats.batches == 3
    assert rt._feed.stats.devices == 1
