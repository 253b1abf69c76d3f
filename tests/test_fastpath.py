"""Epoch-batched fast path: bit-identical RunReports vs the event loop (in
and out of the full-RX-ring drop regime), epoch-slicing invariants,
per-queue writeback thresholds, and the alloc-failure attribution bugfix.

The engine's contract is absolute: for every config, ``engine="epoch"``
produces the same RunReport as ``engine="event"`` — either through the
closed-form fast path (validated pure, committed atomically) or by falling
back to the event loop itself.  These tests pin both halves: fast-path
configs must *stay on* the fast path (and match bit-for-bit), unsupported
configs must fall back (and trivially match).
"""
import numpy as np
import pytest

from repro.core import (BypassL2FwdServer, EpochRunInfo, HostCostModel,
                        LoadGen, PacketPool, Port, SimClock, TrafficPattern,
                        run_epoch_sim)
from repro.core.fastpath import (_build_plan, default_epoch_ns,
                                 iter_epoch_slices)
from repro.exp import (CostConfig, DcaConfig, ExperimentConfig, NodeConfig,
                       PoolConfig, PortConfig, StackConfig, TopologyConfig,
                       TrafficConfig, Testbed, run_experiment, run_testbed)
from repro.exp.config import LinkConfig
from repro.exp.testbed import effective_writeback_threshold
from repro.exp.topology import Cluster


def build(n_queues=4, ring=1024, wb=32, burst=64, n_lcores=4, gbps=40.0,
          lat=1000, pool_slots=8192, nports=1, cost=None):
    pools = [PacketPool(pool_slots, 2048) for _ in range(nports)]
    ports = [Port.make(pools[i], ring_size=ring, writeback_threshold=wb,
                       n_queues=n_queues, link_gbps=gbps, link_latency_ns=lat)
             for i in range(nports)]
    server = BypassL2FwdServer(ports, burst_size=burst, n_lcores=n_lcores)
    clock = SimClock()
    server.attach_clock(clock, cost=cost)
    return server, ports, clock


def report_key(rep):
    """Every observable in a RunReport, comparable bit-for-bit."""
    lat = None if rep.latency is None else rep.latency.as_dict()
    return (rep.offered_gbps, rep.achieved_gbps, rep.achieved_mpps, rep.sent,
            rep.received, rep.dropped, lat,
            tuple(tuple(sorted(h.items())) for h in rep.histogram),
            tuple(sorted(rep.extras.items())))


def queue_stats_key(server):
    return {k: (v.rx_packets, v.tx_packets, v.rx_bytes, v.burst_count,
                v.burst_packets, tuple(v.burst_buckets))
            for k, v in server.per_queue_stats().items()}


def ring_key(ports):
    """Every RX and TX ring counter, with each writeback's size in order."""
    return tuple(
        (tuple(r.writeback_sizes), r.writebacks, r.delivered,
         r.delivered_bytes, r.dropped, r.head, r.tail, r.published,
         t.posted, t.posted_bytes, t.transmitted, t.transmitted_bytes,
         t.head, t.tail)
        for p in ports for r, t in zip(p.rx_queues, p.tx_queues))


def run_pair(pattern, dur, use_jax=False, **kw):
    """One config, both engines, fresh state each: returns both observations
    plus the epoch engine's out-of-band info."""
    server, ports, clock = build(**kw)
    lg = LoadGen(ports)
    rep_e = lg.run_sim(server, pattern, duration_s=dur, clock=clock)
    ev = (report_key(rep_e), queue_stats_key(server), clock.now_ns,
          ring_key(ports))

    server2, ports2, clock2 = build(**kw)
    lg2 = LoadGen(ports2)
    info = EpochRunInfo()
    rep_f = run_epoch_sim(lg2, server2, pattern, duration_s=dur, clock=clock2,
                          use_jax=use_jax, info=info)
    ep = (report_key(rep_f), queue_stats_key(server2), clock2.now_ns,
          ring_key(ports2))
    return ev, ep, info


# -- engine equivalence: fast-path configs ------------------------------------

FASTPATH_CASES = [
    ("uniform-4q", TrafficPattern(rate_gbps=40.0, packet_size=1518),
     0.002, {}),
    ("poisson-4q", TrafficPattern(rate_gbps=40.0, packet_size=1518,
                                  kind="poisson", seed=3), 0.002, {}),
    ("bursty-4q", TrafficPattern(rate_gbps=40.0, packet_size=1518,
                                 kind="bursty", burst_len=32), 0.002, {}),
    ("uniform-1q", TrafficPattern(rate_gbps=2.0, packet_size=1518),
     0.002, dict(n_queues=1, n_lcores=1)),
    ("two-ports", TrafficPattern(rate_gbps=40.0, packet_size=1518),
     0.002, dict(nports=2, n_lcores=8)),
    ("ideal-wire", TrafficPattern(rate_gbps=40.0, packet_size=1518),
     0.001, dict(gbps=0.0, lat=0)),
    ("one-lcore-4q", TrafficPattern(rate_gbps=20.0, packet_size=1518),
     0.002, dict(n_lcores=1)),
    # whole-ring writeback (threshold None): every publish is a ring fill
    ("wb-none", TrafficPattern(rate_gbps=5.0, packet_size=1518),
     0.001, dict(wb=None, ring=64)),
    # 64B @ 100G overloads 4 lcores: the rings genuinely fill and drop (on
    # a 100 GbE wire, so that no wire backlog exhausts the pool)
    ("overload-64B-100G", TrafficPattern(rate_gbps=100.0, packet_size=64),
     0.0005, dict(gbps=100.0)),
    # one lcore at ~551 ns/pkt cannot keep up with 256B @ 10G (~205 ns/pkt)
    ("overload-1q", TrafficPattern(rate_gbps=10.0, packet_size=256),
     0.001, dict(n_queues=1, n_lcores=1)),
]


@pytest.mark.parametrize("name,pattern,dur,kw", FASTPATH_CASES,
                         ids=[c[0] for c in FASTPATH_CASES])
def test_epoch_engine_bit_identical_on_fastpath(name, pattern, dur, kw):
    ev, ep, info = run_pair(pattern, dur, **kw)
    assert info.fastpath, info.fallback_reason  # must NOT have fallen back
    assert info.n_packets > 0
    assert info.n_dropped == ep[0][5]
    assert ev == ep


# -- engine equivalence: fallback configs -------------------------------------

FALLBACK_CASES = [
    # a free PMD never advances its lcore's busy window
    ("zero-cost", TrafficPattern(rate_gbps=5.0, packet_size=1518), 0.0005,
     dict(cost=HostCostModel(pmd_poll_cycles=0, pmd_per_packet_cycles=0))),
    # a 64-frame burst cannot fit a 32-descriptor TX ring
    ("burst-gt-tx-ring", TrafficPattern(rate_gbps=5.0, packet_size=1518),
     0.0005, dict(ring=32)),
]


@pytest.mark.parametrize("name,pattern,dur,kw", FALLBACK_CASES,
                         ids=[c[0] for c in FALLBACK_CASES])
def test_epoch_engine_falls_back_and_matches(name, pattern, dur, kw):
    ev, ep, info = run_pair(pattern, dur, **kw)
    assert not info.fastpath and info.fallback_reason
    assert ev == ep


# -- the full-RX-ring drop regime: the clipped cascade -------------------------

# a cost model whose every burst of h frames costs 200 + 100 h ns: with a
# frame every 100 ns on an ideal wire, every harvest instant is an arrival
_TICK_COST = HostCostModel(cpu_ghz=1.0, pmd_poll_cycles=200,
                           pmd_per_packet_cycles=100)

CLIP_CASES = [
    # burst 8 lets the published backlog pass the threshold of 32, so the
    # frame that fills the 64-descriptor ring writes back a partial batch
    ("ring64-thr32", TrafficPattern(rate_gbps=40.0, packet_size=1518),
     0.0005, dict(n_queues=1, n_lcores=1, ring=64, wb=32, burst=8)),
    # a threshold that does not divide the ring
    ("ring100-thr32", TrafficPattern(rate_gbps=40.0, packet_size=1518),
     0.0005, dict(n_queues=1, n_lcores=1, ring=100, wb=32, burst=32)),
    # four RSS queues behind one overloaded lcore
    ("4q-1lcore", TrafficPattern(rate_gbps=40.0, packet_size=1518),
     0.0005, dict(n_queues=4, n_lcores=1, ring=128, wb=32, burst=32)),
    # 1,250 B at 100 Gbit/s is a frame every 100 ns; bursts of 8 take 1 us
    ("harvest-at-arrival", TrafficPattern(rate_gbps=100.0, packet_size=1250),
     0.0002, dict(n_queues=1, n_lcores=1, ring=64, wb=16, burst=8, gbps=0.0,
                  lat=0, cost=_TICK_COST)),
]


@pytest.mark.parametrize("use_jax", [False, True], ids=["epoch", "epoch-jit"])
@pytest.mark.parametrize("name,pattern,dur,kw", CLIP_CASES,
                         ids=[c[0] for c in CLIP_CASES])
def test_full_ring_clip_is_bit_identical(name, pattern, dur, kw, use_jax):
    """Overloaded rings stay on the fast path: drops, partial full-ring
    writebacks and every counter equal the event loop's."""
    ev, ep, info = run_pair(pattern, dur, use_jax=use_jax, **kw)
    assert info.fastpath, info.fallback_reason
    assert info.used_jax == use_jax
    assert info.n_dropped == ep[0][5] > 0
    assert ev == ep


def _plan(pattern, dur, **kw):
    server, ports, clock = build(**kw)
    return _build_plan(LoadGen(ports), server, pattern, clock, dur, None,
                       False, EpochRunInfo())


@pytest.mark.parametrize("name,pattern,dur,kw", CLIP_CASES[:2],
                         ids=[c[0] for c in CLIP_CASES[:2]])
def test_full_ring_writeback_is_partial(name, pattern, dur, kw):
    """The filling frame writes back fewer than a threshold's descriptors,
    before the quiet-wire flush, in the event loop and in the plan."""
    server, ports, clock = build(**kw)
    LoadGen(ports).run_sim(server, pattern, duration_s=dur, clock=clock)
    sizes = ports[0].rx_queues[0].writeback_sizes
    assert any(s < kw["wb"] for s in sizes[:-1])
    qp, = _plan(pattern, dur, **kw).qplans
    assert qp.wb_sizes == sizes


def test_clip_drops_arrivals_at_the_harvest_instant():
    """An arrival at the very instant of a harvest that finds the ring full
    is dropped before the harvest frees its slot."""
    _name, pattern, dur, kw = CLIP_CASES[3]
    qp, = _plan(pattern, dur, **kw).qplans
    harvest_t = {t for t, _h in qp.harvests}
    last_dropped = {int(qp.arr[hi - 1]) for _lo, hi in qp.drops}
    assert harvest_t & last_dropped


def _l2fwd(nports, rate_gbps):
    """DPDK l2fwd's defaults at Fig. 3(a)'s 1- and 4-NIC points: a queue and
    an lcore per 100 GbE port, 1,024 descriptors, burst 32, 1,518 B frames
    in a 4 ms trial, max(ports x (2,048 + 32 + lcores x 256), 8,192) mbufs."""
    port = PortConfig(n_queues=1, ring_size=1024, writeback_threshold=32,
                      link=LinkConfig(gbps=100.0, latency_ns=1000))
    return ExperimentConfig(
        pool=PoolConfig(n_slots=max(nports * (2080 + nports * 256), 8192),
                        slot_size=2176),
        ports=(port,) * nports,
        stack=StackConfig(kind="bypass", burst_size=32, n_lcores=nports,
                          cost=CostConfig(cpu_ghz=2.0, pmd_poll_cycles=150,
                                          pmd_per_packet_cycles=1100)),
        traffic=TrafficConfig(mode="open_loop", rate_gbps=rate_gbps,
                              packet_size=1518, duration_s=0.004,
                              max_tx_burst=64))


def _testbed_run(cfg, info=None):
    tb = Testbed.build(cfg)
    rep = run_testbed(tb, info=info)
    return (rep.to_dict(), queue_stats_key(tb.server), tb.clock.now_ns,
            ring_key(tb.devs))


@pytest.fixture(scope="module", params=[(1, 25.6), (4, 102.4)],
                ids=["1port-25.6G", "4port-102.4G"])
def dropping_trial(request):
    """A Fig. 3(a) search's dropping trial and its event-loop run."""
    cfg = _l2fwd(*request.param)
    return cfg, _testbed_run(cfg.with_traffic(engine="event"))


@pytest.mark.parametrize("use_jax", [False, True], ids=["epoch", "epoch-jit"])
def test_l2fwd_search_dropping_trial_is_bit_identical(dropping_trial,
                                                      use_jax):
    """The trial of each Fig. 3(a) search that drops runs on the fast path."""
    cfg, ev = dropping_trial
    info = EpochRunInfo()
    ep = _testbed_run(
        cfg.with_traffic(engine="epoch-jit" if use_jax else "epoch"), info)
    assert info.fastpath, info.fallback_reason
    assert info.n_dropped == ep[0]["dropped"] > 0
    assert ev == ep


def test_epoch_jit_matches_when_available():
    pattern = TrafficPattern(rate_gbps=40.0, packet_size=1518, kind="poisson",
                             seed=7)
    ev, ep, info = run_pair(pattern, 0.002, use_jax=True)
    assert info.fastpath and info.used_jax
    assert ev == ep


def test_epoch_jit_raises_when_device_pass_fails(monkeypatch):
    """A failing device pass propagates: no numpy pass, no event loop."""
    import repro.core.fastpath as fp

    def broken(*args, **kwargs):
        raise RuntimeError("device pass unavailable")

    monkeypatch.setattr(fp, "epoch_pass_jax", broken)
    server, ports, clock = build()
    info = EpochRunInfo()
    with pytest.raises(RuntimeError, match="device pass unavailable"):
        run_epoch_sim(LoadGen(ports), server,
                      TrafficPattern(rate_gbps=40.0, packet_size=1518),
                      duration_s=0.001, clock=clock, use_jax=True, info=info)
    assert not info.fastpath
    assert clock.now_ns == 0  # the event loop never ran


@pytest.mark.parametrize("engine,pattern,ran", [
    ("epoch", TrafficPattern(rate_gbps=40.0, packet_size=1518), "epoch"),
    ("epoch-jit", TrafficPattern(rate_gbps=40.0, packet_size=1518),
     "epoch-jit"),
    # 4 buffers starve the run: the exact fallback runs the event loop
    ("epoch-jit", TrafficPattern(rate_gbps=40.0, packet_size=1518), "event"),
], ids=["epoch", "epoch-jit", "epoch-jit-fallback"])
def test_info_engine_names_what_ran(engine, pattern, ran):
    cfg = ExperimentConfig(
        pool=PoolConfig(n_slots=8192 if ran != "event" else 4,
                        slot_size=2048),
        ports=(PortConfig(n_queues=4, ring_size=1024,
                          writeback_threshold=32),),
        stack=StackConfig(kind="bypass", burst_size=64, n_lcores=4),
        traffic=TrafficConfig(mode="open_loop", rate_gbps=pattern.rate_gbps,
                              packet_size=pattern.packet_size,
                              duration_s=0.0005, engine=engine))
    info = EpochRunInfo()
    rep = run_experiment(cfg, info=info)
    assert info.engine == ran
    assert info.fastpath == (ran != "event")
    assert info.used_jax == (ran == "epoch-jit")
    assert report_key(rep) == report_key(
        run_experiment(cfg.with_traffic(engine="event")))


def test_epoch_jit_one_compile_per_bucket():
    """Slices of different lengths share a padded power-of-two shape."""
    from repro.kernels import epoch_fastpath as ef
    rng = np.random.default_rng(0)
    compiled_before = ef._scan_i32._cache_size()
    for n in (1500, 1900, 2048):
        t = np.sort(rng.integers(0, 10**6, size=n)).astype(np.int64)
        s = rng.integers(1, 200, size=n).astype(np.int64)
        want = ef.wire_arrival_pass_np(t, s, 123_456, 50)
        got, busy, _ = ef.epoch_pass_jax(t, s, 123_456, 50, None, None)
        assert np.array_equal(want[0], got) and want[1] == busy
    assert ef._scan_i32._cache_size() - compiled_before <= 1


def test_epoch_jit_rebase_is_exact_and_refuses_int32_overflow():
    from repro.kernels import epoch_fastpath as ef
    base = 5 * 10**12  # absolute times far beyond int32
    t = base + np.array([0, 5, 5, 40, 2**30], dtype=np.int64)
    s = np.array([10, 10, 10, 10, 7], dtype=np.int64)
    for busy0 in (0, base + 3, base + 10**9):  # idle, queued, long backlog
        want = ef.wire_arrival_pass_np(t, s, busy0, 7)
        got, busy, _ = ef.epoch_pass_jax(t, s, busy0, 7, None, None)
        assert np.array_equal(want[0], got) and want[1] == busy
    with pytest.raises(ValueError, match="int32"):
        ef.epoch_pass_jax(t, s + 2**30, 0, 7, None, None)


# -- engine equivalence through run_experiment (paper-config shapes) ----------

def _fig_configs():
    fig3a = ExperimentConfig(
        name="fig3a-like",
        pool=PoolConfig(n_slots=16384, slot_size=1518),
        ports=(PortConfig(n_queues=4, ring_size=1024,
                          writeback_threshold=32),),
        stack=StackConfig(kind="bypass", burst_size=64),
        traffic=TrafficConfig(mode="open_loop", rate_gbps=20.0,
                              duration_s=0.002))
    fig3b = fig3a.with_ports(writeback_threshold=128)
    # fig4-style: sim-time DCA accumulate + writeback-timeout timers — the
    # epoch engine must detect the armed timers and run the event loop
    fig4 = ExperimentConfig(
        name="fig4-like",
        pool=PoolConfig(n_slots=16384, slot_size=1518),
        ports=(PortConfig(n_queues=2, ring_size=1024),),
        stack=StackConfig(kind="bypass", burst_size=32),
        traffic=TrafficConfig(mode="open_loop", rate_gbps=10.0,
                              duration_s=0.002, kind="bursty", burst_len=64),
        dca=DcaConfig(burst_size=64, writeback_threshold=16,
                      writeback_timeout_ns=50_000))
    # timeout-timer dominant: threshold too high to cross within a burst
    timer = fig4.with_dca(writeback_threshold=512, burst_size=32)
    return [("fig3a", fig3a), ("fig3b", fig3b), ("fig4-dca", fig4),
            ("timer", timer)]


@pytest.mark.parametrize("name,cfg", _fig_configs(),
                         ids=[n for n, _ in _fig_configs()])
def test_run_experiment_engine_parity(name, cfg):
    rep_e = run_experiment(cfg.with_traffic(engine="event"))
    rep_f = run_experiment(cfg.with_traffic(engine="epoch"))
    assert report_key(rep_e) == report_key(rep_f)


def test_dca_config_forces_fallback():
    """Armed writeback timers / DCA accumulate are outside the fast-path
    regime; the engine must refuse them statically (not mis-simulate)."""
    _, cfg = _fig_configs()[2]
    tb = Testbed.build(cfg)
    t = cfg.traffic
    pattern = TrafficPattern(rate_gbps=t.rate_gbps, packet_size=t.packet_size,
                             kind=t.kind, burst_len=t.burst_len, seed=t.seed)
    info = EpochRunInfo()
    run_epoch_sim(tb.loadgen, tb.server, pattern, duration_s=t.duration_s,
                  clock=tb.clock, sched=tb.sched, info=info)
    assert not info.fastpath and info.fallback_reason


# -- epoch slicing of the emission schedule -----------------------------------

def _schedules():
    out = []
    for kind, seed in [("uniform", 0), ("poisson", 1), ("bursty", 2)]:
        p = TrafficPattern(rate_gbps=25.0, packet_size=512, kind=kind,
                           seed=seed, burst_len=16)
        times, _ = p.emission_schedule(2_000_000,
                                       np.random.default_rng(seed))
        out.append((kind, np.sort(times)))
    return out


@pytest.mark.parametrize("kind,times", _schedules(),
                         ids=[k for k, _ in _schedules()])
@pytest.mark.parametrize("epoch_ns", [1, 1000, 77_777, 10_000_000])
def test_epoch_slices_partition_in_order(kind, times, epoch_ns):
    """No packet lost or reordered at epoch boundaries: the slices are a
    contiguous, in-order, exhaustive partition of the schedule, and every
    slice stays inside one epoch window."""
    slices = list(iter_epoch_slices(times, epoch_ns))
    assert slices, "nonempty schedule must yield slices"
    assert slices[0][0] == 0 and slices[-1][1] == len(times)
    t0 = int(times[0])
    for (lo, hi), (lo2, _) in zip(slices, slices[1:] + [(len(times), None)]):
        assert lo < hi, "slices are nonempty"
        assert hi == lo2, "slices are contiguous (nothing lost or duplicated)"
        # all times in one slice share the window keyed by its first element
        k = (int(times[lo]) - t0) // epoch_ns
        assert (int(times[hi - 1]) - t0) // epoch_ns == k
    # reassembly is the identity — order preserved
    joined = np.concatenate([times[lo:hi] for lo, hi in slices])
    assert np.array_equal(joined, times)


def test_epoch_slices_empty_and_degenerate():
    assert list(iter_epoch_slices(np.empty(0, dtype=np.int64), 100)) == []
    times = np.array([5, 5, 5], dtype=np.int64)
    assert list(iter_epoch_slices(times, 10)) == [(0, 3)]
    # epoch_ns <= 0 degrades to one slice covering everything
    assert list(iter_epoch_slices(times, 0)) == [(0, 3)]


def test_default_epoch_ns_bounds():
    pool = PacketPool(64, 2048)
    port = Port.make(pool, link_gbps=100.0, link_latency_ns=1_000)
    times = np.arange(0, 10_000, 100, dtype=np.int64)
    e = default_epoch_ns([port], times)
    assert e >= 1_000  # never below the min link latency (SimBricks bound)
    # huge schedules get chunked near the 64k-packet target
    big = np.arange(1 << 20, dtype=np.int64) * 50
    e_big = default_epoch_ns([port], big)
    n_slices = len(list(iter_epoch_slices(big, e_big)))
    assert 2 <= n_slices <= 32


# -- per-queue writeback thresholds (satellite) -------------------------------

def test_per_queue_thresholds_validation():
    with pytest.raises(ValueError, match="2 entries"):
        ExperimentConfig(ports=(PortConfig(n_queues=4),),
                         dca=DcaConfig(per_queue_writeback_thresholds=(8, 8)))
    with pytest.raises(ValueError, match=">= 1 or None"):
        DcaConfig(per_queue_writeback_thresholds=(0, 1))
    with pytest.raises(ValueError, match="exceeds"):
        ExperimentConfig(
            ports=(PortConfig(n_queues=2, ring_size=64),),
            dca=DcaConfig(per_queue_writeback_thresholds=(128, 1)))
    with pytest.raises(ValueError, match="nonempty"):
        DcaConfig(per_queue_writeback_thresholds=())


def test_per_queue_thresholds_fold_through_testbed():
    cfg = ExperimentConfig(
        ports=(PortConfig(n_queues=4),),
        dca=DcaConfig(per_queue_writeback_thresholds=(8, None, 64, 1)))
    tb = Testbed.build(cfg)
    thrs = [rq.writeback_threshold for rq in tb.devs[0].rx_queues]
    # None entries fall through to the DcaConfig-global threshold (32)
    assert thrs == [8, 32, 64, 1]
    # round-trips through plain dicts (JSON) exactly
    assert ExperimentConfig.from_dict(cfg.to_dict()) == cfg


def test_per_queue_thresholds_fold_through_topology():
    cfg = TopologyConfig(
        nodes=(NodeConfig(
            name="srv", port=PortConfig(n_queues=2),
            dca=DcaConfig(per_queue_writeback_thresholds=(4, 16))),),
        traffic=TrafficConfig(mode="open_loop", duration_s=0.0005))
    cluster = Cluster.build(cfg)
    thrs = [rq.writeback_threshold
            for rq in cluster.nodes[0].dev.rx_queues]
    assert thrs == [4, 16]


def test_effective_writeback_threshold_helper():
    dca = DcaConfig(writeback_threshold=32,
                    per_queue_writeback_thresholds=(8, None))
    assert effective_writeback_threshold(dca, 99, 0) == 8
    assert effective_writeback_threshold(dca, 99, 1) == 32   # falls through
    assert effective_writeback_threshold(None, 99, 1) == 99  # legacy
    with pytest.raises(ValueError, match="out of range"):
        dca.threshold_for(2)


# -- alloc-failure attribution (satellite bugfix) -----------------------------

def test_alloc_failures_attributed_in_report():
    """A frame that fails pool.alloc() counts toward ``sent`` (offered load)
    but used to vanish without attribution; it must now show up as
    ``extras["loadgen_alloc_failures"]``.  4 slots cannot carry a 2000-packet
    open-loop run, so starvation is guaranteed."""
    server, ports, clock = build(pool_slots=4, n_queues=1, n_lcores=1)
    lg = LoadGen(ports)
    pattern = TrafficPattern(rate_gbps=40.0, packet_size=1518)
    rep = lg.run_sim(server, pattern, duration_s=0.0005, clock=clock)
    failures = rep.extras["loadgen_alloc_failures"]
    assert failures > 0
    # every failed emission is part of `sent` but never reached a wire:
    # the unattributed gap this bugfix closes
    assert failures <= rep.sent - rep.received
    assert rep.dropped >= failures


def test_alloc_failures_zero_on_healthy_run():
    server, ports, clock = build()
    lg = LoadGen(ports)
    pattern = TrafficPattern(rate_gbps=10.0, packet_size=1518)
    rep = lg.run_sim(server, pattern, duration_s=0.001, clock=clock)
    assert rep.extras["loadgen_alloc_failures"] == 0.0
    assert rep.dropped == 0


def test_alloc_failure_starved_run_engine_parity():
    """Buffer starvation is outside the fast-path regime (the plan's pool
    validation rejects it) — but the fallback keeps reports identical."""
    pattern = TrafficPattern(rate_gbps=40.0, packet_size=1518)
    ev, ep, info = run_pair(pattern, 0.0005, pool_slots=4, n_queues=1,
                            n_lcores=1)
    assert not info.fastpath
    assert ev == ep
