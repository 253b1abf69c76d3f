"""Fallback-reason taxonomy for the epoch engine (satellite of PR 7).

``repro.core.fastpath._fallback_reason`` documents a closed list of reasons a
config is outside the closed-form fast-path regime.  Each reason is a
contract: the engine must *refuse* (and run the event loop bit-identically)
rather than mis-simulate.  This file gives every documented reason a
triggering configuration — through the public run paths where a config can
reach it, and through direct ``_fallback_reason`` probes for the mid-run
states no fresh config can produce.  The serving stacks ride the same
taxonomy: they fall back on the server-type check, cleanly and bit-identically.
"""
import pytest

from repro.core import (BypassL2FwdServer, EpochRunInfo, EventScheduler,
                        HostCostModel, KernelStackServer, LoadGen, PacketPool,
                        PipelineServer, Port, SimClock, TrafficPattern,
                        run_epoch_sim)
from repro.core.fastpath import _fallback_reason

PATTERN = TrafficPattern(rate_gbps=5.0, packet_size=1518)
DUR = 0.0005


def _ports(ring=1024, wb=32, n_queues=2, pool_slots=8192):
    pool = PacketPool(pool_slots, 2048)
    return [Port.make(pool, ring_size=ring, writeback_threshold=wb,
                      n_queues=n_queues, link_gbps=40.0, link_latency_ns=1000)]


def _report_key(rep):
    lat = None if rep.latency is None else rep.latency.as_dict()
    return (rep.offered_gbps, rep.achieved_gbps, rep.sent, rep.received,
            rep.dropped, lat, tuple(sorted(rep.extras.items())))


def _bypass(ports, burst=32, **kw):
    srv = BypassL2FwdServer(ports, burst_size=burst, n_lcores=1, **kw)
    srv.attach_clock(SimClock())
    return srv


# -- config-reachable reasons: engine-parity pair runs -------------------------
#
# Each case is a factory returning (loadgen, server, sched); the test runs it
# once per engine on fresh state and demands the exact reason plus identical
# reports.

def _case_pipeline():
    ports = _ports()
    srv = PipelineServer(ports[0])
    srv.attach_clock(SimClock())
    return LoadGen(ports), srv, None


def _case_kernel():
    ports = _ports()
    srv = KernelStackServer(ports)
    srv.attach_clock(SimClock())
    return LoadGen(ports), srv, None


def _case_serving_prefill():
    import repro.serving as serving
    ports = _ports()
    srv = serving.PrefillServer(ports[0])
    srv.attach_clock(SimClock())
    return LoadGen(ports), srv, None


def _case_serving_balancer():
    import repro.serving as serving
    ports = _ports()
    srv = serving.BalancerServer(ports[0])
    srv.attach_clock(SimClock())
    return LoadGen(ports), srv, None


def _case_dca_accumulate():
    ports = _ports()
    srv = _bypass(ports)
    srv.enable_dca_accumulate(200_000)
    return LoadGen(ports), srv, None


def _case_integrity():
    ports = _ports()
    return LoadGen(ports, verify_integrity=True), _bypass(ports), None


def _case_dctcp_cc():
    # a rate controller adapts the emission schedule mid-trial on echo
    # feedback; the epoch planner precomputes the whole schedule up front
    from repro.core import DctcpRateController
    ports = _ports()
    lg = LoadGen(ports)
    lg.attach_cc(DctcpRateController(rate_gbps=5.0, window_ns=100_000,
                                     max_gbps=40.0, max_inflight=8))
    return lg, _bypass(ports), None


def _case_zero_cost():
    ports = _ports()
    srv = BypassL2FwdServer(ports, burst_size=32, n_lcores=1)
    srv.attach_clock(SimClock(), cost=HostCostModel(pmd_poll_cycles=0,
                                                    pmd_per_packet_cycles=0))
    return LoadGen(ports), srv, None


def _case_custom_fn():
    ports = _ports()
    srv = BypassL2FwdServer(ports, burst_size=32, n_lcores=1,
                            process_fn=lambda frame: None)
    srv.attach_clock(SimClock())
    return LoadGen(ports), srv, None


def _case_burst_exceeds_max_tx():
    ports = _ports()
    return LoadGen(ports, max_tx_burst=16), _bypass(ports, burst=64), None


def _case_burst_exceeds_tx_ring():
    ports = _ports(ring=32)
    return LoadGen(ports), _bypass(ports, burst=64), None


def _case_writeback_timers():
    ports = _ports()
    srv = _bypass(ports)
    sched = EventScheduler(srv.clock)
    for ring in ports[0].rx_queues:
        ring.attach_scheduler(sched, timeout_ns=100_000)
    return LoadGen(ports), srv, sched


def _case_writeback_dma():
    # timeout 0 disarms the idle timer so the DMA check is what trips
    ports = _ports()
    srv = _bypass(ports)
    sched = EventScheduler(srv.clock)
    for ring in ports[0].rx_queues:
        ring.attach_scheduler(sched, timeout_ns=0, writeback_dma_ns=500)
    return LoadGen(ports), srv, sched


CONFIG_CASES = [
    ("pipeline", _case_pipeline,
     "server type PipelineServer is not BypassL2FwdServer"),
    ("kernel", _case_kernel,
     "server type KernelStackServer is not BypassL2FwdServer"),
    ("serving-prefill", _case_serving_prefill,
     "server type PrefillServer is not BypassL2FwdServer"),
    ("serving-balancer", _case_serving_balancer,
     "server type BalancerServer is not BypassL2FwdServer"),
    ("custom-fn", _case_custom_fn, "custom packet-processing function"),
    ("dca-accumulate", _case_dca_accumulate, "DCA accumulate mode"),
    ("integrity", _case_integrity, "integrity verification enabled"),
    ("dctcp-cc", _case_dctcp_cc, "DCTCP rate-adaptive loadgen active"),
    ("zero-cost", _case_zero_cost, "zero-cost host model"),
    ("burst-gt-max-tx", _case_burst_exceeds_max_tx,
     "lcore burst exceeds loadgen max_tx_burst (TX would linger)"),
    ("burst-gt-tx-ring", _case_burst_exceeds_tx_ring,
     "lcore burst exceeds TX ring size"),
    ("wb-timers", _case_writeback_timers, "writeback-timeout timers armed"),
    ("wb-dma", _case_writeback_dma, "writeback DMA latency armed"),
]


@pytest.mark.parametrize("name,make,reason",
                         CONFIG_CASES, ids=[c[0] for c in CONFIG_CASES])
def test_reason_fires_and_engines_match(name, make, reason):
    lg, srv, sched = make()
    assert _fallback_reason(lg, srv, sched) == reason

    # engine parity on the same (fresh) config
    lg_e, srv_e, sched_e = make()
    ev = _report_key(lg_e.run_sim(srv_e, PATTERN, duration_s=DUR,
                                  clock=srv_e.clock, sched=sched_e))
    lg_f, srv_f, sched_f = make()
    info = EpochRunInfo()
    ep = _report_key(run_epoch_sim(lg_f, srv_f, PATTERN, duration_s=DUR,
                                   clock=srv_f.clock, sched=sched_f,
                                   info=info))
    assert not info.fastpath
    assert info.fallback_reason == reason
    assert ev == ep


# -- mid-run / degenerate states: direct probes --------------------------------
#
# These reasons guard against *reusing* a warm testbed; no fresh config can
# produce them, so we probe the predicate directly.

def test_no_clock():
    ports = _ports()
    srv = BypassL2FwdServer(ports, burst_size=32, n_lcores=1)  # no clock
    assert _fallback_reason(LoadGen(ports), srv, None) == "no SimClock attached"


def test_pending_queue_deadlines():
    ports = _ports()
    srv = _bypass(ports)
    srv.enable_dca_accumulate(100_000)
    srv._queue_deadline[(0, 0)] = 123  # lcore mid-accumulation
    assert _fallback_reason(LoadGen(ports), srv, None) \
        == "DCA accumulate mode"  # accumulate check dominates...
    srv._dca_wait_ns = None  # ...so strip it to expose the deadline check
    assert _fallback_reason(LoadGen(ports), srv, None) \
        == "pending queue accumulation deadlines"


def test_pending_scheduler_events():
    ports = _ports()
    srv = _bypass(ports)
    sched = EventScheduler(srv.clock)
    sched.schedule_in(1_000, lambda: None)
    assert _fallback_reason(LoadGen(ports), srv, sched) \
        == "pending scheduler events"


def test_no_ports():
    ports = _ports()
    srv = _bypass(ports)
    lg = LoadGen(ports)
    lg.ports = []
    assert _fallback_reason(lg, srv, None) == "no ports"


def test_port_lists_differ():
    ports_a, ports_b = _ports(), _ports()
    srv = _bypass(ports_a)
    assert _fallback_reason(LoadGen(ports_b), srv, None) \
        == "server and loadgen port lists differ"


def test_rx_ring_not_idle():
    ports = _ports(wb=1)
    srv = _bypass(ports)
    ports[0].rx_queues[0].nic_deliver(0, 100)  # published, unharvested
    assert _fallback_reason(LoadGen(ports), srv, None) == "RX ring not idle"


def test_rx_ring_not_idle_includes_dma_flight():
    ports = _ports(wb=2)
    srv = _bypass(ports)
    sched = EventScheduler(srv.clock)
    ring = ports[0].rx_queues[0]
    ring.attach_scheduler(sched, timeout_ns=0, writeback_dma_ns=700)
    ring.nic_deliver(0, 100)
    ring.nic_deliver(1, 100)           # threshold crossing starts the DMA
    assert ring._dma_pending == 2
    ring._sched = None                 # mask the armed-DMA check itself
    ring._dma_ns = 0
    assert _fallback_reason(LoadGen(ports), srv, None) == "RX ring not idle"


def test_tx_ring_not_idle():
    ports = _ports()
    srv = _bypass(ports)
    slot = ports[0].pool.alloc()
    assert ports[0].tx_queues[0].post(slot, 100)
    assert _fallback_reason(LoadGen(ports), srv, None) == "TX ring not idle"


def test_clean_bypass_config_has_no_reason():
    ports = _ports()
    srv = _bypass(ports)
    assert _fallback_reason(LoadGen(ports), srv, None) is None
    assert _fallback_reason(LoadGen(ports), srv,
                            EventScheduler(srv.clock)) is None


# -- topology-level reason: partitioned execution ------------------------------
#
# PR 8 adds one reason the per-host predicate can never see: a topology run
# under a partition mode executes domain-by-domain, and the epoch fast path
# only exists inside the shared event loop.  ``run_topology_experiment`` is
# the layer that knows, so it stamps the info struct itself.

def test_partitioned_reason_is_distinct_and_stamped():
    from repro.core.fastpath import PARTITIONED_REASON
    from repro.exp import (LinkConfig, NodeConfig, StackConfig, SwitchConfig,
                           TopologyConfig, TrafficConfig,
                           run_topology_experiment)

    assert PARTITIONED_REASON not in [c[2] for c in CONFIG_CASES]
    cfg = TopologyConfig(
        name="taxonomy-partitioned",
        nodes=(NodeConfig(name="srv",
                          stack=StackConfig(kind="bypass", burst_size=32)),),
        n_clients=2,
        switch=SwitchConfig(link=LinkConfig(gbps=40.0, latency_ns=1000)),
        traffic=TrafficConfig(mode="open_loop", rate_gbps=2.0,
                              duration_s=0.0002, packet_size=512, seed=7,
                              sim_time=True, engine="epoch"),
    ).with_partition("partitioned")
    info = EpochRunInfo()
    rep = run_topology_experiment(cfg, info=info)
    assert not info.fastpath
    assert info.fallback_reason == PARTITIONED_REASON
    # refusal, not mis-simulation: bit-identical to the shared-clock run
    shared = run_topology_experiment(cfg.with_partition("shared-clock"))
    assert rep.to_dict() == shared.to_dict()


# -- the taxonomy is CLOSED (PR 9 satellite) -----------------------------------
#
# Both info dataclasses validate every ``fallback_reason`` assignment against
# a closed reason list, so a typo'd or ad-hoc reason fails loudly at the
# assignment site instead of silently forking the taxonomy these tests and
# the sweep tooling key on.

def test_every_documented_epoch_reason_is_in_the_closed_enum():
    from repro.core.fastpath import validate_epoch_fallback_reason
    for _name, _make, reason in CONFIG_CASES:
        validate_epoch_fallback_reason(reason)  # must not raise
    for reason in (
            "no SimClock attached",
            "pending queue accumulation deadlines",
            "pending scheduler events",
            "no ports",
            "server and loadgen port lists differ",
            "RX ring not idle",
            "TX ring not idle",
            "packet pool would exhaust",
            "planning failed: ValueError('boom')",
            "server type PrefillServer is not BypassL2FwdServer",
            "partitioned domain execution",
            None):
        validate_epoch_fallback_reason(reason)


def test_ring_fill_is_no_fallback_reason():
    """The planner carries full RX rings (drops and early writebacks), so
    a filling ring is no reason to leave the fast path."""
    from repro.core.fastpath import validate_epoch_fallback_reason
    with pytest.raises(ValueError, match="closed"):
        validate_epoch_fallback_reason(
            "RX ring would fill (overflow writeback/drop regime)")
    ports = _ports(ring=64)
    lg, srv = LoadGen(ports), _bypass(ports)
    info = EpochRunInfo()
    rep = run_epoch_sim(lg, srv, TrafficPattern(rate_gbps=40.0,
                                                packet_size=1518),
                        duration_s=DUR, clock=srv.clock, info=info)
    assert info.fastpath and rep.dropped == info.n_dropped > 0


def test_epoch_info_rejects_unknown_reason():
    info = EpochRunInfo()
    with pytest.raises(ValueError, match="closed"):
        info.fallback_reason = "RX ring nearly full"  # typo'd variant
    with pytest.raises(ValueError, match="closed"):
        EpochRunInfo(fallback_reason="made-up reason")
    info.fallback_reason = "RX ring not idle"  # exact member: fine
    info.fallback_reason = None


def test_partition_info_rejects_unknown_reason():
    from repro.core import PartitionRunInfo
    info = PartitionRunInfo()
    with pytest.raises(ValueError, match="closed"):
        info.fallback_reason = "partition disabled"
    with pytest.raises(ValueError, match="closed"):
        PartitionRunInfo(fallback_reason="nope")
    info.fallback_reason = (
        "serving topology: balancer reads live cross-domain state")
    info.fallback_reason = None


def test_partition_fallback_reasons_cover_the_policy_layer():
    """Every string ``repro.exp.topology.partition_fallback_reason`` can
    produce must validate against the closed partition taxonomy."""
    from repro.core import validate_partition_fallback_reason
    for reason in (
            "serving topology: balancer reads live cross-domain state",
            "zero-latency links leave no conservative lookahead window",
            "node 'srv': zero-cost PMD model needs the shared loop's "
            "every-round polling",
            "node 'srv': zero-cost kernel model needs the shared loop's "
            "every-round polling",
            "node 'srv': stack kind 'pipeline' not proven "
            "partition-equivalent",
            "AQM policy 'ecn' not proven partition-equivalent",
            "AQM policy 'red' not proven partition-equivalent",
            "DCTCP rate-adaptive clients adapt on cross-domain echo feedback",
            "multi-switch trunk fabric not proven partition-equivalent",
            None):
        validate_partition_fallback_reason(reason)
    with pytest.raises(ValueError, match="closed"):
        validate_partition_fallback_reason("node srv is weird")


# -- PR 10 partition reasons: triggering configs + refusal parity --------------
#
# Each new fabric/loadgen feature is conservatively excluded from partitioned
# execution until proven equivalent.  The contract per reason: the policy
# layer names it, the run stamps it, and the "partitioned" run is the
# shared-clock run bit-for-bit (refusal, never mis-simulation).

def _pr10_topology(**kw):
    from repro.exp import (LinkConfig, NodeConfig, PoolConfig, SwitchConfig,
                           TopologyConfig, TrafficConfig)
    switch_kw = {k: kw.pop(k) for k in ("pipeline", "trunk") if k in kw}
    traffic_kw = {k: kw.pop(k) for k in ("cc_mode",) if k in kw}
    return TopologyConfig(
        name="taxonomy-pr10",
        nodes=(NodeConfig(name="srv", pool=PoolConfig(n_slots=8192)),),
        n_clients=2,
        switch=SwitchConfig(egress_capacity=16,
                            link=LinkConfig(gbps=10.0, latency_ns=1000),
                            **switch_kw),
        traffic=TrafficConfig(mode="open_loop", rate_gbps=2.0,
                              duration_s=0.0002, packet_size=512, seed=7,
                              cc_window_ns=100_000, cc_max_inflight=8,
                              **traffic_kw),
        target="srv", **kw)


def _pr10_cases():
    from repro.exp import AqmConfig, LinkConfig, PipelineConfig
    ecn = PipelineConfig(aqm=AqmConfig(kind="ecn", min_thresh=2,
                                       max_thresh=8, max_p=0.2, seed=1))
    red = PipelineConfig(aqm=AqmConfig(kind="red", min_thresh=2,
                                       max_thresh=8, max_p=0.2, seed=1))
    return [
        ("aqm-ecn", _pr10_topology(pipeline=ecn),
         "AQM policy 'ecn' not proven partition-equivalent"),
        ("aqm-red", _pr10_topology(pipeline=red),
         "AQM policy 'red' not proven partition-equivalent"),
        ("dctcp", _pr10_topology(cc_mode="dctcp"),
         "DCTCP rate-adaptive clients adapt on cross-domain echo feedback"),
        ("trunk", _pr10_topology(trunk=LinkConfig(gbps=40.0,
                                                  latency_ns=2000)),
         "multi-switch trunk fabric not proven partition-equivalent"),
    ]


@pytest.mark.parametrize("name,cfg,reason",
                         _pr10_cases(), ids=[c[0] for c in _pr10_cases()])
def test_pr10_partition_reasons_fire_and_refusal_is_bit_identical(
        name, cfg, reason):
    from repro.core import PartitionRunInfo
    from repro.exp import run_topology_experiment
    from repro.exp.topology import partition_fallback_reason

    assert partition_fallback_reason(cfg) == reason
    info = PartitionRunInfo()
    rep = run_topology_experiment(cfg.with_partition("partitioned"),
                                  partition_info=info)
    assert info.mode_requested == "partitioned"
    assert info.mode_used == "shared-clock"
    assert info.fallback_reason == reason
    shared = run_topology_experiment(cfg.with_partition("shared-clock"))
    assert rep.to_dict() == shared.to_dict()
