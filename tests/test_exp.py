"""repro.exp: config round-tripping, the Testbed builder, the stack
registry, and run_experiment conservation across all three stacks."""
import json

import pytest

from repro.core import (DEFAULT_RSS_KEY, BypassL2FwdServer, EthDevState,
                        KernelStackServer, PipelineServer, SimClock)
from repro.exp import (CostConfig, ExperimentConfig, LinkConfig, PoolConfig,
                       PortConfig, RssConfig, StackConfig, TrafficConfig,
                       Testbed, make_server_factory, register_stack,
                       run_experiment, run_testbed, stack_kinds)

ZERO_COST = CostConfig(interrupt_cycles=0, syscall_cycles=0,
                       per_packet_kernel_cycles=0)


def _full_config() -> ExperimentConfig:
    """Non-default values in every field that supports them."""
    return ExperimentConfig(
        name="roundtrip",
        pool=PoolConfig(n_slots=4096, slot_size=1024),
        ports=(PortConfig(n_queues=4, ring_size=512, writeback_threshold=None,
                          rss=RssConfig(table_size=64, key_hex="ab" * 40),
                          link=LinkConfig(gbps=25.0, latency_ns=350)),
               PortConfig(n_queues=2)),
        stack=StackConfig(kind="kernel", burst_size=32, n_lcores=2,
                          per_lcore_bursts=(8, 16), sockbuf_budget=32,
                          cost=CostConfig(cpu_ghz=3.0, interrupt_cycles=4000,
                                          pmd_per_packet_cycles=900)),
        traffic=TrafficConfig(mode="closed_loop", n_packets=500, window=64,
                              payload_seed=7, verify_integrity=True,
                              packet_size=300, sim_time=False))


# -- config layer -------------------------------------------------------------

def test_config_round_trip():
    """Acceptance: ExperimentConfig.from_dict(cfg.to_dict()) == cfg."""
    for cfg in (ExperimentConfig(), _full_config()):
        assert ExperimentConfig.from_dict(cfg.to_dict()) == cfg


def test_config_survives_json():
    cfg = _full_config()
    assert ExperimentConfig.from_dict(
        json.loads(json.dumps(cfg.to_dict()))) == cfg


def test_config_validation():
    with pytest.raises(ValueError):
        TrafficConfig(mode="warp")
    with pytest.raises(ValueError):
        PortConfig(n_queues=0)
    with pytest.raises(ValueError):
        ExperimentConfig(ports=())
    with pytest.raises(ValueError):
        ExperimentConfig(stack=StackConfig(kind="pipeline"),
                         ports=(PortConfig(), PortConfig()))


def test_with_helpers_return_new_frozen_configs():
    cfg = ExperimentConfig()
    c2 = cfg.with_traffic(rate_gbps=2.0).with_stack(burst_size=128)
    assert cfg.traffic.rate_gbps == 1.0  # original untouched
    assert c2.traffic.rate_gbps == 2.0
    assert c2.stack.burst_size == 128
    c3 = c2.with_ports(n_queues=4)
    assert all(p.n_queues == 4 for p in c3.ports)


def test_cost_config_maps_to_host_cost_model():
    m = CostConfig(cpu_ghz=3.0, interrupt_cycles=1).to_host_cost_model()
    assert m.cpu_ghz == 3.0 and m.interrupt_cycles == 1
    assert CostConfig.from_host_cost_model(m) == CostConfig(
        cpu_ghz=3.0, interrupt_cycles=1)


# -- testbed builder ----------------------------------------------------------

def test_testbed_builds_started_devices_per_config():
    cfg = ExperimentConfig(
        pool=PoolConfig(n_slots=2048),
        ports=(PortConfig(n_queues=2, ring_size=128),
               PortConfig(n_queues=1, ring_size=64)),
        stack=StackConfig(kind="bypass"))
    tb = Testbed.build(cfg)
    assert len(tb.devs) == 2
    assert all(d.state is EthDevState.STARTED for d in tb.devs)
    assert tb.devs[0].n_queues == 2 and tb.devs[1].n_queues == 1
    assert tb.devs[0].rx_queues[0].size == 128
    assert tb.devs[1].rx_queues[0].size == 64
    assert isinstance(tb.server, BypassL2FwdServer)
    assert tb.pool.n_slots == 2048


def test_stack_registry_selects_server_class():
    mk = lambda kind, cost=None: Testbed.build(ExperimentConfig(
        stack=StackConfig(kind=kind, cost=cost))).server
    assert isinstance(mk("bypass"), BypassL2FwdServer)
    assert isinstance(mk("pipeline"), PipelineServer)
    assert isinstance(mk("kernel", ZERO_COST), KernelStackServer)
    assert {"bypass", "kernel", "pipeline"} <= set(stack_kinds())


def test_register_stack_extension_point():
    calls = []

    @register_stack("test-custom")
    def _build(cfg, devs):
        calls.append(cfg.kind)
        return BypassL2FwdServer(list(devs), burst_size=cfg.burst_size)

    try:
        cfg = ExperimentConfig(stack=StackConfig(kind="test-custom"))
        assert ExperimentConfig.from_dict(cfg.to_dict()) == cfg
        tb = Testbed.build(cfg)
        assert isinstance(tb.server, BypassL2FwdServer)
        assert calls == ["test-custom"]
    finally:
        from repro.exp import testbed
        testbed._STACKS.pop("test-custom", None)


def test_unknown_stack_kind_raises_at_build_time():
    cfg = ExperimentConfig(stack=StackConfig(kind="no-such-stack"))
    with pytest.raises(ValueError, match="unknown stack kind"):
        Testbed.build(cfg)


# -- run_experiment -----------------------------------------------------------

def _closed_loop(kind: str, **stack_kw) -> ExperimentConfig:
    return ExperimentConfig(
        name=f"t-{kind}",
        pool=PoolConfig(n_slots=4096),
        ports=(PortConfig(n_queues=2, ring_size=256),),
        stack=StackConfig(kind=kind, burst_size=32,
                          cost=ZERO_COST if kind == "kernel" else None,
                          **stack_kw),
        traffic=TrafficConfig(mode="closed_loop", n_packets=400,
                              packet_size=256, verify_integrity=True,
                              payload_seed=3))


@pytest.mark.parametrize("kind", ["bypass", "pipeline", "kernel"])
def test_run_experiment_conserves_packets(kind):
    rep = run_experiment(_closed_loop(kind))
    assert rep.received == 400
    assert rep.dropped == 0
    assert rep.extras["integrity_errors"] == 0


def test_run_experiment_is_deterministic_from_config():
    """Same config → byte-identical per-queue stats, twice."""
    def once():
        tb = Testbed.build(_closed_loop("bypass"))
        run_testbed(tb)
        return {k: (v.rx_packets, v.tx_packets, v.rx_bytes)
                for k, v in tb.server.per_queue_stats().items()}
    assert once() == once()


def test_run_experiment_msb_mode():
    cfg = ExperimentConfig(
        traffic=TrafficConfig(mode="msb", trial_s=0.002, refine_iters=1,
                              start_gbps=0.1))
    rep = run_experiment(cfg)
    assert rep.extras["msb_gbps"] > 0
    assert rep.extras["msb_trials"] >= 1


def _keyed(key_hex):
    """Two 1-queue ports, as the l2fwd benchmark configurations set them."""
    port = PortConfig(ring_size=512, rss=RssConfig(key_hex=key_hex))
    return ExperimentConfig(
        ports=(port, port),
        stack=StackConfig(kind="bypass", n_lcores=2),
        traffic=TrafficConfig(mode="open_loop", rate_gbps=20.0,
                              duration_s=0.0002))


def test_rebuilding_a_keyed_testbed_builds_no_key_tables():
    """Every port of every later build reuses the key's tables, and an
    explicit default key reports exactly what the implicit one does."""
    from repro.core.rss import key_tables_built
    cfg = _keyed(DEFAULT_RSS_KEY.hex())
    Testbed.build(cfg)
    misses = key_tables_built()
    Testbed.build(cfg)
    assert key_tables_built() == misses
    rep = run_experiment(cfg).to_dict()
    assert rep == run_experiment(cfg).to_dict()
    assert rep == run_experiment(_keyed(None)).to_dict()
    assert rep["received"] > 0


def test_sim_time_default_builds_clocked_testbed():
    tb = Testbed.build(ExperimentConfig())
    assert isinstance(tb.clock, SimClock)
    assert tb.server.clock is tb.clock
    # links flow config -> EthDev -> engine
    assert tb.devs[0].link_gbps == 100.0
    assert tb.devs[0].link_latency_ns == 1_000
    # wall-clock mode opts out
    tb_wall = Testbed.build(ExperimentConfig(
        traffic=TrafficConfig(sim_time=False)))
    assert tb_wall.clock is None
    assert tb_wall.server.clock is None


def test_make_server_factory_fresh_state():
    f = make_server_factory(_closed_loop("bypass"))
    s1, d1 = f()
    s2, d2 = f()
    assert s1 is not s2
    assert d1[0] is not d2[0]
    assert d1[0].pool is not d2[0].pool


def test_run_testbed_rejects_msb():
    cfg = ExperimentConfig(traffic=TrafficConfig(mode="msb"))
    with pytest.raises(ValueError):
        run_testbed(Testbed.build(cfg))
