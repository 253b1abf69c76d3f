"""Multi-queue RSS NIC model: hash correctness/balance, packet conservation,
per-queue stats aggregation, and lcore-schedule determinism."""
import numpy as np
import pytest

from repro.core import (BurstPlan, BypassL2FwdServer, KernelStackServer,
                        LoadGen, PacketPool, Port, RssIndirection,
                        TrafficPattern, flow_tuple_for_id, rss_skew,
                        toeplitz_hash, toeplitz_hash_vec, write_flow)
from repro.core.cost import HostCostModel
from repro.core.rss import (DEFAULT_RSS_KEY, FLOW_TUPLE_BYTES, _key_byte_tables,
                            _key_tables, _key_windows, key_tables_built)


def _flow_bytes(src_ip, dst_ip, sport, dport):
    raw = (src_ip.to_bytes(4, "big") + dst_ip.to_bytes(4, "big")
           + sport.to_bytes(2, "big") + dport.to_bytes(2, "big"))
    return np.frombuffer(raw, dtype=np.uint8)


def _ip(a, b, c, d):
    return (a << 24) | (b << 16) | (c << 8) | d


# Microsoft verification-suite vectors: (IPv4 4-tuple, Toeplitz hash)
_MS_VECTORS = [
    ((_ip(66, 9, 149, 187), _ip(161, 142, 100, 80), 2794, 1766), 0x51CCC178),
    ((_ip(199, 92, 111, 2), _ip(65, 69, 140, 83), 14230, 4739), 0xC626B0EA),
    ((_ip(24, 19, 198, 95), _ip(12, 22, 207, 184), 12898, 38024), 0x5C2B394A),
    ((_ip(38, 27, 205, 30), _ip(209, 142, 163, 6), 48228, 2217), 0xAFC7327F),
    ((_ip(153, 39, 163, 191), _ip(202, 188, 127, 2), 44251, 1303), 0x10E828A2),
]


def test_toeplitz_matches_microsoft_vectors():
    """The hash is the real RSS algorithm: verify against the published
    Microsoft verification-suite vectors (IPv4 with ports)."""
    for args, want in _MS_VECTORS:
        assert toeplitz_hash(_flow_bytes(*args)) == want


def test_toeplitz_vectorized_matches_scalar():
    rng = np.random.default_rng(7)
    flows = rng.integers(0, 256, size=(64, 12), dtype=np.uint8)
    vec = toeplitz_hash_vec(flows)
    for i in range(len(flows)):
        assert int(vec[i]) == toeplitz_hash(flows[i])


def test_hash_distribution_balances_queues():
    """Distinct flows spread near-uniformly over queues (RSS's whole point)."""
    n_flows, n_queues = 4096, 4
    flows = np.stack([
        np.frombuffer(
            b"".join(int(x).to_bytes(n, "big") for x, n in
                     zip(flow_tuple_for_id(f), (4, 4, 2, 2))),
            dtype=np.uint8)
        for f in range(n_flows)
    ])
    rss = RssIndirection(n_queues)
    queues = rss.steer(flows)
    counts = np.bincount(queues, minlength=n_queues)
    assert counts.min() > 0
    skew = rss_skew(list(counts))
    assert skew["max_over_mean"] < 1.3, f"queue counts too skewed: {counts}"


def test_flow_affinity():
    """All packets of one flow land on one queue — no intra-flow reordering."""
    rss = RssIndirection(8)
    flow = _flow_bytes(_ip(10, 0, 0, 1), _ip(192, 168, 0, 1), 5555, 443)
    qs = rss.steer(np.repeat(flow.reshape(1, -1), 32, axis=0))
    assert len(set(int(q) for q in qs)) == 1


def test_scalar_steer_one_matches_vectorized():
    """The allocation-free single-packet path (table-lookup Toeplitz) must
    agree with the vectorized burst path bit for bit — default key, custom
    key, and after a rebalance."""
    rng = np.random.default_rng(11)
    flows = rng.integers(0, 256, size=(512, 12), dtype=np.uint8)
    for key in (None, bytes(rng.integers(0, 256, size=40, dtype=np.uint8))):
        rss = RssIndirection(4, key=key)
        vec = rss.steer(flows)
        for i in range(len(flows)):
            assert rss.steer_one(flows[i]) == int(vec[i])
            assert rss.hash_one(flows[i]) == int(
                toeplitz_hash_vec(flows[i].reshape(1, -1), key=key)[0])
        # (1, 12)-shaped input (the legacy calling convention) still works
        assert rss.steer_one(flows[0].reshape(1, -1)) == int(vec[0])
    rss = RssIndirection(4)
    rss.rebalance([3] * 128)
    assert all(rss.steer_one(flows[i]) == 3 for i in range(16))
    with pytest.raises(ValueError):
        rss.hash_one(flows[0][:8])  # not a 12-byte tuple


def test_indirection_rebalance():
    rss = RssIndirection(4)
    rss.rebalance([0] * 128)  # pin everything to queue 0
    flows = np.random.default_rng(3).integers(0, 256, size=(100, 12), dtype=np.uint8)
    assert (rss.steer(flows) == 0).all()
    with pytest.raises(ValueError):
        rss.rebalance([7] * 128)  # names a queue that doesn't exist


# -- key tables, built once per key -------------------------------------------

_OTHER_KEY = bytes(range(7, 7 + 40))


@pytest.mark.parametrize("key", [DEFAULT_RSS_KEY, _OTHER_KEY],
                         ids=["default-key", "other-key"])
def test_memoised_key_tables_equal_a_fresh_build(key):
    windows, tables = _key_tables(key)
    fresh = _key_windows(key, FLOW_TUPLE_BYTES * 8)
    assert windows.dtype == fresh.dtype and windows.tolist() == fresh.tolist()
    assert [list(row) for row in tables] == [
        list(row) for row in _key_byte_tables(fresh)]


def test_equal_keys_share_one_read_only_table():
    """A second port with an equal key (an equal-valued copy, not the same
    object) builds nothing: it reuses the first port's windows, which nobody
    can write, while each keeps its own indirection table."""
    a = RssIndirection(4, key=bytes(bytearray(_OTHER_KEY)))
    misses = key_tables_built()
    b = RssIndirection(4, key=bytes(bytearray(_OTHER_KEY)))
    assert key_tables_built() == misses
    assert b._windows is a._windows and b._byte_tables is a._byte_tables
    assert not a._windows.flags.writeable
    with pytest.raises(ValueError):
        a._windows[0] = 0
    before = b.table.copy()
    a.rebalance([1] * 128)
    assert (b.table == before).all() and b._table_list == before.tolist()
    assert (a.table == 1).all()


@pytest.mark.parametrize("key", [None, DEFAULT_RSS_KEY,
                                 bytes(bytearray(DEFAULT_RSS_KEY))],
                         ids=["no-key", "explicit-key", "equal-copy"])
def test_shared_tables_still_match_microsoft_vectors(key):
    """hash_one, steer and toeplitz_hash_vec keep the published hashes with
    the key given explicitly; with a 128-queue identity table, steer is the
    hash's low seven bits."""
    rss = RssIndirection(128, 128, key=key)
    flows = np.stack([_flow_bytes(*args) for args, _ in _MS_VECTORS])
    want = np.array([h for _, h in _MS_VECTORS], dtype=np.uint32)
    assert [rss.hash_one(f) for f in flows] == want.tolist()
    assert (toeplitz_hash_vec(flows, key=key) == want).all()
    assert (rss.steer(flows) == want % 128).all()


def _mk_bypass(n_queues=4, n_lcores=4, pool_slots=8192, ring=512, **kw):
    pool = PacketPool(pool_slots, 1518)
    ports = [Port.make(pool, ring_size=ring, n_queues=n_queues)]
    return BypassL2FwdServer(ports, n_lcores=n_lcores, **kw), ports


def test_multiqueue_closed_loop_conserves_packets():
    """Acceptance: 1 port / 4 queues / 4 lcores, closed loop — zero
    unattributed loss and per-queue stats summing to the aggregate."""
    server, ports = _mk_bypass()
    lg = LoadGen(ports, verify_integrity=True)
    rep = lg.run_closed_loop(server, n_packets=2000, packet_size=256,
                             rng=np.random.default_rng(0))
    assert rep.received == 2000
    assert rep.dropped == 0
    assert rep.extras["integrity_errors"] == 0
    per_queue = server.per_queue_stats()
    assert set(per_queue) == {(0, q) for q in range(4)}
    agg = server.stats
    assert sum(s.rx_packets for s in per_queue.values()) == agg.rx_packets == 2000
    assert sum(s.tx_packets for s in per_queue.values()) == agg.tx_packets
    assert sum(s.rx_bytes for s in per_queue.values()) == agg.rx_bytes
    # every queue saw traffic (256 default flows over 4 queues)
    assert all(s.rx_packets > 0 for s in per_queue.values())
    # NIC-side per-queue accounting reached the report and sums to sent
    delivered = sum(rep.extras[f"p0q{q}_rx_delivered"] for q in range(4))
    dropped = sum(rep.extras[f"p0q{q}_rx_dropped"] for q in range(4))
    assert delivered + dropped == rep.sent


def test_multiqueue_open_loop_accounts_every_packet():
    """sent == received + attributable drops under overload, multi-queue."""
    pool = PacketPool(256, 1518)
    ports = [Port.make(pool, ring_size=16, writeback_threshold=8, n_queues=4)]

    class DeadServer:
        def poll_once(self):
            return 0

    lg = LoadGen(ports)
    rep = lg.run(DeadServer(), TrafficPattern(rate_gbps=5.0, packet_size=1518),
                 duration_s=0.05, drain_timeout_s=0.05)
    assert rep.sent > 0
    assert rep.dropped > 0
    assert rep.received + rep.dropped == rep.sent


def test_lcore_round_robin_schedule_is_deterministic():
    """Two identical single-core runs produce identical per-queue stats."""
    def run_once():
        server, ports = _mk_bypass(burst_size=16)
        lg = LoadGen(ports)
        lg.run_closed_loop(server, n_packets=1500, packet_size=200, window=64)
        return {
            k: (v.rx_packets, v.tx_packets, v.rx_bytes, v.burst_count,
                v.burst_packets)
            for k, v in server.per_queue_stats().items()
        }
    assert run_once() == run_once()


def test_lcore_assignment_covers_all_queues():
    server, _ = _mk_bypass(n_queues=4, n_lcores=3)
    assigned = [pair for lc in server.lcores for pair in lc.assignments]
    assert sorted(assigned) == [(0, 0), (0, 1), (0, 2), (0, 3)]
    # round-robin: 3 lcores over 4 queues -> loads 2/1/1
    assert sorted(len(lc.assignments) for lc in server.lcores) == [1, 1, 2]


def test_per_lcore_burst_plan():
    plan = BurstPlan(per_lcore=(8, 64))
    server, ports = _mk_bypass(n_queues=2, n_lcores=2, plan=plan)
    assert [lc.burst_size for lc in server.lcores] == [8, 64]
    lg = LoadGen(ports)
    rep = lg.run_closed_loop(server, n_packets=500, packet_size=128)
    assert rep.received == 500
    with pytest.raises(ValueError):
        BurstPlan(per_lcore=(0,))


def test_kernel_stack_multiqueue_conservation():
    pool = PacketPool(8192, 1518)
    ports = [Port.make(pool, ring_size=512, n_queues=2)]
    server = KernelStackServer(ports, cost_model=HostCostModel(
        interrupt_cycles=0, syscall_cycles=0, per_packet_kernel_cycles=0))
    lg = LoadGen(ports, verify_integrity=True)
    rep = lg.run_closed_loop(server, n_packets=600, packet_size=300,
                             rng=np.random.default_rng(2))
    assert rep.received == 600
    assert rep.extras["integrity_errors"] == 0
    per_queue = server.per_queue_stats()
    assert sum(s.rx_packets for s in per_queue.values()) == 600
    assert all(s.interrupts > 0 for s in per_queue.values())
    assert server.stats.copies >= 3 * 600  # still 3 copies per packet


def test_burst_histogram_is_bounded():
    """Satellite: stats memory stays O(1) however long the run is."""
    server, ports = _mk_bypass(n_queues=1, n_lcores=1)
    lg = LoadGen(ports)
    lg.run_closed_loop(server, n_packets=3000, packet_size=128, window=64)
    agg = server.stats
    assert agg.burst_count > 0
    assert agg.burst_buckets.shape == server.stats_cls().burst_buckets.shape
    hist = agg.burst_histogram
    assert sum(b["count"] for b in hist) == agg.burst_count
    assert agg.avg_burst == pytest.approx(agg.burst_packets / agg.burst_count)


def test_single_queue_port_keeps_seed_semantics():
    """n_queues=1 ports bypass hashing and expose the legacy .rx/.tx views."""
    pool = PacketPool(1024, 1518)
    port = Port.make(pool, ring_size=128)
    assert port.n_queues == 1
    assert port.rx is port.rx_queues[0]
    assert port.tx is port.tx_queues[0]
    server = BypassL2FwdServer([port], burst_size=16)
    lg = LoadGen([port])
    rep = lg.run_closed_loop(server, n_packets=200, packet_size=128)
    assert rep.received == 200 and rep.dropped == 0
