"""Plain reference of one open-loop l2fwd trial in virtual time.

A straightforward per-event simulation of the deployment a configuration
file in ``perfbench/configs`` states: a load generator emitting frames on a
schedule, a FIFO wire per direction, a NIC that steers each frame by a
Toeplitz hash and an indirection table to one of its RX rings and writes
descriptors back every ``writeback_threshold`` frames, run-to-completion
lcores polling their rings in bursts and charging a per-burst cost, and the
return wire on which each frame's round-trip time is taken.

It imports nothing of the program under test.  It computes one trial's
statistics file, with the same fields and the same arithmetic as the
simulator's ``RunReport.to_dict()``.

``dtype`` computes the trial in a floating type instead (the forward
wire's times and the report's statistics): the control, which has to come
out as not correct.
"""
from __future__ import annotations

import math
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np


def frame_table(frame_mix) -> Tuple[float, np.ndarray]:
    """Mean frame (float64 bytes) and draw table of a frame-size mix, a
    list of ``[frame bytes, weight]`` pairs: each size repeated by its
    integer weight, in the listed order."""
    sizes = [int(b) for b, _w in frame_mix]
    weights = [w for _b, w in frame_mix]
    if not sizes or any(not isinstance(w, int) or w < 1 for w in weights) \
            or any(b < 64 for b in sizes):
        raise ValueError(f"frame_mix needs sizes >= 64 B and integer "
                         f"weights >= 1: {frame_mix!r}")
    mean = (np.float64(sum(b * w for b, w in zip(sizes, weights)))
            / np.float64(sum(weights)))
    return float(mean), np.repeat(np.asarray(sizes, dtype=np.int32), weights)


def emission_schedule(kind: str, rate_gbps: float,
                      packet_size: Optional[int], duration_ns: int,
                      seed: int, *, frame_mix=None):
    """Emission times (int64 ns) and sizes (int32) of one trial: a uniform
    grid, or Poisson arrivals drawn block by block from ``seed``.

    Every frame is ``packet_size`` bytes, or, with ``frame_mix`` instead,
    each is drawn from the mix's table after the times: uniformly over its
    entries, by ``np.random.default_rng(seed)`` (for Poisson arrivals the
    generator that drew the times, continued)."""
    if (frame_mix is None) == (packet_size is None):
        raise ValueError("give packet_size or frame_mix, not both or neither")
    if frame_mix is None:
        mean, table = packet_size, None
    else:
        mean, table = frame_table(frame_mix)
    pps = rate_gbps * 1e9 / 8.0 / mean
    gap_ns = 1e9 / pps
    rng = np.random.default_rng(seed)
    if kind == "uniform":
        n = int(duration_ns * 1e-9 * pps)
        times = (np.arange(n, dtype=np.float64) * gap_ns).astype(np.int64)
    elif kind == "poisson":
        block = max(64, int(duration_ns * 1e-9 * pps) + 64)
        chunks, last = [], 0.0
        while last < duration_ns:
            cum = np.cumsum(rng.exponential(gap_ns, size=block)) + last
            chunks.append(cum)
            last = float(cum[-1])
        cat = np.concatenate(chunks)
        times = cat[cat < duration_ns].astype(np.int64)
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    if table is None:
        return times, np.full(len(times), packet_size, dtype=np.int32)
    return times, table[rng.integers(0, len(table), size=len(times))]


def toeplitz(key: bytes, data: bytes) -> int:
    """Microsoft RSS Toeplitz hash of ``data`` under ``key``, bit by bit."""
    k = int.from_bytes(key, "big")
    nbits = len(key) * 8
    h = 0
    for i in range(len(data) * 8):
        if data[i // 8] & (0x80 >> (i % 8)):
            h ^= (k >> (nbits - 32 - i)) & 0xFFFFFFFF
    return h


def flow_queue(cfg: Dict, flow_id: int) -> int:
    """RX queue of the loadgen's flow ``flow_id``: its 4-tuple (source
    10.0.<id>, destination 192.168.0.1, source port 1024 + id % 60000,
    destination port 443, big-endian) hashed and looked up in a table that
    round-robins the queues."""
    src = cfg["src_ip_base"] | (flow_id & 0xFFFF)
    data = (src.to_bytes(4, "big") + cfg["dst_ip"].to_bytes(4, "big")
            + (1024 + flow_id % 60000).to_bytes(2, "big")
            + (443).to_bytes(2, "big"))
    h = toeplitz(bytes.fromhex(cfg["rss_key_hex"]), data)
    return (h % cfg["rss_table_size"]) % cfg["n_queues"]


def serialization_ns(nbytes: int, gbps: float) -> int:
    return int(round(nbytes * 8 / gbps)) if gbps > 0 else 0


class _Ring:
    """One RX ring: delivered (head), written back (published), harvested
    (tail) counters and the frames in it."""

    def __init__(self, size: int, threshold: int):
        self.size, self.thr = size, threshold
        self.frames: deque = deque()
        self.head = self.tail = self.published = self.cached = 0
        self.delivered = self.dropped = 0
        self.wb_sizes: List[int] = []

    def writeback(self) -> None:
        if self.cached:
            self.wb_sizes.append(self.cached)
            self.published += self.cached
            self.cached = 0


def simulate(cfg: Dict, kind: str, rate_gbps: float, duration_s: float,
             seed: int, dtype: Optional[str] = None) -> Dict:
    """One trial, event by event; returns its report."""
    nports = cfg["ports"]
    mix = cfg.get("frame_mix")
    if mix is not None and any(b > cfg["slot_size"] for b, _w in mix):
        raise ValueError(f"frame_mix sizes exceed slot_size {cfg['slot_size']}")
    gbps, lat = cfg["link_gbps"], cfg["link_latency_ns"]
    burst, max_tx = cfg["burst"], cfg["max_tx_burst"]
    times, sizes = emission_schedule(kind, rate_gbps, cfg.get("packet_size"),
                                     int(duration_s * 1e9), seed,
                                     frame_mix=mix)
    n = len(times)
    qtab = [flow_queue(cfg, f) for f in range(cfg["n_flows"])]
    nq = cfg["n_queues"]
    rings = [[_Ring(cfg["ring_size"], cfg["writeback_threshold"])
              for _ in range(nq)] for _ in range(nports)]
    tx = [[deque() for _ in range(nq)] for _ in range(nports)]
    pairs = [(p, q) for p in range(nports) for q in range(nq)]
    lcores = [[pr for j, pr in enumerate(pairs) if j % cfg["n_lcores"] == i]
              for i in range(cfg["n_lcores"])]
    free_at = [0] * len(lcores)
    ghz = cfg["cpu_ghz"]
    poll_c, pkt_c = cfg["pmd_poll_cycles"], cfg["pmd_per_packet_cycles"]
    pool_free = cfg["pool_slots"]
    fwd_busy = [0] * nports
    back_busy = [0] * nports
    wire_t = None if dtype is None else np.dtype(dtype).type
    on_wire = [deque() for _ in range(nports)]
    rtts: List[int] = []
    sent = received = alloc_failures = 0
    m_packets = m_bytes = 0
    m_start: Optional[int] = int(times[0]) if n else None
    m_end: Optional[int] = None
    seq = 0
    now = i = 0
    flushed = False
    while True:
        moved = 0
        while i < n and times[i] <= now:           # 1) emissions due
            t, sz, p = int(times[i]), int(sizes[i]), i % nports
            sent += 1
            if pool_free > 0:
                pool_free -= 1
                ser = serialization_ns(sz, gbps)
                if wire_t is None:
                    end = max(t, fwd_busy[p]) + ser
                else:
                    end = int(wire_t(max(wire_t(t), wire_t(fwd_busy[p]))
                                     + wire_t(ser)))
                fwd_busy[p] = end
                q = qtab[seq % cfg["n_flows"]] if nq > 1 else 0
                on_wire[p].append((end + lat, t, sz, q))
                seq += 1
            else:
                alloc_failures += 1
            i += 1
            moved += 1
        for p in range(nports):                      # 2) NIC delivery
            dq = on_wire[p]
            while dq and dq[0][0] <= now:
                _a, t, sz, q = dq.popleft()
                r = rings[p][q]
                moved += 1
                if r.head - r.tail >= r.size:
                    r.dropped += 1
                    pool_free += 1
                    continue
                r.frames.append((t, sz))
                r.head += 1
                r.cached += 1
                r.delivered += 1
                if r.cached >= r.thr or r.head - r.tail >= r.size:
                    r.writeback()
        for li, assigned in enumerate(lcores):       # 3) lcore polls
            if free_at[li] > now:
                continue
            accum = 0.0
            for p, q in assigned:
                r = rings[p][q]
                h = min(burst, r.published - r.tail)
                if h <= 0:
                    continue
                r.tail += h
                txq = tx[p][q]
                for _ in range(h):
                    fr = r.frames.popleft()
                    if len(txq) < cfg["ring_size"]:
                        txq.append(fr)
                    else:
                        pool_free += 1
                moved += h
                accum += (poll_c + h * pkt_c) / ghz
            if accum > 0:
                free_at[li] = now + int(round(accum))
        for p in range(nports):                      # 4) TX drain, RTTs
            batch = []
            for q in range(nq):
                txq = tx[p][q]
                for _ in range(min(max_tx, len(txq))):
                    batch.append(txq.popleft())
            if not batch:
                continue
            t_back = max(now, back_busy[p])
            first = None
            for t, sz in batch:
                t_back += serialization_ns(sz, gbps)
                arr = t_back + lat
                first = arr if first is None else first
                rtts.append(max(0, arr - t))
                m_bytes += sz
            back_busy[p] = t_back
            m_packets += len(batch)
            m_start = first if m_start is None else min(m_start, first)
            m_end = arr if m_end is None else max(m_end, arr)
            received += len(batch)
            pool_free += len(batch)
            moved += len(batch)
        cands = [int(times[i])] if i < n else []     # 5) next event
        cands += [dq[0][0] for dq in on_wire if dq]
        cands += [f for f in free_at if f > now]
        if cands:
            flushed = False
            now = min(cands)
            continue
        if moved:
            flushed = False
            continue
        if not flushed:
            for port_rings in rings:
                for r in port_rings:
                    r.writeback()
            flushed = True
            continue
        break
    return _report(cfg, rate_gbps, sent, received, alloc_failures, rtts,
                   m_packets, m_bytes, m_start, m_end, rings, now,
                   np.float64 if dtype is None else np.dtype(dtype).type)


def _report(cfg, rate_gbps, sent, received, alloc_failures, rtts, m_packets,
            m_bytes, m_start, m_end, rings, now, stat_t=np.float64) -> Dict:
    if m_start is None or m_end is None:
        elapsed = 0.0
    elif m_end <= m_start:
        elapsed = 1e-9 if m_packets > 0 else 0.0
    else:
        elapsed = (m_end - m_start) / 1e9
    latency, histogram = None, []
    if rtts:
        v = np.asarray(rtts, dtype=np.int64).astype(stat_t)
        latency = dict(
            count=len(rtts), mean_ns=float(v.mean()),
            median_ns=float(np.median(v)), std_ns=float(v.std()),
            p95_ns=float(np.percentile(v, 95)),
            p99_ns=float(np.percentile(v, 99)),
            p999_ns=float(np.percentile(v, 99.9)),
            max_ns=float(v.max()), min_ns=float(v.min()))
        lo = max(1.0, float(v.min()))
        hi = max(lo * 1.0001, float(v.max()))
        edges = np.logspace(math.log10(lo), math.log10(hi), 25)
        counts, _ = np.histogram(v, bins=edges)
        histogram = [{"lo_ns": float(edges[k]), "hi_ns": float(edges[k + 1]),
                      "count": int(counts[k])} for k in range(24)]
    extras: Dict[str, float] = {
        "integrity_errors": 0.0,
        "loadgen_alloc_failures": float(alloc_failures)}
    for p, port_rings in enumerate(rings):
        for q, r in enumerate(port_rings):
            k = f"p{p}q{q}"
            extras[f"{k}_writebacks"] = float(len(r.wb_sizes))
            extras[f"{k}_wb_size_mean"] = (float(np.mean(r.wb_sizes))
                                           if r.wb_sizes else 0.0)
            extras[f"{k}_wb_size_max"] = (float(max(r.wb_sizes))
                                          if r.wb_sizes else 0.0)
            extras[f"{k}_timeout_flushes"] = 0.0
    for p, port_rings in enumerate(rings):
        if len(port_rings) <= 1:
            continue
        for q, r in enumerate(port_rings):
            extras[f"p{p}q{q}_rx_delivered"] = float(r.delivered)
            extras[f"p{p}q{q}_rx_dropped"] = float(r.dropped)
        c = np.asarray([r.delivered for r in port_rings], dtype=np.float64)
        if c.sum() == 0:
            extras[f"p{p}_rss_imbalance"] = extras[f"p{p}_rss_cov"] = 0.0
        else:
            extras[f"p{p}_rss_imbalance"] = float(c.max() / c.mean())
            extras[f"p{p}_rss_cov"] = float(c.std() / c.mean())
    extras["sim_time"] = 1.0
    extras["virtual_elapsed_ns"] = float(now)
    return {
        "offered_gbps": rate_gbps,
        "achieved_gbps": (m_bytes * 8 / 1e9 / elapsed) if elapsed > 0 else 0.0,
        "achieved_mpps": (m_packets / 1e6 / elapsed) if elapsed > 0 else 0.0,
        "sent": sent, "received": received, "dropped": sent - received,
        "latency": latency, "histogram": histogram, "extras": extras}


def report_mismatches(got: Dict, want: Dict) -> int:
    """Number of leaf fields in which two report dicts differ (exactly)."""
    def flat(d, prefix=""):
        out = {}
        if isinstance(d, dict):
            for k, v in d.items():
                out.update(flat(v, f"{prefix}{k}."))
        elif isinstance(d, list):
            for k, v in enumerate(d):
                out.update(flat(v, f"{prefix}{k}."))
        else:
            out[prefix] = d
        return out
    a, b = flat(got), flat(want)
    return sum(1 for k in a.keys() | b.keys() if a.get(k) != b.get(k))
