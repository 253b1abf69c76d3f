"""Epoch fast-path array kernels: the pure-array inner pass of the
epoch-batched simulation engine (:mod:`repro.core.fastpath`).

One epoch slice of the analytic emission schedule is advanced as whole-array
passes instead of per-event Python rounds:

* **emission → arrival**: the FIFO wire recursion
  ``end_i = max(end_{i-1}, t_i) + ser_i`` is a max-plus scan.  With
  ``S_i = cumsum(ser)_i`` it closes to
  ``end_i = max(busy0, cummax_j<=i(t_j - S_{j-1})) + S_i`` — one cumsum and
  one cummax, bit-identical to :meth:`repro.core.simclock.Wire.transmit`
  called per frame (serialization uses the same ``round(bytes*8/gbps)``
  half-to-even float64 arithmetic);
* **steer**: the per-frame RSS queue is a gather through a precomputed
  per-flow-id queue table (the Toeplitz hash + indirection lookup of
  :meth:`repro.core.rss.RssIndirection.steer` hoisted out of the per-packet
  path — the loadgen's synthetic flow ids cycle mod ``n_flows``);
* **charge**: per-burst lcore busy-time ``(poll + n*per_packet)/ghz`` as a
  vectorized cost table, consumed by the harvest cascade.

The numpy implementation is the portable reference and the default.  The JAX
variant (:func:`epoch_pass_jax`) runs the same integer scan on the device in
int32, on offsets from the start of each slice, and returns results
bit-identical to the numpy pass.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "serialization_ns_vec",
    "wire_arrival_pass_np",
    "epoch_pass_np",
    "pmd_burst_cost_table",
    "epoch_pass_jax",
]


def serialization_ns_vec(lengths: np.ndarray, gbps: float) -> np.ndarray:
    """Per-frame serialization delay, matching ``Wire.serialization_ns``
    element-for-element (``int(round(bytes*8/gbps))``, half-to-even)."""
    if gbps <= 0.0:
        return np.zeros(len(lengths), dtype=np.int64)
    return np.round(np.asarray(lengths, dtype=np.float64) * 8.0
                    / gbps).astype(np.int64)


def wire_arrival_pass_np(
    handed_ns: np.ndarray, ser_ns: np.ndarray, busy0_ns: int, latency_ns: int,
) -> Tuple[np.ndarray, int]:
    """Arrival times of frames handed one-at-a-time to a FIFO wire.

    ``handed_ns`` must be non-decreasing (the emission schedule is).  Returns
    ``(arrivals, busy_until)`` — exactly what N sequential
    ``Wire.transmit(t_i, size_i)`` calls would produce.
    """
    n = len(handed_ns)
    if n == 0:
        return np.empty(0, dtype=np.int64), int(busy0_ns)
    handed = np.asarray(handed_ns, dtype=np.int64)
    ser = np.asarray(ser_ns, dtype=np.int64)
    cum = np.cumsum(ser)
    # end_i = max(busy0, max_{j<=i}(t_j - S_{j-1})) + S_i ; S_{-1} = 0
    pre = handed - (cum - ser)
    m = np.maximum(np.maximum.accumulate(pre), np.int64(busy0_ns))
    ends = m + cum
    return ends + np.int64(latency_ns), int(ends[-1])


def epoch_pass_np(
    handed_ns: np.ndarray,
    ser_ns: np.ndarray,
    busy0_ns: int,
    latency_ns: int,
    flow_queue_table: Optional[np.ndarray],
    flow_ids: Optional[np.ndarray],
) -> Tuple[np.ndarray, int, Optional[np.ndarray]]:
    """One epoch slice: wire arrivals + RSS steering in one pass.

    Returns ``(arrival_ns, busy_until, queue_idx)``; ``queue_idx`` is None
    for single-queue ports (no steering).
    """
    arrivals, busy = wire_arrival_pass_np(handed_ns, ser_ns, busy0_ns,
                                          latency_ns)
    queues = None
    if flow_queue_table is not None and flow_ids is not None:
        queues = flow_queue_table[flow_ids]
    return arrivals, busy, queues


def pmd_burst_cost_table(max_burst: int, poll_cycles: int,
                         per_packet_cycles: int, cpu_ghz: float) -> np.ndarray:
    """``cost[n] = pmd_burst_ns(n)`` for n in [0, max_burst] — the vectorized
    charge table the harvest cascade indexes per burst (float64, identical
    arithmetic to :meth:`repro.core.cost.HostCostModel.pmd_burst_ns`)."""
    n = np.arange(max_burst + 1, dtype=np.float64)
    table = (poll_cycles + n * per_packet_cycles) / cpu_ghz
    table[0] = 0.0
    return table


# slices are padded to a power-of-two length (at least this) before the
# device pass, so a run compiles once per bucket instead of once per length
_MIN_BUCKET = 1024
_I32_LIMIT = 2**31


@jax.jit
def _scan_i32(handed, ser):
    # the wire recursion of wire_arrival_pass_np, rebased so that the
    # carried-in busy time is 0: see epoch_pass_jax
    cum = jnp.cumsum(ser)
    m = jnp.maximum(jax.lax.cummax(handed - (cum - ser)), 0)
    return m + cum


@jax.jit
def _gather(table, ids):
    return table[ids]


def _pad_to_bucket(x: np.ndarray) -> np.ndarray:
    n = len(x)
    size = max(_MIN_BUCKET, 1 << (n - 1).bit_length())
    return np.concatenate([x, np.zeros(size - n, dtype=x.dtype)])


def epoch_pass_jax(
    handed_ns: np.ndarray,
    ser_ns: np.ndarray,
    busy0_ns: int,
    latency_ns: int,
    flow_queue_table: Optional[np.ndarray],
    flow_ids: Optional[np.ndarray],
) -> Tuple[np.ndarray, int, Optional[np.ndarray]]:
    """:func:`epoch_pass_np` on the default JAX device, bit-identical.

    The scan runs in int32 on offsets from ``base = max(handed[0], busy0)``
    (a TPU has no native int64, and its compiler fails on the int64 scan at
    epoch-slice sizes).  With that base the carried-in busy time becomes 0:
    an emission before ``base`` gives a negative ``t_j - S_{j-1}`` that the
    ``max`` with 0 discards either way, so it is clamped to 0.  Every value
    the scan forms then lies in ``[-sum(ser), span + sum(ser)]``; a slice
    whose bound reaches 2**31 ns raises instead of wrapping.  Slices are
    zero-padded at the end to a power-of-two length: the scan is a prefix
    computation, so the padding leaves the first ``n`` results unchanged.
    """
    n = len(handed_ns)
    if n == 0:
        return np.empty(0, dtype=np.int64), int(busy0_ns), None
    handed = np.asarray(handed_ns, dtype=np.int64)
    ser = np.asarray(ser_ns, dtype=np.int64)
    base = max(int(handed[0]), int(busy0_ns))
    rel = np.maximum(handed - base, 0)
    bound = int(rel[-1]) + int(ser.sum())
    if bound >= _I32_LIMIT:
        raise ValueError(
            f"epoch slice spans {bound} ns from its base; the int32 device "
            f"pass holds at most {_I32_LIMIT - 1} ns (use engine='epoch')")
    ends = np.asarray(_scan_i32(_pad_to_bucket(rel.astype(np.int32)),
                                _pad_to_bucket(ser.astype(np.int32))))[:n]
    ends = ends.astype(np.int64) + base
    queues = None
    if flow_queue_table is not None and flow_ids is not None:
        ids = _pad_to_bucket(np.asarray(flow_ids).astype(np.int32))
        queues = np.asarray(_gather(
            np.asarray(flow_queue_table).astype(np.int32), ids))[:n]
    return ends + np.int64(latency_ns), int(ends[-1]), queues
