"""Device ingest dataplane: kernel-style blocking feed vs. PMD-style bypass feed.

This module carries the paper's insight onto the accelerator boundary.  On a
TPU pod the host→device input path has exactly the kernel-stack pathologies the
paper bypasses on a NIC:

* blocking `device_put` inside the step loop  == syscall + interrupt semantics
* fresh host allocations per batch            == per-packet skb allocation
* implicit synchronization (`block_until_ready`) == interrupt-driven completion

:class:`KernelStackFeed` implements that baseline honestly.
:class:`BypassDataplane` is the DPDK analogue: a depth-K ring of pre-issued
asynchronous transfers ("pinned hugepage" buffer recycling via donation),
readiness *polling* (`jax.Array.is_ready`), multi-port host production, and
burst-size control — so device DMA overlaps both host production and device
compute (the DCA overlap, paper §5.2).

Both feeds speak the same protocol so the trainer/server runtime and the
benchmarks can swap them with one flag.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator, List, Optional, Sequence

import jax
import numpy as np

from .rings import SpscRing

HostBatch = Any  # pytree of np.ndarray
DeviceBatch = Any  # pytree of jax.Array


@dataclass
class FeedStats:
    batches: int = 0
    bytes: int = 0
    wait_ns: int = 0          # time the consumer stalled waiting for data
    put_ns: int = 0           # time spent issuing transfers
    host_alloc_ns: int = 0    # host-side production time on the critical path
    empty_polls: int = 0
    occupancy_sum: int = 0    # ring occupancy integral (for avg occupancy)
    devices: int = 0          # devices the last batch was placed across

    @property
    def avg_occupancy(self) -> float:
        return self.occupancy_sum / self.batches if self.batches else 0.0

    def gbps(self, elapsed_s: float) -> float:
        return self.bytes * 8 / 1e9 / elapsed_s if elapsed_s > 0 else 0.0


def _tree_bytes(tree: Any) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def _tree_devices(tree: Any) -> int:
    return max(len(x.sharding.device_set)
               for x in jax.tree_util.tree_leaves(tree))


class KernelStackFeed:
    """Baseline feed: synchronous, copying, interrupt-style.

    Each ``next_batch``: produce host batch (fresh allocation), blocking
    transfer, full synchronization.  No overlap anywhere — the device idles
    while the host works and vice versa.
    """

    def __init__(self, batch_iter: Iterator[HostBatch], sharding: Optional[Any] = None):
        self._it = batch_iter
        self._sharding = sharding
        self.stats = FeedStats()

    def next_batch(self) -> Optional[DeviceBatch]:
        t0 = time.perf_counter_ns()  # simlint: disable=SL001 -- wall-clock feed mode
        try:
            host = next(self._it)
        except StopIteration:
            return None
        # defensive copy: the kernel stack never trusts caller buffers (skb copy)
        host = jax.tree_util.tree_map(np.array, host)
        t1 = time.perf_counter_ns()  # simlint: disable=SL001 -- wall-clock feed mode
        dev = (jax.device_put(host, self._sharding) if self._sharding is not None
               else jax.device_put(host))
        jax.block_until_ready(dev)  # interrupt-driven completion: hard sync
        t2 = time.perf_counter_ns()  # simlint: disable=SL001 -- wall-clock feed mode
        self.stats.host_alloc_ns += t1 - t0
        self.stats.put_ns += t2 - t1
        self.stats.batches += 1
        self.stats.bytes += _tree_bytes(host)
        self.stats.devices = _tree_devices(dev)
        return dev

    def stop(self) -> None:
        pass


class BypassDataplane:
    """PMD-style device feed: pre-issued async DMA ring + readiness polling.

    * ``depth`` in-flight transfers (descriptor-ring depth);
    * ``ports`` host producer threads filling an SPSC staging ring each
      (multi-NIC analogue — Fig. 3(a) scalability axis);
    * consumer *polls* (`is_ready`) instead of blocking; a not-ready head with
      ready successors is reordered like out-of-order descriptor completion;
    * consumed device buffers are donated by the step function, so steady-state
      runs in place ("hugepage" recycling — allocation happens once).
    """

    def __init__(
        self,
        batch_iter_factory: Callable[[int, int], Iterator[HostBatch]],
        *,
        depth: int = 3,
        ports: int = 1,
        sharding: Optional[Any] = None,
        staging_capacity: int = 8,
        poll_interval_s: float = 0.0,
    ):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        if ports < 1:
            raise ValueError("ports must be >= 1")
        self._sharding = sharding
        self._depth = depth
        self._ports = ports
        self._poll_interval_s = poll_interval_s
        self.stats = FeedStats()
        self._stage: List[SpscRing] = [SpscRing(staging_capacity) for _ in range(ports)]
        self._stop_evt = threading.Event()
        self._producers: List[threading.Thread] = []
        self._exhausted = [False] * ports
        self._rr = 0  # round-robin port cursor
        self._inflight: List[DeviceBatch] = []
        for p in range(ports):
            it = batch_iter_factory(p, ports)
            t = threading.Thread(
                target=self._producer_loop, args=(p, it), daemon=True,
                name=f"dataplane-port{p}",
            )
            self._producers.append(t)
            t.start()

    # -- host producer threads (the "NIC ports") -----------------------------
    def _producer_loop(self, port: int, it: Iterator[HostBatch]) -> None:
        ring = self._stage[port]
        while not self._stop_evt.is_set():
            try:
                host = next(it)
            except StopIteration:
                self._exhausted[port] = True
                return
            while not ring.try_push(host):
                if self._stop_evt.is_set():
                    return
                time.sleep(0)  # staging full: yield (backpressure, no drop)

    # -- DMA issue -------------------------------------------------------------
    def _issue_one(self) -> bool:
        """Pop one staged host batch and start its async transfer."""
        for _ in range(self._ports):
            ring = self._stage[self._rr]
            self._rr = (self._rr + 1) % self._ports
            host = ring.try_pop()
            if host is not None:
                t0 = time.perf_counter_ns()  # simlint: disable=SL001 -- wall-clock feed mode
                dev = (jax.device_put(host, self._sharding)
                       if self._sharding is not None else jax.device_put(host))
                # NOTE: no block_until_ready — the transfer proceeds while we
                # return to compute. Readiness is observed by polling.
                self.stats.put_ns += time.perf_counter_ns() - t0  # simlint: disable=SL001 -- wall-clock feed mode
                self._inflight.append(dev)
                return True
        return False

    def _refill(self) -> None:
        while len(self._inflight) < self._depth:
            if not self._issue_one():
                break

    # -- consumer API ------------------------------------------------------------
    def next_batch(self, timeout_s: float = 30.0) -> Optional[DeviceBatch]:
        """Poll for the next ready batch (PMD rx_burst of size 1)."""
        deadline = time.perf_counter_ns() + int(timeout_s * 1e9)  # simlint: disable=SL001 -- wall-clock feed mode
        t_start = time.perf_counter_ns()  # simlint: disable=SL001 -- wall-clock feed mode
        self._refill()
        while True:
            # poll in-flight transfers; prefer the oldest ready one
            for i, dev in enumerate(self._inflight):
                ready = True
                for leaf in jax.tree_util.tree_leaves(dev):
                    if hasattr(leaf, "is_ready") and not leaf.is_ready():
                        ready = False
                        break
                if ready:
                    self._inflight.pop(i)
                    self._refill()  # keep the ring full before returning
                    self.stats.batches += 1
                    self.stats.bytes += _tree_bytes(dev)
                    self.stats.devices = _tree_devices(dev)
                    self.stats.occupancy_sum += len(self._inflight) + 1
                    self.stats.wait_ns += time.perf_counter_ns() - t_start  # simlint: disable=SL001 -- wall-clock feed mode
                    return dev
            if not self._inflight:
                if all(self._exhausted) and all(r.is_empty() for r in self._stage):
                    return None  # clean end of stream
                self._refill()
            self.stats.empty_polls += 1
            if time.perf_counter_ns() > deadline:  # simlint: disable=SL001 -- wall-clock feed mode
                raise TimeoutError("dataplane: no batch became ready in time")
            if self._poll_interval_s:
                time.sleep(self._poll_interval_s)
            else:
                time.sleep(0)  # single-core: let producers run

    def stop(self) -> None:
        self._stop_evt.set()
        for t in self._producers:
            t.join(timeout=5)
        self._inflight.clear()


def make_feed(kind: str, batch_iter_factory: Callable[[int, int], Iterator[HostBatch]],
              **kw: Any):
    """Factory: kind in {"kernel", "bypass"} — one flag swaps the stacks."""
    if kind == "kernel":
        it = batch_iter_factory(0, 1)
        return KernelStackFeed(it, sharding=kw.get("sharding"))
    if kind == "bypass":
        return BypassDataplane(batch_iter_factory, **kw)
    raise ValueError(f"unknown feed kind: {kind}")
