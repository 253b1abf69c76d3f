"""The one traffic generator: every input of a run, drawn from ``--seed``
and the parameters of a traffic file.

Simulator traffic is a recorded schedule of offered
rates, ``rates_gbps``, each an open-loop trial of ``arrivals`` kind for
``trial_s`` simulated seconds.  Schedule entry ``i`` has a seed of its own,
derived from the run's seed, so every search of a run offers the same
trials; each search runs them in an order drawn from the seed.
"""
from __future__ import annotations

import numpy as np

_U64 = 2**64


def derive(seed: int, *path: int) -> int:
    """A 63-bit seed for item ``path`` of the run seeded with ``seed``."""
    entropy = [seed % _U64] + [p % _U64 for p in path]
    return int(np.random.default_rng(entropy).integers(2**63))


def trial_seed(seed: int, i: int) -> int:
    """Seed of schedule entry ``i``."""
    return derive(seed, 1, i)


def search_order(seed: int, c: int, n: int) -> np.ndarray:
    """The order of the ``n`` schedule entries in search ``c`` (search 0
    warms up; the window starts at 1)."""
    return np.random.default_rng(derive(seed, 2, c)).permutation(n)
