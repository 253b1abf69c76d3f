"""Repo-wide test session config.

Two jobs:

1. **JAX persistent compilation cache** — the tier-1 suite's wall time is
   dominated by XLA compiles of the model smoke tests; caching them where
   :func:`repro.launch.compile_cache.compile_cache_dir` says (``.jax_cache/``
   in the checkout unless ``JAX_COMPILATION_CACHE_DIR`` is set) makes every
   rerun start warm.  Set via environment variables (before jax
   initializes) so subprocess tests inherit the same cache.

2. **Suite runtime budget** — now that the network tests run in virtual
   time, the default suite has a wall-clock budget (satisfying the CI gate:
   fail if tier-1 exceeds it).  Enabled by exporting
   ``SUITE_BUDGET_S`` (CI sets 90); local runs are unaffected.
"""
import os
import time

import pytest

from repro.launch.compile_cache import compile_cache_dir

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")

_SESSION_T0 = time.monotonic()


def pytest_sessionfinish(session, exitstatus):
    budget = os.environ.get("SUITE_BUDGET_S")
    if not budget:
        return
    elapsed = time.monotonic() - _SESSION_T0
    if elapsed > float(budget):
        session.exitstatus = 1
        tr = session.config.pluginmanager.get_plugin("terminalreporter")
        if tr is not None:
            tr.write_line(
                f"FAILED suite-runtime budget: {elapsed:.1f}s > {budget}s "
                "(virtual-time tests should not wait on the host clock — "
                "see EXPERIMENTS.md §virtual time)", red=True)
