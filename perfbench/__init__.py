"""The benchmark: harness, traffic generator, counters, trace reduction
and plain references.  Run a cell with ``python3 perfbench/run.py``."""
