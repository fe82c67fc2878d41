"""Chip benchmark of the scenario-grid engine.

`bench/run.py` is the entry point; `bench/harness.py` resolves a cell of
``BENCHMARK.json`` to its files (`configs/`, `traffic/`, `cells/`,
`metrics/`) and runs it.  Everything under this directory is the
yardstick: traffic generation, the plain reference, the trace reduction,
the peaks table and the FLOP counters.  From the program it takes only the
system under test (`repro.fl.scenarios.GridRunner`) and its kernel names.
"""
