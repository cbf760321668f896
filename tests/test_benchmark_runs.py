"""Tier-1 runs the benchmark's whole rehearsal runs too: every cell of
`BENCHMARK.json` end to end on the CPU backend, and each control with
a guarantee broken (`benchmark/tests/test_runs.py`; see
`test_benchmark.py`). Each run is a child process with its own limit.
"""

from benchmark.tests.test_runs import *             # noqa: F401,F403
