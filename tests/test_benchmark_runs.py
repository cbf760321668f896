"""Tier-1 runs the benchmark's whole rehearsal runs too: every cell of
`BENCHMARK.json` end to end on the CPU backend, and each control with
a guarantee broken (`benchmark/tests/test_runs.py`; see
`test_benchmark.py`). Each run is a child process with its own limit.
"""

from benchmark.tests.test_runs import *             # noqa: F401,F403

import pytest                                       # noqa: E402

from benchmark.tests import test_runs as _runs      # noqa: E402

_FANIN = "fanin-workers.flood"


@pytest.mark.parametrize("cell", [
    pytest.param(c, marks=pytest.mark.xfail(strict=True, reason=(
        "benchmark/tests/test_runs.py pins `fuse_depth.flood` as in "
        "`share50-250k.flood`'s `by_counter` alone (its last line); PR 44's "
        "cell is listed for it too (ISSUE 44: it tells this cell's steady "
        "states apart as well) and may not edit that file: the pin is a "
        "`benchmark` PR's to move. Everything else the case holds is held "
        "for the cell by test_fanin_workers.py::test_the_rehearsal_acks_"
        "every_delivery_and_picks_on_the_device (CHANGES.md, PR 44)")))
    if c == _FANIN else c for c in _runs.CELLS])
def test_rehearsal_of_each_cell_end_to_end(cell):   # noqa: F811
    _runs.test_rehearsal_of_each_cell_end_to_end(cell)
