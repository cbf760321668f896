"""Tier-1 runs the benchmark's own tests too.

`benchmark/tests/` holds the harness's cases (codec, plain matcher,
populations, the comparison that decides `correct`, the trace readers,
and whole rehearsal runs of every cell on the CPU backend). The tier-1
command collects `tests/` only, so they are brought in here by name:
each still counts as a case of its own, and a change to the program
that breaks the harness fails tier-1 and not only the next chip run.
The whole runs of `test_runs.py` are in `test_benchmark_runs.py`, a
file of their own, so that another worker takes them.
"""

import pytest

from benchmark.tests import test_fleet_bcast as _fleet
from benchmark.tests import test_mixed_zipf as _zipf
from benchmark.tests import test_tenant_umbrella as _tenant
from benchmark.tests import test_trace_loop as _loop
from benchmark.tests import test_trace_readers as _readers
from benchmark.tests import test_umbrella_cover as _umbrella
from benchmark.tests.test_fanin_workers import *     # noqa: F401,F403
from benchmark.tests.test_fleet_bcast import *      # noqa: F401,F403
from benchmark.tests.test_mixed_zipf import *       # noqa: F401,F403
from benchmark.tests.test_pieces import *           # noqa: F401,F403
from benchmark.tests.test_tenant_umbrella import *  # noqa: F401,F403
from benchmark.tests.test_trace_loop import *       # noqa: F401,F403
from benchmark.tests.test_trace_readers import *    # noqa: F401,F403
from benchmark.tests.test_umbrella_cover import *   # noqa: F401,F403


@pytest.mark.xfail(strict=True, reason=(
    "benchmark/tests/test_trace_readers.py pins the `workloads` lists "
    "of PRs 24-26's metrics to PR 26's two cells letter for letter; "
    "PR 28 appends `mixed-zipf.flood` to them and may not edit that "
    "file: the pin is a `benchmark` PR's to move (CHANGES.md, PR 28)"))
def test_every_new_metric_file_reads_the_recorded_trace(  # noqa: F811
        recorded):                                  # noqa: F405
    _readers.test_every_new_metric_file_reads_the_recorded_trace(recorded)


@pytest.mark.xfail(strict=True, reason=(
    "benchmark/tests/test_fleet_bcast.py pins the manifest at PR 32's "
    "four cells and four configurations (its last line); PR 38 appends "
    "`umbrella-cover.flood` and may not edit that file: the pin is a "
    "`benchmark` PR's to drop. Everything else the case holds is held "
    "by test_umbrella_cover.py::"
    "test_fleet_bcast_still_reports_its_33_metrics (CHANGES.md, PR 38)"))
def test_fleet_bcast_reports_its_33_metrics():      # noqa: F811
    _fleet.test_fleet_bcast_reports_its_33_metrics()


_PR40 = (
    "benchmark/tests/{file} pins the number of per-layer metrics listed "
    "for {cell} at {n}{also}; PR 40 appends thirteen metrics of the loop "
    "for every cell and may not edit that file: the pin is a `benchmark` "
    "PR's to move. Everything else the case holds is held, at the new "
    "count, by test_trace_loop.py::{held} (CHANGES.md, PR 40)")


@pytest.mark.xfail(strict=True, reason=_PR40.format(
    file="test_mixed_zipf.py", cell="mixed-zipf.flood", n=34, also="",
    held="test_mixed_zipf_reports_its_47_metrics_and_the_trie"))
def test_the_cell_reports_its_34_metrics_and_the_trie():    # noqa: F811
    _zipf.test_the_cell_reports_its_34_metrics_and_the_trie()


@pytest.mark.xfail(strict=True, reason=_PR40.format(
    file="test_umbrella_cover.py", cell="umbrella-cover.flood", n=34,
    also=" and its own three at the manifest's end",
    held="test_umbrella_cover_reports_its_47_metrics_and_its_own_three"))
def test_the_cell_reports_its_34_metrics_and_the_three_new_ones():  # noqa: F811,E501
    _umbrella.test_the_cell_reports_its_34_metrics_and_the_three_new_ones()


@pytest.mark.xfail(strict=True, reason=_PR40.format(
    file="test_umbrella_cover.py", cell="fleet-bcast.flood", n=33, also="",
    held="test_fleet_bcast_reports_its_46_metrics"))
def test_fleet_bcast_still_reports_its_33_metrics():        # noqa: F811
    _umbrella.test_fleet_bcast_still_reports_its_33_metrics()


_PR42 = (
    "benchmark/tests/test_trace_loop.py pins {what}; PR 42 appends "
    "`tenant-umbrella.flood` to every list `umbrella-cover.flood` is on "
    "and one metric of its own, and may not edit that file: the pin is a "
    "`benchmark` PR's to move. Everything else the case holds is held, "
    "at the new lists, by test_tenant_umbrella.py::{held} (CHANGES.md, "
    "PR 42)")


@pytest.mark.xfail(strict=True, reason=_PR42.format(
    what="the thirteen `loop_*` / `lane_*` / `egress_*` entries as the "
         "manifest's last, each listed for PR 40's five cells",
    held="test_the_thirteen_loop_entries_fit_their_files_at_the_new_lists"))
def test_the_thirteen_entries_are_the_manifests_last_and_fit_their_files():  # noqa: F811,E501
    _loop.test_the_thirteen_entries_are_the_manifests_last_and_fit_their_files()  # noqa: E501


@pytest.mark.xfail(strict=True, reason=_PR42.format(
    what="`umbrella-cover.flood` as the manifest's last cell and its "
         "three cover metrics as listed for it alone",
    held="test_umbrella_cover_still_reports_its_47_and_shares_its_three"))
def test_umbrella_cover_reports_its_47_metrics_and_its_own_three():  # noqa: F811,E501
    _loop.test_umbrella_cover_reports_its_47_metrics_and_its_own_three()


_PR44 = (
    "benchmark/tests/{file} pins {what}; PR 44 appends "
    "`fanin-workers.flood` as the manifest's seventh cell, its "
    "configuration and five metrics of its own as the last entries, and "
    "the cell to the lists its counters move, and may not edit that "
    "file: the pin is a `benchmark` PR's to move. Everything else the "
    "case holds is held, at the new lists, by test_fanin_workers.py::"
    "{held} (CHANGES.md, PR 44)")


@pytest.mark.xfail(strict=True, reason=_PR44.format(
    file="test_tenant_umbrella.py",
    what="`tenant-umbrella`'s source as that of the manifest's last "
         "configuration",
    held="test_tenant_full_size_still_has_the_stated_counts"))
def test_tenant_full_size_has_the_stated_counts():          # noqa: F811
    _tenant.test_tenant_full_size_has_the_stated_counts()


@pytest.mark.xfail(strict=True, reason=_PR44.format(
    file="test_tenant_umbrella.py",
    what="`tenant-umbrella.flood` as the manifest's last cell of six, "
         "its one metric as the last entry and six configurations",
    held="test_tenant_umbrella_still_reports_its_48_metrics"))
def test_the_cell_reports_its_48_metrics_and_joined_every_list_last():  # noqa: F811,E501
    _tenant.test_the_cell_reports_its_48_metrics_and_joined_every_list_last()


@pytest.mark.xfail(strict=True, reason=_PR44.format(
    file="test_tenant_umbrella.py",
    what="the thirteen `loop_*` / `lane_*` / `egress_*` entries at "
         "[-14:-1] of the manifest, each listed for six cells",
    held="test_the_thirteen_loop_entries_fit_their_files_at_seven_cells"))
def test_the_thirteen_loop_entries_fit_their_files_at_the_new_lists():  # noqa: F811,E501
    _tenant.test_the_thirteen_loop_entries_fit_their_files_at_the_new_lists()


@pytest.mark.xfail(strict=True, reason=_PR44.format(
    file="test_tenant_umbrella.py",
    what="`umbrella-cover.flood`'s three cover metrics at [-17:-14] of "
         "the manifest and the cell as its last but one",
    held="test_umbrella_covers_three_are_still_the_two_covering_cells"))
def test_umbrella_cover_still_reports_its_47_and_shares_its_three():  # noqa: F811,E501
    _tenant.test_umbrella_cover_still_reports_its_47_and_shares_its_three()


@pytest.mark.xfail(strict=True, reason=_PR44.format(
    file="test_tenant_umbrella.py",
    what="`cover_roots_per_topic.flood` as the manifest's last entry",
    held="test_the_roots_metric_is_still_read_through_its_own_file"))
def test_the_roots_metric_through_its_own_file():           # noqa: F811
    _tenant.test_the_roots_metric_through_its_own_file()


@pytest.mark.xfail(strict=True, reason=_PR44.format(
    file="test_trace_readers.py",
    what="`fuse_depth.flood` and `shared_lane_share.flood` as listed "
         "for `share50-250k.flood` alone",
    held="test_the_counters_of_prs_29_and_35_still_have_readers_at_two_"
         "cells"))
def test_the_counters_of_prs_29_and_35_and_the_fuse_depth_have_readers():  # noqa: F811,E501
    _readers.test_the_counters_of_prs_29_and_35_and_the_fuse_depth_have_readers()  # noqa: E501
