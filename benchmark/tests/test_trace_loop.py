"""PR 40's thirteen metrics of the loop: the reader of the loop's own
line on a hand-made trace, each counter metric through its own file on
hand-made counters, the manifest's entries, and what three older cases
held beside a count that these entries moved.

The hand-made trace, times in us, window [1000, 11000] (10 ms):

    line "loop" (the asyncio thread)          innermost share, us
    bench:trace_window  1000 + 10000          -
    emqx:loop_wait       500 +  1500          1000 (clipped at 1000)
    emqx:ingress        2000 +  1000          1000
    emqx:control        3000 +   500           500
    emqx:lane           4000 +  3000          3000 - 400 - 100 = 2500
      emqx:settle         4500 +   400          400
      emqx:gc             5000 +   100          100  (inside the lane)
    emqx:loop_wait      7000 +  1000          1000
    emqx:batch_form     8000 +   250           250
    emqx:dispatch       8500 +   500           500  (on the loop: other)
    emqx:finish_sub    10500 +  1000           500 (clipped at 11000)
    bench:finish_sub   10400 +  1200          -    (the harness's own)

    named 1000+1000+500+2500+400+100+1000+250+500+500 = 7750,
    unnamed 10000 - 7750 = 2250

    line "route-dispatch": emqx:dispatch 1500 + 6000, emqx:lane 9000 + 900
    line "route-read":     emqx:materialize 2500 + 3000
"""

import json
import os

import pytest

from benchmark import manifest
from benchmark.readers import read_metric, trace_loop

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
US = 1000.0
CELLS = ["plus-100k.flood", "share50-250k.flood", "mixed-zipf.flood",
         "fleet-bcast.flood", "umbrella-cover.flood"]
COUNTER = ["loop_busy_share.flood", "loop_cpu_share.flood",
           "offloop_cpu_ms_per_s.flood", "lane_us_per_delivery.flood",
           "lane_accept_share.flood", "egress_write_ms_per_s.flood"]
TRACE = ["loop_wait_ms_per_s.flood", "loop_ingress_ms_per_s.flood",
         "loop_batcher_ms_per_s.flood", "loop_deliver_ms_per_s.flood",
         "loop_gc_ms_per_s.flood", "loop_unnamed_ms_per_s.flood",
         "loop_other_ms_per_s.flood"]


def _ev(name, start_us, dur_us):
    return [name, start_us * US, dur_us * US]


def loop_trace():
    loop = [_ev("bench:trace_window", 1000, 10000),
            _ev("emqx:loop_wait", 500, 1500),
            _ev("emqx:ingress", 2000, 1000),
            _ev("emqx:control", 3000, 500),
            _ev("emqx:lane", 4000, 3000),
            _ev("emqx:settle", 4500, 400),
            _ev("emqx:gc", 5000, 100),
            _ev("emqx:loop_wait", 7000, 1000),
            _ev("emqx:batch_form", 8000, 250),
            _ev("emqx:dispatch", 8500, 500),
            _ev("bench:finish_sub", 10400, 1200),
            _ev("emqx:finish_sub", 10500, 1000)]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            _ev("%fusion.1", 2000, 4000),
            # a device line's event by that name would not be the loop's
            _ev("emqx:loop_wait", 1000, 10000)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "route-dispatch", "events": [
                _ev("emqx:dispatch", 1500, 6000),
                _ev("emqx:lane", 9000, 900)]},
            {"name": "loop", "events": loop},
            {"name": "route-read", "events": [
                _ev("emqx:materialize", 2500, 3000)]}]}]}


def loop_spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def loop_bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def through_its_file(ctx, name):
    spec = loop_spec(name)
    return read_metric(ctx, spec["reader"], spec["args"])


# ----------------------------------------------------------- trace_loop

def test_every_nanosecond_of_the_loops_line_goes_to_one_name():
    r = trace_loop.second_by_span(loop_trace())
    assert r["window_ns"] == 10000 * US
    assert {k: v / US for k, v in r["by_span"].items()} == {
        "emqx:loop_wait": 1000 + 1000, "emqx:ingress": 1000,
        "emqx:control": 500, "emqx:lane": 2500, "emqx:settle": 400,
        "emqx:gc": 100, "emqx:batch_form": 250, "emqx:dispatch": 500,
        "emqx:finish_sub": 500}
    assert r["unnamed_ns"] / US == 2250
    assert sum(r["by_span"].values()) + r["unnamed_ns"] == r["window_ns"]


def test_the_seven_loop_metrics_add_up_to_a_second():
    ctx = {"trace": loop_trace()}
    got = {n: through_its_file(ctx, n) for n in TRACE}
    assert got == {
        "loop_wait_ms_per_s.flood": pytest.approx(200.0),
        "loop_ingress_ms_per_s.flood": pytest.approx(100.0 + 50.0),
        "loop_batcher_ms_per_s.flood": pytest.approx(25.0),
        "loop_deliver_ms_per_s.flood": pytest.approx(250.0 + 40.0 + 50.0),
        "loop_gc_ms_per_s.flood": pytest.approx(10.0),
        "loop_unnamed_ms_per_s.flood": pytest.approx(225.0),
        "loop_other_ms_per_s.flood": pytest.approx(50.0)}
    assert sum(got.values()) == pytest.approx(1000.0)


def test_a_span_on_another_host_line_is_not_the_loops():
    """`emqx:dispatch` [1500, 7500] and `emqx:materialize` run on their
    executors while the loop works and waits: the loop's second holds
    neither, and the lane span that a worker thread might carry (none
    does) would not count either. Take the other lines away and nothing
    moves."""
    whole = trace_loop.second_by_span(loop_trace())
    alone = loop_trace()
    alone["planes"][1]["lines"] = [alone["planes"][1]["lines"][1]]
    assert trace_loop.second_by_span(alone) == whole
    assert whole["by_span"]["emqx:dispatch"] == 500 * US    # the loop's own
    assert "emqx:materialize" not in whole["by_span"]
    assert whole["by_span"]["emqx:lane"] == 2500 * US       # not 2500 + 900


def test_the_line_with_the_most_wait_is_the_loops():
    """A second loop in the process (a tool's, a test's) that sleeps
    through the window has more `emqx:loop_wait` than the broker's; a
    line whose waits lie outside the window has none."""
    t = loop_trace()
    t["planes"][1]["lines"].append({"name": "before", "events": [
        _ev("emqx:loop_wait", 0, 900), _ev("emqx:lane", 2000, 100)]})
    assert trace_loop.second_by_span(t) == \
        trace_loop.second_by_span(loop_trace())
    t["planes"][1]["lines"].append({"name": "sleeper", "events": [
        _ev("emqx:loop_wait", 1000, 9000)]})
    r = trace_loop.second_by_span(t)
    assert r["by_span"] == {"emqx:loop_wait": 9000 * US}
    assert r["unnamed_ns"] == 1000 * US


def test_a_trace_without_loop_wait_reads_none():
    """An older program has every other span and no `emqx:loop_wait`:
    the seven read None and the harness leaves them out; so does a run
    without a trace."""
    old = loop_trace()
    for p in old["planes"]:
        for ln in p["lines"]:
            ln["events"] = [ev for ev in ln["events"]
                            if ev[0] != "emqx:loop_wait"]
    assert trace_loop.second_by_span(old) is None
    for ctx in ({"trace": old}, {"trace": None}, {}):
        assert [through_its_file(ctx, n) for n in TRACE] == [None] * 7


def test_the_files_name_between_them_what_the_reader_calls_named():
    named = []
    for n in TRACE:
        spec = loop_spec(n)
        assert spec["reader"] == "trace_loop"
        named += spec["args"].get("names", [])
    assert sorted(named) == sorted(trace_loop.NAMED)
    assert loop_spec("loop_unnamed_ms_per_s.flood")["args"] == \
        {"unnamed": True}
    assert loop_spec("loop_other_ms_per_s.flood")["args"] == {"other": True}
    # the same spans as the idle_* metrics they will stand in for
    for mine, theirs in (("loop_ingress", "idle_ingress"),
                         ("loop_batcher", "idle_batcher"),
                         ("loop_deliver", "idle_deliver"),
                         ("loop_gc", "idle_gc")):
        a = loop_spec(mine + "_ms_per_s.flood")["args"]["names"]
        b = loop_spec(theirs + "_ms_per_s.flood")["args"]["names"]
        assert set(b) <= set(a) and set(a) - set(b) <= {"control"}


# ------------------------------------------------------ the counter metrics

def hand_counters():
    return {"window": {"seconds": 50.0},
            "m0": {"runtime.loop.busy_us": 1_000_000,
                   "runtime.loop.wait_us": 9_000_000,
                   "runtime.loop.cpu_us": 900_000,
                   "runtime.dispatch.cpu_us": 100_000,
                   "pipeline.deliver.deliveries": 1_000,
                   "pipeline.deliver.lane_us": 5_000,
                   "pipeline.egress.write_us": 10_000},
            "m1": {"runtime.loop.busy_us": 46_000_000,
                   "runtime.loop.wait_us": 14_000_000,
                   "runtime.loop.cpu_us": 36_900_000,
                   "runtime.dispatch.cpu_us": 3_100_000,
                   "runtime.readback.cpu_us": 500_000,
                   "pipeline.deliver.deliveries": 2_001_000,
                   "pipeline.deliver.lane_us": 7_005_000,
                   "pipeline.deliver.accept_us": 4_900_000,
                   "pipeline.egress.write_us": 2_510_000}}


@pytest.mark.parametrize("name,value", [
    ("loop_busy_share.flood", 100 * 45 / (45 + 5)),
    ("loop_cpu_share.flood", 100 * 36 / 45),
    ("offloop_cpu_ms_per_s.flood", (3_000 + 500) / 50.0),
    ("lane_us_per_delivery.flood", 7_000_000 / 2_000_000),
    ("lane_accept_share.flood", 100 * 4.9 / 7.0),
    ("egress_write_ms_per_s.flood", 2_500 / 50.0),
])
def test_a_counter_metric_of_the_loop_through_its_own_file(name, value):
    spec = loop_spec(name)
    assert spec["reader"] == "counter" and name in COUNTER
    assert read_metric(hand_counters(), "counter", spec["args"]) == \
        pytest.approx(value)
    # a program without the counters (the parent) reads 0 and raises
    # nothing: the ratio's denominator did not move
    bare = {"window": {"seconds": 50.0}, "m0": {}, "m1": {}}
    assert read_metric(bare, "counter", spec["args"]) == 0.0


# ------------------------------------------------------------ the manifest

def test_the_thirteen_entries_are_the_manifests_last_and_fit_their_files():
    b = loop_bench()
    new = b["per_layer"][-13:]
    assert [m["name"] for m in new] == COUNTER + TRACE
    layers = {m["layer"] for m in b["per_layer"][:-13]}
    for m in new:
        assert m["workloads"] == CELLS and m["moves"] == "delivered_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["layer"] in layers         # no layer of its own
        spec = loop_spec(m["name"])
        assert (spec["name"], spec["unit"], spec["moves"]) == \
            (m["name"], m["unit"], m["moves"])
        assert m["source"] == ("program_counter" if m["name"] in COUNTER
                               else "device_trace")
        assert (spec["reader"] == "counter") == (m["name"] in COUNTER)
    assert {m["name"]: m["better"] for m in new if m["better"] == "higher"} \
        == {"loop_cpu_share.flood": "higher",
            "loop_wait_ms_per_s.flood": "higher"}
    assert {m["name"]: m["layer"] for m in new} == {
        "loop_busy_share.flood": "runtime",
        "loop_cpu_share.flood": "runtime",
        "offloop_cpu_ms_per_s.flood": "runtime",
        "lane_us_per_delivery.flood": "consume + lanes",
        "lane_accept_share.flood": "consume + lanes",
        "egress_write_ms_per_s.flood": "consume + lanes",
        "loop_wait_ms_per_s.flood": "runtime",
        "loop_ingress_ms_per_s.flood": "ingress decode",
        "loop_batcher_ms_per_s.flood": "batcher + chooser",
        "loop_deliver_ms_per_s.flood": "consume + lanes",
        "loop_gc_ms_per_s.flood": "runtime",
        "loop_unnamed_ms_per_s.flood": "runtime",
        "loop_other_ms_per_s.flood": "runtime"}
    assert {m["unit"] for m in new} == {"%", "ms/s", "us"}
    # nothing that was there moved: the idle_* metrics stay
    assert sum(m["name"].startswith("idle_") for m in b["per_layer"]) == 7


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_the_thirteen_with_their_readers(cell):
    c = manifest.Cell(cell)
    mine = {m["name"]: m for m in c.per_layer}
    assert [m["name"] for m in c.per_layer][-13:] == COUNTER + TRACE
    assert all(mine[n]["reader"] == "counter" for n in COUNTER)
    assert all(mine[n]["reader"] == "trace_loop" for n in TRACE)
    # the six that need the counters alone are on an untraced run's
    # `split.window.by_counter` too (run.py prints every counter metric)
    assert set(COUNTER) <= {m["name"] for m in c.per_layer
                            if m["reader"] == "counter"}


def _listed_for(b, cell):
    return [m["name"] for m in b["per_layer"]
            if cell in m.get("workloads", ())]


def test_mixed_zipf_reports_its_47_metrics_and_the_trie():
    """What `test_mixed_zipf.py::test_the_cell_reports_its_34_metrics_
    and_the_trie` holds beside its count (a strict xfail in tier-1 since
    this PR's thirteen entries)."""
    b = loop_bench()
    mine = _listed_for(b, "mixed-zipf.flood")
    assert len(mine) == 34 + 13
    assert {"nfa_window_share.flood", "match_overflow_share.flood",
            "route_nfa_roofline.flood", "snapshot_build_s",
            "nfa_narrow_step_share.flood",
            "match_cache_hit_share.flood"} <= set(mine)
    assert "route_roofline.flood" not in mine \
        and "puback_per_s.flood" not in mine
    cell = manifest.Cell("mixed-zipf.flood")
    assert cell.chips == 1 and [m["name"] for m in cell.end_to_end] \
        == ["delivered_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == mine


def test_umbrella_cover_reports_its_47_metrics_and_its_own_three():
    """What `test_umbrella_cover.py::test_the_cell_reports_its_34_
    metrics_and_the_three_new_ones` holds beside its count and the
    place of its three at the manifest's end."""
    b = loop_bench()
    cell_name = "umbrella-cover.flood"
    mine = _listed_for(b, cell_name)
    assert len(mine) == 34 + 13
    assert {"cover_expand_window_share.flood", "host_fallback_share.flood",
            "match_cache_hit_share.flood", "cached_window_share.flood",
            "snapshot_build_s", "device_routed_share.flood",
            "route_match_device_ms_per_window.flood"} <= set(mine)
    assert not {"route_roofline.flood", "puback_per_s.flood",
                "nfa_window_share.flood", "match_overflow_share.flood",
                "route_nfa_roofline.flood",
                "wide_fanout_delivery_share.flood"} & set(mine)
    new = [m for m in b["per_layer"] if m["workloads"] == [cell_name]]
    assert [(m["name"], m["unit"], m["better"], m["source"])
            for m in new] == [
        ("route_match_roofline.flood", "%", "higher", "device_trace"),
        ("cover_candidates_per_topic.flood", "candidates", "lower",
         "program_counter"),
        ("cover_overflow_share.flood", "%", "lower", "program_counter")]
    assert new == b["per_layer"][-16:-13]
    assert all(m["layer"] == "route programs + kernels"
               and m["moves"] == "delivered_per_s" for m in new)
    cell = manifest.Cell(cell_name)
    assert cell.chips == 1 and [m["name"] for m in cell.end_to_end] \
        == ["delivered_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == mine
    assert {m["name"]: m["reader"] for m in cell.per_layer[-16:-13]} == {
        "route_match_roofline.flood": "route_match_roofline",
        "cover_candidates_per_topic.flood": "counter",
        "cover_overflow_share.flood": "counter"}
    assert b["workloads"][-1]["name"] == cell_name \
        and b["configs"][-1]["name"] == "umbrella-cover" \
        and b["configs"][-1]["reduced"] == ["filters"]
    # every list the cell joined, it joined at the end
    assert all(m["workloads"][-1] == cell_name for m in b["per_layer"]
               + b["end_to_end"] if cell_name in m.get("workloads", ()))


def test_fleet_bcast_reports_its_46_metrics():
    """What `test_umbrella_cover.py::test_fleet_bcast_still_reports_its_
    33_metrics` holds beside its count."""
    b = loop_bench()
    fleet = "fleet-bcast.flood"
    mine = _listed_for(b, fleet)
    assert len(mine) == 33 + 13
    assert {"host_fallback_share.flood", "wide_fanout_delivery_share.flood",
            "route_roofline.flood", "match_cache_hit_share.flood",
            "cached_window_share.flood", "snapshot_build_s",
            "device_routed_share.flood"} <= set(mine)
    assert not {"puback_per_s.flood", "nfa_window_share.flood",
                "match_overflow_share.flood",
                "route_nfa_roofline.flood"} & set(mine)
    its = [m for m in b["per_layer"] if m["workloads"][0] == fleet]
    assert [m["name"] for m in its] == ["host_fallback_share.flood",
                                        "wide_fanout_delivery_share.flood"]
    assert all(m["layer"] == "route programs + kernels"
               and m["source"] == "program_counter"
               and m["moves"] == "delivered_per_s" for m in its)
    cell = manifest.Cell(fleet)
    assert cell.chips == 1 and [m["name"] for m in cell.end_to_end] \
        == ["delivered_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == mine
    assert all(m["reader"] == "counter" for m in cell.per_layer
               if m["name"] in {n["name"] for n in its})
