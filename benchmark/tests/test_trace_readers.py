"""The readers of this program's own spans and scopes, held to values
computed by hand on a small recorded trace.

`data/trace_spans_small.json` is a cut of a real traced run of
`plus-100k.flood` on a TPU v5 lite (1.05 s around three executions of
`jit_route_window_full_compact`), thinned to 14 operations, 3 module
executions and 19 host events, with every time rounded to a whole
microsecond so that the arithmetic below can be followed: nested
operations three deep (`while.199` > `while.191` > `fusion.645`),
five scopes, and `emqx:` spans on four threads. It is in the form of
`trace_scope.load` (an event's stats as a 4th element).

    operations (start + duration, us)              scope    self us
    copy.219       368402 + 13                     -        13
    while.199      368425 + 278783                 -        271386
      fusion.657     368428 + 5                    match    5
      copy-done.57   368438 + 16                   scan     16
      fusion.659     368464 + 75                   match    75
      while.191      368753 + 5705                 -        2860
        fusion.644     368754 + 5                  -        5
        fusion.645     368759 + 2836               match    2836
        select_select_fusion.33  371596 + 4        match    4
      fusion.647     393021 + 177                  shared   177
      fusion.652     394170 + 1419                 fanout   1419
    fusion.772     647226 + 1869                   compact  1869
    copy.219       704996 + 13                     -        13
    while.199      705019 + 278764                 -        278764

busy = 13 + 278783 + 1869 + 13 + 278764 = 559442 us of the 1050000 us
window, idle 490558 us in six gaps: [0, 368402], [368415, 368425],
[647208, 647226], [649095, 704996], [705009, 705019],
[983783, 1050000].
"""

import copy
import json
import os

import pytest

from benchmark.readers import (read_metric, trace_join, trace_scope,
                               trace_spans, xplane)

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1000.0


@pytest.fixture()
def recorded():
    with open(os.path.join(HERE, "data", "trace_spans_small.json")) as f:
        return json.load(f)


def metric_spec(name):
    """A per-layer metric's own file."""
    with open(os.path.join(os.path.dirname(HERE), "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def names_only(trace):
    """The form `xplane.from_file` gives (what `ctx["trace"]` holds)."""
    return {"planes": [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [ev[:3] for ev in ln["events"]]}
        for ln in p["lines"]]} for p in trace["planes"]]}


def ctx_of(trace, windows=16):
    return {"trace": names_only(trace), "trace_stats": trace,
            "trace_m0": {"routing.device.batches": 100},
            "trace_m1": {"routing.device.batches": 100 + windows}}


# ------------------------------------------------------------ trace_scope

def test_self_times_sum_to_the_union_with_nothing_counted_twice(recorded):
    ops = [(ev[0], ev[1], ev[1] + ev[2], ev[3]) for ev in xplane._line(
        recorded["planes"][0], xplane.OPS_LINE)]
    selfs = {(ev[0].split(" = ")[0], ev[1]): ns / US
             for ev, ns in trace_scope.self_times(ops)}
    assert selfs[("%while.199", 368425 * US)] == \
        278783 - (5 + 16 + 75 + 5705 + 177 + 1419) == 271386
    assert selfs[("%while.191", 368753 * US)] == 5705 - (5 + 2836 + 4)
    assert selfs[("%fusion.645", 368759 * US)] == 2836       # a leaf
    assert selfs[("%while.199", 705019 * US)] == 278764      # no child kept
    union = sum(e - s for s, e in xplane.union(
        [(s, e) for _n, s, e, _st in ops]))
    assert sum(selfs.values()) * US == union == 559442 * US
    # `xplane.reduce` counts a `while` together with its body
    by_op = dict(xplane.reduce(names_only(recorded))["device_ops"])
    assert by_op["while.199"] == pytest.approx((278783 + 278764) / 1e6)
    assert sum(by_op.values()) > 559442 / 1e6


def test_scope_is_the_innermost_known_one_of_the_path():
    path = ("jit(route_window_full_compact)/jit(route_window_full)/scan/"
            "while/body/closed_call/jit(route_step_shapes)/match/"
            "jit(shape_match)/vmap(jit(searchsorted))/vmap()/while/body/"
            "closed_call/gather:")
    assert trace_scope.scope_of({"tf_op": path}) == "match"
    assert trace_scope.scope_of(
        {"tf_op": "jit(route_window_full)/scan/while:"}) == "scan"
    assert trace_scope.scope_of({"tf_op": "jit(matcher)/add:"}) == ""
    assert trace_scope.scope_of({}) == ""


def test_scope_self_time_per_device_window(recorded):
    got = trace_scope.scope_seconds(recorded, ["route"])
    assert {k: round(v * 1e6) for k, v in got.items()} == {
        "match": 5 + 75 + 2836 + 4, "scan": 16, "shared": 177,
        "fanout": 1419, "compact": 1869,
        "": 13 + 271386 + 2860 + 5 + 13 + 278764}
    assert sum(got.values()) == pytest.approx(0.559442)
    ctx = ctx_of(recorded, windows=16)
    for scope, us in (("match", 2920), ("fanout", 1419), ("shared", 177),
                      ("compact", 1869), ("delta", 0)):
        assert trace_scope.read(ctx, scope, ["route"]) == \
            pytest.approx(us / 1000 / 16)
    # operations outside the programs asked for are not counted
    assert trace_scope.scope_seconds(recorded, ["no_such_program"]) == {}
    # no device window formed while the trace ran: 0, not a division
    assert trace_scope.read(ctx_of(recorded, windows=0), "match",
                            ["route"]) == 0.0


def test_a_trace_without_scope_paths_reads_zero(recorded):
    """An older program, or a CPU rehearsal: every operation lands
    under no scope and each scope's metric is 0, not missing."""
    bare = copy.deepcopy(recorded)
    for ev in xplane._line(bare["planes"][0], xplane.OPS_LINE):
        ev[3].pop("tf_op", None)
    assert trace_scope.read(ctx_of(bare), "match", ["route"]) == 0.0
    assert list(trace_scope.scope_seconds(bare, ["route"])) == [""]
    hostonly = {"planes": [p for p in recorded["planes"]
                           if not p["name"].startswith("/device:")]}
    assert trace_scope.read(ctx_of(hostonly), "match", ["route"]) == 0.0
    assert trace_scope.read({}, "match", ["route"]) is None


def test_event_metadata_is_read_from_the_file(tmp_path):
    """`op_paths` reads what ProfileData does not show: a stat kept in
    an operation's event metadata. A three-field XSpace by hand."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    def field(num, payload):
        if isinstance(payload, int):
            return varint(num << 3) + varint(payload)
        return varint(num << 3 | 2) + varint(len(payload)) + payload

    stat = field(1, 26) + field(5, b"jit(f)/match/jit(g)/add:")
    meta = field(1, 7) + field(2, b"%fusion.1 = s32[] fusion()") \
        + field(5, stat)
    other = field(1, 8) + field(2, b"%copy.2 = s32[] copy()")
    plane = field(2, b"/device:TPU:0") \
        + field(3, field(2, b"XLA Ops") + field(4, field(1, 7))) \
        + field(4, field(1, 7) + field(2, meta)) \
        + field(4, field(1, 8) + field(2, other)) \
        + field(5, field(1, 26) + field(2, field(1, 26)
                                        + field(2, b"tf_op")))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, plane))
    assert trace_scope.op_paths(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = s32[] fusion()": "jit(f)/match/jit(g)/add:"}}


# ------------------------------------------------------------ trace_spans

def test_idle_goes_to_the_innermost_span_and_adds_up(recorded):
    r = trace_spans.idle_by_span(names_only(recorded))
    assert r["window_ns"] == 1050000 * US
    assert r["idle_ns"] == (1050000 - 559442) * US == 490558 * US
    by = {k: v / US for k, v in r["by_span"].items()}
    assert by == {
        "emqx:batch_form": 528, "emqx:prepare_window": 6066,
        # inside emqx:dispatch [8339, 54128] on another thread, and
        # shorter: the ingress span takes its 9054, the dispatch the rest
        "emqx:ingress": 9054,
        "emqx:dispatch": (45789 - 9054) + (704996 - 686714) + 10,
        "emqx:finish_sub": 1194,
        # emqx:settle [281640, 283664] lies inside the third lane span
        "emqx:lane": 10399 + 7964 + (11429 - 2024),
        "emqx:settle": 2024,
        # [338929, 368402] + the 10 us gap at 368415; then
        # [668367, 686714], where the shorter emqx:dispatch takes over;
        # then the whole last span, in the gap after the last program
        "emqx:materialize": 29473 + 10 + 18347 + 33342}
    assert r["unnamed_ns"] / US == 255560 + 18 + 19272 + 32875 == 307725
    assert sum(r["by_span"].values()) + r["unnamed_ns"] == r["idle_ns"]
    # the harness's own spans (bench:) name nothing here
    assert not [n for n in by if not n.startswith("emqx:")]


def test_idle_metrics_in_ms_per_traced_second(recorded):
    ctx = ctx_of(recorded)
    def ms_per_s(**args):
        return trace_spans.read(ctx, **args)
    assert ms_per_s(names=["ingress"]) == pytest.approx(9.054 / 1.05)
    assert ms_per_s(names=["batch_form", "host_route",
                           "prepare_window"]) == \
        pytest.approx((0.528 + 6.066) / 1.05)
    assert ms_per_s(names=["finish_sub", "lane", "settle"]) == \
        pytest.approx((1.194 + 27.768 + 2.024) / 1.05)
    assert ms_per_s(names=["gc"]) == 0.0
    assert ms_per_s(unnamed=True) == pytest.approx(307.725 / 1.05)
    total = sum(ms_per_s(names=[n]) for n in (
        "ingress", "batch_form", "host_route", "prepare_window",
        "finish_sub", "lane", "settle", "gc", "dispatch",
        "materialize")) + ms_per_s(unnamed=True)
    assert total == pytest.approx(490.558 / 1.05)
    assert trace_spans.read({}, names=["gc"]) is None


def test_a_program_without_spans_leaves_all_idle_unnamed(recorded):
    old = names_only(recorded)
    for p in old["planes"]:
        for ln in p["lines"]:
            ln["events"] = [ev for ev in ln["events"]
                            if not ev[0].startswith("emqx:")]
    r = trace_spans.idle_by_span(old)
    assert r["by_span"] == {} and r["unnamed_ns"] == r["idle_ns"]


def test_innermost_segments_are_disjoint_and_shortest_first():
    segs = trace_spans.innermost([("a", 0, 100), ("b", 10, 30),
                                  ("c", 20, 25), ("d", 90, 120)])
    assert segs == [(0, 10, "a"), (10, 20, "b"), (20, 25, "c"),
                    (25, 30, "b"), (30, 90, "a"), (90, 100, "d"),
                    (100, 120, "d")]


# ------------------------------------------------------------- trace_join

def test_programs_pair_with_the_dispatch_that_launched_them(recorded):
    """Three executions inside the window and two dispatch spans: the
    first execution [31827, 368382] was launched before the window.
    It starts after the first span does, so only the whole alignment
    shows it: paired from the first, the second span [686714, ..]
    would have launched a program that started at 368402, and the
    first window's readback would have waited 342835 us for it."""
    joined = trace_join.pairs(recorded, ["route"])
    assert [(s[2]["trace_id"], r[0] / US, r[1] / US)
            for s, r in joined] == [
        (377821, 368402, 368402 + 336573),
        (383840, 704995, 704995 + 336554)]
    ctx = ctx_of(recorded)
    # 368402 - 54128 = 314274 us behind the program before it; the
    # second started while its span [686714, 727683] was still open
    assert trace_join.read(ctx, "queue", ["route"]) == \
        pytest.approx((314.274 + 0) / 2)
    # 711217 - 704975 = 6242 us; 1046394 - 1041549 = 4845 us
    assert trace_join.read(ctx, "tail", ["route"]) == \
        pytest.approx((6.242 + 4.845) / 2)


def test_a_readback_asked_for_late_counts_its_own_length(recorded):
    late = copy.deepcopy(recorded)
    for ln in late["planes"][1]["lines"]:
        for ev in ln["events"]:
            if ev[0] == "emqx:materialize" \
                    and ev[3]["trace_id"] == 377821:
                ev[1], ev[2] = 720000 * US, 150 * US   # after 704975
    assert trace_join.read(ctx_of(late), "tail", ["route"]) == \
        pytest.approx((0.150 + 4.845) / 2)


def test_pairing_is_refused_where_it_cannot_be_held(recorded):
    # more executions than the pipeline can hold ahead of the spans
    more = copy.deepcopy(recorded)
    mods = xplane._line(more["planes"][0], xplane.MODULES_LINE)
    for k in range(trace_join.MAX_LEAD):   # 11 executions, 2 spans
        mods.append(["jit_route_window_full(1)", (10 + k) * US, US, {}])
    assert trace_join.pairs(more, ["route"]) is None
    assert trace_join.read(ctx_of(more), "queue", ["route"]) == 0.0
    assert trace_join.read(ctx_of(more), "tail", ["route"]) == 0.0
    # no alignment is possible: the only span starts after every program
    late = copy.deepcopy(recorded)
    for ln in late["planes"][1]["lines"]:
        ln["events"] = [ev for ev in ln["events"]
                        if ev[0] != "emqx:dispatch" or ev[1] > 500000 * US]
        for ev in ln["events"]:
            if ev[0] == "emqx:dispatch":
                ev[1], ev[2] = 1045000 * US, 1000 * US
    assert trace_join.pairs(late, ["route"]) is None
    # nothing to hold an alignment by: no window's readback in the trace
    blind = copy.deepcopy(recorded)
    for ln in blind["planes"][1]["lines"]:
        ln["events"] = [ev for ev in ln["events"]
                        if ev[0] != "emqx:materialize"]
    assert trace_join.pairs(blind, ["route"]) is None
    hostonly = {"planes": recorded["planes"][1:]}
    assert trace_join.read(ctx_of(hostonly), "queue", ["route"]) == 0.0


def test_of_two_possible_alignments_the_tighter_readbacks_win(recorded):
    """A chip whose queue is full: every program starts as the one
    before it ends, and each span could have launched its own program
    or the one before. The readbacks end 4-6 ms after their own."""
    full = copy.deepcopy(recorded)
    for ln in full["planes"][1]["lines"]:
        for ev in ln["events"]:
            if ev[0] == "emqx:dispatch" and ev[3]["trace_id"] == 383840:
                ev[1] = 40000 * US      # launched early, waited long
    joined = trace_join.pairs(full, ["route"])
    assert [(s[2]["trace_id"], r[0] / US) for s, r in joined] == [
        (377821, 368402), (383840, 704995)]


# ------------------------------------------------- the manifest's entries

def held_to_the_recorded_trace(recorded, letter_for_letter: bool) -> None:
    """Each per-layer metric of PRs 24-26, through its own file, on the
    recorded trace plus hand-made counters. Its `workloads` list starts
    with the cells it was added for; later PRs append theirs."""
    ctx = ctx_of(recorded)
    ctx.update(
        window={"seconds": 51.0},
        m0={"runtime.gc.pause_us": 1_000_000,
            "routing.chooser.cost_device": 10},
        m1={"runtime.gc.pause_us": 4_570_000,
            "routing.chooser.cost_device": 100,
            "routing.chooser.cost_host": 2,
            "routing.chooser.host_probe": 7,
            "routing.chooser.device_probe": 1},
        tele0={}, tele1={"chooser": {"margin": 0.8125,
                                     "dev_batch_ms": 71.0}})
    want = {
        "route_match_device_ms_per_window.flood": 2.920 / 16,
        "route_fanout_device_ms_per_window.flood": 1.419 / 16,
        "route_shared_device_ms_per_window.flood": 0.177 / 16,
        "route_compact_device_ms_per_window.flood": 1.869 / 16,
        "idle_ingress_ms_per_s.flood": 9.054 / 1.05,
        "idle_batcher_ms_per_s.flood": 6.594 / 1.05,
        "idle_deliver_ms_per_s.flood": 30.986 / 1.05,
        "idle_gc_ms_per_s.flood": 0.0,
        "idle_unnamed_ms_per_s.flood": 307.725 / 1.05,
        "idle_dispatch_ms_per_s.flood": 55.027 / 1.05,
        "idle_readback_ms_per_s.flood": 81.172 / 1.05,
        "device_queue_ms_per_window.flood": 157.137,
        "readback_tail_ms_per_window.flood": 5.5435,
        "gc_pause_ms_per_s.flood": 3570.0 / 51.0,
        "chooser_margin.flood": 0.8125,
        "chooser_probe_share.flood": 100.0 * 8 / 100,
    }
    # PR 26's three, listed for the `$share` cell alone
    ctx["m0"].update({"match_cache.hits": 100, "match_cache.misses": 50,
                      "routing.device.windows": 10})
    ctx["m1"].update({"match_cache.hits": 740, "match_cache.misses": 410,
                      "routing.device.windows": 50,
                      "routing.device.cached_windows": 8,
                      "packets.puback.sent": 102_000})
    of_share = {
        "match_cache_hit_share.flood": 64.0,
        "cached_window_share.flood": 20.0,
        "puback_per_s.flood": 2000.0,
    }
    want.update(of_share)
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        manifest = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, value in want.items():
        spec = metric_spec(name)
        assert spec["unit"] == manifest[name]["unit"]
        first = ["plus-100k.flood"] * (name not in of_share) \
            + ["share50-250k.flood"]
        listed = manifest[name]["workloads"]
        assert listed == first if letter_for_letter \
            else listed[:len(first)] == first, name
        assert len(set(listed)) == len(listed), name
        assert read_metric(ctx, spec["reader"], spec["args"]) == \
            pytest.approx(value), name


@pytest.mark.xfail(strict=True, reason=(
    "PR 26's pin, letter for letter; kept red for the strict xfail "
    "wrapper of the same name in tests/test_benchmark.py, which calls "
    "it: the next PR that may touch tests/ deletes wrapper and case"))
def test_every_new_metric_file_reads_the_recorded_trace(recorded):
    """PR 26's pin, the lists letter for letter: red since PR 28
    appended `mixed-zipf.flood`. Tier-1 carries it under this name as a
    strict xfail in `tests/test_benchmark.py`, a file no `benchmark` PR
    may touch, so it stays as it was until that wrapper goes (PERF.md,
    section 7), marked here as what it is so that a direct run of
    `benchmark/tests` is green; the case below is the one that holds."""
    held_to_the_recorded_trace(recorded, letter_for_letter=True)


def test_every_metric_file_reads_the_recorded_trace(recorded):
    """The same metrics, values and files; a `workloads` list has to
    start with the cells the metric was added for, and later cells may
    follow."""
    held_to_the_recorded_trace(recorded, letter_for_letter=False)


def test_the_counters_of_prs_29_and_35_and_the_fuse_depth_have_readers():
    """PR 37's per-layer metrics, through their own files, on hand-made
    counters: each is listed for the one cell where its counters move."""
    ctx = {"window": {"seconds": 51.0},
           "m0": {"routing.device.windows": 40,
                  "routing.device.window_subs": 50,
                  "routing.device.shared_lane_rows": 1_000,
                  "routing.device.nfa_steps": 1_700,
                  "routing.device.nfa_narrow_steps": 700},
           "m1": {"routing.device.windows": 540,
                  "routing.device.window_subs": 810,
                  "routing.device.shared_lane_rows": 241_000,
                  "pipeline.deliver.slow_msgs": 60_000,
                  "routing.device.nfa_steps": 18_700,
                  "routing.device.nfa_narrow_steps": 13_450}}
    want = {"fuse_depth.flood": (1.52, "share50-250k.flood",
                                 "batcher + chooser"),
            "shared_lane_share.flood": (80.0, "share50-250k.flood",
                                        "consume + lanes"),
            "nfa_narrow_step_share.flood": (75.0, "mixed-zipf.flood",
                                            "route programs + kernels")}
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, (value, cell, layer) in want.items():
        spec = metric_spec(name)
        entry = manifest[name]
        assert (spec["unit"], spec["moves"]) == (entry["unit"],
                                                 entry["moves"])
        assert entry["workloads"] == [cell] and entry["layer"] == layer
        assert entry["source"] == "program_counter"
        assert read_metric(ctx, spec["reader"], spec["args"]) == \
            pytest.approx(value), name
    # a window in which nothing was consumed by a closure reads 100
    del ctx["m1"]["pipeline.deliver.slow_msgs"]
    spec = metric_spec("shared_lane_share.flood")
    assert read_metric(ctx, spec["reader"], spec["args"]) == 100.0
