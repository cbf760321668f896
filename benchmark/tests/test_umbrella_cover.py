"""The `umbrella-cover` deployment's own pieces, checked on the CPU: the
`umbrella_cover` population against its file, against `plain.py` and
against the program's own covering predicate, the match floor's byte
count on topics counted by hand, its roofline reader on a hand-made
trace, the cell's manifest entries, and the cell's rehearsal with a
guarantee broken.
"""

import json
import os

import numpy as np
import pytest

from benchmark import check, manifest, plain, populations, traffic_gen
from benchmark.populations import mixed_depth, umbrella_cover
from benchmark.readers import (match_floor_bytes, route_bytes,
                               route_match_roofline)
from benchmark.tests.test_runs import run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "umbrella-cover.flood"
FLEET = "fleet-bcast.flood"
SIZES = [6, 13]             # areas: each prefix depth once, and twice


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "umbrella-cover.json")) as f:
        return json.load(f)


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def pop_of(areas, conns=16):
    return umbrella_cover.Population({"areas": areas}, conns)


def interned(filters):
    """The filters as the program's matchers see them: [F, L] interned
    word ids and lengths."""
    from emqx_tpu.ops.intern import InternTable
    intern = InternTable()
    words = [intern.encode_filter(f.split("/")) for f in filters]
    rows = np.zeros((len(words), max(map(len, words))), np.int32)
    for i, w in enumerate(words):
        rows[i, :len(w)] = w
    return rows, np.array([len(w) for w in words], np.int64), words


# ------------------------------------------------------- the population

@pytest.mark.parametrize("areas", SIZES)
def test_umbrella_population_is_what_its_file_says(areas):
    pop = pop_of(areas)
    filters = pop.filters()
    assert len(filters) == len(set(filters)) == 100 * areas == pop.n
    split = [f.split("/") for f in filters]
    for a in range(areas):
        mine = split[100 * a:100 * (a + 1)]
        depth = 2 + a % 6
        umbrella, covered, alone = mine[0], mine[1:50], mine[50:]
        assert umbrella == mine[1][:depth] + ["#"] \
            and umbrella[:2] == [f"org{a % 50}", f"area{a}"]
        # 49 covered filters, 1 to 4 levels under the umbrella's prefix,
        # the last level the filter's own, '+' only between the two
        assert all(f[:depth] == umbrella[:-1] for f in covered)
        assert sorted({len(f) - depth for f in covered}) == [1, 2, 3, 4]
        assert len({f[-1] for f in covered}) == 49 \
            and not any(f[-1] in "+#" for f in covered)
        assert sum("+" in f for f in covered) == 25
        # 50 exact filters under the sibling prefix
        assert all(f[1] == f"solo{a}" and f[2:depth] == umbrella[2:-1]
                   and "+" not in f and "#" not in f for f in alone)
        assert sorted({len(f) - depth for f in alone}) == [1, 2]
    assert {len(f) for f in split} == set(range(3, 12))
    # every subscription is somebody's, once, by its number % conns
    owned = [pop.subscriptions(c) for c in range(pop.conns)]
    assert sorted(f for s in owned for f, q in s if q == 0) \
        == sorted(filters)
    assert all(f == filters[c + 16 * i] for c, s in enumerate(owned)
               for i, (f, _q) in enumerate(s))


@pytest.mark.parametrize("areas", SIZES)
def test_the_full_set_overflows_the_shape_table_and_the_roots_fit(areas):
    """`covering_decision` engages only where the full set's shapes
    pass the engine's 32 and pays only where the roots' fit."""
    from emqx_tpu.ops import cover
    pop = pop_of(areas)
    filters = pop.filters()
    rows, lens, _w = interned(filters)
    root = np.array([k % 100 == 0 or k % 100 >= 50
                     for k in range(len(filters))])
    full = cover.full_shape_count(rows, lens)
    assert full == route_bytes.shapes_of(filters) == 61 and full >= 48
    assert cover.full_shape_count(rows[root], lens[root]) == 13 <= 32
    assert cover.full_shape_count(rows[~root], lens[~root]) == 55
    assert cover.covering_decision(full, 32, rows.shape[1]) \
        == (True, "engaged")


@pytest.mark.parametrize("areas", SIZES)
def test_only_an_umbrella_covers_and_only_its_own_49(areas):
    """By the program's own predicate (`ops.cover.covers_pair`), over
    every pair of filters that share an `org` (others differ at level
    0): an umbrella covers its area's 49 filters and nothing else; no
    covered or standalone filter covers anything."""
    from emqx_tpu.ops.cover import covers_pair
    pop = pop_of(areas)
    filters = pop.filters()
    _rows, _lens, words = interned(filters)
    pairs = 0
    for a in range(len(filters)):
        for b in range(len(filters)):
            if a == b or words[a][0] != words[b][0]:
                continue
            pairs += 1
            want = a % 100 == 0 and a < b < a + 50
            assert covers_pair(list(words[a]), list(words[b])) == want, \
                (filters[a], filters[b])
    assert pairs >= 100 * 99 * areas


@pytest.mark.parametrize("areas", SIZES)
def test_umbrella_closed_form_equals_brute_force_on_every_key(areas):
    pop = pop_of(areas)
    keys = np.arange(areas * 8 * 49)
    assert check.brute_force(pop, keys, len(keys), seed=5) == 0
    want = pop.expect(keys)
    fan = (want >= 0).sum(axis=1)
    assert (fan == 2).mean() == 0.5 and (fan == 1).mean() == 0.5
    assert populations.expected_count(pop, keys) == len(keys) * 3 // 2
    topics = [pop.topic(k) for k in keys]
    assert {len(t.split("/")) for t in topics} == set(range(3, 12))
    under = np.array([t.split("/")[1].startswith("area") for t in topics])
    assert under.mean() == 0.75
    # a connection that owns both of a topic's filters is named twice
    assert (want[:, 0] == want[:, 1]).sum() > 0
    # the standalone filter k = 49 gets no traffic
    sent = set(topics)
    assert sum(f in sent for f in pop.filters() if "/solo" in f) \
        == 49 * areas


def test_brute_force_sees_a_forgotten_umbrella():
    class Off(umbrella_cover.Population):
        def expect(self, keys):
            out = super().expect(keys)
            two = out[:, 1] >= 0
            out[two, 0], out[two, 1] = out[two, 1], -1
            return out
    pop = Off({"areas": 6}, 16)
    keys = np.arange(6 * 8 * 49)
    assert check.brute_force(pop, keys, len(keys), seed=5) == 6 * 4 * 49


def test_a_program_without_the_cover_counters_is_refused(monkeypatch,
                                                         tmp_path):
    """The parent runs this cell on or off the chip by its chooser's
    draw (7,883 and 12,709 deliveries/s on one chip; a traced run with
    no device operation in its trace), so the population refuses it at
    once. What it asks for is the counter that the cell's own
    `cover_candidates_per_topic.flood` reads, anywhere in the program."""
    import sys
    import types

    with open(umbrella_cover.CANDIDATES_METRIC) as f:
        counter = json.load(f)["args"]["num"][0]
    assert counter == "routing.device.cover_candidates"
    assert pop_of(6).dims == (6, 8, 49)         # this program has it
    (tmp_path / "broker").mkdir()
    old = tmp_path / "broker" / "engine.py"
    old.write_text('metrics.inc("pipeline.cover.windows")\n')
    (tmp_path / "__init__.py").write_text("")
    fake = types.ModuleType("emqx_tpu")
    fake.__file__ = str(tmp_path / "__init__.py")
    monkeypatch.setitem(sys.modules, "emqx_tpu", fake)
    with pytest.raises(manifest.ManifestError, match="leaves the chip"):
        pop_of(6)
    old.write_text(f'metrics.inc("{counter}", n)\n')     # wherever it is
    assert pop_of(6).dims == (6, 8, 49)
    # a generator process has no program loaded: nothing to ask
    monkeypatch.delitem(sys.modules, "emqx_tpu")
    assert pop_of(6).dims == (6, 8, 49)


def test_umbrella_full_size_has_the_stated_counts():
    cfg = config()
    pop = populations.load(cfg)
    filters = pop.filters()
    assert len(filters) == len(set(filters)) == cfg["filters"] \
        == cfg["subscriptions"] == 250000
    assert sum(f.endswith("/#") for f in filters) == 2500
    assert route_bytes.shapes_of(filters) == 61
    assert sum(len(pop.subscriptions(c)) for c in range(16)) == 250000
    assert pop.dims == (2500, 8, 49) and pop.conns == 16
    assert cfg["population"]["params"] == {"areas": 2500}
    assert cfg["rehearse"]["population"] == {"areas": 12}
    assert cfg["node"] == {} and len(cfg["reduced"]) == 1 \
        and cfg["reduced"][0].startswith(
            "filters: 1,000,000 -> 250,000 (4x)")
    assert cfg["publish"] == {
        "keys": {"dist": "zipf", "s": 1.3, "dim": 0},
        "qos1_every": 0, "payload_bytes": 256}
    assert set(cfg["guarantees"]) == {"delivery", "qos", "order"}
    assert (umbrella_cover.SLOTS, umbrella_cover.COVERED,
            umbrella_cover.STANDALONE, umbrella_cover.FILL) \
        == (8, 49, 50, 16)


def test_umbrella_zipf_draw_stays_inside_the_key_space():
    cfg = config()
    pop = populations.load(cfg)
    keys = traffic_gen.draw_keys(traffic_gen.rng_for(2**31 + 5, 1),
                                 200000, pop.dims, cfg["publish"]["keys"])
    assert keys.min() >= 0 and keys.max() < 2500 * 8 * 49
    a, r, pick = np.unravel_index(keys, pop.dims)
    share = np.bincount(a, minlength=2500) / len(keys)
    assert 0.23 < share[0] < 0.27            # area 0 carries a quarter
    assert share[2499] < 0.09                # the folded tail
    assert abs(np.bincount(r, minlength=8).std() / (len(keys) / 8)) < 0.02
    assert abs(np.bincount(pick, minlength=49).std()
               / (len(keys) / 49)) < 0.04
    fan = (pop.expect(keys) >= 0).sum(axis=1)
    assert abs(fan.mean() - 1.5) < 0.01
    assert abs((r < 6).mean() - 0.75) < 0.01     # under an umbrella
    assert check.brute_force(pop, keys, 24, seed=2**31 + 5) == 0
    # one key of each kind by hand: area 7 has a prefix of 3 levels
    base = 7 * 8 * 49
    assert pop.topic(base + 0 * 49 + 5) == "org7/area7/p2w9/v5/c5"
    assert pop.topic(base + 3 * 49 + 5) == "org7/area7/p2w9/v8/c5"
    assert pop.topic(base + 4 * 49 + 5) == "org7/area7/p2w9/z5"
    assert pop.topic(base + 5 * 49 + 5) == "org7/area7/p2w9/z5/y"
    assert pop.topic(base + 6 * 49 + 5) == pop.topic(base + 7 * 49 + 5) \
        == "org7/solo7/p2w9/x5/t5"
    assert pop.topic(base + 7) == "org7/area7/p2w9/v7/m7e1/m7e2/c7"
    assert pop.filters()[700:702] + pop.filters()[706:709:2] == [
        "org7/area7/p2w9/#", "org7/area7/p2w9/c0",
        "org7/area7/p2w9/+/c5", "org7/area7/p2w9/+/m7e1/m7e2/c7"]
    want = pop.expect([base + 5, base + 4 * 49 + 5, base + 6 * 49 + 5])
    assert want.tolist() == [[700 % 16, 706 % 16], [700 % 16, -1],
                             [755 % 16, -1]]


# ------------------------------------------------------------- the bytes

def test_match_floor_bytes_on_topics_counted_by_hand():
    # org7/area7/p2w9/v5/c5: 5 levels in; it matches the umbrella
    # org7/area7/p2w9/# (4 levels) and org7/area7/p2w9/+/c5 (5)
    #   in    5 words * 4 + 4                       = 24
    #   tell  (4 + 5) levels * 4                    = 36
    #   out   2 ids * 4                             =  8
    assert match_floor_bytes.topic_bytes(5, [4, 5]) == 24 + 36 + 8 == 68
    # a standalone filter's own topic, 4 levels: 20 + 16 + 4
    assert match_floor_bytes.topic_bytes(4, [4]) == 40
    # a topic nothing matches still comes in
    assert match_floor_bytes.topic_bytes(3, []) == 16
    pop = pop_of(13)
    index = match_floor_bytes.FilterLevels(pop.filters())
    base = 7 * 8 * 49
    assert sorted(index.matched(pop.topic(base + 5))) == [4, 5]
    assert index.matched(pop.topic(base + 4 * 49 + 5)) == [4]
    assert index.matched(pop.topic(base + 6 * 49 + 5)) == [5]
    assert index.matched("org7/nobody/p2w9") == []


@pytest.mark.parametrize("pop", [
    pop_of(6), mixed_depth.Population({"gateways": 4, "streams": 60}, 16)],
    ids=["umbrella_cover", "mixed_depth"])
def test_the_floor_s_walk_equals_plain_on_every_key(pop):
    filters = pop.filters()
    split = [f.split("/") for f in filters]
    index = match_floor_bytes.FilterLevels(filters)
    for k in range(int(np.prod(pop.dims))):
        topic = pop.topic(k)
        assert sorted(index.matched(topic)) == sorted(
            len(split[i]) for i in plain.matching(topic, split)), topic


def test_the_floor_s_walk_knows_the_specification_s_corners():
    filters = ["a/#", "a", "+/b", "$SYS/#", "#", "a/+/#", "+/+", "a/b"]
    index = match_floor_bytes.FilterLevels(filters)
    split = [f.split("/") for f in filters]
    for topic in ("a", "a/b", "a/b/c", "$SYS/x", "$SYS", "b", "x/b"):
        assert sorted(index.matched(topic)) == sorted(
            len(split[i]) for i in plain.matching(topic, split)), topic


def _traced_ctx(lanes, match_ms, device=True):
    """A hand-made trace: one route program of 10 ms on a covering
    snapshot, `match_ms` of it under scope `match` (half of that under
    `match/cover`), and the counters and logs the reader asks."""
    pop = pop_of(6)
    ms = 1e6
    body = "jit(route_window)/scan/while/body/"
    ops = [["fusion.1", 0.0, match_ms / 2 * ms,
            {"tf_op": body + "match/jit(shape_match)/gather:"}],
           ["fusion.2", match_ms / 2 * ms, match_ms / 2 * ms,
            {"tf_op": body + "match/jit(shape_match)/cover/sort:"}],
           ["fusion.3", match_ms * ms, (10 - match_ms) * ms,
            {"tf_op": body + "fanout/sort:"}]]
    trace = {"planes": [
        {"name": "/device:TPU:0" if device else "/host:XLA", "lines": [
            {"name": "XLA Modules",
             "events": [["jit_route_window(1)", 0.0, 10 * ms, {}]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["bench:trace_window", 0.0, 20 * ms, {}]]}]}]}
    keys = np.array([5, 5, 4 * 49 + 5, 6 * 49 + 5], np.int64)
    return {
        "trace_stats": trace, "pop": pop,
        "peaks": {"hbm_bytes_per_s": 819e9},
        "trace_m0": {"routing.device.match_lanes": 100},
        "trace_m1": {"routing.device.match_lanes": 100 + lanes},
        "window": {"t0_ns": 0, "t1_ns": 10},
        "pub": {"key": keys, "send_ns": np.arange(4, dtype=np.int64)},
    }, keys, pop


def test_match_roofline_on_a_hand_made_trace():
    ctx, _keys, _pop = _traced_ctx(lanes=2048, match_ms=4.0)
    # area 0 has a prefix of 2 levels: org0/area0/+/c5 (4 levels) under
    # org0/area0/# (3), sent twice; org0/area0/z5 under the umbrella
    # alone; org0/solo0/x5/t5 its own filter's
    per = [match_floor_bytes.topic_bytes(4, [3, 4])] * 2 \
        + [match_floor_bytes.topic_bytes(3, [3]),
           match_floor_bytes.topic_bytes(4, [4])]
    assert per == [56, 56, 32, 40]
    want = 100.0 * (2048 * sum(per) / 4 / 819e9) / 4e-3
    got = route_match_roofline.read(ctx, match=["route"])
    # the expansion's operations, under `match/cover`, count as `match`
    assert got == pytest.approx(want) and 0 < got < 1
    # nothing matched in the span: a reading of 0
    ctx, _k, _p = _traced_ctx(lanes=0, match_ms=4.0)
    assert route_match_roofline.read(ctx, match=["route"]) == 0.0
    # the counter is there and the trace has no device plane (a CPU
    # rehearsal): 0, so that the cell's line carries the metric
    ctx, _k, _p = _traced_ctx(lanes=5, match_ms=4.0, device=False)
    assert route_match_roofline.read(ctx, match=["route"]) == 0.0
    # a program without the counter (the parent): nothing, no raise
    ctx, _k, _p = _traced_ctx(lanes=5, match_ms=4.0)
    del ctx["trace_m1"]["routing.device.match_lanes"]
    assert route_match_roofline.read(ctx, match=["route"]) is None
    # no trace at all
    assert route_match_roofline.read({"peaks": {}}, match=["route"]) is None


def test_a_sample_of_the_keys_stands_for_the_rest(monkeypatch):
    pop = pop_of(13)
    keys = traffic_gen.draw_keys(traffic_gen.rng_for(9, 1), 20000,
                                 pop.dims, config()["publish"]["keys"])
    whole = route_match_roofline.mean_topic_bytes(pop, keys)
    monkeypatch.setattr(route_match_roofline, "SAMPLE", 400)
    assert route_match_roofline.mean_topic_bytes(pop, keys) \
        == pytest.approx(whole, rel=0.05)
    assert 40 < whole < 90
    assert route_match_roofline.mean_topic_bytes(pop, keys[:0]) == 0.0


# ----------------------------------------------------------- the manifest

def test_the_cell_reports_its_34_metrics_and_the_three_new_ones():
    b = bench()
    mine = [m["name"] for m in b["per_layer"]
            if CELL in m.get("workloads", ())]
    assert len(mine) == 34
    assert {"cover_expand_window_share.flood", "host_fallback_share.flood",
            "match_cache_hit_share.flood", "cached_window_share.flood",
            "snapshot_build_s", "device_routed_share.flood",
            "route_match_device_ms_per_window.flood"} <= set(mine)
    assert not {"route_roofline.flood", "puback_per_s.flood",
                "nfa_window_share.flood", "match_overflow_share.flood",
                "route_nfa_roofline.flood",
                "wide_fanout_delivery_share.flood"} & set(mine)
    new = [m for m in b["per_layer"] if m["workloads"] == [CELL]]
    assert [(m["name"], m["unit"], m["better"], m["source"])
            for m in new] == [
        ("route_match_roofline.flood", "%", "higher", "device_trace"),
        ("cover_candidates_per_topic.flood", "candidates", "lower",
         "program_counter"),
        ("cover_overflow_share.flood", "%", "lower", "program_counter")]
    assert new == b["per_layer"][-3:]
    assert all(m["layer"] == "route programs + kernels"
               and m["moves"] == "delivered_per_s" for m in new)
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and [m["name"] for m in cell.end_to_end] \
        == ["delivered_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == mine
    assert {m["name"]: m["reader"] for m in cell.per_layer[-3:]} == {
        "route_match_roofline.flood": "route_match_roofline",
        "cover_candidates_per_topic.flood": "counter",
        "cover_overflow_share.flood": "counter"}
    assert b["workloads"][-1]["name"] == CELL \
        and b["configs"][-1]["name"] == "umbrella-cover" \
        and b["configs"][-1]["reduced"] == ["filters"]
    # every list the cell joined, it joined at the end
    assert all(m["workloads"][-1] == CELL for m in b["per_layer"]
               + b["end_to_end"] if CELL in m.get("workloads", ()))


def test_fleet_bcast_still_reports_its_33_metrics():
    """`test_fleet_bcast.py`'s case of this name also pins the manifest
    at four cells and four configurations, which this cell breaks
    (tier-1 carries that case as a strict xfail); everything else it
    held is held here."""
    b = bench()
    mine = [m["name"] for m in b["per_layer"]
            if FLEET in m.get("workloads", ())]
    assert len(mine) == 33
    assert {"host_fallback_share.flood", "wide_fanout_delivery_share.flood",
            "route_roofline.flood", "match_cache_hit_share.flood",
            "cached_window_share.flood", "snapshot_build_s",
            "device_routed_share.flood"} <= set(mine)
    assert not {"puback_per_s.flood", "nfa_window_share.flood",
                "match_overflow_share.flood",
                "route_nfa_roofline.flood"} & set(mine)
    its = [m for m in b["per_layer"] if m["workloads"][0] == FLEET]
    assert [m["name"] for m in its] == ["host_fallback_share.flood",
                                        "wide_fanout_delivery_share.flood"]
    assert all(m["layer"] == "route programs + kernels"
               and m["source"] == "program_counter"
               and m["moves"] == "delivered_per_s" for m in its)
    cell = manifest.Cell(FLEET)
    assert cell.chips == 1 and [m["name"] for m in cell.end_to_end] \
        == ["delivered_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == mine
    assert all(m["reader"] == "counter" for m in cell.per_layer
               if m["name"] in {n["name"] for n in its})


# ------------------------------------------------------------ whole runs

@pytest.mark.parametrize("control,number", [
    ("lose", "wrong_delivery_sets"),
    ("duplicate", "wrong_delivery_sets"),
    ("reorder", "order_breaks"),
])
def test_umbrella_cover_with_a_guarantee_broken_is_not_correct(control,
                                                               number):
    r, out = run_cell("--workload", CELL, "--seed", "41", "--seconds", "1",
                      "--trace", "0", "--rehearse", "--control", control)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is False and out["failed"] > 0
    assert out["compared"][number]["value"] > out["compared"][number]["limit"]


def test_the_rehearsal_engages_covering_on_the_served_path():
    """The cell's rehearsal, traced: `correct`, every window over the
    covering snapshot, nothing sent to the host by the expansion."""
    r, out = run_cell("--workload", CELL, "--seed", str(2**31 + 41),
                      "--seconds", "2", "--trace", "1", "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is True and out["failed"] == 0, out["compared"]
    assert all(c["value"] == 0 for c in out["compared"].values())
    values = {k: v["value"] for k, v in out["rehearsal_values"].items()}
    assert values["cover_expand_window_share.flood"] == 100.0
    assert values["cover_overflow_share.flood"] == 0.0 \
        == values["host_fallback_share.flood"]
    assert values["route_match_roofline.flood"] == 0.0    # no device plane
    assert values["device_routed_share.flood"] > 50
