"""The `tenant-umbrella` deployment's own pieces, checked on the CPU:
the `tenant_umbrella` population against its file, against `plain.py`
and against the program's own covering predicate, the cell's manifest
entries (and what two older cases of the manifest held beside the pins
this cell's entries break), its one new metric through its reader, the
refusal of a program without the counter that metric reads, and the
cell's rehearsal whole and with a guarantee broken.
"""

import json
import os

import numpy as np
import pytest

from benchmark import check, manifest, populations, traffic_gen
from benchmark.populations import tenant_umbrella, umbrella_cover
from benchmark.readers import read_metric, route_bytes
from benchmark.tests.test_runs import run_cell
from benchmark.tests.test_trace_loop import (COUNTER, TRACE, _listed_for,
                                             loop_spec)
from benchmark.tests.test_umbrella_cover import interned

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "tenant-umbrella.flood"
UMBRELLA = "umbrella-cover.flood"
OLDER = ["plus-100k.flood", "share50-250k.flood", "mixed-zipf.flood",
         "fleet-bcast.flood", UMBRELLA]
SIZES = [(12, 2), (13, 3)]      # areas, orgs: the rehearsal's, and one
#                                 in which the orgs hold 5, 4 and 4 areas


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tenant-umbrella.json")) as f:
        return json.load(f)


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def pop_of(areas, orgs, conns=16):
    return tenant_umbrella.Population({"areas": areas, "orgs": orgs}, conns)


# ------------------------------------------------------- the population

@pytest.mark.parametrize("areas,orgs", SIZES)
def test_tenant_population_is_umbrella_covers_areas_under_org_umbrellas(
        areas, orgs):
    pop = pop_of(areas, orgs)
    filters = pop.filters()
    assert len(filters) == len(set(filters)) == 100 * areas + orgs == pop.n
    # the areas are `umbrella_cover`'s, filter for filter and number
    # for number, with the tenant's name for level 0
    theirs = umbrella_cover.Population({"areas": areas}, 16).filters()
    for k, (mine, its) in enumerate(zip(filters, theirs)):
        a = k // 100
        assert its.split("/")[0] == f"org{a % 50}"
        assert mine.split("/") == [f"org{a % orgs}"] + its.split("/")[1:]
    assert filters[100 * areas:] == [f"org{k}/#" for k in range(orgs)]
    # every subscription is somebody's, once, by its number % conns
    owned = [pop.subscriptions(c) for c in range(pop.conns)]
    assert sorted(f for s in owned for f, q in s if q == 0) \
        == sorted(filters)
    assert all(f == filters[c + 16 * i] for c, s in enumerate(owned)
               for i, (f, _q) in enumerate(s))
    assert pop.dims == (areas, 8, 49)
    # the topics are `umbrella_cover`'s under the tenant's name
    its = umbrella_cover.Population({"areas": areas}, 16)
    for key in range(0, areas * 8 * 49, 7):
        a = key // (8 * 49)
        assert pop.topic(key).split("/") == [f"org{a % orgs}"] \
            + its.topic(key).split("/")[1:]


def test_at_fifty_orgs_the_areas_are_umbrella_covers_letter_for_letter():
    pop = pop_of(60, 50)
    its = umbrella_cover.Population({"areas": 60}, 16)
    assert pop.filters()[:6000] == its.filters()
    assert [pop.topic(k) for k in range(0, 60 * 392, 11)] \
        == [its.topic(k) for k in range(0, 60 * 392, 11)]
    keys = np.arange(60 * 392)
    assert (pop.expect(keys)[:, 1:] == its.expect(keys)).all()


@pytest.mark.parametrize("areas,orgs", SIZES)
def test_cover_chains_are_two_deep_and_nothing_else_covers(areas, orgs):
    """By the program's own predicate (`ops.cover.covers_pair`), over
    every pair of filters: `org{k}/#` covers every filter of its org
    and nothing else, an area's historian its own 49 and nothing else,
    and no other filter covers anything."""
    from emqx_tpu.ops.cover import covers_pair
    pop = pop_of(areas, orgs)
    filters = pop.filters()
    _rows, _lens, words = interned(filters)
    n_area = 100 * areas
    fan_in = np.zeros(len(filters), int)
    for a in range(len(filters)):
        for b in range(len(filters)):
            if a == b:
                continue
            if a >= n_area:         # an org's umbrella
                want = b < n_area and (b // 100) % orgs == a - n_area
            else:                   # an area's historian, or nothing
                want = a % 100 == 0 and a < b < a + 50
            assert covers_pair(list(words[a]), list(words[b])) == want, \
                (filters[a], filters[b])
            fan_in[a] += want
    per_org = [100 * len(range(k, areas, orgs)) for k in range(orgs)]
    assert fan_in[n_area:].tolist() == per_org and min(per_org) > 256
    assert set(fan_in[:n_area].tolist()) == {0, 49}


@pytest.mark.parametrize("areas,orgs", SIZES)
def test_the_roots_the_engine_keeps_fit_the_shape_table(areas, orgs):
    from emqx_tpu.ops import cover
    pop = pop_of(areas, orgs)
    filters = pop.filters()
    rows, lens, _w = interned(filters)
    full = cover.full_shape_count(rows, lens)
    assert full == route_bytes.shapes_of(filters) == 62
    assert cover.covering_decision(full, 32, rows.shape[1]) \
        == (True, "engaged")
    # the roots under the rule: the org umbrellas, the historians, the
    # standalone filters
    root = np.array([k >= 100 * areas or k % 100 == 0 or k % 100 >= 50
                     for k in range(len(filters))])
    assert cover.full_shape_count(rows[root], lens[root]) == 14 <= 32
    # the roots the parent's rule left: all but the first 256 an org's
    # umbrella met, in more shapes than the table holds
    taken = np.zeros(len(filters), bool)
    for k in range(orgs):
        mine = [i for i in range(100 * areas) if (i // 100) % orgs == k]
        taken[mine[:256]] = True
    assert cover.full_shape_count(rows[~taken], lens[~taken]) > 32


@pytest.mark.parametrize("areas,orgs", SIZES)
def test_tenant_closed_form_equals_brute_force_on_every_key(areas, orgs):
    pop = pop_of(areas, orgs)
    keys = np.arange(areas * 8 * 49)
    assert check.brute_force(pop, keys, len(keys), seed=5) == 0
    want = pop.expect(keys)
    assert want.shape == (len(keys), 3) and (want[:, :2] >= 0).all()
    fan = (want >= 0).sum(axis=1)
    assert (fan == 3).mean() == 0.5 and (fan == 2).mean() == 0.5
    assert populations.expected_count(pop, keys) == len(keys) * 5 // 2
    # the org's umbrella is connection (areas * 100 + org) % 16's
    a = keys // 392
    assert (want[:, 0] == (areas * 100 + a % orgs) % 16).all()
    # every topic lies under its org's umbrella, three quarters under
    # an area's as well
    topics = [pop.topic(k) for k in keys]
    assert all(t.split("/")[0] == f"org{(k // 392) % orgs}"
               for k, t in zip(keys, topics))
    under = np.array([t.split("/")[1].startswith("area") for t in topics])
    assert under.mean() == 0.75
    assert {len(t.split("/")) for t in topics} == set(range(3, 12))


def test_brute_force_sees_a_forgotten_org_umbrella():
    class Off(tenant_umbrella.Population):
        def expect(self, keys):
            out = super().expect(keys)
            out[:, 0] = -1
            return out
    pop = Off({"areas": 12, "orgs": 2}, 16)
    keys = np.arange(12 * 8 * 49)
    assert check.brute_force(pop, keys, len(keys), seed=5) == len(keys)


def test_orgs_that_cover_nothing_are_refused():
    with pytest.raises(manifest.ManifestError, match="has to cover"):
        pop_of(12, 50)
    with pytest.raises(manifest.ManifestError, match="has to cover"):
        pop_of(12, 0)


def test_a_program_without_the_roots_counter_is_refused(monkeypatch,
                                                        tmp_path):
    """The parent serves this cell from the host (every topic passes
    the candidate plane under its org's umbrella), so the population
    refuses it at once. What it asks for is the counter that the
    cell's own `cover_roots_per_topic.flood` reads, anywhere in the
    program; `umbrella_cover`'s question is asked as well."""
    import sys
    import types

    with open(tenant_umbrella.ROOTS_METRIC) as f:
        counter = json.load(f)["args"]["num"][0]
    assert counter == "routing.device.cover_roots"
    assert pop_of(12, 2).dims == (12, 8, 49)    # this program has it
    (tmp_path / "broker").mkdir()
    old = tmp_path / "broker" / "engine.py"
    # PR 41's program: it counts the candidates, not the roots
    old.write_text('metrics.inc("routing.device.cover_candidates", n)\n')
    (tmp_path / "__init__.py").write_text("")
    fake = types.ModuleType("emqx_tpu")
    fake.__file__ = str(tmp_path / "__init__.py")
    monkeypatch.setitem(sys.modules, "emqx_tpu", fake)
    with pytest.raises(manifest.ManifestError, match="the host route"):
        pop_of(12, 2)
    # `umbrella_cover`, which that program serves from the chip, loads
    assert umbrella_cover.Population({"areas": 6}, 16).dims == (6, 8, 49)
    old.write_text(f'metrics.inc("routing.device.cover_candidates", n)\n'
                   f'metrics.inc("{counter}", n)\n')
    assert pop_of(12, 2).dims == (12, 8, 49)
    # a generator process has no program loaded: nothing to ask
    monkeypatch.delitem(sys.modules, "emqx_tpu")
    assert pop_of(12, 2).dims == (12, 8, 49)


def test_tenant_full_size_has_the_stated_counts():
    cfg = config()
    pop = populations.load(cfg)
    filters = pop.filters()
    assert len(filters) == len(set(filters)) == cfg["filters"] \
        == cfg["subscriptions"] == 250050
    assert sum(f.endswith("/#") for f in filters) == 2550
    assert filters[250000:] == [f"org{k}/#" for k in range(50)]
    assert filters[:250000] == umbrella_cover.Population(
        {"areas": 2500}, 16).filters()
    assert route_bytes.shapes_of(filters) == 62
    assert sum(len(pop.subscriptions(c)) for c in range(16)) == 250050
    assert [pop.subscriptions(k % 16)[-1 - (49 - k) // 16][0]
            for k in (49, 33, 0)] == ["org49/#", "org33/#", "org0/#"]
    assert pop.dims == (2500, 8, 49) and pop.conns == 16 and pop.orgs == 50
    assert cfg["population"]["params"] == {"areas": 2500, "orgs": 50}
    assert cfg["rehearse"]["population"] == {"areas": 12, "orgs": 2}
    assert cfg["node"] == {} and len(cfg["reduced"]) == 1 \
        and cfg["reduced"][0].startswith(
            "filters: 1,000,000 + 50 -> 250,000 + 50 (4x)") \
        and "20,000 covered filters to 5,000" in cfg["reduced"][0]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "umbrella-cover.json")) as f:
        theirs = json.load(f)
    # the guarantees are `umbrella-cover`'s word for word, the traffic
    # its own, and nothing it assumed is dropped
    assert cfg["guarantees"] == theirs["guarantees"]
    assert cfg["publish"] == theirs["publish"]
    assert set(theirs["assumed"]) | {"orgs"} == set(cfg["assumed"])
    assert cfg["source"] == bench()["configs"][-1]["source"] \
        and len(cfg["source"]) <= 200
    assert {"deployment", "layout", "chips"} <= set(cfg)


def test_tenant_zipf_draw_and_one_key_of_each_kind_by_hand():
    cfg = config()
    pop = populations.load(cfg)
    keys = traffic_gen.draw_keys(traffic_gen.rng_for(2**31 + 5, 1),
                                 200000, pop.dims, cfg["publish"]["keys"])
    assert keys.min() >= 0 and keys.max() < 2500 * 8 * 49
    a, r, _pick = np.unravel_index(keys, pop.dims)
    share = np.bincount(a, minlength=2500) / len(keys)
    assert 0.23 < share[0] < 0.27            # area 0 carries a quarter
    fan = (pop.expect(keys) >= 0).sum(axis=1)
    assert abs(fan.mean() - 2.5) < 0.01
    assert abs((r < 6).mean() - 0.75) < 0.01     # under a historian too
    assert check.brute_force(pop, keys, 24, seed=2**31 + 5) == 0
    # area 7 has a prefix of 3 levels and is org7's
    base = 7 * 8 * 49
    assert pop.topic(base + 5) == "org7/area7/p2w9/v5/c5"
    assert pop.topic(base + 4 * 49 + 5) == "org7/area7/p2w9/z5"
    assert pop.topic(base + 6 * 49 + 5) == "org7/solo7/p2w9/x5/t5"
    want = pop.expect([base + 5, base + 4 * 49 + 5, base + 6 * 49 + 5])
    org7 = (250000 + 7) % 16
    assert want.tolist() == [[org7, 700 % 16, 706 % 16],
                             [org7, 700 % 16, -1], [org7, 755 % 16, -1]]
    # area 57 is org7's too
    assert pop.topic(57 * 392 + 5).startswith("org7/area57/")


# ----------------------------------------------------------- the manifest

def test_the_cell_reports_its_48_metrics_and_joined_every_list_last():
    b = bench()
    mine = _listed_for(b, CELL)
    assert len(mine) == 48
    # everything `umbrella-cover.flood` reports, and one of its own
    assert mine[:-1] == _listed_for(b, UMBRELLA) \
        and mine[-1] == "cover_roots_per_topic.flood"
    assert set(COUNTER + TRACE) | {
        "host_fallback_share.flood", "match_cache_hit_share.flood",
        "cached_window_share.flood", "route_match_roofline.flood",
        "cover_candidates_per_topic.flood", "cover_overflow_share.flood",
        "cover_expand_window_share.flood", "snapshot_build_s",
        "device_routed_share.flood"} <= set(mine)
    assert not {"route_roofline.flood", "puback_per_s.flood",
                "nfa_window_share.flood", "match_overflow_share.flood",
                "route_nfa_roofline.flood", "fuse_depth.flood",
                "wide_fanout_delivery_share.flood"} & set(mine)
    new = b["per_layer"][-1]
    assert new == {"name": "cover_roots_per_topic.flood", "unit": "roots",
                   "better": "lower", "source": "program_counter",
                   "layer": "route programs + kernels",
                   "moves": "delivered_per_s", "workloads": [CELL]}
    assert [m["name"] for m in b["per_layer"]
            if m["workloads"] == [CELL]] == [new["name"]]
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and [m["name"] for m in cell.end_to_end] \
        == ["delivered_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == mine
    assert cell.per_layer[-1]["reader"] == "counter"
    assert cell.traffic["name"] == "flood"
    assert b["workloads"][-1] == {
        "name": CELL, "config": "tenant-umbrella", "traffic": "flood",
        "chips": 1, "why": b["workloads"][-1]["why"]}
    assert len(b["workloads"][-1]["why"]) <= 200
    assert b["configs"][-1]["name"] == "tenant-umbrella" \
        and b["configs"][-1]["reduced"] == ["filters"] \
        and b["configs"][-1]["file"] \
        == "benchmark/configs/tenant-umbrella.json" \
        and len(b["configs"][-1]["why"]) <= 200
    # every list the cell joined, it joined at the end, after the five
    # that were there, in the order they had
    joined = [m for m in b["per_layer"] + b["end_to_end"]
              if CELL in m.get("workloads", ())]
    assert len(joined) == 49
    assert all(m["workloads"][-1] == CELL
               and m["workloads"][:-1] == [c for c in OLDER
                                           if c in m["workloads"]]
               for m in joined)
    assert [w["name"] for w in b["workloads"]] == OLDER + [CELL]
    assert len(b["configs"]) == 6 and b["run_seconds"] == 51
    assert [(m["name"], m["bound"]) for m in b["end_to_end"]] \
        == [("delivered_per_s", 0.25), ("setup_s", 0.25)]


def test_the_thirteen_loop_entries_fit_their_files_at_the_new_lists():
    """What `test_trace_loop.py::test_the_thirteen_entries_are_the_
    manifests_last_and_fit_their_files` holds beside its two pins (the
    thirteen as the manifest's last entries, listed for five cells: a
    strict xfail in tier-1 since this cell's entries)."""
    b = bench()
    new = b["per_layer"][-14:-1]
    assert [m["name"] for m in new] == COUNTER + TRACE
    layers = {m["layer"] for m in b["per_layer"][:-14]}
    for m in new:
        assert m["workloads"] == OLDER + [CELL] \
            and m["moves"] == "delivered_per_s"
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
    assert sum(m["name"].startswith("idle_") for m in b["per_layer"]) == 7
    # the new cell loads them with their readers like the other five
    c = manifest.Cell(CELL)
    by_name = {m["name"]: m for m in c.per_layer}
    assert [m["name"] for m in c.per_layer][-14:-1] == COUNTER + TRACE
    assert all(by_name[n]["reader"] == "counter" for n in COUNTER)
    assert all(by_name[n]["reader"] == "trace_loop" for n in TRACE)


def test_umbrella_cover_still_reports_its_47_and_shares_its_three():
    """What `test_trace_loop.py::test_umbrella_cover_reports_its_47_
    metrics_and_its_own_three` holds beside its two pins (its three
    cover metrics listed for it alone, and it the manifest's last cell:
    a strict xfail in tier-1 since this cell's entries)."""
    b = bench()
    mine = _listed_for(b, UMBRELLA)
    assert len(mine) == 34 + 13
    assert {"cover_expand_window_share.flood", "host_fallback_share.flood",
            "match_cache_hit_share.flood", "cached_window_share.flood",
            "snapshot_build_s", "device_routed_share.flood",
            "route_match_device_ms_per_window.flood"} <= set(mine)
    assert not {"route_roofline.flood", "puback_per_s.flood",
                "nfa_window_share.flood", "match_overflow_share.flood",
                "route_nfa_roofline.flood", "cover_roots_per_topic.flood",
                "wide_fanout_delivery_share.flood"} & set(mine)
    its = [m for m in b["per_layer"] if m["workloads"][0] == UMBRELLA]
    assert [(m["name"], m["unit"], m["better"], m["source"])
            for m in its] == [
        ("route_match_roofline.flood", "%", "higher", "device_trace"),
        ("cover_candidates_per_topic.flood", "candidates", "lower",
         "program_counter"),
        ("cover_overflow_share.flood", "%", "lower", "program_counter")]
    assert its == b["per_layer"][-17:-14]
    assert all(m["workloads"] == [UMBRELLA, CELL]
               and m["layer"] == "route programs + kernels"
               and m["moves"] == "delivered_per_s" for m in its)
    cell = manifest.Cell(UMBRELLA)
    assert cell.chips == 1 and [m["name"] for m in cell.end_to_end] \
        == ["delivered_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == mine
    assert {m["name"]: m["reader"] for m in cell.per_layer[-16:-13]} == {
        "route_match_roofline.flood": "route_match_roofline",
        "cover_candidates_per_topic.flood": "counter",
        "cover_overflow_share.flood": "counter"}
    assert b["workloads"][-2]["name"] == UMBRELLA \
        and b["configs"][-2]["name"] == "umbrella-cover" \
        and b["configs"][-2]["reduced"] == ["filters"]
    assert all(m["workloads"][-2:] == [UMBRELLA, CELL]
               for m in b["per_layer"] + b["end_to_end"]
               if UMBRELLA in m.get("workloads", ()))


def test_the_roots_metric_through_its_own_file():
    with open(tenant_umbrella.ROOTS_METRIC) as f:
        spec = json.load(f)
    entry = bench()["per_layer"][-1]
    assert (spec["name"], spec["unit"], spec["moves"], spec["reader"]) \
        == (entry["name"], entry["unit"], entry["moves"], "counter")
    assert spec["args"] == {"num": ["routing.device.cover_roots"],
                            "den": ["routing.device.match_lanes"]}
    ctx = {"window": {"seconds": 50.0},
           "m0": {"routing.device.cover_roots": 400,
                  "routing.device.match_lanes": 200,
                  "routing.device.cover_candidates": 7750},
           "m1": {"routing.device.cover_roots": 400 + 2_000_000,
                  "routing.device.match_lanes": 200 + 1_000_000,
                  "routing.device.cover_candidates": 7750 + 38_750_000}}
    assert read_metric(ctx, "counter", spec["args"]) == 2.0
    with open(umbrella_cover.CANDIDATES_METRIC) as f:
        cands = json.load(f)
    # with the candidates a topic it gives the candidates a root
    assert read_metric(ctx, "counter", cands["args"]) / 2.0 == 19.375
    # a program without the counter (the parent), or a window in which
    # the match cache served every topic: 0, and nothing raised
    bare = {"window": {"seconds": 50.0}, "m0": {},
            "m1": {"routing.device.match_lanes": 10}}
    assert read_metric(bare, "counter", spec["args"]) == 0.0
    bare["m1"] = {}
    assert read_metric(bare, "counter", spec["args"]) == 0.0


# ------------------------------------------------------------ whole runs

@pytest.mark.parametrize("control,number", [
    ("lose", "wrong_delivery_sets"),
    ("duplicate", "wrong_delivery_sets"),
    ("reorder", "order_breaks"),
])
def test_tenant_umbrella_with_a_guarantee_broken_is_not_correct(control,
                                                                number):
    r, out = run_cell("--workload", CELL, "--seed", "42", "--seconds", "1",
                      "--trace", "0", "--rehearse", "--control", control)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is False and out["failed"] > 0
    assert out["compared"][number]["value"] > out["compared"][number]["limit"]


def test_the_rehearsal_keeps_the_org_umbrellas_wide_on_the_served_path():
    """The cell's rehearsal, traced: `correct`, every window over the
    covering snapshot, no lane sent to the host by the expansion (the
    parent's sent them all), two roots a topic wherever the match
    stage matched one in the window (at 4,704 keys the match cache may
    have served them all). Up to three seeds, until one's window
    holds a device window."""
    for seed in (2**31 + 42, 2**31 + 43, 2**31 + 44):
        r, out = run_cell("--workload", CELL, "--seed", str(seed),
                          "--seconds", "2", "--trace", "1", "--rehearse")
        assert r.returncode == 0, r.stderr[-2000:]
        assert out["correct"] is True and out["failed"] == 0, \
            out["compared"]
        assert all(c["value"] == 0 for c in out["compared"].values())
        # under other workers' load the CPU backend's chooser can keep
        # a 2 s window on the host: that run says nothing of the chip's
        # path, so ask again
        if out["split"]["window"]["device_windows"]:
            break
    values = {k: v["value"] for k, v in out["rehearsal_values"].items()}
    assert len(values) == 48
    assert values["cover_expand_window_share.flood"] == 100.0
    assert values["cover_overflow_share.flood"] == 0.0 \
        == values["host_fallback_share.flood"]
    assert values["route_match_roofline.flood"] == 0.0    # no device plane
    assert values["device_routed_share.flood"] > 50
    roots = values["cover_roots_per_topic.flood"]
    cands = values["cover_candidates_per_topic.flood"]
    if values["match_cache_hit_share.flood"] < 100.0:
        assert roots == 2.0 and 2.0 <= cands <= 51.0
    else:
        assert roots == 0.0 == cands
    assert out["split"]["window"]["by_counter"][
        "cover_roots_per_topic.flood"] == pytest.approx(roots, abs=1e-3)
