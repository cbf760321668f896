"""The `fanin-workers` deployment's own pieces, checked on the CPU: the
`fanin_workers` population against its file and against `plain.py` on
every key, the cell's manifest entries (and what five older cases of
the manifest held beside the pins this cell's entries break), its five
new metrics through the counter reader, and the cell's rehearsal whole
and with each guarantee broken.
"""

import json
import os

import numpy as np
import pytest

from benchmark import check, manifest, populations, traffic_gen
from benchmark.populations import fanin_workers, tenant_umbrella, \
    umbrella_cover
from benchmark.readers import read_metric, route_bytes
from benchmark.tests.test_runs import run_cell
from benchmark.tests.test_trace_loop import (COUNTER, TRACE, _listed_for,
                                             loop_bench as bench,
                                             loop_spec as spec_of)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "fanin-workers.flood"
TENANT = "tenant-umbrella.flood"
UMBRELLA = "umbrella-cover.flood"
OLDER = ["plus-100k.flood", "share50-250k.flood", "mixed-zipf.flood",
         "fleet-bcast.flood", UMBRELLA, TENANT]
SIZES = [40, 97]        # devices: a multiple of nothing, and a prime
NEW = ["mqueue_parked_share.flood", "sub_puback_per_s.flood",
       "ack_us_per_puback.flood", "shared_picks_per_publish.flood",
       "mqueue_dropped.flood"]
POOLS = {"store": (16, 32), "rules": (48, 8), "alert": (56, 4)}


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "fanin-workers.json")) as f:
        return json.load(f)


def pop_of(devices, conns=60):
    return fanin_workers.Population(
        {"devices": devices, "gateways": 16, "store": 32, "rules": 8,
         "alert": 4}, conns)


# ------------------------------------------------------- the population

@pytest.mark.parametrize("devices", SIZES)
def test_fanin_population_is_a_fleet_under_three_worker_pools(devices):
    pop = pop_of(devices)
    filters = pop.filters()
    assert len(filters) == len(set(filters)) == devices + 3
    assert filters[:devices] == [f"down/d{i}/cmd/+" for i in range(devices)]
    assert filters[devices:] == ["up/#", "up/+/state/#", "up/+/event/+"]
    assert route_bytes.shapes_of(filters) == 4
    owned = [pop.subscriptions(c) for c in range(60)]
    assert all(q == 1 for s in owned for _f, q in s)
    # a device's command filter is its gateway's, once
    assert sorted(f for s in owned[:16] for f, _q in s) \
        == sorted(filters[:devices])
    assert all(f == f"down/d{c + 16 * i}/cmd/+"
               for c, s in enumerate(owned[:16])
               for i, (f, _q) in enumerate(s))
    for (name, (first, size)), f in zip(POOLS.items(), filters[devices:]):
        assert [owned[c] for c in range(first, first + size)] \
            == [[(f"$share/{name}/{f}", 1)]] * size
    assert sum(map(len, owned)) == devices + 44
    assert pop.dims == (devices, 20) and pop.conns == 60
    assert pop.sub_qos == {"plain": 1, "shared": 1}
    with pytest.raises(ValueError, match="no connection"):
        pop.subscriptions(60)


@pytest.mark.parametrize("devices", SIZES)
def test_fanin_closed_form_equals_brute_force_on_every_key(devices):
    pop = pop_of(devices)
    keys = np.arange(devices * 20)
    assert check.brute_force(pop, keys, len(keys), seed=5) == 0
    i, slot = np.divmod(keys, 20)
    want = pop.expect(keys)
    assert want.shape == (len(keys), 1)
    assert (want[:, 0] == np.where(slot < 2, i % 16, -1)).all()
    shared = pop.expect_shared(keys)
    assert shared.shape == (len(keys), 2, 32)
    gids = pop.group_ids(keys)
    assert gids.shape == (len(keys), 2)
    # store takes all of the uplink, rules the state reports, alert the
    # events; a command reaches no group
    assert (gids[:, 0] == np.where(slot >= 2, 0, -1)).all()
    assert (gids[:, 1] == np.select(
        [slot == 19, slot >= 15], [2, 1], -1)).all()
    for g, (first, size) in enumerate(POOLS.values()):
        rows = shared[gids == g]
        assert (rows[:, :size] == first + np.arange(size)).all() \
            and (rows[:, size:] == -1).all()
    assert (shared[gids < 0] == -1).all()
    # no two groups that match one key share a member
    both = shared[(gids >= 0).all(axis=1)]
    assert all(not set(a[a >= 0]) & set(b[b >= 0]) for a, b in both[:50])
    # 10 % commands, 65 % metrics, 20 % state, 5 % events: 1.25
    # deliveries and 1.15 picks a PUBLISH
    assert populations.expected_count(pop, keys) == len(keys) * 5 // 4
    assert (gids >= 0).sum() * 20 == len(keys) * 23
    kinds = [pop.topic(k).split("/")[2] for k in keys]
    assert {k: kinds.count(k) / len(keys) for k in set(kinds)} \
        == {"cmd": 0.1, "metric": 0.65, "state": 0.2, "event": 0.05}


def test_brute_force_sees_a_group_left_out_and_a_member_too_many():
    class NoRules(fanin_workers.Population):
        def expect_shared(self, keys):
            out = super().expect_shared(keys)
            out[self.group_ids(keys)[:, 1] == 1, 1] = -1
            return out

    class OneMore(fanin_workers.Population):
        def expect_shared(self, keys):
            out = super().expect_shared(keys)
            out[:, 0, 31] = np.where(out[:, 0, 0] >= 0, 48, -1)
            return out
    keys = np.arange(40 * 20)
    params = {"devices": 40, "gateways": 16, "store": 32, "rules": 8,
              "alert": 4}
    assert check.brute_force(NoRules(params, 60), keys, len(keys), 5) \
        == 40 * 4
    assert check.brute_force(OneMore(params, 60), keys, len(keys), 5) \
        == 40 * 18


def test_pools_that_do_not_add_up_to_the_connections_are_refused():
    with pytest.raises(ValueError, match="are not 16 connections"):
        pop_of(40, conns=16)
    with pytest.raises(ValueError, match="are not 60 connections"):
        fanin_workers.Population({"devices": 40, "gateways": 16,
                                  "store": 32, "rules": 8, "alert": 0}, 60)


def test_fanin_full_size_has_the_stated_counts():
    cfg = config()
    pop = populations.load(cfg)
    assert cfg["population"]["params"] == {
        "devices": 1000000, "gateways": 16, "store": 32, "rules": 8,
        "alert": 4}
    assert pop.dims == (1000000, 20) and pop.conns == 60 \
        == cfg["connections"]["subscribers"]
    # counted without building a million strings twice over
    assert cfg["filters"] == pop.devices + 3 == 1000003
    assert cfg["subscriptions"] == sum(
        len(range(c, pop.devices, 16)) for c in range(16)) + 44 == 1000044
    assert len(pop.subscriptions(5)) == 62500
    assert pop.subscriptions(5)[-1] == ("down/d999989/cmd/+", 1)
    assert pop.subscriptions(47) == [("$share/store/up/#", 1)]
    assert pop.subscriptions(59) == [("$share/alert/up/+/event/+", 1)]
    assert cfg["rehearse"]["population"] == {"devices": 400}
    assert cfg["node"] == {} and cfg["reduced"] == []
    assert cfg["publish"] == {"keys": {"dist": "zipf", "s": 1.3, "dim": 0},
                              "qos1_every": 1, "payload_bytes": 256}
    assert cfg["limits"] == {"rr_excess_vs_random": 0.3}
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "share50-250k.json")) as f:
        theirs = json.load(f)
    assert cfg["limits"] == theirs["limits"]
    assert cfg["guarantees"]["share"].startswith(
        theirs["guarantees"]["share"].split(" Held as")[0])
    assert set(cfg["guarantees"]) == {"delivery", "qos", "order", "share"}
    assert "none is lost" in cfg["guarantees"]["qos"]
    # the zone defaults it runs under are the program's own
    from emqx_tpu.broker.config import DEFAULTS
    z = cfg["zone_defaults"]
    assert (z["max_inflight"], z["max_mqueue_len"], z["mqueue_store_qos0"],
            z["retry_interval_s"]) == tuple(
        DEFAULTS["mqtt"][k] for k in ("max_inflight", "max_mqueue_len",
                                      "mqueue_store_qos0",
                                      "retry_interval"))
    assert {"groups", "kinds", "topic_names", "gateways",
            "subscription_qos", "publish_qos", "payload_bytes",
            "publish_keys", "mqueue_headroom"} == set(cfg["assumed"])
    entry = [c for c in bench()["configs"] if c["name"] == "fanin-workers"]
    assert entry == [bench()["configs"][-1]]
    assert cfg["source"] == entry[0]["source"] and len(cfg["source"]) <= 200
    assert "emqx_shared_sub.erl:62-67,239-290" in cfg["source"] \
        and "BASELINE.json configs[3]" in cfg["source"]
    assert {"deployment", "layout", "chips", "why"} <= set(cfg)
    assert cfg["chips"] == 1


def test_fanin_zipf_draw_and_one_key_of_each_kind_by_hand():
    cfg = config()
    pop = populations.load(cfg)
    keys = traffic_gen.draw_keys(traffic_gen.rng_for(2**31 + 5, 1),
                                 200000, pop.dims, cfg["publish"]["keys"])
    assert keys.min() >= 0 and keys.max() < 20000000
    i, slot = np.unravel_index(keys, pop.dims)
    share = np.bincount(np.minimum(i, 9), minlength=10) / len(keys)
    assert 0.23 < share[0] < 0.27           # device 0 carries a quarter
    assert (i == 999999).mean() < 0.02      # the folded tail
    assert abs(populations.expected_count(pop, keys) / len(keys) - 1.25) \
        < 0.01
    assert abs((pop.group_ids(keys) >= 0).sum() / len(keys) - 1.15) < 0.01
    assert (pop.expect_shared(keys[:1000]) >= 0).any(axis=2).sum() \
        == (pop.group_ids(keys[:1000]) >= 0).sum()
    assert [pop.topic(7 * 20 + s) for s in (0, 1, 2, 14, 15, 18, 19)] == [
        "down/d7/cmd/c0", "down/d7/cmd/c1", "up/d7/metric/n2",
        "up/d7/metric/n14", "up/d7/state/n15", "up/d7/state/n18",
        "up/d7/event/n19"]
    by_hand = np.array([7 * 20 + 1, 23 * 20 + 9, 23 * 20 + 16,
                        999999 * 20 + 19])
    assert pop.expect(by_hand).tolist() == [[7], [-1], [-1], [-1]]
    assert pop.group_ids(by_hand).tolist() \
        == [[-1, -1], [0, -1], [0, 1], [0, 2]]
    members = pop.expect_shared(by_hand)
    assert members[0].max() == -1
    assert members[2, 0].tolist() == list(range(16, 48))
    assert members[2, 1, :9].tolist() == list(range(48, 56)) + [-1]
    assert members[3, 1, :5].tolist() == [56, 57, 58, 59, -1]


# ----------------------------------------------------------- the manifest

def test_the_cell_reports_its_53_metrics_and_joined_every_list_last():
    b = bench()
    mine = _listed_for(b, CELL)
    assert len(mine) == 53
    # the 41 every cell reports, share50-250k.flood's own three, the
    # shape-hash roofline, the two cache shares, the host fallback, and
    # five of its own at the manifest's end
    assert mine[-5:] == NEW == [m["name"] for m in b["per_layer"][-5:]]
    shared = set(_listed_for(b, "share50-250k.flood"))
    assert shared | {"host_fallback_share.flood"} == set(mine[:-5])
    assert set(COUNTER + TRACE) | {
        "puback_per_s.flood", "shared_lane_share.flood", "fuse_depth.flood",
        "route_roofline.flood", "match_cache_hit_share.flood",
        "cached_window_share.flood", "host_fallback_share.flood",
        "cover_expand_window_share.flood", "snapshot_build_s",
        "device_routed_share.flood"} <= set(mine)
    assert not {"route_match_roofline.flood", "nfa_window_share.flood",
                "match_overflow_share.flood", "route_nfa_roofline.flood",
                "nfa_narrow_step_share.flood",
                "cover_candidates_per_topic.flood",
                "cover_overflow_share.flood", "cover_roots_per_topic.flood",
                "wide_fanout_delivery_share.flood"} & set(mine)
    for m in b["per_layer"][-5:]:
        assert m == {"name": m["name"], "unit": m["unit"],
                     "better": m["better"], "source": "program_counter",
                     "layer": "consume + lanes",
                     "moves": "delivered_per_s", "workloads": [CELL]}
    assert [(m["unit"], m["better"]) for m in b["per_layer"][-5:]] == [
        ("%", "lower"), ("acks/s", "higher"), ("us", "lower"),
        ("picks", "higher"), ("msgs", "lower")]
    assert [m["name"] for m in b["per_layer"]
            if m["workloads"] == [CELL]] == NEW
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and [m["name"] for m in cell.end_to_end] \
        == ["delivered_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == mine
    assert all(m["reader"] == "counter" for m in cell.per_layer[-5:])
    assert cell.traffic["name"] == "flood"
    assert b["workloads"][-1] == {
        "name": CELL, "config": "fanin-workers", "traffic": "flood",
        "chips": 1, "why": b["workloads"][-1]["why"]}
    assert len(b["workloads"][-1]["why"]) <= 200
    assert b["configs"][-1] == {
        "name": "fanin-workers", "source": b["configs"][-1]["source"],
        "file": "benchmark/configs/fanin-workers.json", "reduced": [],
        "why": b["configs"][-1]["why"]}
    assert len(b["configs"][-1]["why"]) <= 200
    # every list the cell joined, it joined at the end, after the six
    # that were there, in the order they had
    joined = [m for m in b["per_layer"] + b["end_to_end"]
              if CELL in m.get("workloads", ())]
    assert len(joined) == 54
    assert all(m["workloads"][-1] == CELL
               and m["workloads"][:-1] == [c for c in OLDER
                                           if c in m["workloads"]]
               for m in joined)
    assert [w["name"] for w in b["workloads"]] == OLDER + [CELL]
    assert len(b["configs"]) == 7 and b["run_seconds"] == 51
    assert [(m["name"], m["bound"]) for m in b["end_to_end"]] \
        == [("delivered_per_s", 0.25), ("setup_s", 0.25)]
    assert len(json.dumps(b, indent=1)) < 64 * 1024


# ---- what five cases of `test_tenant_umbrella.py` held beside the pins
# that a seventh cell moves (each a strict xfail in tier-1 since this
# cell's entries: `tests/test_benchmark.py`)

def test_tenant_full_size_still_has_the_stated_counts():
    """`test_tenant_full_size_has_the_stated_counts`, with its
    configuration looked up by name and not as the manifest's last."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tenant-umbrella.json")) as f:
        cfg = json.load(f)
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
    assert pop.dims == (2500, 8, 49) and pop.conns == 16 and pop.orgs == 50
    assert cfg["population"]["params"] == {"areas": 2500, "orgs": 50}
    assert cfg["rehearse"]["population"] == {"areas": 12, "orgs": 2}
    assert cfg["node"] == {} and len(cfg["reduced"]) == 1
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "umbrella-cover.json")) as f:
        theirs = json.load(f)
    assert cfg["guarantees"] == theirs["guarantees"]
    assert cfg["publish"] == theirs["publish"]
    assert set(theirs["assumed"]) | {"orgs"} == set(cfg["assumed"])
    entry = {c["name"]: c for c in bench()["configs"]}["tenant-umbrella"]
    assert cfg["source"] == entry["source"] and len(cfg["source"]) <= 200
    assert bench()["configs"][-2] == entry


def test_tenant_umbrella_still_reports_its_48_metrics():
    """`test_the_cell_reports_its_48_metrics_and_joined_every_list_
    last`, beside its pins (it the manifest's last cell, its metric the
    last entry, six configurations)."""
    b = bench()
    mine = _listed_for(b, TENANT)
    assert len(mine) == 48
    assert mine[:-1] == _listed_for(b, UMBRELLA) \
        and mine[-1] == "cover_roots_per_topic.flood"
    assert not {"route_roofline.flood", "puback_per_s.flood",
                "nfa_window_share.flood", "fuse_depth.flood",
                "wide_fanout_delivery_share.flood"} & set(mine) \
        and not set(NEW) & set(mine)
    new = b["per_layer"][-6]
    assert new == {"name": "cover_roots_per_topic.flood", "unit": "roots",
                   "better": "lower", "source": "program_counter",
                   "layer": "route programs + kernels",
                   "moves": "delivered_per_s", "workloads": [TENANT]}
    assert [m["name"] for m in b["per_layer"]
            if m["workloads"] == [TENANT]] == [new["name"]]
    cell = manifest.Cell(TENANT)
    assert cell.chips == 1 and [m["name"] for m in cell.end_to_end] \
        == ["delivered_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == mine
    assert cell.per_layer[-1]["reader"] == "counter"
    assert b["workloads"][-2]["name"] == TENANT \
        and b["configs"][-2]["name"] == "tenant-umbrella" \
        and b["configs"][-2]["reduced"] == ["filters"]
    joined = [m for m in b["per_layer"] + b["end_to_end"]
              if TENANT in m.get("workloads", ())]
    assert len(joined) == 49
    assert all(m["workloads"][:m["workloads"].index(TENANT)]
               == [c for c in OLDER[:5] if c in m["workloads"]]
               for m in joined)


def test_the_thirteen_loop_entries_fit_their_files_at_seven_cells():
    """`test_the_thirteen_loop_entries_fit_their_files_at_the_new_
    lists`, beside its pins (the thirteen at [-14:-1], listed for six
    cells)."""
    b = bench()
    new = b["per_layer"][-19:-6]
    assert [m["name"] for m in new] == COUNTER + TRACE
    layers = {m["layer"] for m in b["per_layer"][:-19]}
    for m in new:
        assert m["workloads"] == OLDER + [CELL] \
            and m["moves"] == "delivered_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["layer"] in layers         # no layer of its own
        spec = spec_of(m["name"])
        assert (spec["name"], spec["unit"], spec["moves"]) == \
            (m["name"], m["unit"], m["moves"])
        assert m["source"] == ("program_counter" if m["name"] in COUNTER
                               else "device_trace")
        assert (spec["reader"] == "counter") == (m["name"] in COUNTER)
    assert {m["name"]: m["better"] for m in new if m["better"] == "higher"} \
        == {"loop_cpu_share.flood": "higher",
            "loop_wait_ms_per_s.flood": "higher"}
    assert {m["unit"] for m in new} == {"%", "ms/s", "us"}
    assert sum(m["name"].startswith("idle_") for m in b["per_layer"]) == 7
    # the new cell's own five sit in a layer that was there
    assert {m["layer"] for m in b["per_layer"][-5:]} <= layers
    c = manifest.Cell(CELL)
    by_name = {m["name"]: m for m in c.per_layer}
    assert all(by_name[n]["reader"] == "counter" for n in COUNTER)
    assert all(by_name[n]["reader"] == "trace_loop" for n in TRACE)


def test_umbrella_covers_three_are_still_the_two_covering_cells():
    """`test_umbrella_cover_still_reports_its_47_and_shares_its_three`,
    beside its pin (its three cover metrics at [-17:-14])."""
    b = bench()
    mine = _listed_for(b, UMBRELLA)
    assert len(mine) == 34 + 13 and not set(NEW) & set(mine)
    its = [m for m in b["per_layer"] if m["workloads"][0] == UMBRELLA]
    assert [(m["name"], m["unit"], m["better"], m["source"])
            for m in its] == [
        ("route_match_roofline.flood", "%", "higher", "device_trace"),
        ("cover_candidates_per_topic.flood", "candidates", "lower",
         "program_counter"),
        ("cover_overflow_share.flood", "%", "lower", "program_counter")]
    assert its == b["per_layer"][-22:-19]
    assert all(m["workloads"] == [UMBRELLA, TENANT]
               and m["layer"] == "route programs + kernels"
               and m["moves"] == "delivered_per_s" for m in its)
    cell = manifest.Cell(UMBRELLA)
    assert [m["name"] for m in cell.per_layer] == mine
    assert b["workloads"][-3]["name"] == UMBRELLA \
        and b["configs"][-3]["name"] == "umbrella-cover"
    # the covering cells keep their lists to themselves: the new cell
    # is cover-free and joined none of them but the host fallback's
    assert all((CELL in m["workloads"])
               == (m["name"] == "host_fallback_share.flood")
               for m in b["per_layer"]
               if m["workloads"][:2] in ([UMBRELLA, TENANT],
                                         ["fleet-bcast.flood", UMBRELLA]))


def test_the_roots_metric_is_still_read_through_its_own_file():
    """`test_the_roots_metric_through_its_own_file`, with the entry
    looked up by name and not as the manifest's last."""
    with open(tenant_umbrella.ROOTS_METRIC) as f:
        spec = json.load(f)
    entry = {m["name"]: m for m in bench()["per_layer"]}[spec["name"]]
    assert (spec["name"], spec["unit"], spec["moves"], spec["reader"]) \
        == (entry["name"], entry["unit"], entry["moves"], "counter")
    assert spec["args"] == {"num": ["routing.device.cover_roots"],
                            "den": ["routing.device.match_lanes"]}
    ctx = {"window": {"seconds": 50.0},
           "m0": {"routing.device.cover_roots": 400,
                  "routing.device.match_lanes": 200},
           "m1": {"routing.device.cover_roots": 400 + 2_000_000,
                  "routing.device.match_lanes": 200 + 1_000_000}}
    assert read_metric(ctx, "counter", spec["args"]) == 2.0
    assert read_metric({"window": {}, "m0": {}, "m1": {}}, "counter",
                       spec["args"]) == 0.0


def test_the_counters_of_prs_29_and_35_still_have_readers_at_two_cells():
    """`test_trace_readers.py::test_the_counters_of_prs_29_and_35_and_
    the_fuse_depth_have_readers`, beside its pin (`fuse_depth` and
    `shared_lane_share` listed for `share50-250k.flood` alone): this
    cell's counters move them too, so it joined both lists."""
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
    want = {"fuse_depth.flood": (1.52, ["share50-250k.flood", CELL],
                                 "batcher + chooser"),
            "shared_lane_share.flood": (80.0, ["share50-250k.flood", CELL],
                                        "consume + lanes"),
            "puback_per_s.flood": (0.0, ["share50-250k.flood", CELL],
                                   "consume + lanes"),
            "nfa_narrow_step_share.flood": (75.0, ["mixed-zipf.flood"],
                                            "route programs + kernels")}
    entries = {m["name"]: m for m in bench()["per_layer"]}
    for name, (value, cells, layer) in want.items():
        spec, entry = spec_of(name), entries[name]
        assert (spec["unit"], spec["moves"]) == (entry["unit"],
                                                 entry["moves"])
        assert entry["workloads"] == cells and entry["layer"] == layer
        assert entry["source"] == "program_counter"
        assert read_metric(ctx, spec["reader"], spec["args"]) == \
            pytest.approx(value), name
    del ctx["m1"]["pipeline.deliver.slow_msgs"]
    spec = spec_of("shared_lane_share.flood")
    assert read_metric(ctx, spec["reader"], spec["args"]) == 100.0


# ------------------------------------------------- the five new metrics

def test_the_five_metrics_through_the_counter_reader():
    entries = {m["name"]: m for m in bench()["per_layer"]}
    for name in NEW:
        spec = spec_of(name)
        assert (spec["name"], spec["unit"], spec["moves"], spec["reader"]) \
            == (name, entries[name]["unit"], "delivered_per_s", "counter")
        assert set(spec) == {"name", "what", "unit", "moves", "reader",
                             "args"}
    m0 = {"delivery.queued": 10, "delivery.dequeued": 4,
          "packets.puback.received": 100, "session.ack_us": 5_000,
          "routing.device.shared_lane_rows": 1_000,
          "delivery.dropped.queue_full": 0}
    m1 = {"delivery.queued": 10 + 120_000, "delivery.dequeued": 119_990,
          "packets.puback.received": 100 + 500_000,
          "session.ack_us": 5_000 + 4_000_000,
          "routing.device.shared_lane_rows": 1_000 + 460_000,
          "delivery.dropped.queue_full": 3}
    ctx = {"window": {"seconds": 50.0, "publishes": 400_000,
                      "deliveries": 500_000}, "m0": m0, "m1": m1}
    read = {n: read_metric(ctx, "counter", spec_of(n)["args"]) for n in NEW}
    assert read == {"mqueue_parked_share.flood": 24.0,
                    "sub_puback_per_s.flood": 10_000.0,
                    "ack_us_per_puback.flood": 8.0,
                    "shared_picks_per_publish.flood": 1.15,
                    "mqueue_dropped.flood": 3.0}
    # the parent's program has neither `delivery.queued` nor
    # `session.ack_us`: 0, and nothing raised
    for k in ("delivery.queued", "session.ack_us"):
        del m0[k], m1[k]
    assert read_metric(ctx, "counter",
                       spec_of(NEW[0])["args"]) == 0.0 \
        == read_metric(ctx, "counter", spec_of(NEW[2])["args"])
    # and a window in which nothing was delivered or acknowledged
    idle = {"window": {"seconds": 50.0, "publishes": 0, "deliveries": 0},
            "m0": {}, "m1": {}}
    assert [read_metric(idle, "counter", spec_of(n)["args"])
            for n in NEW] == [0.0] * 5


def test_the_program_counts_what_the_five_metrics_read():
    """Each counter the new files name is one the program moves (or
    one the harness counts itself, `window.*`)."""
    import emqx_tpu
    src = ""
    for where, _dirs, files in os.walk(os.path.dirname(emqx_tpu.__file__)):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(where, name), encoding="utf-8") as f:
                    src += f.read()
    named = {c for n in NEW for k in ("num", "den")
             for c in spec_of(n)["args"].get(k, ())}
    assert named == {
        "delivery.queued", "window.deliveries", "packets.puback.received",
        "window.seconds", "session.ack_us",
        "routing.device.shared_lane_rows", "window.publishes",
        "delivery.dropped.queue_full"}
    for c in named - {"packets.puback.received"}:
        assert c.startswith("window.") or f'"{c}"' in src, c
    from emqx_tpu.broker.metrics import ALL_METRICS
    assert {"delivery.queued", "delivery.dequeued", "session.ack_us",
            "packets.puback.received",
            "delivery.dropped.queue_full"} <= set(ALL_METRICS)


# ------------------------------------------------------------ whole runs

def test_the_rehearsal_acks_every_delivery_and_picks_on_the_device():
    """The cell's rehearsal, traced: `correct`, every compared number 0
    but the round-robin one (under its limit), every pick a row of the
    plan, the five new metrics on the traced line and on the untraced
    one's `by_counter`, every delivery acknowledged under `emqx:ack`
    (the loop's `other`). Whether a 2 s window on the CPU parks a row
    in an mqueue depends on what else the machine runs (a worker needs
    33 rows unacknowledged): the share is held to its counter, not to
    a value; `tests/test_session_window.py` holds the parked path. Up
    to three seeds, until one's window holds a device window."""
    for seed in (2**31 + 44, 2**31 + 45, 2**31 + 46):
        r, out = run_cell("--workload", CELL, "--seed", str(seed),
                          "--seconds", "2", "--trace", "1", "--rehearse")
        assert r.returncode == 0, r.stderr[-2000:]
        assert out["correct"] is True and out["failed"] == 0, \
            out["compared"]
        compared = {k: (v["value"], v["limit"])
                    for k, v in out["compared"].items()}
        rr = compared.pop("rr_excess_vs_random")
        assert rr[1] == 0.3 and 0 <= rr[0] < 0.3
        assert set(compared) == {
            "wrong_delivery_sets", "stray_deliveries",
            "topic_or_payload_mismatches", "missing_pubacks",
            "order_breaks", "oracle_vs_plain_mismatches",
            "delivery_qos_mismatches"}
        assert all(v == (0, 0) for v in compared.values())
        # under other workers' load the CPU backend's chooser can keep
        # a 2 s window on the host: ask again
        if out["split"]["window"]["device_windows"]:
            break
    # what `test_runs.py::test_rehearsal_of_each_cell_end_to_end` holds
    # of a cell beside its pin (`fuse_depth.flood` in one cell's split)
    assert out["attempted"] > 100
    assert out["metrics"] == {} and out["device"]["platform"] == "cpu"
    values = {k: v["value"] for k, v in out["rehearsal_values"].items()}
    assert set(values) == set(_listed_for(bench(), CELL)) \
        and len(values) == 53
    assert out["device"]["busy_s"] >= 0 and out["device"]["window_s"] > 1.5
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["split"]["window"]["publishes"] > 0
    by_counter = out["split"]["window"]["by_counter"]
    assert set(NEW) | {"fuse_depth.flood"} <= set(by_counter)
    for name, value in by_counter.items():
        assert spec_of(name)["reader"] == "counter"
        assert value == pytest.approx(values[name], abs=1e-3)
    assert values["mqueue_dropped.flood"] == 0.0
    assert 0.0 <= values["mqueue_parked_share.flood"] < 100.0
    assert values["ack_us_per_puback.flood"] > 0.0
    assert values["sub_puback_per_s.flood"] > 0.0
    assert values["shared_lane_share.flood"] == 100.0
    assert values["cover_expand_window_share.flood"] == 0.0 \
        == values["host_fallback_share.flood"]
    assert values["device_routed_share.flood"] > 50
    assert 0.5 < values["shared_picks_per_publish.flood"] < 2.5
    # every PUBLISH is QoS 1: acknowledged to its publisher
    w = out["split"]["window"]
    assert values["puback_per_s.flood"] * 2 \
        == pytest.approx(w["publishes"], rel=0.5)
    # emqx:ack is no span `trace_loop.NAMED` lists: the loop's `other`
    assert values["loop_other_ms_per_s.flood"] > 0.0
    assert values["route_roofline.flood"] == 0.0       # no device plane


@pytest.mark.parametrize("control,number", [
    ("lose", "wrong_delivery_sets"),
    ("duplicate", "wrong_delivery_sets"),
    ("reorder", "order_breaks"),
    ("random_pick", "rr_excess_vs_random"),
])
def test_fanin_workers_with_a_guarantee_broken_is_not_correct(control,
                                                              number):
    r, out = run_cell("--workload", CELL, "--seed", "44", "--seconds", "1",
                      "--trace", "0", "--rehearse", "--control", control)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is False and out["failed"] > 0
    assert out["compared"][number]["value"] > out["compared"][number]["limit"]
    assert out["control"] == [control]
