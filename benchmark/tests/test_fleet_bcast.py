"""The `fleet-bcast` deployment's own pieces, checked on the CPU: the
`fleet_broadcast` population against its file and against `plain.py`,
the cell's manifest entries, and the cell's rehearsal with a guarantee
broken.
"""

import json
import os

import numpy as np
import pytest

from benchmark import check, manifest, populations, traffic_gen
from benchmark.populations import fleet_broadcast
from benchmark.readers import route_bytes
from benchmark.tests.test_mixed_zipf import covers
from benchmark.tests.test_runs import run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "fleet-bcast.flood"


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "fleet-bcast.json")) as f:
        return json.load(f)


def small(conns=1280, **over):
    """The rehearsal's population (132 x 2, one group) unless `over`
    says otherwise."""
    cfg = config()
    params = dict(cfg["population"]["params"],
                  **cfg["rehearse"]["population"])
    return fleet_broadcast.Population(dict(params, **over), conns)


def test_fleet_full_size_has_the_stated_counts():
    cfg = config()
    pop = populations.load(cfg)
    filters = pop.filters()
    assert len(filters) == len(set(filters)) == cfg["filters"] == 102409
    subs = [pop.subscriptions(c) for c in range(pop.conns)]
    assert pop.conns == cfg["connections"]["subscribers"] == 1280
    assert sum(map(len, subs)) == cfg["subscriptions"] == 104960
    assert all(q == 0 and not f.startswith("$share") for s in subs
               for f, q in s)
    # a gateway: its 80 devices, then its group, then the fleet
    assert [f for f, _q in subs[161][:2] + subs[161][-2:]] == [
        "fleet/g1/gw161/d0/cmd/+", "fleet/g1/gw161/d1/cmd/+",
        "fleet/g1/all/#", "fleet/all/#"]
    # subscribers a filter: 102,400 with one, 8 with 160, 1 with 1,280
    owners = {}
    for s in subs:
        for f, _q in s:
            owners[f] = owners.get(f, 0) + 1
    assert sorted(owners) == sorted(filters)
    widths = np.bincount(list(owners.values()))
    assert widths[1] == 102400 and widths[160] == 8 and widths[1280] == 1
    assert widths.sum() == 102409
    assert route_bytes.shapes_of(filters) == 3
    # ISSUE 32's own shares: the fleet tier 1 key slot of 16, groups 3
    assert pop.dims == (16, 102400)
    assert (fleet_broadcast.SLOTS, fleet_broadcast.GROUP_SLOTS) == (16, 3)
    assert cfg["rehearse"]["population"] == {"gateways": 132, "groups": 1,
                                             "devices": 2}
    assert cfg["reduced"] == [] and cfg["node"] == {}
    assert cfg["publish"] == {"keys": {"dist": "uniform"}, "qos1_every": 0,
                              "payload_bytes": 256}


def test_fleet_no_filter_covers_another():
    pop = small()
    split = [f.split("/") for f in pop.filters()]
    wild = [f for f in split if f[-1] == "#"]
    assert len(wild) == 2 and not any(
        covers(a, b) for a in wild for b in split if a is not b)
    # the 264 device filters end in one '+' under a prefix of their own
    assert len({tuple(f[:-1]) for f in split if f[-1] == "+"}) == 264


def test_fleet_tier_shares_over_the_key_space():
    cfg = config()
    pop = populations.load(cfg)
    keys = traffic_gen.draw_keys(traffic_gen.rng_for(2**31 + 9, 1), 64000,
                                 pop.dims, cfg["publish"]["keys"])
    fan = (pop.expect(keys) >= 0).sum(axis=1)
    assert set(np.unique(fan)) == {1, 160, 1280}
    share = {n: float((fan == n).mean()) for n in (1, 160, 1280)}
    assert abs(share[1280] - 1 / 16) < 0.004
    assert abs(share[160] - 3 / 16) < 0.006
    assert abs(share[1] - 12 / 16) < 0.007
    # over the whole key space: exactly 1/16, 3/16, 12/16; mean 110.75
    assert (1280 + 3 * 160 + 12) / 16 == 110.75
    assert abs(fan.mean() - 110.75) < 4
    # 72 % of deliveries from fleet messages, 27 % from group messages
    total = fan.sum()
    assert abs(fan[fan == 1280].sum() / total - 0.722) < 0.03
    assert abs(fan[fan == 160].sum() / total - 0.271) < 0.03
    assert abs((fan > 128).mean() - 0.25) < 0.01 \
        and fan[fan > 128].sum() / total > 0.99
    topics = {pop.topic(int(k)) for k in keys[fan > 1]}
    assert len(topics) == 16 + 8 * 16           # the broadcasts repeat
    assert {len(t.split("/")) for t in topics} == {4, 5}
    assert len(pop.topic(int(keys[fan == 1][0])).split("/")) == 6


@pytest.mark.parametrize("over", [{}, {"groups": 4}, {"devices": 5},
                                  {"gateways": 24, "groups": 3, "kinds": 5}])
def test_fleet_closed_form_equals_brute_force_on_every_key(over):
    pop = small(**over)
    keys = np.arange(pop.dims[0] * pop.dims[1])
    assert check.brute_force(pop, keys, len(keys), seed=5) == 0
    fan = (pop.expect(keys) >= 0).sum(axis=1)
    assert populations.expected_count(pop, keys) == fan.sum() \
        == pop.n_devices * (pop.gateways + 3 * pop.per_group + 12)


def test_fleet_closed_form_equals_brute_force_at_full_size():
    cfg = config()
    pop = populations.load(cfg)
    keys = traffic_gen.draw_keys(traffic_gen.rng_for(2**31 + 5, 1), 4000,
                                 pop.dims, cfg["publish"]["keys"])
    assert check.brute_force(pop, keys, 48, seed=2**31 + 5) == 0
    # one key of each tier by hand
    nd = pop.n_devices
    want = pop.expect(np.array([5, 2 * nd + 11, 9 * nd + 80 * 700 + 3]))
    assert pop.topic(3 * nd + 11) == "fleet/g3/all/cfg/k1"      # r <= 3
    assert pop.topic(4 * nd + 3) == "fleet/g0/gw0/d3/cmd/k4"
    assert pop.topic(5) == "fleet/all/ota/k5"
    assert (want[0] == np.arange(1280)).all()
    assert pop.topic(2 * nd + 11) == "fleet/g3/all/cfg/k1"
    assert (want[1][:160] == np.arange(480, 640)).all() \
        and (want[1][160:] == -1).all()
    assert pop.topic(9 * nd + 80 * 700 + 3) == "fleet/g4/gw700/d3/cmd/k9"
    assert want[2][0] == 700 and (want[2][1:] == -1).all()


def test_fleet_brute_force_sees_a_forgotten_gateway():
    class Off(fleet_broadcast.Population):
        def expect(self, keys):
            out = super().expect(keys)
            out[(out >= 0).sum(axis=1) == 132, 131] = -1    # one gateway
            return out
    pop = Off({"gateways": 132, "groups": 1, "devices": 2, "kinds": 16},
              1280)
    keys = np.arange(16 * 264)
    assert check.brute_force(pop, keys, len(keys), seed=5) == 4 * 264


def test_a_program_without_the_wide_counter_is_refused(monkeypatch,
                                                        tmp_path):
    """The parent routes every filter wider than `fanout_cap` on the
    host and cannot end this cell's set-up inside a run's limit: the
    population refuses it at once (a run that exits is not a run that
    is killed). What it asks for is the counter that the cell's own
    `wide_fanout_delivery_share.flood` reads, anywhere in the program."""
    import sys
    import types

    with open(fleet_broadcast.WIDE_METRIC) as f:
        counter = json.load(f)["args"]["num"][0]
    assert counter == "routing.device.wide_rows"
    assert small().dims == (16, 264)            # this program has it
    (tmp_path / "broker").mkdir()
    old = tmp_path / "broker" / "engine.py"
    old.write_text('metrics.inc("routing.device.host_fallback")\n')
    (tmp_path / "__init__.py").write_text("")
    fake = types.ModuleType("emqx_tpu")
    fake.__file__ = str(tmp_path / "__init__.py")
    monkeypatch.setitem(sys.modules, "emqx_tpu", fake)
    with pytest.raises(manifest.ManifestError, match="wider than"):
        small()
    old.write_text(f'metrics.inc("{counter}", n)\n')     # wherever it is
    assert small().dims == (16, 264)
    # a generator process has no program loaded: nothing to ask
    monkeypatch.delitem(sys.modules, "emqx_tpu")
    assert small().dims == (16, 264)


def test_a_connection_beyond_the_gateways_subscribes_to_nothing():
    pop = small()
    assert pop.conns == 1280 and len(pop.subscriptions(131)) == 4
    assert pop.subscriptions(132) == [] == pop.subscriptions(1279)
    with pytest.raises(ValueError):
        small(conns=100)


# ------------------------------------------------------------ whole runs

@pytest.mark.parametrize("control,number", [
    ("lose", "wrong_delivery_sets"),
    ("duplicate", "wrong_delivery_sets"),
    ("reorder", "order_breaks"),
])
def test_fleet_bcast_with_a_guarantee_broken_is_not_correct(control, number):
    r, out = run_cell("--workload", CELL, "--seed", "37", "--seconds", "1",
                      "--trace", "0", "--rehearse", "--control", control)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is False and out["failed"] > 0
    assert out["compared"][number]["value"] > out["compared"][number]["limit"]


def test_fleet_bcast_reports_its_33_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())]
    assert len(mine) == 33
    assert {"host_fallback_share.flood", "wide_fanout_delivery_share.flood",
            "route_roofline.flood", "match_cache_hit_share.flood",
            "cached_window_share.flood", "snapshot_build_s",
            "device_routed_share.flood"} <= set(mine)
    assert not {"puback_per_s.flood", "nfa_window_share.flood",
                "match_overflow_share.flood",
                "route_nfa_roofline.flood"} & set(mine)
    new = [m for m in bench["per_layer"] if m["workloads"] == [CELL]]
    assert [m["name"] for m in new] == ["host_fallback_share.flood",
                                        "wide_fanout_delivery_share.flood"]
    assert all(m["layer"] == "route programs + kernels"
               and m["source"] == "program_counter"
               and m["moves"] == "delivered_per_s" for m in new)
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and [m["name"] for m in cell.end_to_end] \
        == ["delivered_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == mine
    assert all(m["reader"] == "counter" for m in cell.per_layer
               if m["name"] in {n["name"] for n in new})
    assert len(bench["workloads"]) == 4 and len(bench["configs"]) == 4
