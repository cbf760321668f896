"""The `mixed-zipf` deployment's own pieces, checked on the CPU: the
`mixed_depth` population against `plain.py`, the NFA's byte count on a
window counted by hand, its roofline reader on a hand-made trace, and
the cell's rehearsal with a guarantee broken.
"""

import json
import os
import sys

import numpy as np
import pytest

from benchmark import check, manifest, populations, traffic_gen
from benchmark.populations import mixed_depth
from benchmark.readers import nfa_bytes, route_bytes, route_nfa_roofline
from benchmark.tests.test_runs import run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "mixed-zipf.flood"


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mixed-zipf.json")) as f:
        return json.load(f)


def pop_of(gateways, streams, conns=16):
    return mixed_depth.Population(
        {"gateways": gateways, "streams": streams}, conns)


def covers(a: list, b: list) -> bool:
    """Does filter a match every topic filter b matches? (Neither
    starts with a wildcard here, so `$` topics play no part.)"""
    for i, w in enumerate(a):
        if w == "#":
            return True
        if i >= len(b) or b[i] == "#":
            return False
        if w != "+" and w != b[i]:
            return False
    return len(a) == len(b)


@pytest.mark.parametrize("gateways,streams", [(4, 60), (6, 100)])
def test_population_is_what_its_file_says(gateways, streams):
    pop = pop_of(gateways, streams)
    filters = pop.filters()
    n = gateways * streams
    assert len(filters) == len(set(filters)) == n + n // 4
    assert route_bytes.shapes_of(filters) == 72
    split = [f.split("/") for f in filters]
    # one '+' a filter, never on the first two levels; half '#'-tailed
    assert all(f.count("+") == 1 and "+" not in f[:2] for f in split)
    own = split[:n]
    assert abs(sum(f[-1] == "#" for f in own) - n / 2) <= 8   # runs of 8
    assert all(f[-1] == "#" for f in split[n:])
    assert {len(f) for f in own} == set(range(4, 12))
    # no filter covers another (only a stream's own two could)
    by_stream = {}
    for f in split:
        by_stream.setdefault((f[0], f[1]), []).append(f)
    assert not any(covers(a, b) for fs in by_stream.values()
                   for a in fs for b in fs if a is not b)
    # every subscription is somebody's, once
    owned = [f for c in range(pop.conns) for f, q in pop.subscriptions(c)
             if q == 0]
    assert sorted(owned) == sorted(filters)


@pytest.mark.parametrize("gateways,streams", [(4, 60), (6, 100)])
def test_closed_form_equals_brute_force_on_every_key(gateways, streams):
    pop = pop_of(gateways, streams)
    keys = np.arange(gateways * streams)
    assert check.brute_force(pop, keys, len(keys), seed=5) == 0
    want = pop.expect(keys)
    fan = (want >= 0).sum(axis=1)
    assert (fan == 1).mean() == 0.75 and (fan == 2).mean() == 0.25
    assert populations.expected_count(pop, keys) == len(keys) * 5 // 4
    depths = {len(pop.topic(k).split("/")) for k in keys}
    assert depths == set(range(4, 13))
    # a connection that owns both of a topic's filters is named twice
    both = want[(want[:, 0] == want[:, 1])]
    assert len(both) > 0


def test_brute_force_sees_a_forgotten_second_filter():
    class Off(mixed_depth.Population):
        def expect(self, keys):
            out = super().expect(keys)
            out[:, 1] = -1              # forgets the second filter
            return out
    pop = Off({"gateways": 4, "streams": 60}, 16)
    assert check.brute_force(pop, np.arange(240), 240, seed=5) == 60


def test_full_size_has_the_stated_counts():
    cfg = config()
    pop = populations.load(cfg)
    filters = pop.filters()
    assert len(filters) == cfg["filters"] == cfg["subscriptions"] == 125000
    assert sum(len(f.split("/")) for f in filters) == 925000
    assert route_bytes.shapes_of(filters) == 72
    assert sum(len(pop.subscriptions(c)) for c in range(16)) == 125000
    assert pop.dims == (100, 1000)


def test_the_zipf_draw_stays_inside_the_key_space():
    cfg = config()
    pop = populations.load(cfg)
    keys = traffic_gen.draw_keys(traffic_gen.rng_for(2**31 + 5, 1),
                                 200000, pop.dims, cfg["publish"]["keys"])
    assert keys.min() >= 0 and keys.max() < pop.n
    g, m = np.divmod(keys, pop.dims[1])
    share = np.bincount(g, minlength=100) / len(keys)
    assert 0.23 < share[0] < 0.27            # gateway 0 carries a quarter
    assert 0.19 < share[99] < 0.23           # the folded tail
    assert 0.43 < share[:4].sum() < 0.49     # the match cache's reach
    assert abs(np.bincount(m, minlength=1000).std()
               / (len(keys) / 1000) - 0.0707) < 0.02   # streams uniform


def test_a_program_that_cannot_fuse_a_trie_window_is_refused(monkeypatch):
    """Set-up waits for the fused class to come warm; a program whose
    window program cannot scan the NFA never reports that for this
    population, so the population refuses it at once."""
    from emqx_tpu.models import router_engine

    def old_window_full(tables, cursors, topics, lens, is_dollar, msg_hash,
                        strategy, *, fanout_cap=128, slot_cap=16):
        raise AssertionError("never called")
    monkeypatch.setattr(router_engine, "route_window_full", old_window_full)
    with pytest.raises(manifest.ManifestError, match="trie NFA"):
        pop_of(4, 60)
    # a generator process has no program loaded: nothing to ask
    monkeypatch.delitem(sys.modules, "emqx_tpu")
    assert pop_of(4, 60).n == 240


# ------------------------------------------------------------- the bytes

def test_nfa_bytes_on_a_window_counted_by_hand():
    # gw0/n5/p2w7/p3w8/t5, matched by one filter: 5 levels
    #   in    5 words * 4 + 8                         =  28
    #   node  depths 0..5, one 12-byte row each       =  72
    #   edge  5 levels, one 12-byte slot each         =  60
    #   out   1 fid * 4 + 4                           =   8
    assert nfa_bytes.topic_bytes(5, 1) == 28 + 72 + 60 + 8 == 168
    # 12 levels, two filters: 56 + 156 + 144 + 12
    assert nfa_bytes.topic_bytes(12, 2) == 368
    # a window of three such topics: 168 + 368 + 168 bytes, and the
    # roofline reader weighs each key by how often it was sent


def _traced_ctx(lanes, match_ms):
    """A hand-made trace: one route program of 10 ms, `match_ms` of it
    under scope `match`, and the counters and logs the reader asks."""
    pop = pop_of(4, 60)
    ms = 1e6
    body = "jit(route_window_full)/scan/while/body/"
    ops = [["fusion.1", 0.0, match_ms * ms,
            {"tf_op": body + "match/jit(match_batch)/while/body/gather:"}],
           ["fusion.2", match_ms * ms, (10 - match_ms) * ms,
            {"tf_op": body + "fanout/sort:"}]]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules",
             "events": [["jit_route_window_full(1)", 0.0, 10 * ms, {}]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["bench:trace_window", 0.0, 20 * ms, {}]]}]}]}
    keys = np.array([0, 0, 1, 9], np.int64)
    return {
        "trace_stats": trace, "pop": pop,
        "peaks": {"hbm_bytes_per_s": 819e9},
        "trace_m0": {"routing.device.nfa_lanes": 100},
        "trace_m1": {"routing.device.nfa_lanes": 100 + lanes},
        "window": {"t0_ns": 0, "t1_ns": 10},
        "pub": {"key": keys, "send_ns": np.arange(4, dtype=np.int64)},
    }, keys, pop


def test_nfa_roofline_on_a_hand_made_trace():
    ctx, keys, pop = _traced_ctx(lanes=2048, match_ms=4.0)
    per = [nfa_bytes.topic_bytes(len(pop.topic(k).split("/")),
                                 int((pop.expect([k]) >= 0).sum()))
           for k in keys]
    want = 100.0 * (2048 * sum(per) / 4 / 819e9) / 4e-3
    got = route_nfa_roofline.read(ctx, match=["route"])
    assert got == pytest.approx(want) and 0 < got < 1
    # nothing matched by the NFA in the span: a reading of 0
    ctx, _k, _p = _traced_ctx(lanes=0, match_ms=4.0)
    assert route_nfa_roofline.read(ctx, match=["route"]) == 0.0
    # a program without the counter (the parent): nothing, no raise
    ctx, _k, _p = _traced_ctx(lanes=5, match_ms=4.0)
    del ctx["trace_m1"]["routing.device.nfa_lanes"]
    assert route_nfa_roofline.read(ctx, match=["route"]) is None
    # no trace at all
    assert route_nfa_roofline.read({"peaks": {}}, match=["route"]) is None


# ------------------------------------------------------------ whole runs

@pytest.mark.parametrize("control,number", [
    ("lose", "wrong_delivery_sets"),
    ("duplicate", "wrong_delivery_sets"),
    ("reorder", "order_breaks"),
])
def test_the_cell_with_a_guarantee_broken_is_not_correct(control, number):
    r, out = run_cell("--workload", CELL, "--seed", "31", "--seconds", "1",
                      "--trace", "0", "--rehearse", "--control", control)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is False and out["failed"] > 0
    assert out["compared"][number]["value"] > out["compared"][number]["limit"]


def test_the_cell_reports_its_34_metrics_and_the_trie():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())]
    assert len(mine) == 34      # PR 37: nfa_narrow_step_share.flood
    assert {"nfa_window_share.flood", "match_overflow_share.flood",
            "route_nfa_roofline.flood", "snapshot_build_s",
            "nfa_narrow_step_share.flood",
            "match_cache_hit_share.flood"} <= set(mine)
    assert "route_roofline.flood" not in mine \
        and "puback_per_s.flood" not in mine
    cell = manifest.Cell(CELL)
    assert cell.chips == 1 and [m["name"] for m in cell.end_to_end] \
        == ["delivered_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == mine
