"""The benchmark's `mixed-zipf` deployment at its rehearsal size, through
Node -> batcher -> DeviceRouteEngine.

72 filter shapes overflow the engine's 32-shape table, so the snapshot
is a trie and every device window is matched by the level-stepped NFA
(`ops/match.match_batch`). What a subscriber gets is held against the
benchmark's own plain matcher, by brute force over every filter; the
NFA's counters are held against the windows the engine prepared.
"""

import asyncio
import json
import os

import numpy as np
import pytest

from benchmark import plain, populations
from emqx_tpu.broker.message import make
from emqx_tpu.broker.node import Node

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Sink:
    def __init__(self, conn):
        self.conn, self.got = conn, []

    def deliver(self, topic_filter, msg):
        self.got.append((topic_filter, msg.topic))
        return True


def _population():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mixed-zipf.json")) as f:
        cfg = json.load(f)
    cfg["population"]["params"].update(cfg["rehearse"]["population"])
    return populations.load(cfg)


def _brute_force(pop):
    """{topic: sorted [(filter, connection)]} with `plain.py`."""
    owner = {f: c for c in range(pop.conns)
             for f, _q in pop.subscriptions(c)}
    filters = pop.filters()
    split = [f.split("/") for f in filters]
    return {pop.topic(k): sorted(
        (filters[i], owner[filters[i]])
        for i in plain.matching(pop.topic(k), split))
        for k in range(pop.n)}


def _node(pop, **engine_caps):
    node = Node({"broker": {"device_min_batch": 4, "batch_window_us": 1000,
                            "deliver_lanes": 2}})
    for name, cap in engine_caps.items():
        setattr(node.device_engine, name, cap)    # before the first build
    sinks = []
    for c in range(pop.conns):
        sinks.append(Sink(c))
        sid = node.broker.register(sinks[-1], f"c{c}")
        for f, qos in pop.subscriptions(c):
            node.broker.subscribe(sid, f, {"qos": qos})
    return node, sinks


async def _publish_all(node, pop, batch=64):
    """Every key once, through the batcher, once the chip routes."""
    eng = node.device_engine
    eng.route_batch([make("warm", 0, "warm/none", b"")])   # first build
    for _ in range(3000):
        if eng.batch_class_warm(batch) and eng.max_fuse() > 1:
            break
        eng._kick_class_warm()
        await asyncio.sleep(0.05)
    else:
        raise AssertionError("the trie's standard classes never warmed")
    # the host wins on the CPU backend: pin the choice, not the path
    node.publish_batcher._device_worth_it = lambda n, n_subs=1: True
    for lo in range(0, pop.n, batch):
        await asyncio.gather(*[
            node.publish_async(make("pub", 0, pop.topic(k), b"x"))
            for k in range(lo, min(lo + batch, pop.n))])
    pool = node.deliver_lanes
    if pool is not None and pool.busy():
        await pool.drain()


def _delivered(sinks):
    got = {}
    for s in sinks:
        for f, topic in s.got:
            got.setdefault(topic, []).append((f, s.conn))
    return {t: sorted(v) for t, v in got.items()}


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, 600))
    finally:
        loop.close()


@pytest.fixture(scope="module")
def pop():
    return _population()


@pytest.fixture(scope="module")
def want(pop):
    return _brute_force(pop)


def test_trie_snapshot_delivers_what_plain_matching_says(pop, want):
    node, sinks = _node(pop)
    _run(_publish_all(node, pop))
    st = node.device_engine.stats()
    assert st["backend"] == "trie"
    assert st["cover_decision"] == "none_covered"
    assert _delivered(sinks) == want
    m = node.metrics
    assert m.val("messages.routed.device") > 0
    assert m.val("routing.device.match_overflow") == 0
    # fan-out 1 for three quarters of the topics, 2 for a quarter
    assert sum(len(v) for v in want.values()) == pop.n + pop.n // 4


@pytest.mark.parametrize("caps", [
    {"frontier_cap": 1},            # a '+' beside a literal: two live paths
    {"match_cap": 1},               # a quarter of the topics match two
], ids=["frontier_cap1", "match_cap1"])
def test_nfa_overflow_lanes_go_to_the_host_and_deliver_the_same(
        pop, want, caps):
    node, sinks = _node(pop, **caps)
    _run(_publish_all(node, pop))
    m = node.metrics
    assert node.device_engine.stats()["backend"] == "trie"
    over = m.val("routing.device.match_overflow")
    assert over > 0
    # host_fallback still counts them, with whatever else overflowed
    assert m.val("routing.device.host_fallback") >= over
    assert _delivered(sinks) == want


def test_nfa_windows_move_with_device_windows(pop):
    node, _sinks = _node(pop)
    _run(_publish_all(node, pop))
    m = node.metrics
    assert m.val("routing.device.windows") > 0
    assert m.val("routing.device.nfa_windows") \
        == m.val("routing.device.windows")
    assert m.val("routing.device.nfa_lanes") >= pop.n
    # a set the shape table holds never counts one
    other = Node()
    sid = other.broker.register(Sink(0), "c")
    other.broker.subscribe(sid, "a/+/c", {"qos": 0})
    other.device_engine.route_batch([make("p", 0, "a/b/c", b"")])
    assert other.device_engine.stats()["backend"] == "shapes"
    assert other.metrics.val("routing.device.windows") == 1
    assert other.metrics.val("routing.device.nfa_windows") == 0
