"""The `fanin-workers` population through the engine (ISSUE 44): three
`$share` worker pools of 32 / 8 / 4 on one uplink, two of them a
message for a quarter of it, every subscription QoS 1.

The benchmark's `fanin_workers` population at a small size (97
devices, its 60 connections kept), installed through `Node` and served
by `device_engine`'s own stages with the delivery lanes on, as the
batcher serves it: single windows, a fused one, and a batch the host
routes in between (what a chooser probe does). Held to the
population's closed form and to the host's `router.match`: delivery
sets, one member of every matching group and nobody else, two picks
for a `state` / `event` topic, QoS 1 on every row, every pick a row of
the plan (`shared_lane_share` 100), a pool's members in turn across the
windows and across the host's batch, and a cover-free snapshot although
`up/#` covers the two narrower group filters.
"""

import asyncio

import numpy as np

from benchmark.populations import fanin_workers
from emqx_tpu.broker.message import make
from emqx_tpu.broker.node import Node
from tests.test_deliver_lanes import OptRec     # got: (filter, topic,
#                                                 payload, subopts)

DEVICES, CONNS = 97, 60
PARAMS = {"devices": DEVICES, "gateways": 16, "store": 32, "rules": 8,
          "alert": 4}
POOLS = {"store": range(16, 48), "rules": range(48, 56),
         "alert": range(56, 60)}


def _world():
    pop = fanin_workers.Population(PARAMS, CONNS)
    node = Node({"broker": {"deliver_lanes": 4}})
    sinks = [OptRec() for _ in range(CONNS)]
    for c, sink in enumerate(sinks):
        sid = node.broker.register(sink, f"c{c}")
        for f, qos in pop.subscriptions(c):
            node.broker.subscribe(sid, f, {"qos": qos})
    node.device_engine.rebuild()
    return pop, node, sinks


def _batch(pop, rng, n, first):
    keys = rng.integers(0, DEVICES * fanin_workers.SLOTS, n)
    return keys, [make("pub", 1, pop.topic(int(k)), b"%06d" % (first + i))
                  for i, k in enumerate(keys)]


async def _serve(node, lives):
    """One device window (fused where `lives` holds several sub-batches)
    through the serving stages, the lanes on."""
    eng, pool = node.device_engine, node.deliver_lanes
    loop = asyncio.get_running_loop()
    pool.ensure_loop()
    h = eng.prepare_window(lives, gate_cold=False) if len(lives) > 1 \
        else eng.prepare(lives[0], gate_cold=False)
    assert h is not None
    await loop.run_in_executor(None, eng.dispatch, h)
    await loop.run_in_executor(None, eng.materialize, h)
    counts = [eng.finish_sub(h, k) for k in range(len(lives))]
    await pool.drain()
    return [n for c in counts for n in c]


def _picks(sinks, pool, since):
    """Deliveries each member of a pool got from row `since[c]` on."""
    return [len(sinks[c].got) - since[c] for c in POOLS[pool]]


def test_three_pools_on_one_uplink_through_the_lanes():
    pop, node, sinks = _world()
    eng, m = node.device_engine, node.metrics
    st = eng.stats()
    # four shapes fit the table: no cover state is built, so no group's
    # filter is ever expanded out of another group's root
    assert (st["backend"], st["cover_decision"], st["cover"]) \
        == ("shapes", "fits_shapes", None)
    assert m.val("routing.cover.skipped_builds") == 1
    rng = np.random.default_rng(44)
    sent_keys, sent_msgs = [], []

    def note(keys, msgs):
        sent_keys.append(keys)
        sent_msgs.extend(msgs)

    async def go():
        n = 0
        device_picks = {p: np.zeros(len(r), int) for p, r in POOLS.items()}
        host_picks = {p: np.zeros(len(r), int) for p, r in POOLS.items()}
        # two single windows, one fused of three, then the host's batch,
        # then a single window again
        for lives_n, host in ((1, False), (1, False), (3, False),
                              (0, True), (1, False)):
            since = [len(s.got) for s in sinks]
            if host:
                keys, msgs = _batch(pop, rng, 64, n)
                note(keys, msgs)
                n += len(msgs)
                for msg in msgs:        # as `batcher` routes a probe
                    node.broker._route(
                        msg, node.broker.router.match(msg.topic))
                for p in POOLS:
                    host_picks[p] += _picks(sinks, p, since)
                continue
            lives = []
            for _k in range(lives_n):
                keys, msgs = _batch(pop, rng, 160, n)
                note(keys, msgs)
                lives.append(msgs)
                n += len(msgs)
            counts = await _serve(node, lives)
            keys = np.concatenate(sent_keys[-lives_n:])
            assert counts == _fan(pop, keys)
            for p in POOLS:
                device_picks[p] += _picks(sinks, p, since)
        return device_picks, host_picks

    loop = asyncio.new_event_loop()
    try:
        device_picks, host_picks = loop.run_until_complete(
            asyncio.wait_for(go(), 300))
    finally:
        loop.close()

    keys = np.concatenate(sent_keys)
    slot = keys % fanin_workers.SLOTS
    # ---- every pick was a row of the plan, none a closure
    on_device = np.ones(len(keys), bool)
    on_device[5 * 160:5 * 160 + 64] = False     # the host's batch
    picks_on_device = int((pop.group_ids(keys[on_device]) >= 0).sum())
    assert m.val("routing.device.shared_lane_rows") == picks_on_device > 0
    assert m.val("pipeline.deliver.slow_msgs") == 0
    assert m.val("pipeline.deliver.barriers") == 0
    assert m.val("routing.device.shared_repick") == 0
    assert m.val("routing.device.host_fallback") == 0
    assert m.val("pipeline.cover.windows") == 0

    # ---- delivery sets: the closed form, and the host's own match
    got = {}
    for c, sink in enumerate(sinks):
        for f, topic, payload, so in sink.got:
            assert so["qos"] == 1               # every row asks QoS 1
            got.setdefault(payload, []).append(
                (c, f, so.get("share"), topic))
    want_plain = pop.expect(keys)
    want_groups = pop.expect_shared(keys)
    names = ["store", "rules", "alert"]
    gids = pop.group_ids(keys)
    for i, msg in enumerate(sent_msgs):
        rows = got.pop(bytes(msg.payload))
        assert all(t == msg.topic for *_x, t in rows)
        plain = sorted(c for c, _f, share, _t in rows if share is None)
        assert plain == sorted(int(c) for c in want_plain[i] if c >= 0)
        shared = [(c, f, share) for c, f, share, _t in rows
                  if share is not None]
        # exactly one member of each matching group, under that
        # group's own filter, nobody else
        assert sorted(s for _c, _f, s in shared) \
            == sorted(names[g] for g in gids[i] if g >= 0)
        for c, f, share in shared:
            assert c in POOLS[share]
            assert f == dict(fanin_workers.GROUPS)[share]
            g = names.index(share)
            row = want_groups[i][0 if g == 0 else 1]
            assert c in row[row >= 0]
        filters = sorted(f for _c, f, _s, _t in rows)
        assert filters == sorted(node.broker.router.match(msg.topic))
        assert len(shared) == (0 if slot[i] < 2 else
                               2 if slot[i] >= 15 else 1)
    assert not got

    # ---- a pool's members in turn: on the device's cursor across the
    # windows, on the host's across its batch; the two do not share one
    # (the configuration's `rr_excess_vs_random` allows for that)
    for p in POOLS:
        assert device_picks[p].max() - device_picks[p].min() <= 1, p
        assert host_picks[p].max() - host_picks[p].min() <= 1, p
        total = device_picks[p] + host_picks[p]
        assert total.max() - total.min() <= 2
    assert device_picks["store"].sum() == int((slot[on_device] >= 2).sum())
    assert device_picks["rules"].sum() \
        == int(((slot[on_device] >= 15) & (slot[on_device] < 19)).sum())
    assert device_picks["alert"].sum() == int((slot[on_device] == 19).sum())

    # ---- per session, order: a worker's deliveries of one publisher
    # arrive in the order they were sent
    for sink in sinks:
        seq = [p for _f, _t, p, _so in sink.got]
        assert seq == sorted(seq)


def _fan(pop, keys):
    """Deliveries each key promises: its gateway's, one a group."""
    return ((pop.expect(keys) >= 0).sum(axis=1)
            + (pop.group_ids(keys) >= 0).sum(axis=1)).tolist()
