"""Parallel fan-out delivery lanes (ISSUE 5).

The egress stage must be invisible except for speed: per-session
delivery order with `deliver_lanes=N` is bit-identical to the inline
`deliver_lanes=0` loop across randomized windows — including shared-
group and dirty-filter slow-path interleaving and a mid-window
unsubscribe — and a blocked lane stalls the pipeline (backpressure to
`_inflight`) instead of dropping deliveries.
"""

import asyncio

import numpy as np
import pytest

from emqx_tpu.broker.deliver import (DeliveryView, OPT_TABLE,
                                     resolve_deliver_lanes)
from emqx_tpu.broker.message import Message, make
from emqx_tpu.broker.node import Node


class Rec:
    """Recording sink: per-session delivery log for the order oracle."""

    def __init__(self):
        self.got = []

    def deliver(self, topic_filter, msg):
        self.got.append((topic_filter, msg.topic, bytes(msg.payload)))
        return True


class RecBatch(Rec):
    """Recording sink with the coalesced-drain protocol."""

    def __init__(self):
        super().__init__()
        self.drains = 0

    def deliver_batch(self, items):
        self.drains += 1
        for f, m in items:
            self.got.append((f, m.topic, bytes(m.payload)))
        return len(items)


def mkmsg(topic, payload=b"x"):
    return make("pub", 0, topic, payload)


def run(coro, timeout=120):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()


def _node(lanes: int, depth: int = 8, readback: str = "csr",
          backend: str = "shapes") -> Node:
    node = Node({"broker": {"deliver_lanes": lanes,
                            "deliver_lane_depth": depth,
                            "device_fanout_cap": 16,
                            "device_slot_cap": 4,
                            "compact_readback": readback == "csr"}})
    if backend == "trie":
        # the worlds here have two filter shapes or more
        node.device_engine.shape_cap = 1
    return node


def _build_world(node, rng, sink_cls=Rec):
    """Mixed subscription world: clean filters (2 subs each), shared
    groups, one rich-subopts filter — plus the sinks, keyed by sid."""
    b = node.broker
    sinks = {}

    def sub(filt, opts=None):
        s = sink_cls()
        sid = b.register(s, f"c{len(sinks)}")
        sinks[sid] = s
        b.subscribe(sid, filt, opts or {"qos": 0})
        return sid

    for i in range(24):
        sub(f"p/{i}/+")
        sub(f"p/{i}/+", {"qos": 1})
    for i in range(3):
        sub(f"$share/g/s/{i}/+")
        sub(f"$share/g/s/{i}/+")
    sub("rich/+", {"qos": 1, "subid": 7})   # rich: host-dict slow path
    return sinks


def _schedule(rng, n_windows=6, batch=48):
    """Deterministic topic schedule + churn actions between windows."""
    topics = [f"p/{i}/x" for i in range(24)] + \
        [f"s/{i}/y" for i in range(3)] + ["rich/z", "none/q"]
    wins = []
    seq = 0
    for _w in range(n_windows):
        msgs = []
        for _ in range(batch):
            t = topics[rng.randint(0, len(topics))]
            msgs.append((t, b"m%06d" % seq))
            seq += 1
        wins.append(msgs)
    return wins


async def _drive(node, windows, actions):
    """Run the serving stages window by window (dispatch/materialize on
    executor threads so lane delivery genuinely overlaps), applying the
    churn action scheduled before each window."""
    eng = node.device_engine
    eng.rebuild()
    loop = asyncio.get_running_loop()
    pool = node.deliver_lanes
    all_counts = []
    for w, msgs in enumerate(windows):
        act = actions.get(w)
        if act is not None:
            # churn is applied between windows with the lanes drained:
            # an unsubscribe legitimately RACES deliveries still in
            # flight (inline delivers "as of consume time", lanes "as
            # of delivery time" — MQTT allows either), so the oracle
            # synchronizes churn to pin order AND counts exactly
            if pool is not None:
                await pool.drain()
            act(node)
        batch = [mkmsg(t, p) for t, p in msgs]
        h = eng.prepare(batch, gate_cold=False)
        if h is None:
            eng.rebuild()
            h = eng.prepare(batch, gate_cold=False)
        await loop.run_in_executor(None, eng.dispatch, h)
        await loop.run_in_executor(None, eng.materialize, h)
        counts = eng.finish_sub(h, 0)
        if pool is not None:
            await pool.admit()
        all_counts.append(counts)
    if pool is not None:
        await pool.drain()
    return [list(c) for c in all_counts]


def _churn_actions():
    """Keyed by window index: subscribe-to-existing (dirty filter),
    mid-schedule unsubscribe, and a fresh delta filter."""
    extra = {}

    def dirty(node):
        s = Rec()
        sid = node.broker.register(s, "dirty-join")
        extra[id(node)] = (sid, s)
        node.broker.subscribe(sid, "p/3/+", {"qos": 0})

    def unsub(node):
        sid, _s = extra[id(node)]
        node.broker.unsubscribe(sid, "p/3/+")

    def fresh(node):
        s = Rec()
        sid = node.broker.register(s, "fresh")
        node.broker.subscribe(sid, "none/+", {"qos": 0})

    return {2: dirty, 3: unsub, 4: fresh}


class TestOrderProperty:
    @pytest.mark.parametrize("backend", ["shapes", "trie"])
    @pytest.mark.parametrize("readback", ["csr", "dense"])
    @pytest.mark.parametrize("lanes", [1, 4])
    def test_per_session_order_identical_to_inline(self, lanes, readback,
                                                   backend):
        """The acceptance oracle: per-session delivery sequences are
        bit-identical between deliver_lanes=0 and deliver_lanes=N,
        across clean/shared/rich/dirty interleaving, churn mid-schedule
        and a mid-window unsubscribe; the groups' device-picked members
        ride the lanes as rows (ISSUE 35), read from the CSR payload or
        the dense planes of a shape-hash or a trie snapshot."""
        rng = np.random.RandomState(7)
        windows = _schedule(rng)

        n0 = _node(0, readback=readback, backend=backend)
        s0 = _build_world(n0, rng)
        c0 = run(_drive(n0, windows, _churn_actions()))

        nL = _node(lanes, readback=readback, backend=backend)
        sL = _build_world(nL, rng)
        cL = run(_drive(nL, windows, _churn_actions()))

        assert n0.deliver_lanes is None
        assert nL.deliver_lanes is not None
        assert nL.device_engine.stats()["backend"] == backend
        m = nL.metrics
        assert (m.val("pipeline.readback.windows.compact") > 0) \
            == (readback == "csr")
        # every s/<i>/y message is a row of its plan (the fresh filter
        # of window 4 is the delta overlay's, and not on their topics)
        n_shared = sum(t.startswith("s/") for w in windows for t, _p in w)
        assert m.val("routing.device.shared_lane_rows") == n_shared > 20
        assert m.val("routing.device.shared_repick") == 0

        got0 = {sid: s.got for sid, s in s0.items()}
        gotL = {sid: s.got for sid, s in sL.items()}
        assert got0.keys() == gotL.keys()
        for sid in got0:
            assert gotL[sid] == got0[sid], f"sid {sid} order diverged"
        # delivery counts settle identically too
        assert cL == c0

    def test_coalesced_batch_subscriber(self):
        """A subscriber with deliver_batch gets same-session runs in
        one call — fewer drains than deliveries, same content/order."""
        rng = np.random.RandomState(9)
        windows = _schedule(rng, n_windows=3)

        n0 = _node(0)
        s0 = _build_world(n0, rng, sink_cls=Rec)
        run(_drive(n0, windows, {}))

        n2 = _node(2)
        s2 = _build_world(n2, rng, sink_cls=RecBatch)
        run(_drive(n2, windows, {}))

        for sid in s0:
            assert s2[sid].got == s0[sid].got
        drains = n2.metrics.val("pipeline.deliver.drains")
        rows = n2.metrics.val("pipeline.deliver.deliveries")
        assert rows > 0 and drains < rows, (drains, rows)
        snap = n2.pipeline_telemetry.snapshot()["deliver"]
        assert snap["coalesce_ratio"] > 0


# ---------- ISSUE 32: a wide filter's rows come by reference ----------

# (filter, subscribers, subopts) over the 600 sessions of the world;
# `_node`'s fanout_cap is 16, so 17 is the first width that is wide
_WIDE_SPEC = [("b/#", 600, {"qos": 0}), ("b/+/x", 300, {"qos": 1}),
              ("b/g/#", 40, {"qos": 0, "nl": 1, "rap": 1}),
              ("b/g/x", 17, {"qos": 2}), ("+/g/x", 15, {"qos": 0}),
              ("b/g/+", 1, {"qos": 0}), ("u/+", 15, {"qos": 0}),
              ("r/#", 60, {"qos": 1, "subid": 9})]
_WIDE_TOPICS = ["b/g/x", "b/q", "b/h/x", "u/1", "none/q", "b/g/y"]


def _wide_world(node, rng, sink_cls=Rec):
    b = node.broker
    sinks = {}
    sids = []
    for i in range(600):
        s = sink_cls()
        sid = b.register(s, f"w{i}")
        sinks[sid] = s
        sids.append(sid)
    for f, width, opts in _WIDE_SPEC:
        for i in rng.choice(600, size=width, replace=False):
            b.subscribe(sids[int(i)], f, dict(opts))
    return sinks, sids


def _wide_schedule(rng, n_windows=5, batch=40):
    wins, seq = [], 0
    for _w in range(n_windows):
        # the rich filter's messages last: they are the slow ones, and
        # a window's clean messages are delivered before them
        topics = [_WIDE_TOPICS[rng.randint(len(_WIDE_TOPICS))]
                  for _ in range(batch - 4)] + ["r/z"] * 4
        wins.append([(t, b"m%06d" % (seq + i))
                     for i, t in enumerate(topics)])
        seq += batch
    return wins


def _wide_churn(joined):
    """After the snapshot: a session joins the 600-wide filter (window
    1) and a 40-wide one (window 2), then leaves the first (window 3):
    a dirty filter's match stands and its delivery comes from the live
    host dict, whatever its width."""
    def join_wide(node):
        s = Rec()
        sid = node.broker.register(s, "late")
        joined[id(node)] = (sid, s)
        node.broker.subscribe(sid, "b/#", {"qos": 1})

    def join_mid(node):
        node.broker.subscribe(joined[id(node)][0], "b/g/#", {"qos": 0})

    def leave_wide(node):
        node.broker.unsubscribe(joined[id(node)][0], "b/#")

    return {1: join_wide, 2: join_mid, 3: leave_wide}


class TestWideFanoutThroughTheLanes:
    @pytest.mark.parametrize("lanes", [0, 4])
    def test_wide_filters_equal_the_host_route(self, lanes):
        """Filters of 17..600 subscribers beside narrow ones in every
        window, a wide one with `nl` / `rap`, a rich one, several on
        one topic: with the lanes on (and inline), every session gets
        every topic's messages in the host route's order, each with
        the host route's (filter) set, nothing is routed by the host
        for its width, and a subscriber that joins or leaves a wide
        filter after the snapshot is delivered / not delivered."""
        rng = np.random.RandomState(13)
        windows = _wide_schedule(rng)
        node, host = _node(lanes), _node(0)
        rng_w = np.random.RandomState(14)
        sinks, _ = _wide_world(node, rng_w)
        rng_w = np.random.RandomState(14)
        hsinks, _ = _wide_world(host, rng_w)
        joined = {}
        churn = _churn = _wide_churn(joined)
        counts = run(_drive(node, windows, churn))
        want = []
        for w, msgs in enumerate(windows):
            if w in _churn:
                _churn[w](host)
            want.append([host.broker._route(
                m, host.broker.router.match(m.topic))
                for m in (mkmsg(t, p) for t, p in msgs)])
        assert counts == want

        def by_msg(got):
            """topic -> its messages in order, each with its filters
            (a dirty filter's messages are a window's slow ones: the
            order MQTT keeps is a topic's)."""
            out = {}
            for f, t, p in got:
                seq = out.setdefault(t, [])
                if seq and seq[-1][0] == p:
                    seq[-1][1].append(f)
                else:
                    seq.append((p, [f]))
            return {t: [(p, sorted(fs)) for p, fs in seq]
                    for t, seq in out.items()}
        sinks[joined[id(node)][0]] = joined[id(node)][1]
        hsinks[joined[id(host)][0]] = joined[id(host)][1]
        assert sinks.keys() == hsinks.keys()
        for sid in sinks:
            assert by_msg(sinks[sid].got) == by_msg(hsinks[sid].got), sid
        late = joined[id(node)][1].got
        in_win = {p: w for w, msgs in enumerate(windows) for _t, p in msgs}
        assert {in_win[p] for f, _t, p in late if f == "b/#"} == {1, 2}
        assert {in_win[p] for f, _t, p in late if f == "b/g/#"} == {2, 3, 4}
        m = node.metrics
        assert m.val("routing.device.host_fallback") == 0
        assert m.val("routing.device.wide_segments") > 100
        # window 0 whole; from window 1 on the 600-wide filter is dirty
        # and its rows come from the live dict
        assert m.val("routing.device.wide_rows") > sum(want[0])
        if lanes:
            # window 0 went through the lanes but for its rich tail
            assert m.val("pipeline.deliver.rows") >= sum(want[0]) - 4 * 60

    def test_lanes_equal_inline_delivery_for_delivery(self):
        """The order contract with wide segments in the plan: every
        session's deliveries under four lanes are the inline loop's,
        filter for filter (a message's rows are its matched filters'
        segments in match order, a wide one at its place)."""
        rng = np.random.RandomState(21)
        windows = _wide_schedule(rng, n_windows=3)
        logs = []
        for lanes in (0, 4):
            node = _node(lanes)
            sinks, _ = _wide_world(node, np.random.RandomState(22),
                                   sink_cls=RecBatch if lanes else Rec)
            counts = run(_drive(node, windows, {}))
            logs.append(([s.got for s in sinks.values()], counts))
        assert logs[0] == logs[1]
        assert max(len(g) for g in logs[0][0]) > 60


# ---------- ISSUE 35: a device-picked $share delivery is a row ----------

class OptRec:
    """Recording sink that keeps the delivered subopts; `accept` False
    nacks (and records nothing), as a subscriber whose session is gone."""

    def __init__(self):
        self.got = []
        self.accept = True

    def deliver(self, topic_filter, msg):
        if not self.accept:
            return False
        self.got.append((topic_filter, msg.topic, bytes(msg.payload),
                         dict(msg.headers.get("subopts"))))
        return True


class OptRecBatch(OptRec):
    def deliver_batch(self, items):
        if not self.accept:
            return 0
        for f, m in items:
            self.deliver(f, m)
        return len(items)


def _members(node, filt, n, sink_cls=OptRec, opts=None):
    out = []
    for i in range(n):
        s = sink_cls()
        sid = node.broker.register(s, f"{filt}#{i}")
        node.broker.subscribe(sid, filt, dict(opts or {"qos": 0}))
        out.append((sid, s))
    return out


async def _window(node, msgs, held=None):
    """One window through the serving stages with the lanes on; `held`
    runs between plan and delivery (the lanes paused meanwhile)."""
    eng = node.device_engine
    loop = asyncio.get_running_loop()
    pool = node.deliver_lanes
    pool.ensure_loop()
    h = eng.prepare(msgs, gate_cold=False)
    await loop.run_in_executor(None, eng.dispatch, h)
    await loop.run_in_executor(None, eng.materialize, h)
    if held is not None:
        pool.pause()
    counts = eng.finish_sub(h, 0)
    if held is not None:
        held()
        pool.resume()
    await pool.drain()
    return list(counts)


_LANE_COUNTERS = ("routing.device.shared_lane_rows",
                  "routing.device.shared_repick",
                  "pipeline.deliver.slow_msgs", "pipeline.deliver.barriers",
                  "pipeline.deliver.rows", "messages.delivered",
                  "messages.routed.device")


def _lane_counters(node):
    return {n.rsplit(".", 1)[1]: node.metrics.val(n)
            for n in _LANE_COUNTERS}


class TestSharedRowsThroughTheLanes:
    @pytest.mark.parametrize("readback", ["csr", "dense"])
    def test_clean_shared_window_raises_no_barrier(self, readback):
        """A window of nothing but device-picked group deliveries: every
        one is a row of the plan, no closure, no barrier; members in
        turn, `share=<group>` beside the packed options."""
        node = _node(4, readback=readback)
        a = _members(node, "$share/ga/job/+", 2, opts={"qos": 1})
        b = _members(node, "$share/gb/job/+", 3)
        node.device_engine.rebuild()
        msgs = [mkmsg(f"job/{i % 5}", b"m%03d" % i) for i in range(60)]
        counts = run(_window(node, msgs))
        assert counts == [2] * 60
        c = _lane_counters(node)
        assert c["barriers"] == 0 and c["slow_msgs"] == 0
        assert c["shared_lane_rows"] == c["rows"] == 120
        assert c["delivered"] == c["device"] == 120
        assert c["shared_repick"] == 0
        assert [len(s.got) for _sid, s in a] == [30, 30]
        assert [len(s.got) for _sid, s in b] == [20, 20, 20]
        for group, qos, members in (("ga", 1, a), ("gb", 0, b)):
            got = sorted(p for _sid, s in members for _f, _t, p, _o in s.got)
            assert got == [m.payload for m in msgs]     # once each
            for _sid, s in members:
                assert [p for _f, _t, p, _o in s.got] == sorted(
                    p for _f, _t, p, _o in s.got)
                assert all(f == "job/+" and o == {
                    "qos": qos, "nl": 0, "rap": 0, "rh": 0,
                    "share": group} for f, _t, _p, o in s.got)
        # one frozen dict a (packed opts, group), not one a delivery
        assert len(node.device_engine._built.picks._subopts) == 2

    @pytest.mark.parametrize("reason", [
        "dirty_slot", "new_group", "cluster", "remote_member",
        "hostside_filter", "none"])
    def test_what_keeps_the_closure_delivers_once(self, reason):
        """Each state `_consume_one`'s shared branch has a case for
        keeps the message behind the barrier, and the group still gets
        the message exactly once."""
        node = _node(4)
        members = _members(node, "$share/g/s/+", 2)
        plain = _members(node, "p/+", 1)
        extra = []
        if reason == "hostside_filter":
            extra = _members(node, "s/#", 1, opts={"qos": 1, "subid": 3})
        eng = node.device_engine
        eng.rebuild()
        want = 1
        if reason == "dirty_slot":
            members += _members(node, "$share/g/s/+", 1)
        elif reason == "new_group":
            extra = _members(node, "$share/g2/s/+", 1)
        elif reason == "cluster":
            class Cluster:      # joined since the build; nothing remote
                _groups_by_real = {}

                def forward(self, msg, matched):
                    return 0

                def _dispatch_one_group(self, broker, f, g, msg):
                    raise AssertionError("a clean slot is the device's")
            node.broker.cluster = Cluster()
        elif reason == "remote_member":
            # a member on a node that has left with the cluster
            eng._built.remote_members.append(("gone@host", 7))
        want += len(extra)
        msgs = [mkmsg("s/1", b"m%03d" % i) for i in range(12)] \
            + [mkmsg("p/1", b"plain")]
        counts = run(_window(node, msgs))
        assert counts == [want] * 12 + [1]
        got = sorted(p for _sid, s in members for _f, _t, p, _o in s.got)
        assert got == [m.payload for m in msgs[:12]]
        assert all(o.get("share") == "g" for _sid, s in members
                   for _f, _t, _p, o in s.got)
        for _sid, s in extra:
            assert [p for _f, _t, p, _o in s.got] == got
        assert len(plain[0][1].got) == 1
        c = _lane_counters(node)
        if reason == "none":
            assert (c["shared_lane_rows"], c["slow_msgs"],
                    c["barriers"]) == (12, 0, 0)
        else:
            # new_group and cluster take the whole window
            n_slow = 13 if reason in ("new_group", "cluster") else 12
            assert (c["shared_lane_rows"], c["slow_msgs"],
                    c["barriers"]) == (0, n_slow, 1)

    @pytest.mark.parametrize("sink_cls", [OptRec, OptRecBatch])
    @pytest.mark.parametrize("how", ["closed", "unsubscribed"])
    def test_a_pick_that_left_is_redispatched_once(self, how, sink_cls):
        """The picked member goes between plan and delivery (its
        connection closes, or it unsubscribes and its session nacks):
        each of its rows goes once through the host's dispatch of the
        group, to a member that is there, in message order."""
        node = _node(4)
        (sid0, s0), (sid1, s1) = _members(node, "$share/g/s/+", 2,
                                          sink_cls=sink_cls)
        node.device_engine.rebuild()
        msgs = [mkmsg("s/1", b"m%03d" % i) for i in range(8)]

        def leave():
            if how == "closed":
                node.broker.subscriber_down(sid0)
            else:
                node.broker.unsubscribe(sid0, "$share/g/s/+")
                s0.accept = False

        counts = run(_window(node, msgs, held=leave))
        assert counts == [1] * 8
        assert s0.got == []
        # its own four, then (the lane of sid 0 or after it) the other's
        assert sorted(p for _f, _t, p, _o in s1.got) == \
            [m.payload for m in msgs]
        c = _lane_counters(node)
        assert c["shared_lane_rows"] == 8 and c["shared_repick"] == 4
        assert c["slow_msgs"] == 0 and c["delivered"] == 8
        picked = [p for _f, _t, p, o in s1.got]
        mine = [m.payload for m in msgs[1::2]]
        assert [p for p in picked if p in mine] == mine
        assert [p for p in picked if p not in mine] == \
            [m.payload for m in msgs[0::2]]

    @pytest.mark.parametrize("ack", [False, True])
    def test_a_live_members_nack_is_final_without_the_ack_protocol(
            self, ack):
        node = _node(4)
        node.broker.shared_dispatch_ack = ack
        (sid0, s0), (sid1, s1) = _members(node, "$share/g/s/+", 2)
        node.device_engine.rebuild()
        s0.accept = False
        msgs = [mkmsg("s/1", b"m%03d" % i) for i in range(8)]
        counts = run(_window(node, msgs))
        c = _lane_counters(node)
        assert c["shared_lane_rows"] == 8 and c["slow_msgs"] == 0
        if ack:
            # the host's pick walks the members until one takes it
            assert counts == [1] * 8 and len(s1.got) == 8
            assert c["shared_repick"] == 4
        else:
            assert counts == [0, 1] * 4 and len(s1.got) == 4
            assert c["shared_repick"] == 0
            assert node.metrics.val(
                "messages.dropped.no_subscribers") == 4

    @pytest.mark.parametrize("sink_cls", [OptRec, OptRecBatch])
    def test_one_session_by_a_plain_filter_and_by_a_group(self, sink_cls):
        """A session a message reaches through a plain filter and as
        the picked member of a group gets both, the plain one first as
        `_consume_one` delivers them, message after message, the shared
        one alone with `share=<group>` in its subopts."""
        node = _node(4)
        s = sink_cls()
        sid = node.broker.register(s, "both")
        node.broker.subscribe(sid, "s/+", {"qos": 1})
        node.broker.subscribe(sid, "$share/g/s/+", {"qos": 0})
        other = _members(node, "$share/g/s/+", 1)[0][1]
        node.device_engine.rebuild()
        msgs = [mkmsg(f"s/{i % 3}", b"m%03d" % i) for i in range(10)]
        counts = run(_window(node, msgs))
        assert counts == [2] * 10
        plain = {"qos": 1, "nl": 0, "rap": 0, "rh": 0}
        want = []
        for i, m in enumerate(msgs):
            want.append(("s/+", m.topic, m.payload, plain))
            if i % 2 == 0:      # round robin: this session, then the other
                want.append(("s/+", m.topic, m.payload,
                             {"qos": 0, "nl": 0, "rap": 0, "rh": 0,
                              "share": "g"}))
        assert s.got == want
        assert [p for _f, _t, p, _o in other.got] == \
            [m.payload for m in msgs[1::2]]
        c = _lane_counters(node)
        assert (c["shared_lane_rows"], c["rows"], c["barriers"]) \
            == (10, 20, 0)

    def test_a_qos1_pick_enters_the_inflight_window(self):
        """Through a real connection: a group's QoS 0 deliveries leave
        as the one shared frame, its QoS 1 ones take the copy path into
        the session's inflight window and are acknowledged."""
        from emqx_tpu.broker.connection import Listener
        from emqx_tpu.client import Client
        node = _node(2)

        async def go():
            lst = Listener(node, bind="127.0.0.1", port=0)
            await lst.start()
            c = Client(port=lst.port, clientid="member")
            await c.connect()
            await c.subscribe("$share/g/job/+", qos=1)
            node.device_engine.rebuild()
            ch = next(iter(node.broker._subscribers.values()))
            msgs = [make("pub", i % 2, f"job/{i}", b"m%03d" % i)
                    for i in range(10)]
            before = node.metrics.val("messages.qos0.sent")
            pool = node.deliver_lanes
            pool.ensure_loop()
            h = node.device_engine.prepare(msgs, gate_cold=False)
            node.device_engine.dispatch(h)
            node.device_engine.materialize(h)
            counts = node.device_engine.finish_sub(h, 0)
            await pool.drain()
            counts = list(counts)
            inflight = len(ch.session.inflight)
            got = [await c.recv(10) for _ in range(10)]
            for _ in range(200):
                if len(ch.session.inflight) == 0:
                    break
                await asyncio.sleep(0.01)
            left = len(ch.session.inflight)
            sent0 = node.metrics.val("messages.qos0.sent") - before
            await c.disconnect()
            await lst.stop()
            return counts, inflight, left, got, sent0

        counts, inflight, left, got, sent0 = run(go())
        assert counts == [1] * 10
        assert inflight == 5 and left == 0 and sent0 == 5
        assert sorted((m.topic, m.qos) for m in got) == sorted(
            (f"job/{i}", i % 2) for i in range(10))
        c = _lane_counters(node)
        assert c["shared_lane_rows"] == 10 and c["slow_msgs"] == 0


class TestBackpressure:
    def test_blocked_lane_stalls_admit_not_drops(self):
        """A paused (blocked) lane must stall admit() — the hook the
        batcher awaits, which fills `_inflight` and blocks publishers —
        while dropping nothing: on resume every delivery lands, in
        order."""
        node = _node(2, depth=1)
        b = node.broker
        sink = Rec()
        sid = b.register(sink, "c1")
        b.subscribe(sid, "t/+", {"qos": 0})

        async def go():
            eng = node.device_engine
            eng.rebuild()
            pool = node.deliver_lanes
            loop = asyncio.get_running_loop()
            pool.ensure_loop()
            pool.pause()
            outs = []
            for w in range(4):
                msgs = [mkmsg(f"t/{w}-{i}") for i in range(8)]
                h = eng.prepare(msgs, gate_cold=False)
                await loop.run_in_executor(None, eng.dispatch, h)
                await loop.run_in_executor(None, eng.materialize, h)
                outs.append(eng.finish_sub(h, 0))
            assert pool.busy()
            with pytest.raises(asyncio.TimeoutError):
                # > depth plans queued on a blocked lane: admit stalls
                await asyncio.wait_for(pool.admit(), 0.2)
            assert all(sum(c) == 0 for c in outs)   # nothing settled
            assert len(sink.got) == 0               # and nothing lost
            pool.resume()
            await pool.drain()
            return outs

        outs = run(go())
        assert all(all(c == 1 for c in counts) for counts in outs)
        assert [t for _f, t, _p in sink.got] == \
            [f"t/{w}-{i}" for w in range(4) for i in range(8)]
        assert node.metrics.val("messages.dropped") == 0
        assert node.metrics.val("pipeline.deliver.backpressure_waits") \
            >= 1

    def test_batcher_futures_resolve_after_lane_completion(self):
        """End to end through the PublishBatcher: publisher futures for
        a device-routed batch resolve only once the lanes delivered —
        and a paused pool holds them (backpressure), not drops them."""
        node = _node(2, depth=1)
        b = node.broker
        sink = Rec()
        sid = b.register(sink, "c1")
        b.subscribe(sid, "t/+", {"qos": 0})

        async def go():
            # warm until the device path engages
            for t in range(400):
                await asyncio.gather(*[
                    node.publish_async(mkmsg(f"t/w{t * 8 + i}"))
                    for i in range(8)])
                if node.metrics.val("routing.device.batches") >= 1:
                    break
            else:
                raise AssertionError("device path never engaged")
            warmed = len(sink.got)
            pool = node.deliver_lanes
            pool.pause()
            futs = [asyncio.ensure_future(
                node.publish_async(mkmsg(f"t/{i}"))) for i in range(8)]
            # give the pipeline time: with the pool paused the batch may
            # consume (plan queued) but futures must NOT resolve
            for _ in range(50):
                await asyncio.sleep(0.005)
                if node.metrics.val("routing.device.batches") >= 2:
                    break
            routed_dev = any(not f.done() for f in futs)
            pool.resume()
            counts = await asyncio.gather(*futs)
            await pool.drain()
            return warmed, routed_dev, counts

        warmed, saw_pending, counts = run(go())
        assert all(c == 1 for c in counts)
        assert len(sink.got) == warmed + 8
        # the batch may legitimately route host-side (adaptive chooser);
        # only assert the hold when the lanes actually carried it
        if saw_pending:
            assert node.metrics.val("messages.dropped") == 0


class TestSyncCallerWithWideFilters:
    def test_finish_sub_undeferred_equals_the_host_route(self):
        """A sync caller (`finish_sub(defer=False)`, what `route_batch`
        and a harness's direct warm use) of a window with wide filters
        in it, lanes configured: the rows are walked session by session
        on the caller's stack (`DeliveryLanePool.deliver_now`), counts
        are final on return, and every session gets what the host
        route gives it, in the host route's order a topic."""
        windows = _wide_schedule(np.random.RandomState(31), n_windows=2)
        node, host = _node(4), _node(0)
        sinks, _ = _wide_world(node, np.random.RandomState(32),
                               sink_cls=RecBatch)
        hsinks, _ = _wide_world(host, np.random.RandomState(32))
        eng = node.device_engine
        eng.rebuild()
        got, want = [], []
        for msgs in windows:
            batch = [mkmsg(t, p) for t, p in msgs]
            h = eng.prepare(batch, gate_cold=False)
            eng.dispatch(h)
            eng.materialize(h)
            counts = eng.finish_sub(h, 0, defer=False)
            assert not hasattr(counts, "plan")
            got.append(list(counts))
            want.append([host.broker._route(
                m, host.broker.router.match(m.topic))
                for m in (mkmsg(t, p) for t, p in msgs)])
        assert got == want

        def per_topic(log):
            out = {}
            for f, t, p in log:
                out.setdefault(t, []).append((p, f))
            return out
        for sid in sinks:
            a, b = per_topic(sinks[sid].got), per_topic(hsinks[sid].got)
            assert a.keys() == b.keys()
            for t in a:
                assert sorted(a[t]) == sorted(b[t]), (sid, t)
                # a topic's messages in publish order
                assert [p for p, _f in a[t]] == sorted(
                    p for p, _f in a[t]), (sid, t)
        m = node.metrics
        assert m.val("routing.device.wide_rows") > 1000
        assert m.val("routing.device.host_fallback") == 0
        # the walk coalesced: far fewer drains than rows
        assert m.val("pipeline.deliver.drains") \
            < m.val("pipeline.deliver.deliveries") / 2


class TestRowsInFlight:
    def test_pending_limit_follows_the_fan_out(self):
        from emqx_tpu.broker import batcher as bm
        node = _node(2)
        b = node.publish_batcher
        assert b._pending_limit() == b.max_pending
        b._rows_per_msg = bm._ROWS_IN_FLIGHT / b.max_pending
        assert b._pending_limit() == b.max_pending
        b._rows_per_msg = 100.0
        assert b._pending_limit() == max(
            b.max_batch, int(bm._ROWS_IN_FLIGHT / 100))
        b._rows_per_msg = 1e6
        assert b._pending_limit() == b.max_batch

    @pytest.mark.parametrize("rows_per_msg, due, first, want", [
        (2.5, True, False, 64),         # a narrow fan-out: `_PROBE_MSGS`
        (110.75, True, False, 64),      # 8,192 / 110.75 = 73 is more
        (200.0, True, False, 40),       # `_PROBE_ROWS` deliveries is fewer
        (1e6, True, False, "min"),      # never under `device_min_batch`
        (2.5, False, False, None),      # no probe due: a full batch
        (2.5, False, True, 64),         # the first probe is cut the same
    ], ids=["fanout2.5", "fanout110", "fanout200", "fanout1e6",
            "not_due", "first_probe"])
    def test_a_host_probe_is_bounded_in_messages_and_deliveries(
            self, rows_per_msg, due, first, want):
        """The chooser's host probe routes its batch a delivery at a
        time and measures a cost that is a message's: where one is due
        the batch is cut to `_PROBE_MSGS` messages, or to what stands
        for `_PROBE_ROWS` deliveries where that is fewer; where none is
        due it is a full batch."""
        from emqx_tpu.broker import batcher as bm
        assert (bm._PROBE_MSGS, bm._PROBE_ROWS) == (64, 8192)
        b = _node(2).publish_batcher
        assert b._probe_cap() is None           # nothing measured yet
        b._dev_batch_s = 0.01
        b._host_msg_s = None if first else 1e-5
        b._since_host_probe = b.host_probe_every if due else 0
        b._rows_per_msg = rows_per_msg
        assert b._probe_cap() == (b.device_min_batch if want == "min"
                                  else want)

    def test_one_publishers_stream_through_probes_and_windows_in_order(
            self, monkeypatch):
        """`host_probe_every` 1: every other decision is a host probe
        of at most 64 messages, and the window behind it takes what
        the probe left in the queue to the chip; one publisher's 2,000
        messages on one topic still arrive in publish order, once."""
        from emqx_tpu.broker import batcher as bm
        monkeypatch.setattr(bm, "_PROBE_GAP_MAX", 1)
        # the two costs as a chip that wins would leave them, and held
        # there: what is under test is the interleaving, not the choice
        monkeypatch.setattr(
            bm, "_ewma", lambda cur, sample, streak=0: (cur, 0))
        node = _node(2)
        b = node.broker
        sink = RecBatch()
        b.subscribe(b.register(sink, "c1"), "seq/#", {"qos": 0})
        bat = node.publish_batcher
        bat.host_probe_every = 1
        # many decisions whatever the load: unfused windows of 128
        bat.max_batch, bat.window_fuse = 128, 1
        bat._dev_batch_s, bat._host_msg_s = 1e-6, 1.0
        m = node.metrics

        async def go():
            eng = node.device_engine
            eng.rebuild()
            eng._kick_class_warm()
            if eng._fuse_warm_task is not None:
                await eng._fuse_warm_task
            for k in range(2000):
                while not bat.enqueue(mkmsg("seq/t", b"%05d" % k)):
                    await asyncio.sleep(0.001)
                if k % 500 == 499:
                    await asyncio.sleep(0.002)
            for _ in range(3000):
                if len(sink.got) >= 2000:
                    break
                await asyncio.sleep(0.01)
            await node.deliver_lanes.drain()

        run(go())
        if m.val("routing.device.batches") == 0:
            pytest.skip("the device path never engaged")
        assert [p for _f, _t, p in sink.got] == \
            [b"%05d" % k for k in range(2000)]
        probes = m.val("routing.chooser.host_probe")
        assert probes >= 5 and m.val("routing.device.batches") >= 5
        assert 0 < m.val("routing.host_probe.msgs") <= 64 * probes
        assert m.val("routing.chooser.cost_host") == 0

    @pytest.mark.parametrize("every, want", [
        (None, [64, 872, 500]),
        # a probe due at every decision: what the first left is cut
        # again and again, and still by itself (14 x 64 + 40 = 936)
        (0, [64] * 14 + [40] + [64] * 7 + [52]),
    ], ids=["one_probe", "a_probe_every_batch"])
    def test_what_a_cut_probe_leaves_forms_the_next_batch_alone(
            self, monkeypatch, every, want):
        """A probe is cut from a batch of 936; 500 messages of other
        connections land in the queue while its hooks fold. The 872 it
        left form the next batch at once and by themselves, as the
        uncut batch would have, and the 500 the one after; where the
        next probe is due while they wait, it is cut from them alone."""
        from emqx_tpu.broker import batcher as bm
        monkeypatch.setattr(
            bm, "_ewma", lambda cur, sample, streak=0: (cur, 0))
        node = _node(2)
        b = node.broker
        sink = RecBatch()
        b.subscribe(b.register(sink, "c1"), "seq/#", {"qos": 0})
        bat = node.publish_batcher
        bat.window_fuse = 1
        bat._dev_batch_s, bat._host_msg_s = 1e-6, 1.0
        if every is not None:
            bat.host_probe_every = every
        bat._since_host_probe = bat.host_probe_every     # a probe is due
        formed = []
        fold = bat._fold_hooks

        async def noting(entry):
            formed.append(len(entry["batch"]))
            if len(formed) == 1:
                for k in range(500):
                    assert bat.enqueue(mkmsg("seq/b", b"%05d" % k))
            await fold(entry)
        bat._fold_hooks = noting

        async def go():
            eng = node.device_engine
            eng.rebuild()
            eng._kick_class_warm()
            if eng._fuse_warm_task is not None:
                await eng._fuse_warm_task
            for k in range(936):
                assert bat.enqueue(mkmsg("seq/a", b"%05d" % k))
            for _ in range(3000):
                if len(sink.got) >= 1436:
                    break
                await asyncio.sleep(0.01)
            await node.deliver_lanes.drain()

        run(go())
        assert formed == want
        assert [p for _f, t, p in sink.got if t == "seq/a"] == \
            [b"%05d" % k for k in range(936)]
        assert node.metrics.val("routing.host_probe.msgs") == \
            (64 if every is None else 1436)

    def test_windows_wait_for_the_lanes_rows_not_their_count(
            self, monkeypatch):
        """600 deliveries a message, the lanes blocked: the batcher
        stops forming windows once the deliveries in flight pass the
        bound (three plans here, where the lanes' own bound in plans
        would take nine and the settle ring any number), the queue
        then passes its limit and `enqueue` refuses; on resume every
        delivery lands, in order."""
        from emqx_tpu.broker import batcher as bm
        monkeypatch.setattr(bm, "_ROWS_IN_FLIGHT", 10_000)
        node = _node(2)
        b = node.broker
        sinks = []
        for i in range(600):
            s = RecBatch()
            sinks.append(s)
            b.subscribe(b.register(s, f"w{i}"), "wide/#", {"qos": 0})
        bat = node.publish_batcher
        bat._device_worth_it = lambda n: True
        bat.max_batch = 8
        # as the first settled window leaves it (until then the
        # estimate is 1 and up to `pipeline_depth` windows form)
        bat._rows_per_msg = 600.0

        async def go():
            eng = node.device_engine
            eng.rebuild()
            eng._kick_class_warm()
            if eng._fuse_warm_task is not None:
                await eng._fuse_warm_task
            pool = node.deliver_lanes
            pool.ensure_loop()
            pool.pause()
            sent = 0
            for _ in range(400):
                for _k in range(8):
                    if bat.enqueue(mkmsg(f"wide/{sent % 7}",
                                         b"m%06d" % sent)):
                        sent += 1
                await asyncio.sleep(0.002)
            held = (bat._rows_in_flight(), pool.live_rows,
                    len(bat._queue), bat._pending_limit())
            pool.resume()
            for _ in range(2000):
                if not bat._queue and not pool.busy() \
                        and bat._formed == bat._taken:
                    break
                await asyncio.sleep(0.005)
            await pool.drain()
            return sent, held

        sent, (rows, live, queued, limit) = run(go())
        m = node.metrics
        if m.val("routing.device.batches") == 0:
            pytest.skip("the device path never engaged")
        # one window past the bound at most, and the queue at its
        # (fan-out-aware) limit, far under max_pending
        assert rows <= 10_000 + 2 * 8 * 600, (rows, live)
        assert limit == 10_000 // 600 and queued <= limit + 8
        assert sent < 400 * 8
        for s in sinks:
            assert [p for _f, _t, p in s.got] == \
                [b"m%06d" % i for i in range(sent)]


class TestSharedFrame:
    """ROADMAP Speed 1: a QoS 0 delivery with nothing of the
    subscriber's in it goes out as one frame, serialized once a
    (message, options, protocol version)."""

    CASES = {
        # name: (message qos, flags, properties, subopts, shared?)
        "plain": (0, {}, None, {"qos": 0}, True),
        "sub_qos1_msg_qos0": (0, {}, None, {"qos": 1}, True),
        "msg_qos1_sub_qos0": (1, {}, None, {"qos": 0}, True),
        "retain_rap": (0, {"retain": True}, None,
                       {"qos": 0, "rap": 1}, True),
        "retain_no_rap": (0, {"retain": True}, None, {"qos": 0}, True),
        "retained_replay": (0, {"retain": True, "retained": True}, None,
                            {"qos": 0}, True),
        "dup": (0, {"dup": True}, None, {"qos": 0}, True),
        "user_props": (0, {}, {"user_property": [("k", "v")],
                               "content_type": "t"}, {"qos": 0}, True),
        "qos1": (1, {}, None, {"qos": 1}, False),
        "expiry": (0, {}, {"message_expiry_interval": 60},
                   {"qos": 0}, False),
        "no_local_own": (0, {}, None, {"qos": 0, "nl": 1}, False),
        # ISSUE 35: a device-picked `$share` member's subopts
        "share": (0, {}, None, {"qos": 0, "share": "g"}, True),
        "share_sub_qos1_msg_qos0": (0, {}, None,
                                    {"qos": 1, "share": "g"}, True),
        "share_qos1": (1, {}, None, {"qos": 1, "share": "g"}, False),
    }

    @pytest.mark.parametrize("ver", [4, 5])
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_bytes_and_counters_equal_the_copy_path(self, case, ver):
        from emqx_tpu.broker.connection import Listener
        from emqx_tpu.client import Client
        qos, flags, props, so, shared = self.CASES[case]
        so = dict(OPT_TABLE[0], **so)
        node = Node({"broker": {"deliver_lanes": 2}})
        names = ("messages.sent", "messages.qos0.sent", "packets.sent",
                 "packets.publish.sent", "bytes.sent",
                 "delivery.dropped")

        class Tap:
            def __init__(self):
                self.data = b""

            def write(self, data):
                self.data += data

            def is_closing(self):
                return False

        async def go():
            lst = Listener(node, bind="127.0.0.1", port=0)
            await lst.start()
            c = Client(port=lst.port, clientid="me", proto_ver=ver)
            await c.connect()
            await c.subscribe("t/#", qos=1)
            ch = next(iter(node.broker._subscribers.values()))
            conn = ch.send.__self__
            out = []
            for use_shared in (True, False):
                tap = conn.writer = Tap()
                ch.send_frames = conn._send_frames if use_shared \
                    else None
                before = {n: node.metrics.val(n) for n in names}
                n0 = ch.session.deliver_count
                msgs = [Message(
                    topic=f"t/{i}", payload=b"p%d" % i, qos=qos,
                    from_="me" if case == "no_local_own" else "pub",
                    flags=dict(flags),
                    headers={"properties": dict(props)} if props
                    else {}) for i in range(3)]
                views = [DeliveryView(m, so) for m in msgs]
                assert (views[0].wire_qos0(ver, "me") is not None) \
                    == shared
                got = ch.deliver_batch([("t/#", v) for v in views[:2]])
                ok = ch.deliver("t/#", views[2])
                held = len(ch.session.inflight)
                # packet ids differ run to run: strip them
                ch.session.inflight.clear()
                out.append((tap.data, got, ok,
                            {n: node.metrics.val(n) - before[n]
                             for n in names},
                            ch.session.deliver_count - n0, held))
            await lst.stop()
            return out

        with_frames, with_copies = run(go())
        if qos and so["qos"]:
            # the packet ids move on: same length, same counters, and
            # all three in the session's inflight window
            assert len(with_frames[0]) == len(with_copies[0])
            assert with_frames[1:] == with_copies[1:]
            assert with_frames[5] == 3
        else:
            assert with_frames == with_copies
            assert with_frames[5] == 0
        assert with_frames[1] == 2 and with_frames[2] is True
        if case != "no_local_own":
            assert with_frames[0]


    @pytest.mark.parametrize("entry", ["deliver_frames", "deliver_batch"])
    def test_a_session_with_a_deliver_of_its_own_sees_every_delivery(
            self, entry, monkeypatch):
        """The shared frame stands in for `Session.deliver`: where that
        is not the session's `deliver` (the benchmark's controls patch
        one in to lose, duplicate or reorder) the run goes through it,
        by either entry of the lanes."""
        from emqx_tpu.broker.connection import Listener
        from emqx_tpu.broker.session import Session
        from emqx_tpu.client import Client
        node = Node({"broker": {"deliver_lanes": 2}})
        seen = []
        real = Session.deliver

        def tapped(self, msgs):
            seen.extend(m.topic for m, _so in msgs)
            return real(self, msgs)

        async def go():
            lst = Listener(node, bind="127.0.0.1", port=0)
            await lst.start()
            c = Client(port=lst.port, clientid="me")
            await c.connect()
            await c.subscribe("t/#", qos=0)
            ch = next(iter(node.broker._subscribers.values()))
            views = [DeliveryView(Message(topic=f"t/{i}", payload=b"p",
                                          qos=0, from_="pub"),
                                  OPT_TABLE[0]) for i in range(3)]
            items = [("t/#", v) for v in views]
            assert ch._shared_write() is not None
            monkeypatch.setattr(Session, "deliver", tapped)
            assert ch._shared_write() is None
            if entry == "deliver_frames":
                assert ch.deliver_frames(lambda *a: b"", 0, 3) is False
            assert ch.deliver_batch(items) == 3
            await lst.stop()

        run(go())
        assert seen == ["t/0", "t/1", "t/2"]


class TestDeliveryView:
    def test_view_quacks_like_message(self):
        m = Message(topic="a/b", payload=b"p", qos=1, from_="me",
                    headers={"properties": {"user": 1}},
                    flags={"retain": True})
        so = {"qos": 1, "nl": 0, "rap": 1, "rh": 0}
        v = DeliveryView(m, so)
        assert v.topic == "a/b" and v.qos == 1 and v.payload == b"p"
        assert v.headers["subopts"] is so
        assert v.headers.get("subopts") is so
        assert v.get_header("subopts") is so
        assert v.headers.get("properties") == {"user": 1}
        assert "subopts" in v.headers
        assert v.retain and not v.dup
        # copy() materializes a real, independent Message
        c = v.copy()
        assert isinstance(c, Message)
        assert c.headers["subopts"] == so
        c.headers["extra"] = 1
        assert "extra" not in m.headers and "extra" not in v.headers
        # copy-on-write: a header write never touches the base message
        v.set_header("x", 2)
        assert v.headers["x"] == 2 and "x" not in m.headers
        assert v.headers["subopts"] == so
        v.set_flag("dup", True)
        assert v.dup and not m.get_flag("dup")
        # wire form carries the overlay
        w = v.to_wire()
        assert w["topic"] == "a/b" and w["headers"]["subopts"] == so

    def test_opt_table_round_trips_packed_words(self):
        from emqx_tpu.broker.device_engine import _pack_opts
        for qos in (0, 1, 2):
            for nl in (0, 1):
                for rap in (0, 1):
                    for rh in (0, 1, 2):
                        opts = {"qos": qos, "nl": nl, "rap": rap,
                                "rh": rh}
                        assert OPT_TABLE[_pack_opts(opts)] == opts


class TestKnobs:
    def test_resolve_deliver_lanes(self, monkeypatch):
        monkeypatch.delenv("EMQX_TPU_DELIVER_LANES", raising=False)
        assert resolve_deliver_lanes(2) == 2
        assert resolve_deliver_lanes(0) == 0
        import os
        assert resolve_deliver_lanes(None) == min(4, os.cpu_count() or 1)
        monkeypatch.setenv("EMQX_TPU_DELIVER_LANES", "3")
        assert resolve_deliver_lanes(None) == 3
        assert resolve_deliver_lanes(1) == 1     # config beats env
        monkeypatch.setenv("EMQX_TPU_DELIVER_LANES", "junk")
        with pytest.raises(ValueError):
            resolve_deliver_lanes(None)
        with pytest.raises(ValueError):
            resolve_deliver_lanes(-1)

    def test_lanes_zero_restores_inline(self):
        node = _node(0)
        assert node.deliver_lanes is None
        # sync serving path still fully functional
        b = node.broker
        s = Rec()
        b.subscribe(b.register(s, "c"), "a/+", {"qos": 0})
        assert node.device_engine.route_batch([mkmsg("a/1")]) == [1]
        assert [t for _f, t, _p in s.got] == ["a/1"]


class TestHostsideMemo:
    def test_mask_memoized_until_churn(self):
        node = _node(0)
        b = node.broker
        s = Rec()
        sid = b.register(s, "c1")
        for i in range(8):
            b.subscribe(sid, f"m/{i}/+", {"qos": 0})
        eng = node.device_engine
        eng.rebuild()
        built = eng._built
        # no dirty filters: the snapshot's precomputed mask, no copy
        assert eng._hostside_mask(built) is built.fid_rich
        # dirty one filter: mask computed once, then reused by identity
        s2 = Rec()
        sid2 = b.register(s2, "c2")
        b.subscribe(sid2, "m/1/+", {"qos": 0})
        assert "m/1/+" in eng.dirty_filters
        m1 = eng._hostside_mask(built)
        fid = built.fid_of["m/1/+"]
        assert m1[fid]
        assert eng._hostside_mask(built) is m1
        # further churn invalidates (unsubscribe dirties another filter)
        b.subscribe(sid2, "m/2/+", {"qos": 0})
        m2 = eng._hostside_mask(built)
        assert m2 is not m1
        assert m2[built.fid_of["m/2/+"]]
        assert eng._hostside_mask(built) is m2


class TestTelemetry:
    def test_deliver_section_and_gauges(self):
        rng = np.random.RandomState(3)
        node = _node(2)
        _build_world(node, rng)
        run(_drive(node, _schedule(rng, n_windows=2), {}))
        snap = node.pipeline_telemetry.snapshot()
        d = snap["deliver"]
        assert d["plans"] >= 2
        assert d["deliveries"] > 0
        assert d["state"]["lanes"] == 2
        # per-lane stage histograms landed in the shared registry
        assert any(k.startswith("deliver_lane") for k in snap["stages"])
        # the lane-depth gauge rides the Stats table (all exporters)
        gauges = node.stats.sample()
        assert "pipeline.deliver.lane_depth" in gauges
        # Prometheus exposition carries the counters + gauge family
        from emqx_tpu.apps.prometheus import collect
        text = collect(node)
        assert "emqx_pipeline_deliver_plans" in text
        assert "emqx_pipeline_deliver_lane_depth" in text


# ------ ISSUE 41: a run of shared frames is one gather-and-join ------

class _Wire:
    """What one socket-less `Channel` wrote, in order, whichever write
    it took: packets (`send`) or frames serialized already
    (`send_frames`)."""

    def __init__(self, ver):
        self.ver, self.out = ver, []

    def send(self, pkts):
        from emqx_tpu.mqtt.frame import serialize
        self.out.append(b"".join(serialize(p, self.ver) for p in pkts))


def _chan(node, cid, ver=4):
    """A connected MQTT channel with no socket under it: the subscriber
    the lanes' frame entry is for."""
    from emqx_tpu.broker.channel import CONN_CONNECTED, Channel
    from emqx_tpu.broker.session import Session
    wire = _Wire(ver)
    ch = Channel(node, {}, wire.send, lambda reason: None)
    ch.clientid, ch.proto_ver = cid, ver
    ch.session = Session(cid)
    ch.conn_state = CONN_CONNECTED
    ch.send_frames = wire.out.append
    ch.wire = wire
    return ch


class _World:
    """One node, its subscribers and one plan's rows, built the same
    way on the side that may join frames and on the side a
    `message.delivered` hook holds to the row walk."""

    def __init__(self, hooked, lanes=4):
        self.node = Node({"broker": {"deliver_lanes": lanes}})
        self.pool = self.node.deliver_lanes
        self.subs, self.sids = {}, {}
        self.rows = []                  # (message, name, word, filter)
        self.msgs = []
        self.picks = None
        self.slots = {}                 # row index -> slot of its group
        self.held = None                # runs between submit and the lanes
        self.how = "submit"
        self.hooked_rows = []
        self.redispatched = []
        if hooked:
            self.node.hooks.add(
                "message.delivered",
                lambda meta, m: self.hooked_rows.append((meta, m.topic)))

    def chan(self, name, ver=4):
        self.subs[name] = ch = _chan(self.node, name, ver)
        self.sids[name] = self.node.broker.register(ch, name)
        return ch

    def sink(self, name, cls=Rec):
        self.subs[name] = s = cls()
        self.sids[name] = self.node.broker.register(s, name)
        return s

    def msg(self, topic, payload=b"x", qos=0, from_="pub", flags=None,
            props=None):
        self.msgs.append(Message(
            topic=topic, payload=payload, qos=qos, from_=from_,
            flags=dict(flags or {}),
            headers={"properties": dict(props)} if props else {}))
        return len(self.msgs) - 1

    def row(self, m, name, word=0, filt="t/#", slot=None):
        if slot is not None:
            self.slots[len(self.rows)] = slot
        self.rows.append((m, name, word, filt))

    def groups(self, keys):
        from emqx_tpu.broker.deliver import GroupPicks

        def redispatch(f, g, m):
            self.redispatched.append((f, g, m.topic))
            return 1
        self.picks = GroupPicks(keys, redispatch)

    def columns(self):
        filters = sorted({r[3] for r in self.rows})
        return (np.array([r[0] for r in self.rows], np.int64),
                np.array([self.sids[r[1]] for r in self.rows], np.int64),
                np.array([r[2] for r in self.rows], np.int64),
                np.array([filters.index(r[3]) for r in self.rows],
                         np.int64), filters)

    async def deliver(self):
        pool, n = self.pool, len(self.msgs)
        before = dict(self.node.metrics.all())
        midx, sid, opt, fid, filters = self.columns()
        if self.how == "deliver_now":
            counts = pool.deliver_now(self.msgs, midx, sid, opt, fid,
                                      filters)
        else:
            plan = pool.new_plan(self.msgs)
            plan.routed_device = True
            plan.register_fast(range(n))
            if self.how == "add_rows_py":
                for m in range(n):
                    plan.add_rows_py(m, [
                        (self.sids[name], word, filt)
                        for mi, name, word, filt in self.rows if mi == m])
            elif self.picks is not None:
                slot = np.full(len(self.rows), -1, np.int64)
                for k, s in self.slots.items():
                    slot[k] = s
                plan.add_rows(midx, sid, opt, fid, filters, slot,
                              self.picks)
            else:
                plan.add_rows(midx, sid, opt, fid, filters)
            pool.pause()
            pool.submit(plan)
            if self.held is not None:
                self.held(self)
            pool.resume()
            await pool.drain()
            counts = plan.counts
        after = self.node.metrics.all()
        moved = {k: after[k] - before.get(k, 0) for k in after
                 if k.startswith(("messages.", "packets.", "delivery.",
                                  "pipeline.deliver.", "routing.device."))
                 and after[k] != before.get(k, 0)}
        return counts.tolist(), moved

    def seen(self):
        """What each subscriber has, by name."""
        out = {}
        for name, s in self.subs.items():
            if hasattr(s, "wire"):
                sess = s.session
                out[name] = (b"".join(s.wire.out), sess.deliver_count,
                             len(sess.inflight), len(sess.mqueue))
            else:
                out[name] = list(s.got)
        return out


def _fanout(n_sess, fan, n_msgs):
    def build(w):
        names = [f"c{i}" for i in range(n_sess)]
        for name in names:
            w.chan(name)
        for m in range(n_msgs):
            w.msg(f"t/{m}", b"p%03d" % m)
            for k in range(fan):
                w.row(m, names[(m + k) % n_sess])
        return n_msgs * fan
    return build


def _two_words(w):
    """One retained message by a plain subscription and by one with
    retain-as-published: two frames, one a word."""
    w.chan("a"), w.chan("b")
    m = w.msg("t/1", flags={"retain": True})
    w.row(m, "a", 0), w.row(m, "b", 8), w.row(m, "a", 8, "t/+")
    return 3


def _v4_and_v5(w):
    w.chan("old", 4), w.chan("new", 5), w.chan("new2", 5)
    for i in range(4):
        m = w.msg(f"t/{i}", props={"user_property": [("k", "v")],
                                   "content_type": "c"})
        for name in ("old", "new", "new2"):
            w.row(m, name)
    return 12


def _no_local_own(w):
    """The publisher's own session under no-local: its run is walked
    (the session drops the row), every other run is joined frames."""
    w.chan("pub"), w.chan("other"), w.chan("third")
    for i in range(3):
        m = w.msg(f"t/{i}", from_="pub" if i != 1 else "else")
        for name in ("pub", "other", "third"):
            w.row(m, name, 4)
    return 6


def _subid(w):
    """A row whose subopts carry a subscription identifier (never a
    packed word's: here through a group's subopts) takes a copy."""
    w.chan("a", 5), w.chan("b", 5)
    w.groups([("t/#", "g")])
    w.picks._subopts[(0, "g")] = dict(OPT_TABLE[0], share="g", subid=7)
    for i in range(3):
        m = w.msg(f"t/{i}")
        w.row(m, "a", 0, slot=0), w.row(m, "b", 0)
    return 3


def _retain_rap(w):
    w.chan("plain"), w.chan("rap")
    for i in range(3):
        m = w.msg(f"t/{i}", flags={"retain": True})
        w.row(m, "plain", 0), w.row(m, "rap", 8)
    return 6


def _expiring(w):
    w.chan("a", 5), w.chan("b", 5)
    m0 = w.msg("t/0", props={"message_expiry_interval": 60})
    m1 = w.msg("t/1")
    w.row(m0, "a"), w.row(m1, "a"), w.row(m1, "b")
    return 1


def _qos1_mid_run(w):
    """A QoS 1 row in the middle of a run: the whole run is walked,
    in the order given; the other session's run is frames."""
    w.chan("a"), w.chan("b")
    for i in range(5):
        m = w.msg(f"t/{i}", qos=1 if i == 2 else 0)
        w.row(m, "a", 1), w.row(m, "b", 0)
    return 5


def _share_pick(w):
    w.chan("member"), w.chan("plain")
    w.groups([("t/#", "g"), ("t/+", "h")])
    for i in range(4):
        m = w.msg(f"t/{i}")
        w.row(m, "plain", 0)
        w.row(m, "member", 0, slot=i % 2)
    return 8


def _session_gone(w):
    """A session that leaves the registry while its rows wait: the
    plain ones are dropped, a group's go through the host's dispatch."""
    w.chan("stays"), w.chan("goes")
    w.groups([("t/#", "g")])
    for i in range(3):
        m = w.msg(f"t/{i}")
        w.row(m, "stays", 0), w.row(m, "goes", 0)
        w.row(m, "goes", 0, slot=0)
    w.held = lambda w: w.node.broker.unregister(w.sids["goes"])
    return 3


def _closing(w):
    """The connection's state is read when the run is sent: one that
    stopped being connected since `submit` queues in its session."""
    from emqx_tpu.broker.channel import CONN_DISCONNECTED
    w.chan("up"), w.chan("down")
    for i in range(3):
        m = w.msg(f"t/{i}")
        w.row(m, "up"), w.row(m, "down")

    def close(w):
        w.subs["down"].conn_state = CONN_DISCONNECTED
    w.held = close
    return 3


def _deliver_only(w):
    w.chan("mqtt"), w.sink("gateway", Rec), w.sink("batching", RecBatch)
    for i in range(4):
        m = w.msg(f"t/{i}")
        for name in ("mqtt", "gateway", "batching"):
            w.row(m, name)
    return 4


def _deliver_now(w):
    n = _fanout(5, 3, 7)(w)
    w.how = "deliver_now"
    return n


def _add_rows_py(w):
    n = _fanout(5, 2, 6)(w)
    w.how = "add_rows_py"
    return n


# case -> (builder, rows the frame path is expected to send)
_FRAME_CASES = {
    "fanout_1": _fanout(4, 1, 9), "fanout_2": _fanout(6, 2, 9),
    "fanout_300": _fanout(300, 300, 3), "two_words": _two_words,
    "v4_and_v5": _v4_and_v5, "no_local_own": _no_local_own,
    "subid": _subid, "retain_rap": _retain_rap, "expiring": _expiring,
    "qos1_mid_run": _qos1_mid_run, "share_pick": _share_pick,
    "session_gone": _session_gone, "closing": _closing,
    "deliver_only": _deliver_only, "deliver_now": _deliver_now,
    "add_rows_py": _add_rows_py,
}
_CLOCKS = ("pipeline.deliver.lane_us", "pipeline.deliver.accept_us",
           "pipeline.deliver.frame_rows")


class TestFramePathEqualsTheRowWalk:
    @pytest.mark.parametrize("case", sorted(_FRAME_CASES))
    def test_same_plan_both_ways(self, case):
        """The same plan by the joined-frame path and by the row walk
        (forced by a `message.delivered` hook): byte-identical output a
        connection in identical order, identical counts a message and
        identical counters; and the frame path engaged where the case
        says it does."""
        sides = []
        for hooked in (False, True):
            w = _World(hooked)
            expect = _FRAME_CASES[case](w)
            counts, moved = run(w.deliver())
            sides.append((w, counts, moved, expect))
        (fw, f_counts, f_moved, expect), (hw, h_counts, h_moved, _e) = sides
        assert f_moved.get("pipeline.deliver.frame_rows", 0) == expect
        assert "pipeline.deliver.frame_rows" not in h_moved
        assert fw.seen() == hw.seen()
        assert f_counts == h_counts
        assert {k: v for k, v in f_moved.items() if k not in _CLOCKS} \
            == {k: v for k, v in h_moved.items() if k not in _CLOCKS}
        assert fw.redispatched == hw.redispatched
        assert fw.hooked_rows == []
        # the hook saw every row a subscriber took, once
        assert len(hw.hooked_rows) == h_moved.get("messages.delivered", 0)
        assert f_moved["pipeline.deliver.deliveries"] == len(fw.rows)
        if case == "fanout_300":
            # 900 rows, three frames
            assert f_moved["pipeline.deliver.frames_built"] == 3
            assert f_moved["pipeline.deliver.drains"] == 300
        if case == "no_local_own":
            # its own two are dropped by its session, silently
            assert fw.seen()["pub"][1] == 1
            assert fw.seen()["other"][1] == 3
        if case == "session_gone":
            assert len(fw.redispatched) == 3
            assert f_moved["routing.device.shared_repick"] == 3
        if case == "closing":
            assert fw.seen()["down"] == (b"", 0, 0, 3)


class TestPlanTable:
    """ISSUE 41: one table of views and frames a plan, runs cut by
    numpy, whichever lane, chunk or retry asks first."""

    def test_a_frame_is_serialized_once_a_key_a_version_a_plan(
            self, monkeypatch):
        """Four lanes, chunks of 16 rows, 40 sessions of two protocol
        versions, 30 messages to every one of them and a second word on
        every third row: 1,200 rows, and `serialize` runs once a
        (message, word, version)."""
        from emqx_tpu.broker import deliver as D
        calls = []
        real = D.serialize
        monkeypatch.setattr(
            D, "serialize",
            lambda pkt, ver: calls.append((pkt.topic, pkt.retain, ver))
            or real(pkt, ver))
        w = _World(False)
        w.pool._chunk = 16
        names = [f"c{i}" for i in range(40)]
        for i, name in enumerate(names):
            w.chan(name, 5 if i % 2 else 4)
        for m in range(30):
            w.msg(f"t/{m}", b"p%03d" % m, flags={"retain": True})
            for i, name in enumerate(names):
                w.row(m, name, 8 if i % 3 == 0 else 0)
        counts, moved = run(w.deliver())
        assert counts == [40] * 30
        # sessions 0, 6, 12 ... are v4 under the second word, and so on:
        # both words meet both versions
        assert len(calls) == len(set(calls)) == 30 * 2 * 2
        assert moved["pipeline.deliver.frames_built"] == 120
        assert moved["pipeline.deliver.frame_rows"] == 1200
        assert moved["pipeline.deliver.drains"] == 40
        # a run is one write, whatever the chunking
        assert all(len(s.wire.out) == 1 for s in w.subs.values())
        snap = w.node.pipeline_telemetry.snapshot()["deliver"]
        assert snap["frame_rows"] == 1200 and snap["frames_built"] == 120
        assert snap["frame_share"] == 1.0
        assert snap["frames_per_delivery"] == 0.1
        from emqx_tpu.apps.prometheus import collect
        text = collect(w.node)
        assert "emqx_pipeline_deliver_frame_rows" in text
        assert "emqx_pipeline_deliver_frames_built" in text

    def test_a_long_slice_yields_between_chunks_and_splits_no_run(self):
        """One lane, 5,000 rows in runs of uneven length: a chunk ends
        at the first run boundary 2,048 rows on, the loop gets a turn
        between two chunks, and every session's run is one write."""
        w = _World(False, lanes=1)
        lengths = [1500, 700, 1, 2047, 300, 449, 3]
        for i, n in enumerate(lengths):
            w.chan(f"c{i}")
        w.msg("t/0")
        for i, n in enumerate(lengths):
            for _ in range(n):
                w.row(0, f"c{i}")
        pool = w.pool
        chunks, turns = [], []
        real = pool._deliver_rows

        def rows(plan, lo, hi):
            chunks.append((plan.run_lo[lo], plan.run_lo[hi]))
            real(plan, lo, hi)
        pool._deliver_rows = rows

        async def go():
            async def ticker():
                while len(chunks) < 3:
                    turns.append(len(chunks))
                    await asyncio.sleep(0)
            t = asyncio.ensure_future(ticker())
            out = await w.deliver()
            await t
            return out
        counts, moved = run(go())
        assert counts == [5000]
        # 1500 + 700 pass 2,048 at 2,200; 1 + 2047 end at 4,248
        assert chunks == [(0, 2200), (2200, 4248), (4248, 5000)]
        assert {1, 2} <= set(turns)
        assert [len(s.wire.out) for s in w.subs.values()] == [1] * 7
        assert [s.session.deliver_count for s in w.subs.values()] \
            == lengths
        assert moved["pipeline.deliver.accept_us"] \
            <= moved["pipeline.deliver.lane_us"]
        assert moved["pipeline.deliver.frames_built"] == 1

    def test_a_chunks_retry_reads_the_same_table_and_counts_once(self):
        """The supervisor's per-chunk retry: the chunk fails at its
        third run, is delivered again whole, and nothing is serialized
        or counted a second time."""
        w = _World(False, lanes=1)
        assert w.pool.sup is not None
        names = [f"c{i}" for i in range(5)]
        for name in names:
            w.chan(name)
        for m in range(6):
            w.msg(f"t/{m}", b"p%d" % m)
            for name in names:
                w.row(m, name)
        broker = w.node.broker
        third = sorted(w.sids.values())[2]

        class Faulty(dict):
            armed = True

            def get(self, sid, default=None):
                if sid == third and self.armed:
                    self.armed = False
                    raise RuntimeError("registry fault")
                return dict.get(self, sid, default)
        broker._subscribers = Faulty(broker._subscribers)
        counts, moved = run(w.deliver())
        assert counts == [5] * 6
        assert moved["messages.delivered"] == 30
        assert moved["pipeline.deliver.deliveries"] == 30
        assert moved["pipeline.deliver.frames_built"] == 6
        assert w.node.metrics.val("supervise.faults.lane_deliver") == 1
        # at least once: the two runs before the fault went out twice
        by_sid = sorted(w.subs.values(), key=lambda s: w.sids[s.clientid])
        assert [len(s.wire.out) for s in by_sid] == [2, 2, 1, 1, 1]
        assert by_sid[0].wire.out[0] == by_sid[0].wire.out[1] \
            == by_sid[4].wire.out[0]

    def test_a_raising_frame_entry_is_contained_to_its_run(self):
        w = _World(False, lanes=1)
        for name in ("a", "bad", "c"):
            w.chan(name)
        for m in range(3):
            w.msg(f"t/{m}")
            for name in ("a", "bad", "c"):
                w.row(m, name)

        def boom(data):
            raise OSError("socket gone")
        w.subs["bad"].send_frames = boom
        counts, moved = run(w.deliver())
        assert counts == [2] * 3
        assert moved["pipeline.deliver.deliver_errors"] == 1
        assert moved["pipeline.deliver.frame_rows"] == 6
        assert moved["messages.delivered"] == 6
