"""The device route engine as the LIVE serving path.

Round 2's flagship requirement (VERDICT.md next-round #2): PUBLISHes flowing
through real TCP connections must be matched + fanned out by the fused
device route step (models.router_engine), with RouteResult rows driving the
actual deliveries — asserted via the `messages.routed.device` counter — and
stale-snapshot cases (membership churn, new filters) handled correctly.
Parity target: emqx_broker.erl:199-308 publish/dispatch semantics.
"""

import asyncio

import pytest

from emqx_tpu.broker.message import make
from emqx_tpu.broker.node import Node


class Sink:
    """Fake subscriber recording deliveries."""

    def __init__(self):
        self.got = []

    def deliver(self, topic_filter, msg):
        self.got.append((topic_filter, msg.topic, msg.payload,
                         msg.headers.get("subopts", {})))
        return True


def mkmsg(topic, payload=b"x", qos=0, from_="pub"):
    return make(from_, qos, topic, payload)


@pytest.fixture()
def node():
    n = Node()
    assert n.device_engine is not None  # default-on
    return n


class TestEngineDirect:
    """DeviceRouteEngine.route_batch consumed into deliveries (no sockets)."""

    def test_wildcard_and_exact_device_rows(self, node):
        b = node.broker
        s1, s2, s3 = Sink(), Sink(), Sink()
        sid1 = b.register(s1, "c1")
        sid2 = b.register(s2, "c2")
        sid3 = b.register(s3, "c3")
        b.subscribe(sid1, "dev/+/temp", {"qos": 1})
        b.subscribe(sid2, "dev/7/temp", {"qos": 0})
        b.subscribe(sid3, "exact/topic", {"qos": 2})

        msgs = [mkmsg("dev/7/temp"), mkmsg("exact/topic"),
                mkmsg("dev/9/temp"), mkmsg("none/match")]
        counts = node.device_engine.route_batch(msgs)
        assert counts == [2, 1, 1, 0]
        assert sorted(t for _f, t, _p, _o in s1.got) == \
            ["dev/7/temp", "dev/9/temp"]
        assert [t for _f, t, _p, _o in s2.got] == ["dev/7/temp"]
        assert [t for _f, t, _p, _o in s3.got] == ["exact/topic"]
        # subopts survive the packed-byte round trip
        assert s1.got[0][3]["qos"] == 1
        assert s3.got[0][3]["qos"] == 2
        assert node.metrics.val("messages.routed.device") == 4
        assert node.metrics.val("routing.device.batches") == 1
        assert node.metrics.val("messages.dropped.no_subscribers") == 1

    def test_membership_churn_goes_host(self, node):
        b = node.broker
        s1 = Sink()
        sid1 = b.register(s1, "c1")
        b.subscribe(sid1, "t/+", {"qos": 0})
        assert node.device_engine.route_batch([mkmsg("t/1")]) == [1]
        dev0 = node.metrics.val("messages.routed.device")

        # new member on a built filter -> filter dirty -> host dict path
        s2 = Sink()
        sid2 = b.register(s2, "c2")
        b.subscribe(sid2, "t/+", {"qos": 1})
        assert node.device_engine.route_batch([mkmsg("t/2")]) == [2]
        assert [t for _f, t, _p, _o in s2.got] == ["t/2"]
        assert len(s1.got) == 2
        assert node.metrics.val("messages.routed.device") == dev0

        # unsubscribe -> still dirty -> removed member gets nothing
        b.unsubscribe(sid1, "t/+")
        assert node.device_engine.route_batch([mkmsg("t/3")]) == [1]
        assert len(s1.got) == 2
        assert len(s2.got) == 2

    def test_new_filter_delta_path(self, node):
        b = node.broker
        s1 = Sink()
        sid1 = b.register(s1, "c1")
        b.subscribe(sid1, "a/b", {"qos": 0})
        assert node.device_engine.route_batch([mkmsg("a/b")]) == [1]

        s2 = Sink()
        sid2 = b.register(s2, "c2")
        b.subscribe(sid2, "fresh/#", {"qos": 0})
        counts = node.device_engine.route_batch(
            [mkmsg("fresh/x/y"), mkmsg("a/b")])
        assert counts == [1, 1]
        assert [t for _f, t, _p, _o in s2.got] == ["fresh/x/y"]
        assert node.device_engine.stats()["delta_filters"] == 1

    def test_rebuild_after_threshold(self, node):
        node.device_engine.rebuild_threshold = 4
        # delta overlay OFF restores the pre-ISSUE-4 contract under
        # test here: post-build filters count toward staleness and the
        # threshold crossing triggers a full rebuild (with the overlay
        # on they serve on device and never trip the threshold — see
        # tests/test_delta_overlay.py)
        node.device_engine.delta_overlay = False
        b = node.broker
        s1 = Sink()
        sid1 = b.register(s1, "c1")
        b.subscribe(sid1, "base/t", {"qos": 0})
        node.device_engine.route_batch([mkmsg("base/t")])
        for i in range(5):
            b.subscribe(sid1, f"extra/{i}", {"qos": 0})
        assert node.device_engine.staleness() >= 4
        node.device_engine.route_batch([mkmsg("extra/3")])
        assert node.device_engine.staleness() == 0   # rebuilt
        assert node.device_engine.stats()["delta_filters"] == 0
        assert len([x for x in s1.got if x[1] == "extra/3"]) == 1
        assert node.metrics.val("routing.device.rebuilds") >= 2

    def test_shared_round_robin_device_picks(self, node):
        b = node.broker
        sinks = [Sink() for _ in range(3)]
        sids = [b.register(s, f"m{i}") for i, s in enumerate(sinks)]
        for sid in sids:
            b.subscribe(sid, "$share/g/job/q", {"qos": 1})
        msgs = [mkmsg("job/q", str(i).encode()) for i in range(6)]
        counts = node.device_engine.route_batch(msgs)
        assert counts == [1] * 6
        per = [len(s.got) for s in sinks]
        assert sorted(per) == [2, 2, 2]          # strict round-robin
        assert all(o.get("share") == "g"
                   for s in sinks for _f, _t, _p, o in s.got)
        # cursors persist across batches: next 3 go one to each member
        node.device_engine.route_batch(
            [mkmsg("job/q", b"n1"), mkmsg("job/q", b"n2"),
             mkmsg("job/q", b"n3")])
        assert sorted(len(s.got) for s in sinks) == [3, 3, 3]

    def test_shared_sticky_device_picks(self, node):
        """VERDICT r4 #9: sticky serves ON DEVICE — the cursor is the
        affinity pointer, so every message of every batch goes to the
        same member with zero host feedback."""
        b = node.broker
        b.shared_strategy = "sticky"
        sinks = [Sink() for _ in range(3)]
        for i, s in enumerate(sinks):
            b.subscribe(b.register(s, f"st{i}"), "$share/sg/stick/q",
                        {"qos": 0})
        counts = node.device_engine.route_batch(
            [mkmsg("stick/q", str(i).encode()) for i in range(6)])
        assert counts == [1] * 6
        assert sorted(len(s.got) for s in sinks) == [0, 0, 6]
        # across batches: same member, still on device
        dev0 = node.metrics.val("messages.routed.device")
        assert node.device_engine.route_batch([mkmsg("stick/q", b"n")]) \
            == [1]
        assert sorted(len(s.got) for s in sinks) == [0, 0, 7]
        assert node.metrics.val("messages.routed.device") == dev0 + 1

    def test_sticky_repick_after_member_leave(self, node):
        """The feedback-dependent half stays host-side: when the sticky
        member leaves, the host re-pick re-homes the affinity and the
        next snapshot re-seeds the device cursor from it."""
        b = node.broker
        b.shared_strategy = "sticky"
        s1, s2 = Sink(), Sink()
        sid1, sid2 = b.register(s1, "sm1"), b.register(s2, "sm2")
        b.subscribe(sid1, "$share/sg/re/q", {"qos": 0})
        b.subscribe(sid2, "$share/sg/re/q", {"qos": 0})
        assert node.device_engine.route_batch([mkmsg("re/q")]) == [1]
        owner, other, osid = (s1, s2, sid1) if s1.got else (s2, s1, sid2)
        b.unsubscribe(osid, "$share/sg/re/q")
        counts = node.device_engine.route_batch(
            [mkmsg("re/q", b"2"), mkmsg("re/q", b"3")])
        assert counts == [1, 1]
        assert len(other.got) == 2          # re-homed to the survivor
        # affinity survives a full rebuild (re-seeded from host record)
        node.device_engine.rebuild()
        assert node.device_engine.route_batch([mkmsg("re/q", b"4")]) == [1]
        assert len(other.got) == 3

    def test_shared_dirty_slot_host_pick(self, node):
        b = node.broker
        s1, s2 = Sink(), Sink()
        sid1, sid2 = b.register(s1, "m1"), b.register(s2, "m2")
        b.subscribe(sid1, "$share/g/t", {"qos": 0})
        node.device_engine.route_batch([mkmsg("t")])
        # membership change dirties the slot -> host pick sees new member
        b.subscribe(sid2, "$share/g/t", {"qos": 0})
        counts = node.device_engine.route_batch(
            [mkmsg("t") for _ in range(4)])
        assert counts == [1] * 4
        assert len(s1.got) + len(s2.got) == 5
        assert len(s2.got) >= 1

    def test_new_group_on_built_filter(self, node):
        b = node.broker
        s1, s2 = Sink(), Sink()
        sid1, sid2 = b.register(s1, "m1"), b.register(s2, "m2")
        b.subscribe(sid1, "t/x", {"qos": 0})
        node.device_engine.route_batch([mkmsg("t/x")])
        b.subscribe(sid2, "$share/g2/t/x", {"qos": 0})
        counts = node.device_engine.route_batch([mkmsg("t/x")])
        assert counts == [2]
        assert len(s2.got) == 1

    def test_overflow_falls_back_host(self, node):
        node.device_engine.fanout_cap = 4   # force tiny capacity
        b = node.broker
        sinks = [Sink() for _ in range(8)]
        # two filters of four: each fits a lane's row, together they
        # pass it (one filter of eight would travel by reference)
        for i, s in enumerate(sinks):
            b.subscribe(b.register(s, f"c{i}"),
                        "big/+" if i < 4 else "big/#", {"qos": 0})
        counts = node.device_engine.route_batch(
            [mkmsg("big/t"), mkmsg("big/u")])
        assert counts == [8, 8]
        assert all(len(s.got) == 2 for s in sinks)
        assert node.metrics.val("routing.device.host_fallback") == 2
        assert node.metrics.val("routing.device.fanout_overflow") == 2

    def test_deep_topic_falls_back_host(self, node):
        b = node.broker
        s = Sink()
        b.subscribe(b.register(s, "c"), "deep/#", {"qos": 0})
        deep = "deep/" + "/".join(str(i) for i in range(25))
        assert node.device_engine.route_batch([mkmsg(deep)]) == [1]
        assert len(s.got) == 1

    def test_rich_subopts_host_path(self, node):
        b = node.broker
        s = Sink()
        sid = b.register(s, "c")
        b.subscribe(sid, "r/+", {"qos": 1, "subid": 7})
        assert node.device_engine.route_batch([mkmsg("r/1")]) == [1]
        # subid must survive (packed byte cannot carry it -> host dict)
        assert s.got[0][3].get("subid") == 7

    def test_trie_backend_when_many_shapes(self, node):
        node.device_engine.shape_cap = 2
        b = node.broker
        s = Sink()
        sid = b.register(s, "c")
        for f in ["a", "a/b", "a/+/c", "+/b/#", "x/y/z/w"]:
            b.subscribe(sid, f, {"qos": 0})
        # 'a/b' matches both the exact filter and '+/b/#' ('#' = zero levels)
        assert node.device_engine.route_batch([mkmsg("a/b")]) == [2]
        assert node.device_engine.stats()["backend"] == "trie"
        assert sorted(f for f, _t, _p, _o in s.got) == ["+/b/#", "a/b"]


class TestEndToEnd:
    """Real TCP clients; concurrent publishes form a device batch."""

    def test_concurrent_publishes_routed_on_device(self):
        from emqx_tpu.broker.connection import Listener
        from emqx_tpu.client import Client

        loop = asyncio.new_event_loop()
        try:
            node = Node()
            listener = Listener(node, bind="127.0.0.1", port=0)
            loop.run_until_complete(listener.start())

            async def go():
                sub = Client(port=listener.port, clientid="sub")
                await sub.connect()
                await sub.subscribe("bench/+/t", qos=1)
                pubs = []
                for i in range(8):
                    c = Client(port=listener.port, clientid=f"pub{i}")
                    await c.connect()
                    pubs.append(c)
                # wait until the device path engages (compile classes
                # warm in the background; the batcher routes host-side
                # meanwhile) — raises if it never does
                from tests.test_pipeline import _await_device_engaged
                await _await_device_engaged(node, "warm/{}")
                # pin the choice for the asserted batch (the chooser may
                # legitimately bypass tiny batches on this backend)
                node.publish_batcher._device_worth_it = \
                    lambda n, n_subs=1: True
                # concurrent QoS1 publishes land in one batch window
                await asyncio.gather(*[
                    c.publish(f"bench/{i}/t", b"p%d" % i, qos=1)
                    for i, c in enumerate(pubs)])
                got = []
                for _ in range(8):
                    got.append(await asyncio.wait_for(
                        sub.messages.get(), 10))
                for c in pubs:
                    await c.disconnect()
                await sub.disconnect()
                return got

            got = loop.run_until_complete(asyncio.wait_for(go(), 30))
            assert sorted(m.topic for m in got) == \
                sorted(f"bench/{i}/t" for i in range(8))
            assert node.metrics.val("messages.routed.device") >= 8
            assert node.metrics.val("routing.device.batches") >= 1
            loop.run_until_complete(listener.stop())
        finally:
            loop.close()


    def test_shared_picks_ride_the_lanes_over_tcp(self):
        """ISSUE 35 over real sockets and the batcher: concurrent QoS 1
        publishes to a topic two `$share` members and a plain
        subscriber hold are delivered by the lanes as rows (no closure,
        no barrier): each once inside the group, members in turn, the
        plain subscriber all of them, every publisher acknowledged."""
        from emqx_tpu.broker.connection import Listener
        from emqx_tpu.client import Client

        loop = asyncio.new_event_loop()
        try:
            node = Node()
            listener = Listener(node, bind="127.0.0.1", port=0)
            loop.run_until_complete(listener.start())

            async def go():
                members = []
                for i in range(2):
                    c = Client(port=listener.port, clientid=f"member{i}")
                    await c.connect()
                    await c.subscribe("$share/g/bench/+/t", qos=i)
                    members.append(c)
                plain = Client(port=listener.port, clientid="plain")
                await plain.connect()
                await plain.subscribe("bench/#", qos=0)
                pubs = []
                for i in range(8):
                    c = Client(port=listener.port, clientid=f"pub{i}")
                    await c.connect()
                    pubs.append(c)
                from tests.test_pipeline import _await_device_engaged
                await _await_device_engaged(node, "warm/{}")
                node.publish_batcher._device_worth_it = \
                    lambda n, n_subs=1: True
                for rnd in range(3):
                    await asyncio.gather(*[
                        c.publish(f"bench/{i}/t", b"r%dp%d" % (rnd, i),
                                  qos=1)
                        for i, c in enumerate(pubs)])
                got = [[(m.topic, m.payload, m.qos) for m in
                        [await asyncio.wait_for(c.messages.get(), 10)
                         for _ in range(n)]]
                       for c, n in ((members[0], 12), (members[1], 12),
                                    (plain, 24))]
                await asyncio.sleep(0.05)
                stray = sum(c.messages.qsize()
                            for c in members + [plain])
                for c in pubs + members + [plain]:
                    await c.disconnect()
                return got, stray

            (m0, m1, pl), stray = loop.run_until_complete(
                asyncio.wait_for(go(), 60))
            sent = sorted((f"bench/{i}/t", b"r%dp%d" % (rnd, i))
                          for rnd in range(3) for i in range(8))
            assert stray == 0
            assert sorted((t, p) for t, p, _q in m0 + m1) == sent
            assert sorted((t, p) for t, p, _q in pl) == sent
            assert {q for _t, _p, q in m0} == {0}
            assert {q for _t, _p, q in m1} == {1}
            m = node.metrics
            assert m.val("routing.device.shared_lane_rows") >= 16
            assert m.val("pipeline.deliver.slow_msgs") == 0
            assert m.val("pipeline.deliver.barriers") == 0
            assert m.val("routing.device.shared_repick") == 0
            st = node.device_engine.stats()
            assert st["shared_lane_rows"] == m.val(
                "routing.device.shared_lane_rows")
            loop.run_until_complete(listener.stop())
        finally:
            loop.close()


class TestAdaptiveDeviceChoice:
    """SURVEY §7 hard-part 2: the batcher measures device-batch vs
    host-per-message cost and routes each batch to the cheaper path,
    re-probing the device periodically."""

    def _batcher(self):
        from emqx_tpu.broker.batcher import PublishBatcher
        node = Node(use_device=False)
        return PublishBatcher(node, None), node

    def test_optimistic_until_measured(self):
        b, _ = self._batcher()
        assert b._device_worth_it(1)        # no data yet -> try device

    def test_prefers_cheaper_path_and_reprobes(self):
        from emqx_tpu.broker import batcher as BM
        b, node = self._batcher()
        b._dev_batch_s = 0.200              # a slow round trip: 200ms per batch
        b._host_msg_s = 0.0001              # 10k msg/s host
        assert not b._device_worth_it(64)   # 64 * 0.1ms << 200ms
        assert node.metrics.val("routing.device.bypassed") == 1
        assert b._device_worth_it(4000)     # big batch amortizes
        # co-located-like: device far cheaper
        b._dev_batch_s = 0.001
        assert b._device_worth_it(64)
        # forced re-probe after a long host streak
        b._dev_batch_s = 10.0
        b._since_probe = BM._PROBE_EVERY
        assert b._device_worth_it(4)

    def test_cost_sample_never_spans_an_idle_gap(self):
        """Completion-to-completion is the pipelined cost only while
        windows queue behind each other: the first window of a burst
        started AFTER the last completion, so it samples its own
        latency, not the pause before it (which, taken twice, used to
        write the device off at the start of a flood)."""
        b, _ = self._batcher()
        b._observe_device_cost(100.0, 100.02, 1, False)
        assert b._dev_batch_s == pytest.approx(0.02)
        # pipeline busy, window started before the last completion:
        # completion-to-completion / width
        b._observe_device_cost(100.01, 100.10, 4, True)
        assert b._dev_batch_s == pytest.approx(0.02)
        assert b._last_dev_done == 100.10
        # 5 s pause, then a burst: busy, but started after the pause
        for k in range(3):
            t0 = 105.10 + 0.08 * k
            b._observe_device_cost(t0, t0 + 0.08, 4, True)
        assert b._dev_batch_s == pytest.approx(0.02)
        assert b._dev_spike == 0

    def test_a_window_the_process_compiled_under_is_no_cost_sample(self):
        """Two first calls of two classes in a row (hundreds of ms
        each) read as a sustained slowdown and wrote the device off
        for `_PROBE_EVERY` host batches: a window is no sample where
        the telemetry counted a jit-cache miss since the last one.
        It still ends the completion chain it is part of, and a
        pending re-try waits for a clean window."""
        b, node = self._batcher()
        tele = b.tele = node.pipeline_telemetry
        assert tele is not None and tele.compiles == 0
        tele.compiles = 7                   # the warm-up's
        b._observe_device_cost(1.0, 1.4, 1, False)
        assert b._dev_batch_s is None and b._last_dev_done == 1.4
        assert b._device_worth_it(64)       # still optimistic
        b._observe_device_cost(2.0, 2.02, 1, False)
        assert b._dev_batch_s == pytest.approx(0.02)
        for k in (8, 9):                    # two cold classes in a row
            tele.compiles = k
            b._observe_device_cost(3.0 + k, 3.3 + k, 1, False)
        assert b._dev_batch_s == pytest.approx(0.02)
        assert b._dev_spike == 0
        b._dev_reprobe = True
        tele.compiles = 10
        b._observe_device_cost(20.0, 20.5, 1, False)
        assert b._dev_reprobe and b._dev_batch_s == pytest.approx(0.02)
        b._observe_device_cost(21.0, 21.05, 1, False)
        assert not b._dev_reprobe
        assert b._dev_batch_s == pytest.approx(0.05)

    def test_device_reprobe_sample_is_adopted(self):
        """A pessimized device estimate recovers on the scheduled
        re-try's own sample, not at alpha a probe period."""
        from emqx_tpu.broker import batcher as BM
        b, node = self._batcher()
        b._dev_batch_s, b._host_msg_s = 0.400, 0.00017
        assert not b._device_worth_it(1024)         # host regime
        b._since_probe = BM._PROBE_EVERY
        assert b._device_worth_it(1024)             # the re-try
        assert node.metrics.val("routing.chooser.device_probe") == 1
        b._observe_device_cost(10.0, 10.06, 1, False)
        assert b._dev_batch_s == pytest.approx(0.06)
        assert b._device_worth_it(1024)             # back on the device
        # only the re-try's sample: the next one blends in as ever
        b._observe_device_cost(10.06, 10.16, 1, False)
        assert b._dev_batch_s == pytest.approx(0.8 * 0.06 + 0.2 * 0.10)

    def test_a_narrow_loss_does_not_leave_the_chip(self):
        """Two costs within `_LEAVE_MARGIN` of each other do not pay
        for a change of path: the chooser leaves the chip past the
        margin and comes back at parity (`umbrella-cover.flood`: a
        fused sub-batch every 82 ms against 110-150 ms of host route,
        and a run's rate by how often the two estimates crossed)."""
        from emqx_tpu.broker import batcher as BM
        b, node = self._batcher()
        b._host_msg_s = 0.0001
        b._dev_batch_s = 0.1024 * (BM._LEAVE_MARGIN - 0.05)
        assert b._device_worth_it(1024)             # inside the band
        assert b.chooser_margin == pytest.approx(BM._LEAVE_MARGIN - 0.05)
        assert node.metrics.val("routing.device.bypassed") == 0
        b._dev_batch_s = 0.1024 * (BM._LEAVE_MARGIN + 0.05)
        assert not b._device_worth_it(1024)         # past it
        assert b._fuse_cwnd == 1
        b._dev_batch_s = 0.1024 * 1.1
        assert not b._device_worth_it(1024)         # off: back at parity
        b._dev_batch_s = 0.1024 * 0.95
        assert b._device_worth_it(1024)
        b._dev_batch_s = 0.1024 * 1.1
        assert b._device_worth_it(1024)             # on: the band again
        assert node.metrics.val("routing.device.bypassed") == 2

    def test_ewma_pessimizes_fast_optimizes_slow(self):
        """Cost estimates pessimize fast but not on ONE bad sample: a
        first >3x outlier folds in smoothly and arms the streak; a
        SECOND consecutive outlier (sustained slowdown) is adopted
        outright. Improvements are always smooth (one fast sample must
        not hide a generally slow path — the probes re-measure)."""
        from emqx_tpu.broker.batcher import _ewma
        cur = 0.010
        # one spike: discarded, streak armed — baseline must NOT drift or
        # a sustained 3-4x slowdown would never trip the second check
        v1, s1 = _ewma(cur, 30.0)
        assert s1 == 1 and v1 == cur
        # second consecutive outlier: adopted outright
        v2, s2 = _ewma(v1, 30.0, s1)
        assert v2 == 30.0 and s2 == 2
        # a sustained moderate (3.5x) slowdown adopts on its second window
        w1, t1 = _ewma(0.010, 0.035)
        w2, t2 = _ewma(w1, 0.035, t1)
        assert (w2, t2) == (0.035, 2)
        # a normal sample disarms the streak
        _v3, s3 = _ewma(cur, 0.011, 1)
        assert s3 == 0
        # improvement is smooth
        fast, _ = _ewma(cur, 0.001)
        assert 0.005 < fast < cur
        assert _ewma(None, 0.5) == (0.5, 0)


class TestInternBounded:
    """SURVEY §7 hard-part 3 / round-2 VERDICT weak #9: publish-side topic
    words must NOT grow the intern table — only filter vocabulary
    allocates ids (ops/intern.py lookup() vs intern()). An attacker
    publishing unbounded unique topics must leave host memory bounded."""

    def test_publishes_do_not_grow_intern(self):
        node = Node()
        b = node.broker
        sink = Sink()
        sid = b.register(sink, "c1")
        b.subscribe(sid, "known/+/t", {"qos": 0})
        eng = node.device_engine
        # build the snapshot; record the filter-vocabulary size
        eng.route_batch([mkmsg("known/1/t")])
        base = len(eng.intern)
        # a flood of unique published topics (each word never seen in a
        # filter) routes correctly and interns NOTHING
        for k in range(0, 5000, 50):
            msgs = [mkmsg(f"attack/{k+i}/rnd{k+i}") for i in range(50)]
            eng.route_batch(msgs)
        assert len(eng.intern) == base, \
            "publish-side words leaked into the intern table"
        # known topics still match
        counts = eng.route_batch([mkmsg("known/9/t")])
        assert counts == [1]

    def test_unseen_words_lookup_unknown(self):
        from emqx_tpu.ops import intern as I
        t = I.InternTable()
        t.intern("level")
        n = len(t)
        assert t.lookup("never-seen") == I.UNKNOWN
        assert t.lookup("also-never") == I.UNKNOWN
        assert len(t) == n
