"""What runs under `emqx:lane` and under no span, named by counters at
the boundary (ISSUE 40): `pipeline.deliver.lane_us` / `.accept_us` in
the delivery lanes, `pipeline.egress.write_us` / `.writes` at a
connection's one write, and `emqx:control` with
`pipeline.ingress.control_packets` around every packet of a read that is
no PUBLISH burst. Never per message, and no `emqx:` span around a
suspension.
"""

import asyncio
import time

import numpy as np
import pytest

from emqx_tpu.broker.connection import Listener
from emqx_tpu.broker.deliver import DeliveryView, OPT_TABLE
from emqx_tpu.broker.message import make
from emqx_tpu.broker.node import Node
from emqx_tpu.client import Client
from emqx_tpu.mqtt import packet as P
from emqx_tpu.mqtt.frame import serialize


def run(coro, timeout=60):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()


def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


class Slow:
    """A subscriber whose accept takes a known time."""

    def __init__(self, seconds):
        self.seconds, self.got, self.batches = seconds, 0, 0

    def deliver(self, topic_filter, msg):
        _spin(self.seconds)
        self.got += 1
        return True


class SlowBatch(Slow):
    def deliver_batch(self, items):
        _spin(self.seconds)
        self.batches += 1
        self.got += len(items)
        return len(items)


def _plan(node, sinks, rows_each):
    """One plan of `rows_each` rows a sink, one message a row."""
    b = node.broker
    sids = [b.register(s, f"c{i}") for i, s in enumerate(sinks)]
    n = len(sids) * rows_each
    msgs = [make("pub", 0, f"t/{k}", b"x") for k in range(n)]
    plan = node.deliver_lanes.new_plan(msgs)
    plan.routed_device = True
    plan.register_fast(range(n))
    plan.add_rows(np.arange(n), np.repeat(sids, rows_each),
                  np.zeros(n, np.int64), np.zeros(n, np.int64), ["t/#"])
    return plan


def _d(node, before, name):
    return node.metrics.val(name) - before.get(name, 0)


# ------------------------------------------------------------- the lanes

def test_lane_time_holds_the_accepts_and_no_released_stretch():
    """Eight sessions of one row each on one lane, chunked two by two:
    the worker yields three times, and a task that takes 20 ms of the
    loop whenever it gets it runs in each of those stretches. The
    span's histogram sees the whole item; `lane_us` what `emqx:lane`
    covered."""
    node = Node({"broker": {"deliver_lanes": 1}})
    sinks = [Slow(0.002) for _ in range(8)]

    async def go():
        pool = node.deliver_lanes
        pool._chunk = 2
        plan = _plan(node, sinks, 1)
        inside = []         # the hog's stretches between two chunks

        async def hog():
            while not plan.done:
                t = time.perf_counter()
                _spin(0.02)
                if 0 < sum(s.got for s in sinks) < 8:
                    inside.append(time.perf_counter() - t)
                await asyncio.sleep(0)
        before = dict(node.metrics.all())
        t0 = time.perf_counter()
        pool.submit(plan)
        task = asyncio.ensure_future(hog())
        await pool.drain()
        wall = time.perf_counter() - t0
        await task
        return before, wall, inside
    before, wall, inside = run(go())
    assert [s.got for s in sinks] == [1] * 8 and len(inside) == 3
    lane = _d(node, before, "pipeline.deliver.lane_us") / 1e6
    accept = _d(node, before, "pipeline.deliver.accept_us") / 1e6
    assert _d(node, before, "pipeline.deliver.deliveries") == 8
    assert 0.016 <= accept <= lane              # eight accepts of 2 ms
    # one thread: what the lane held and what the hog took are apart
    assert sum(inside) >= 0.06 and lane + sum(inside) <= wall
    # the stage histogram keeps the whole item, released or not
    whole = node.pipeline_telemetry.snapshot()["stages"]["deliver_lane0"]
    assert whole["count"] == 1
    assert whole["sum_ms"] / 1e3 - lane >= 0.95 * sum(inside)


def test_one_pair_of_clock_reads_a_session_run(monkeypatch):
    """Three sessions of 40 rows each: the accept clock is read twice a
    run (six times), not twice a delivery, with or without a coalesced
    drain."""
    from emqx_tpu.broker import deliver as D
    node = Node({"broker": {"deliver_lanes": 2}})
    sinks = [SlowBatch(0.001), SlowBatch(0.001), Slow(0.0)]
    reads = []

    class Clock:
        perf_counter_ns = staticmethod(
            lambda: reads.append(1) or time.perf_counter_ns())
    monkeypatch.setattr(D, "time", Clock)

    async def go():
        pool = node.deliver_lanes
        plan = _plan(node, sinks, 40)
        before = dict(node.metrics.all())
        pool.submit(plan)
        await pool.drain()
        return before
    before = run(go())
    assert [s.got for s in sinks] == [40, 40, 40]
    assert sinks[0].batches == sinks[1].batches == 1
    assert len(reads) == 6
    assert _d(node, before, "pipeline.deliver.deliveries") == 120
    assert _d(node, before, "pipeline.deliver.drains") == 1 + 1 + 40
    accept = _d(node, before, "pipeline.deliver.accept_us")
    lane = _d(node, before, "pipeline.deliver.lane_us")
    assert 2_000 <= accept <= lane


def test_a_lane_that_waits_at_the_barrier_counts_no_lane_time():
    """Two lanes, rows for one of them and a slow closure behind the
    barrier: the lane that runs the closures counts them, the lane that
    waits them out counts nothing for the wait."""
    node = Node({"broker": {"deliver_lanes": 2}})
    sink = SlowBatch(0.0)

    async def go():
        pool = node.deliver_lanes
        plan = _plan(node, [sink], 4)

        def slow():
            _spin(0.03)
            return 1
        plan.msgs.append(make("pub", 0, "slow/1", b"x"))
        plan.counts = np.zeros(len(plan.msgs), np.int64)
        plan.add_slow(4, slow)
        before = dict(node.metrics.all())
        pool.submit(plan)
        await pool.drain()
        return before
    before = run(go())
    lane = _d(node, before, "pipeline.deliver.lane_us")
    # the closure's 30 ms once, not once a lane
    assert 30_000 <= lane < 58_000
    assert _d(node, before, "pipeline.deliver.accept_us") < 5_000


# -------------------------------------------------- a connection's write

class Tap:
    def __init__(self):
        self.writes = []

    def write(self, data):
        _spin(0.001)
        self.writes.append(data)

    def is_closing(self):
        return False


async def _served(node, **kw):
    lst = Listener(node, bind="127.0.0.1", port=0)
    await lst.start()
    c = Client(port=lst.port, clientid="me", **kw)
    await c.connect()
    await c.subscribe("t/#", qos=1)
    ch = next(iter(node.broker._subscribers.values()))
    return lst, c, ch, ch.send.__self__


@pytest.mark.parametrize("qos", [0, 1])
def test_a_coalesced_drain_is_one_write(qos):
    """A session's run of five deliveries goes out in one
    `writer.write`, shared frames or copies; an acknowledgement is a
    write of its own."""
    node = Node({"broker": {"deliver_lanes": 2}})

    async def go():
        lst, c, ch, conn = await _served(node)
        real, conn.writer = conn.writer, Tap()
        before = dict(node.metrics.all())
        views = [DeliveryView(make("pub", qos, f"t/{i}", b"p"),
                              dict(OPT_TABLE[0], qos=qos))
                 for i in range(5)]
        assert ch.deliver_batch([("t/#", v) for v in views]) == 5
        one = dict(node.metrics.all())
        ch._send([P.Pingresp()])
        writes = list(conn.writer.writes)
        conn.writer = real
        await c.close()
        await lst.stop()
        return before, one, writes
    before, one, writes = run(go())
    assert len(writes) == 2 and len(writes[0]) > 5 * 6
    assert one["pipeline.egress.writes"] \
        - before.get("pipeline.egress.writes", 0) == 1
    took = one["pipeline.egress.write_us"] \
        - before.get("pipeline.egress.write_us", 0)
    assert 900 <= took < 10_000
    assert _d(node, before, "pipeline.egress.writes") == 2
    assert _d(node, before, "bytes.sent") == sum(map(len, writes))


# ----------------------------------------------- every other packet: control

def _watch_spans(node):
    """Count `Spans.span` calls by name, and keep the annotations that
    are open, as the profiler would see them."""
    spans = node.spans
    opened, live = [], []
    real_span, real_annotate = spans.span, spans.annotate

    def span(name, *a, **kw):
        opened.append(name)
        return real_span(name, *a, **kw)

    class Ann:
        def __init__(self, label, inner):
            self.label, self.inner = label, inner
            live.append(label)

        def __exit__(self, *exc):
            live.remove(self.label)
            return self.inner.__exit__(*exc)

    def annotate(label, **kw):
        return Ann(label, real_annotate(label, **kw))
    spans.span, spans.annotate = span, annotate
    return opened, live


def test_control_is_entered_once_a_read_and_never_around_a_wait():
    """One read of PINGREQ, SUBSCRIBE, PINGREQ: one `emqx:control`
    span; the SUBSCRIBE's handler really waits, and while it does the
    annotation is closed and another task runs under no span."""
    node = Node({"broker": {"deliver_lanes": 2}})

    async def go():
        lst, c, ch, conn = await _served(node)
        opened, live = _watch_spans(node)
        seen = {"during": None, "inside": []}
        real = ch._handle_subscribe

        async def waits(pkt):
            seen["inside"].append(list(live))       # before the wait
            other = asyncio.ensure_future(note())
            await asyncio.sleep(0.02)
            await other
            seen["inside"].append(list(live))       # after it
            await real(pkt)

        async def note():
            seen["during"] = list(live)
        ch._handle_subscribe = waits
        before = dict(node.metrics.all())
        c._writer.write(
            serialize(P.Pingreq(), 4)
            + serialize(P.Subscribe(packet_id=77, filters=[
                ("u/#", P.SubOpts(qos=0))]), 4)
            + serialize(P.Pingreq(), 4))
        await c._writer.drain()
        for _ in range(200):
            if node.metrics.val("packets.pingreq.received") \
                    - before.get("packets.pingreq.received", 0) == 2:
                break
            await asyncio.sleep(0.01)
        after = dict(node.metrics.all())
        await c.close()
        await lst.stop()
        return opened, seen, before, after
    opened, seen, before, after = run(go())
    assert opened.count("control") == 1
    assert seen["inside"] == [["emqx:control"], ["emqx:control"]]
    assert seen["during"] == []
    d = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    assert d["pipeline.ingress.control_packets"] == 3
    assert d["pipeline.ingress.fallback_frames"] == 3
    assert d["packets.subscribe.received"] == 1


def test_a_publish_burst_in_the_middle_is_not_under_control():
    """PINGREQ, a columnar burst of PUBLISHes, PINGREQ in one read: one
    control span, released around the burst's hand-off (which has its
    own `emqx:ingress`), and the burst's rows are no control packets."""
    node = Node({"broker": {"deliver_lanes": 2}})

    async def go():
        lst, c, ch, conn = await _served(node)
        opened, live = _watch_spans(node)
        under = []
        real = ch.handle_publish_burst

        async def burst(item):
            under.append(list(live))
            await real(item)
        ch.handle_publish_burst = burst
        before = dict(node.metrics.all())
        pubs = b"".join(serialize(P.Publish(
            topic=f"t/{i}", payload=b"x" * 32, qos=0), 4)
            for i in range(200))           # past the parser's 4 KiB
        c._writer.write(serialize(P.Pingreq(), 4) + pubs
                        + serialize(P.Pingreq(), 4))
        await c._writer.drain()
        for _ in range(200):
            if node.metrics.val("packets.pingreq.received") \
                    - before.get("packets.pingreq.received", 0) == 2:
                break
            await asyncio.sleep(0.01)
        after = dict(node.metrics.all())
        await c.close()
        await lst.stop()
        return opened, under, before, after
    opened, under, before, after = run(go())
    d = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    assert d["pipeline.ingress.bursts"] >= 1
    assert d["pipeline.ingress.rows"] + d.get(
        "pipeline.ingress.fallback_frames", 0) - 2 == 200
    assert d["pipeline.ingress.control_packets"] == 2
    # one control span a read that holds a packet which is no burst
    assert 1 <= opened.count("control") <= 2
    assert under and all("emqx:control" not in u for u in under)


def test_span_run_releases_only_while_the_coroutine_waits():
    """`span.run(coro)`: a coroutine that never waits stays under the
    annotation whole and gives its value; one that waits is released
    for the wait; an exception thrown into the wait reaches the
    coroutine and its own comes out."""
    node = Node({"broker": {"deliver_lanes": 0}})
    opened, live = _watch_spans(node)

    async def straight():
        return list(live)

    async def waits(fut):
        try:
            await fut
        except KeyError:
            return "caught", list(live)
        return "done", list(live)

    async def raises():
        await asyncio.sleep(0)
        raise ValueError("mine")

    async def go():
        loop = asyncio.get_running_loop()
        out = {}
        with node.spans.span("control") as sp:
            out["straight"] = await sp.run(straight())
            assert sp.away == 0.0
            fut = loop.create_future()
            loop.call_later(0.02, fut.set_result, 1)
            loop.call_later(0.01, lambda: out.setdefault(
                "meanwhile", list(live)))
            out["waits"] = await sp.run(waits(fut))
            assert sp.away >= 0.015
            fut = loop.create_future()
            loop.call_soon(fut.set_exception, KeyError("thrown"))
            out["thrown"] = await sp.run(waits(fut))
            with pytest.raises(ValueError, match="mine"):
                await sp.run(raises())
            out["after"] = list(live)
        out["left"] = list(live)
        return out, sp
    out, sp = run(go())
    assert out == {"straight": ["emqx:control"], "meanwhile": [],
                   "waits": ("done", ["emqx:control"]),
                   "thrown": ("caught", ["emqx:control"]),
                   "after": ["emqx:control"], "left": []}
    assert sp.dur > sp.away > 0
