"""A session's delivery window against a plain reference (ISSUE 44).

`tests/session_window_ref.py` says what MQTT and upstream's session
promise of a QoS 1 subscriber: arrival order, at most `max_inflight`
unacknowledged, the rest first-in first-out in a queue of
`max_mqueue_len` that drops its oldest, one waiting delivery sent for
every place an acknowledgement frees. Here a connected `Channel` with
no socket under it is driven the way the delivery lanes drive it (the
frame entry first, which declines a QoS 1 run; then `deliver_batch` for
a run and `deliver` for a single row) and acknowledged through
`Channel._handle_puback`, with seeded random run lengths and a seeded
random order of acknowledgements, and what it writes is compared packet
by packet: id, payload, QoS, order. The node's counters for what was
parked, released and dropped are held to the reference's too.
"""

import numpy as np
import pytest

from emqx_tpu.broker.deliver import DeliveryView
from emqx_tpu.broker.message import make
from emqx_tpu.broker.mqueue import MQueueOpts
from emqx_tpu.broker.node import Node
from emqx_tpu.broker.session import Session, SessionConf
from emqx_tpu.mqtt import packet as P
from emqx_tpu.mqtt.frame import FrameParser
from tests.session_window_ref import WindowRef
from tests.test_deliver_lanes import _chan

SUBOPTS = {"qos": 1, "nl": 0, "rap": 0, "rh": 0, "share": "store"}


def taken(ch) -> list:
    """What the channel wrote since this was last asked, as packets."""
    data = b"".join(ch.wire.out)
    ch.wire.out.clear()
    return [(p.packet_id, bytes(p.payload), p.qos, p.dup)
            for p in ch.parser.feed(data)]


def channel(node, window: int, queue: int):
    ch = _chan(node, "worker")
    ch.parser = FrameParser()
    ch.session = Session("worker", SessionConf(
        max_inflight=window, mqueue=MQueueOpts(max_len=queue)))
    # what `_continue_connect` wires on a session it opens
    ch.session.on_dropped = ch._delivery_dropped
    ch.session.metrics = node.metrics
    return ch


def hand_over(ch, views) -> None:
    """One session's run of a plan, as `DeliveryLanePool._deliver_rows`
    hands it over: the frame entry, then the batch or the single row."""
    no_shared_frame = lambda i, j, ver, clientid: None     # noqa: E731
    assert ch.deliver_frames(no_shared_frame, 0, len(views)) is False
    items = [("up/#", v) for v in views]
    if len(items) > 1:
        assert ch.deliver_batch(items) == len(items)
    else:
        assert ch.deliver(*items[0]) is True


def drive(window: int, queue: int, seed: int, rows: int, run_max: int,
          ack_bias: float):
    """`rows` deliveries in runs of 1..run_max with acknowledgements in
    between (each step acknowledges with probability `ack_bias` where
    something is unacknowledged), then every one left. Returns the
    node, the channel and the reference after the last packet."""
    rng = np.random.default_rng(seed)
    node = Node({"broker": {"deliver_lanes": 0}})
    ch = channel(node, window, queue)
    ref = WindowRef(window, queue)
    sent = 0
    acked = []
    while sent < rows or ref.unacked:
        if ref.unacked and (sent >= rows or rng.random() < ack_bias):
            # any unacknowledged id, not only the oldest
            pid = int(rng.choice(list(ref.unacked)))
            ch._handle_puback(P.Puback(packet_id=pid))
            want = ref.ack(pid)
            acked.append(pid)
        else:
            k = int(min(rows - sent, rng.integers(1, run_max + 1)))
            payloads = [b"m%06d" % (sent + i) for i in range(k)]
            hand_over(ch, [DeliveryView(
                make("pub", 1, f"up/d{n % 7}/metric/n2", p), SUBOPTS)
                for n, p in enumerate(payloads, sent)])
            want = ref.arrive(payloads)
            sent += k
        got = taken(ch)
        assert got == [(pid, p, 1, False) for pid, p in want], \
            (sent, len(acked))
        assert len(ch.session.inflight) == len(ref.unacked) <= window
        assert len(ch.session.mqueue) == len(ref.waiting) <= queue
    return node, ch, ref, acked


@pytest.mark.parametrize("window", [1, 4, 32])
@pytest.mark.parametrize("seed", [44, 2**31 + 44, 7])
def test_the_window_equals_the_reference_packet_by_packet(window, seed):
    node, ch, ref, acked = drive(window, 1000, seed, rows=600,
                                 run_max=3 * window, ack_bias=0.55)
    m = node.metrics
    assert not ref.dropped and ref.parked > 0
    assert ch.session.enqueue_count == m.val("delivery.queued") \
        == ref.parked
    assert ch.session.dequeue_count == m.val("delivery.dequeued") \
        == ref.released == ref.parked
    assert m.val("delivery.dropped.queue_full") == 0 \
        == ch.session.mqueue.dropped
    assert m.val("messages.acked") == len(acked) == 600
    assert m.val("messages.qos1.sent") == 600 == ch.session.deliver_count
    assert m.val("packets.puback.missed") == 0
    assert len(ch.session.inflight) == 0 == len(ch.session.mqueue)


@pytest.mark.parametrize("window,queue", [(1, 5), (4, 16), (32, 40)])
def test_a_queue_that_overflows_drops_its_oldest_and_counts_them(window,
                                                                 queue):
    """Runs far longer than window + queue: upstream's drop-oldest. The
    reference says which deliveries are lost; the channel loses the
    same ones, and `mqueue.dropped`, `delivery.dropped.queue_full` and
    the `delivery.dropped` hook's count are one number."""
    node = Node({"broker": {"deliver_lanes": 0}})
    hooked = []
    node.hooks.add("delivery.dropped",
                   lambda ci, msg, reason: hooked.append(
                       (bytes(msg.payload), reason)))
    # `drive` builds its own node: drive this one by hand
    rng = np.random.default_rng(window)
    ch = channel(node, window, queue)
    ref = WindowRef(window, queue)
    sent, delivered = 0, []
    for _round in range(6):
        k = window + queue + int(rng.integers(3, 3 * window + 9))
        payloads = [b"m%06d" % (sent + i) for i in range(k)]
        hand_over(ch, [DeliveryView(make("pub", 1, "up/d1/state/n15", p),
                                    SUBOPTS) for p in payloads])
        want = ref.arrive(payloads)
        sent += k
        while True:
            got = taken(ch)
            assert got == [(pid, p, 1, False) for pid, p in want]
            delivered += [p for _pid, p, _q, _d in got]
            if not ref.unacked:
                break
            pid = int(rng.choice(list(ref.unacked)))
            ch._handle_puback(P.Puback(packet_id=pid))
            want = ref.ack(pid)
    m = node.metrics
    assert len(ref.dropped) > 6 * window
    assert [p for p, _r in hooked] == ref.dropped
    assert {r for _p, r in hooked} == {"queue_full"}
    assert m.val("delivery.dropped.queue_full") == len(ref.dropped) \
        == ch.session.mqueue.dropped == m.val("delivery.dropped")
    assert sorted(delivered + ref.dropped) \
        == [b"m%06d" % i for i in range(sent)]
    assert m.val("delivery.queued") == ref.parked \
        == ref.released + len(ref.dropped)
    assert m.val("delivery.dequeued") == ref.released


def test_an_acknowledgement_of_no_delivery_frees_nothing():
    node = Node({"broker": {"deliver_lanes": 0}})
    ch = channel(node, 2, 10)
    ref = WindowRef(2, 10)
    payloads = [b"a", b"b", b"c", b"d"]
    hand_over(ch, [DeliveryView(make("pub", 1, "up/d0/event/n19", p),
                                SUBOPTS) for p in payloads])
    assert taken(ch) == [(1, b"a", 1, False), (2, b"b", 1, False)] \
        == [(pid, p, 1, False) for pid, p in ref.arrive(payloads)]
    for pid in (9, 2, 2):       # unknown, real, the real one again
        ch._handle_puback(P.Puback(packet_id=pid))
        assert taken(ch) == [(i, p, 1, False) for i, p in ref.ack(pid)]
    assert ref.unknown_acks == 2 \
        == node.metrics.val("packets.puback.missed")
    assert sorted(ref.unacked) == [1, 3] and list(ref.waiting) == [b"d"]
    assert node.metrics.val("delivery.queued") == 2
    assert node.metrics.val("delivery.dequeued") == 1


def test_a_qos0_row_the_queue_does_not_store_is_qos0_msg_not_queue_full():
    """`mqueue_store_qos0` false: a QoS 0 delivery to a client that is
    away is dropped by kind, not for want of room (upstream's
    `delivery.dropped.qos0_msg`), and still one number with
    `mqueue.dropped`."""
    s = Session("away", SessionConf(
        max_inflight=1, mqueue=MQueueOpts(max_len=2, store_qos0=False)))
    seen = []
    s.on_dropped = lambda msg, reason: seen.append(
        (bytes(msg.payload), reason))
    s.enqueue([(make("pub", q, "t", b"%d" % i), {"qos": 1})
               for i, q in enumerate((0, 1, 1, 1, 0))])
    assert seen == [(b"0", "qos0_msg"), (b"1", "queue_full"),
                    (b"4", "qos0_msg")]
    assert s.mqueue.dropped == 3 and s.enqueue_count == 5
    assert [bytes(m.payload) for m in s.mqueue.to_list()] == [b"2", b"3"]
    # an expiry sweep removes, it does not drop
    assert s.mqueue.filter(lambda m: False) == 2
    assert s.mqueue.dropped == 3 and len(s.mqueue) == 0
