"""A plain reference of a session's delivery window, written from the
MQTT specification and upstream's description of it (emqx_session.erl:
inflight window, mqueue, `dequeue/1`), importing nothing of `emqx_tpu`.

Per session: QoS 1 deliveries leave in arrival order with packet ids
1, 2, ... (wrapping at 65,535, an id still unacknowledged skipped); at
most `max_inflight` are unacknowledged at a time; what arrives while
the window is full waits first-in first-out, at most `max_mqueue_len`
of them, the oldest dropped (and counted) when one more arrives; an
acknowledgement of an id in the window frees its place and sends the
oldest waiting delivery, one of an id that is not in the window frees
nothing.
"""

from collections import deque


class WindowRef:
    def __init__(self, max_inflight: int, max_mqueue_len: int):
        self.max_inflight = max_inflight
        self.max_mqueue_len = max_mqueue_len
        self.unacked = {}           # packet id -> payload, in send order
        self.waiting = deque()
        self.next_id = 1
        self.parked = 0             # deliveries that had to wait
        self.released = 0           # of them, sent on an acknowledgement
        self.dropped = []           # payloads a full queue let go of
        self.unknown_acks = 0

    def _send(self, payload) -> tuple:
        while self.next_id in self.unacked:
            self.next_id = self.next_id % 65535 + 1
        pid = self.next_id
        self.next_id = pid % 65535 + 1
        self.unacked[pid] = payload
        return pid, payload

    def arrive(self, payloads) -> list:
        """Deliveries routed to the session; returns the (packet id,
        payload) pairs that leave now, in order."""
        out = []
        for p in payloads:
            if len(self.unacked) < self.max_inflight:
                out.append(self._send(p))
                continue
            self.parked += 1
            if len(self.waiting) >= self.max_mqueue_len:
                self.dropped.append(self.waiting.popleft())
            self.waiting.append(p)
        return out

    def ack(self, pid: int) -> list:
        """The subscriber's PUBACK; returns what it lets out."""
        if self.unacked.pop(pid, None) is None:
            self.unknown_acks += 1
            return []
        out = []
        while self.waiting and len(self.unacked) < self.max_inflight:
            out.append(self._send(self.waiting.popleft()))
        self.released += len(out)
        return out
