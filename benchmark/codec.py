"""The benchmark's own MQTT 3.1.1 codec: just what the generators speak.

CONNECT, SUBSCRIBE, UNSUBSCRIBE, PUBLISH (QoS 0/1), PUBACK, PINGREQ out;
CONNACK, SUBACK, UNSUBACK, PUBLISH, PUBACK, PINGRESP in. Written from
the MQTT 3.1.1 specification (OASIS, sections 2.2 and 3.1-3.12) and
importing nothing of the program, so a change to `emqx_tpu/mqtt/frame.py`
or `emqx_tpu/client.py` cannot move the yardstick, and a codec fault
cannot hide on both sides of the wire.
"""

from __future__ import annotations

import struct

CONNECT, CONNACK, PUBLISH, PUBACK = 1, 2, 3, 4
SUBSCRIBE, SUBACK, UNSUBSCRIBE, UNSUBACK = 8, 9, 10, 11
PINGREQ, PINGRESP, DISCONNECT = 12, 13, 14

PINGREQ_FRAME = b"\xc0\x00"
DISCONNECT_FRAME = b"\xe0\x00"


def varint(n: int) -> bytes:
    """Remaining-length encoding (spec 2.2.3): 7 bits a byte, low first."""
    if not 0 <= n <= 268_435_455:
        raise ValueError(f"remaining length {n} out of range")
    out = bytearray()
    while True:
        n, low = divmod(n, 128)
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _str(s: str | bytes) -> bytes:
    b = s.encode("utf-8") if isinstance(s, str) else s
    return struct.pack(">H", len(b)) + b


def _frame(first: int, body: bytes) -> bytes:
    return bytes([first]) + varint(len(body)) + body


def connect(clientid: str, keepalive: int = 0) -> bytes:
    """Protocol level 4, clean session, no will, no credentials."""
    return _frame(0x10, _str("MQTT") + bytes([4, 0x02])
                  + struct.pack(">H", keepalive) + _str(clientid))


def subscribe(pid: int, entries) -> bytes:
    """entries: [(filter, qos)]."""
    body = struct.pack(">H", pid) + b"".join(
        _str(f) + bytes([q]) for f, q in entries)
    return _frame(0x82, body)


def unsubscribe(pid: int, filters) -> bytes:
    return _frame(0xA2, struct.pack(">H", pid)
                  + b"".join(_str(f) for f in filters))


def publish_head(topic: str, qos: int, payload_len: int) -> bytes:
    """Everything of a PUBLISH before the packet id: first byte,
    remaining length and topic. A QoS 1 frame is head + id + payload, a
    QoS 0 frame head + payload, so heads are built once per topic."""
    t = _str(topic)
    return bytes([0x30 | (qos << 1)]) \
        + varint(len(t) + (2 if qos else 0) + payload_len) + t


def publish(topic: str, payload: bytes, qos: int = 0, pid: int = 0) -> bytes:
    head = publish_head(topic, qos, len(payload))
    return head + (struct.pack(">H", pid) if qos else b"") + payload


def puback(pid: int) -> bytes:
    return b"\x40\x02" + struct.pack(">H", pid)


def scan(buf, pos: int = 0):
    """Walk the whole packets of `buf` from `pos`.

    Yields (type, flags, body_start, body_end) per complete packet and
    stops at the first incomplete one; the caller keeps the tail from
    the last `body_end` it saw."""
    n = len(buf)
    while pos + 2 <= n:
        first = buf[pos]
        mult, length, i = 1, 0, pos + 1
        while True:
            if i >= n:
                return
            b = buf[i]
            i += 1
            length += (b & 0x7F) * mult
            if not b & 0x80:
                break
            mult *= 128
            if mult > 128 ** 3:
                raise ValueError("malformed remaining length")
        if i + length > n:
            return
        yield first >> 4, first & 0x0F, i, i + length
        pos = i + length


def parse_publish(buf, flags: int, start: int, end: int):
    """(topic bytes, qos, dup, retain, packet id or 0, payload start)."""
    qos = (flags >> 1) & 3
    tlen = (buf[start] << 8) | buf[start + 1]
    t0 = start + 2
    p = t0 + tlen
    pid = 0
    if qos:
        pid = (buf[p] << 8) | buf[p + 1]
        p += 2
    if p > end:
        raise ValueError("malformed PUBLISH")
    return bytes(buf[t0:t0 + tlen]), qos, bool(flags & 8), bool(flags & 1), \
        pid, p
