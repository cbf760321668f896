#!/usr/bin/env python3
"""One load-generator process: every subscriber, or every publisher.

Started by `benchmark/run.py` as a child that imports neither JAX nor
`emqx_tpu` (stdlib and numpy only), so the generators' CPU use is not the
broker's and the wire is the only thing the two sides share. Commands
arrive as JSON lines on stdin, replies leave as JSON lines on stdout, and
the logs of a run are written as `.npz` files into `--out` when asked.

Payload: 256 bytes (the configuration's `payload_bytes`), of which the
first 14 are publisher id (u16), sequence (u32) and due time in
CLOCK_MONOTONIC ns (u64), little endian: one clock for all processes of
one host.
"""

from __future__ import annotations

import argparse
import json
import os
import selectors
import socket
import struct
import sys
import time
import zlib

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import codec, populations, traffic_gen  # noqa: E402

PAY = struct.Struct("<HIQ")
U16 = struct.Struct(">H")
now_ns = time.monotonic_ns


def reply(**kw) -> None:
    sys.stdout.write(json.dumps(kw) + "\n")
    sys.stdout.flush()


def dial(port: int, clientid: str, sndbuf: int = 0) -> socket.socket:
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    if sndbuf:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, sndbuf)
    s.settimeout(120)
    s.connect(("127.0.0.1", port))
    s.sendall(codec.connect(clientid))
    got = b""
    while len(got) < 4:
        chunk = s.recv(4 - len(got))
        if not chunk:
            raise ConnectionError(f"{clientid}: closed before CONNACK")
        got += chunk
    if got[0] >> 4 != codec.CONNACK or got[3] != 0:
        raise ConnectionError(f"{clientid}: CONNACK {got.hex()}")
    return s


class Stdin:
    """Non-blocking line reader over fd 0, for the selector loops."""

    def __init__(self):
        self.buf = b""
        self.closed = False
        os.set_blocking(0, False)

    def lines(self) -> list:
        try:
            data = os.read(0, 65536)
        except BlockingIOError:
            return []
        if not data:
            self.closed = True
            return []
        self.buf += data
        *whole, self.buf = self.buf.split(b"\n")
        return [json.loads(x) for x in whole if x.strip()]


# --------------------------------------------------------------- subscribers

class Subscribers:
    def __init__(self, port: int, config: dict, out: str):
        self.out = out
        self.pop = populations.load(config)
        self.n = self.pop.conns
        self.port = port
        self.socks: list = []
        self.tail = [b""] * self.n          # bytes of an incomplete packet
        self.segs: list = [[] for _ in range(self.n)]   # (recv ns, packets)
        self.pending = [bytearray() for _ in range(self.n)]   # PUBACKs out
        self.received = 0
        self.last_ns = 0

    def subscribe_all(self) -> dict:
        t0 = time.monotonic()
        entries = 0
        for c in range(self.n):
            s = dial(self.port, f"bench-sub{c}")
            subs = self.pop.subscriptions(c)
            entries += len(subs)
            want = []
            for k in range(0, len(subs), 512):
                part = subs[k:k + 512]
                pid = k // 512 + 1
                s.sendall(codec.subscribe(pid, part))
                want.append((pid, bytes(q for _f, q in part)))
                # SUBACKs are read as they come so neither side's buffer
                # fills; at most a few packets are ever outstanding
                if len(want) >= 4:
                    self._suback(s, c, want.pop(0))
            while want:
                self._suback(s, c, want.pop(0))
            self.socks.append(s)
        for s in self.socks:
            s.setblocking(False)
        return {"subscriptions": entries,
                "seconds": time.monotonic() - t0}

    def _suback(self, s, c: int, want) -> None:
        pid, codes = want
        while True:
            for typ, _fl, a, b in codec.scan(self.tail[c]):
                body = self.tail[c][a:b]
                self.tail[c] = self.tail[c][b:]
                if typ != codec.SUBACK or U16.unpack_from(body)[0] != pid \
                        or body[2:] != codes:
                    raise ConnectionError(
                        f"sub{c}: SUBACK {pid} refused or out of order: "
                        f"type {typ} {body[:8].hex()}")
                return
            chunk = s.recv(65536)
            if not chunk:
                raise ConnectionError(f"sub{c}: closed during SUBSCRIBE")
            self.tail[c] += chunk

    def on_readable(self, c: int) -> None:
        s = self.socks[c]
        try:
            data = s.recv(1 << 20)
        except BlockingIOError:
            return
        ts = now_ns()
        if not data:
            raise ConnectionError(f"sub{c}: broker closed the connection")
        buf = self.tail[c] + data if self.tail[c] else data
        n = len(buf)
        pos = 0
        count = 0
        acks = self.pending[c]
        # the live walk finds packet ends and acknowledges QoS 1; the
        # full parse waits for `dump`, outside the measured window
        while pos + 2 <= n:
            first = buf[pos]
            i = pos + 1
            b = buf[i]
            i += 1
            length = b & 0x7F
            if b & 0x80:
                mult = 128
                while True:
                    if i >= n:
                        length = -1
                        break
                    b = buf[i]
                    i += 1
                    length += (b & 0x7F) * mult
                    if not b & 0x80:
                        break
                    mult *= 128
                if length < 0:
                    break
            if i + length > n:
                break
            if first >> 4 == 3:
                count += 1
                if first & 0x06:
                    p = i + 2 + ((buf[i] << 8) | buf[i + 1])
                    acks += b"\x40\x02" + buf[p:p + 2]
            pos = i + length
        if pos:
            self.segs[c].append((ts, buf[:pos]))
        self.tail[c] = buf[pos:]
        self.received += count
        self.last_ns = ts
        if acks:
            self.flush(c)

    def flush(self, c: int) -> None:
        acks = self.pending[c]
        try:
            sent = self.socks[c].send(acks)
        except BlockingIOError:
            return
        del acks[:sent]

    def dump(self) -> dict:
        """Parse every stored packet and write the receive log."""
        sub, pub, seq, due, recv, qos, dup, crc = ([] for _ in range(8))
        other = 0
        for c in range(self.n):
            for ts, seg in self.segs[c]:
                for typ, flags, a, b in codec.scan(seg):
                    if typ != codec.PUBLISH:
                        other += 1
                        continue
                    topic, q, d, _r, _pid, p = codec.parse_publish(
                        seg, flags, a, b)
                    pb, sq, du = PAY.unpack_from(seg, p)
                    sub.append(c)
                    pub.append(pb)
                    seq.append(sq)
                    due.append(du)
                    recv.append(ts)
                    qos.append(q)
                    dup.append(d)
                    crc.append(zlib.crc32(topic))
        path = os.path.join(self.out, "sub.npz")
        np.savez(path, sub=np.array(sub, np.int16),
                 pub=np.array(pub, np.int32), seq=np.array(seq, np.int64),
                 due_ns=np.array(due, np.int64),
                 recv_ns=np.array(recv, np.int64),
                 qos=np.array(qos, np.int8), dup=np.array(dup, np.bool_),
                 crc=np.array(crc, np.uint32))
        return {"path": path, "deliveries": len(sub), "other_packets": other}

    def serve(self) -> None:
        sel = selectors.DefaultSelector()
        stdin = Stdin()
        sel.register(0, selectors.EVENT_READ, -1)
        for c, s in enumerate(self.socks):
            sel.register(s, selectors.EVENT_READ, c)
        while not stdin.closed:
            for key, _ev in sel.select(0.2):
                if key.data >= 0:
                    self.on_readable(key.data)
                    continue
                for cmd in stdin.lines():
                    if cmd["cmd"] == "count":
                        reply(received=self.received, last_ns=self.last_ns)
                    elif cmd["cmd"] == "dump":
                        reply(**self.dump())
                    elif cmd["cmd"] == "reset":
                        self.segs = [[] for _ in range(self.n)]
                        self.received = 0
                        reply(ok=True)
                    elif cmd["cmd"] == "quit":
                        return
            for c in range(self.n):
                if self.pending[c]:
                    self.flush(c)


# ---------------------------------------------------------------- publishers

class SendLog:
    """Every PUBLISH sent, in growable columns; a row's index is its id."""

    COLS = (("pub", np.int32), ("seq", np.int64), ("key", np.int64),
            ("qos", np.int8), ("due_ns", np.int64), ("send_ns", np.int64),
            ("ack_ns", np.int64))

    def __init__(self, cap: int = 1 << 20):
        self.n = 0
        self.col = {name: np.zeros(cap, dt) for name, dt in self.COLS}

    def reserve(self, k: int) -> int:
        cap = len(self.col["pub"])
        if self.n + k > cap:
            for name in self.col:
                self.col[name] = np.concatenate(
                    [self.col[name], np.zeros(max(cap, k), self.col[name].dtype)])
        base = self.n
        self.n += k
        return base

    def save(self, path: str) -> None:
        np.savez(path, **{k: v[:self.n] for k, v in self.col.items()})


class Publishers:
    CHUNK = 64          # frames built per write in the closed loop

    def __init__(self, port: int, config: dict, traffic: dict, seed: int,
                 out: str):
        self.out = out
        self.seed = seed
        # a configuration may set the generator's parameters of a mix
        # for its own cell: `"traffic": {"<mix>": {...}}` in its file
        traffic = {**traffic, **config.get("traffic", {}).get(
            traffic.get("name"), {})}
        self.traffic = traffic
        self.pop = populations.load(config)
        pub = config["publish"]
        self.key_spec = pub["keys"]
        self.qos1_every = int(pub.get("qos1_every", 0))
        self.pad = bytes(int(pub["payload_bytes"]) - PAY.size)
        self.plen = int(pub["payload_bytes"])
        self.n = int(traffic["connections"])
        self.max_unacked = int(traffic.get("max_unacked_qos1", 256))
        # a flood's start: connection p writes its first PUBLISH
        # `start_after_s[p]` seconds after the run's t0 (all at t0
        # without the key)
        self.start_after = [int(float(x) * 1e9) for x in
                            traffic.get("start_after_s", [0.0] * self.n)]
        if len(self.start_after) != self.n:
            raise ValueError(f"start_after_s names {len(self.start_after)} "
                             f"connections, the mix has {self.n}")
        # a closed loop needs an acknowledgement to close it: QoS 1
        # PUBACKs where the configuration has them; where it is all
        # QoS 0, a PINGREQ after every `fence_every` PUBLISHes, with at
        # most `max_fences` unanswered (else the kernel's socket buffers
        # alone, megabytes a connection, are the loop's depth)
        self.fence_every = 0 if self.qos1_every \
            else int(traffic.get("fence_every", 0))
        self.max_fences = int(traffic.get("max_fences", 4))
        self.since_fence = [0] * self.n
        self.fences = [0] * self.n
        self.socks = [dial(port, f"bench-pub{p}",
                           int(traffic.get("sndbuf", 0)))
                      for p in range(self.n)]
        for s in self.socks:
            s.setblocking(False)
        self.sel = selectors.DefaultSelector()
        for p, s in enumerate(self.socks):
            self.sel.register(s, selectors.EVENT_READ, p)
        self.stdin = Stdin()
        self.sel.register(0, selectors.EVENT_READ, -1)
        self.cmds: list = []
        self.log = SendLog()
        self.seq = [0] * self.n
        self.pid = [0] * self.n
        self.unacked = [0] * self.n
        self.slot = [dict() for _ in range(self.n)]     # pid -> log row
        self.rtail = [b""] * self.n
        self.out_buf = [bytearray() for _ in range(self.n)]
        self.rng = traffic_gen.rng_for(seed, 1)
        self.expected = 0            # deliveries the oracle expects so far
        self.acked = 0
        self.qos1_sent = 0

    # -- shared pieces --------------------------------------------------
    def keys(self, n: int) -> np.ndarray:
        return traffic_gen.draw_keys(self.rng, n, self.pop.dims,
                                     self.key_spec)

    def note_expected(self, keys: np.ndarray) -> None:
        self.expected += populations.expected_count(self.pop, keys)

    def frames(self, p: int, keys, due_ns) -> tuple:
        """Serialise one publisher's next PUBLISHes and log them; returns
        (bytes, first log row). `due_ns`: the one stamp they all carry."""
        k = len(keys)
        log, col = self.log, self.log.col
        base = log.reserve(k)
        seq0 = self.seq[p]
        self.seq[p] += k
        every = self.qos1_every
        topic, head = self.pop.topic, codec.publish_head
        pad, plen, pack = self.pad, self.plen, PAY.pack
        parts = []
        for j in range(k):
            seq = seq0 + j
            qos = 1 if every and seq % every == 0 else 0
            parts.append(head(topic(keys[j]), qos, plen))
            if qos:
                pid = self.pid[p] = self.pid[p] % 65535 + 1
                self.slot[p][pid] = base + j
                self.unacked[p] += 1
                self.qos1_sent += 1
                parts.append(U16.pack(pid))
                col["qos"][base + j] = 1
            parts.append(pack(p, seq, due_ns))
            parts.append(pad)
        col["pub"][base:base + k] = p
        col["seq"][base:base + k] = np.arange(seq0, seq0 + k)
        col["key"][base:base + k] = keys
        col["due_ns"][base:base + k] = due_ns
        self.note_expected(np.asarray(keys))
        return b"".join(parts), base

    def on_readable(self, p: int) -> None:
        """PUBACKs: stamp each acknowledged row."""
        try:
            data = self.socks[p].recv(65536)
        except BlockingIOError:
            return
        ts = now_ns()
        if not data:
            raise ConnectionError(f"pub{p}: broker closed the connection")
        buf = self.rtail[p] + data if self.rtail[p] else data
        end = 0
        ack = self.log.col["ack_ns"]
        for typ, _fl, a, b in codec.scan(buf):
            end = b
            if typ == codec.PINGRESP:
                self.fences[p] -= 1
                continue
            if typ != codec.PUBACK:
                raise ConnectionError(f"pub{p}: unexpected packet {typ}")
            row = self.slot[p].pop(U16.unpack_from(buf, a)[0], None)
            if row is not None:
                ack[row] = ts
                self.unacked[p] -= 1
                self.acked += 1
        self.rtail[p] = buf[end:]

    def poll(self, timeout: float) -> None:
        for key, _ev in self.sel.select(timeout):
            if key.data >= 0:
                self.on_readable(key.data)
            else:
                self.cmds += self.stdin.lines()

    def push(self, p: int) -> bool:
        """Write what is pending on p; True when nothing is left."""
        buf = self.out_buf[p]
        if buf:
            try:
                sent = self.socks[p].send(buf)
            except BlockingIOError:
                return False
            del buf[:sent]
        return not buf

    # -- closed loop ----------------------------------------------------
    def flood(self, conns: int, until_ns: int = 0, messages: int = 0,
              t0_ns: int = 0) -> None:
        """`conns` publishers write as fast as TCP backpressure and the
        QoS 1 in-flight bound let them, until a deadline or a count;
        from `t0_ns`, each from its own `start_after_s`."""
        left = messages
        starts = [t0_ns + a for a in self.start_after] if t0_ns else []
        late = any(self.start_after) and bool(starts)
        per_chunk = self.CHUNK
        q1 = -(-per_chunk // self.qos1_every) if self.qos1_every else 0
        send_col = self.log.col["send_ns"]
        while True:
            if until_ns and now_ns() >= until_ns:
                break
            if messages and left <= 0:
                break
            wrote = False
            if late:
                now = now_ns()
                late = now < max(starts)
            for p in range(conns):
                if late and now < starts[p]:
                    continue
                if not self.push(p):
                    continue
                if self.unacked[p] + q1 > self.max_unacked \
                        or self.fences[p] >= self.max_fences > 0 \
                        and self.fence_every:
                    continue
                k = min(per_chunk, left) if messages else per_chunk
                if k <= 0:
                    break
                ts = now_ns()
                data, base = self.frames(p, self.keys(k), ts)
                send_col = self.log.col["send_ns"]
                send_col[base:base + k] = ts
                self.out_buf[p] += data
                if self.fence_every:
                    self.since_fence[p] += k
                    if self.since_fence[p] >= self.fence_every:
                        self.since_fence[p] = 0
                        self.fences[p] += 1
                        self.out_buf[p] += codec.PINGREQ_FRAME
                self.push(p)
                left -= k
                wrote = True
            # blocked on every socket or on PUBACKs: wait for either
            self.poll(0 if wrote else 0.001)
        deadline = time.monotonic() + 60
        while not all(self.push(p) for p in range(conns)):
            if time.monotonic() > deadline:
                raise TimeoutError("flood: broker stopped reading")
            self.poll(0.001)

    # -- commands -------------------------------------------------------
    def status(self) -> dict:
        return {"sent": self.log.n, "expected": self.expected,
                "qos1_sent": self.qos1_sent, "acked": self.acked}

    def run_cmd(self, cmd: dict) -> None:
        kind = cmd["cmd"]
        if kind == "burst":
            self.flood(min(self.n, 8), messages=int(cmd["messages"]))
            reply(**self.status())
        elif kind == "run":
            t0 = int(cmd["t0_ns"])
            while now_ns() < t0:
                self.poll(min(0.001, max(0.0, (t0 - now_ns()) / 1e9)))
            if self.traffic["loop"] != "closed":
                raise ValueError(f"unknown loop {self.traffic['loop']!r}")
            self.flood(self.n, until_ns=t0 + int(cmd["seconds"] * 1e9),
                       t0_ns=t0)
            reply(**self.status())
        elif kind == "status":
            reply(**self.status())
        elif kind == "reset":
            self.log = SendLog()
            self.seq = [0] * self.n
            self.unacked = [0] * self.n
            self.slot = [dict() for _ in range(self.n)]
            self.expected = self.acked = self.qos1_sent = 0
            reply(ok=True)
        elif kind == "dump":
            path = os.path.join(self.out, "pub.npz")
            self.log.save(path)
            reply(path=path, **self.status())
        else:
            raise ValueError(f"unknown command {kind!r}")

    def serve(self) -> None:
        while not self.stdin.closed:
            self.poll(0.2)
            while self.cmds:
                cmd = self.cmds.pop(0)
                if cmd["cmd"] == "quit":
                    return
                self.run_cmd(cmd)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("role", choices=("sub", "pub"))
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        traffic = json.load(f)
    if args.role == "sub":
        subs = Subscribers(args.port, config, args.out)
        reply(ev="subscribed", **subs.subscribe_all())
        subs.serve()
    else:
        pubs = Publishers(args.port, config, traffic, args.seed, args.out)
        reply(ev="connected", connections=pubs.n)
        pubs.serve()
    return 0


if __name__ == "__main__":
    sys.exit(main())
