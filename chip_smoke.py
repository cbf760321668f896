#!/usr/bin/env python3
"""chip_smoke.py — prove the broker serves PUBLISH traffic from the TPU.

One process: a default-configured `Node`, a `Listener` on 127.0.0.1:0 and
the in-repo MQTT clients on one asyncio loop. It installs the reference
harness's subscription population (`emqx_broker_bench.erl:25-34`,
`device/d{i}/+/n{n}/#`; half of it in 2-member `$share` groups split
across connections, BASELINE config 4's shape) through SUBSCRIBE packets,
streams PUBLISHes over real TCP with a mid-stream subscription churn,
then drives the device engine directly at every standard batch class, and
checks every delivery against a closed-form oracle that is cross-checked
with `utils.topic.match` and the host router. Afterwards it reads the
node's own counters and fails if any safety net served instead of the
chip. Nothing here is a speed.

    python3 chip_smoke.py            # one chip; exits 0 only on a TPU
    python3 chip_smoke.py --mesh     # four chips: route=4, then dp=2 x route=2

The last line of stdout is `{"ok": true, "device": {...}}` on success.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import struct
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# BASELINE config 3/4 scale; what the default invocation installs (about
# 370 s cold on a v5e-1 of the 1200 s limit, so the time limit cuts nothing)
FULL_SUBS = 1_000_000
MIN_SUBS = 100_000
# --mesh drives two layouts in one invocation; this many filters each
# keeps the pair inside the same 1200 s
MESH_SUBS = 500_000
MSGS = 65_536                # PUBLISHes of the measured socket stream
SUB_CONNS = 16
PUB_CONNS = 8
CHURN = 64                   # filters added and dropped mid-stream
SHARED_PCT = 50
_PAY = struct.Struct("<BHI")  # phase, publisher id, sequence number

_T0 = time.monotonic()


def say(*a) -> None:
    print(f"[{time.monotonic() - _T0:7.1f}s]", *a, flush=True)


class Population:
    """The subscription population and its closed-form delivery oracle.

    Filter (i, n) is `device/d{i}/+/n{n}/#`, owned by subscriber
    connection i % conns. A shared filter is a 2-member `$share/bg` group
    whose second member is the next connection, so round robin
    alternates sockets. A topic `device/d{i}/x/n{n}/t` matches filter
    (i, n) and nothing else."""

    def __init__(self, n_filters: int, conns: int):
        self.ids = max(8, int(np.sqrt(n_filters)))
        self.nums = max(1, n_filters // self.ids)
        self.conns = conns
        self.dropped: set = set()      # (i, n) unsubscribed by the churn

    @property
    def n_filters(self) -> int:
        return self.ids * self.nums

    def is_shared(self, i: int, n: int) -> bool:
        return (i * self.nums + n) % 100 < SHARED_PCT

    @staticmethod
    def filt(i: int, n: int) -> str:
        return f"device/d{i}/+/n{n}/#"

    def subscriptions(self, c: int) -> list:
        """SUBSCRIBE entries of connection c: its own filters plus the
        second membership of the previous connection's shared groups."""
        from emqx_tpu.mqtt import packet as P
        o0, o1 = P.SubOpts(qos=0), P.SubOpts(qos=1)
        out = []
        for cc, second in ((c, False), ((c - 1) % self.conns, True)):
            for i in range(cc, self.ids, self.conns):
                for n in range(self.nums):
                    if self.is_shared(i, n):
                        out.append((f"$share/bg/{self.filt(i, n)}", o1))
                    elif not second:
                        out.append((self.filt(i, n), o0))
        return out

    def churn_drop_set(self) -> list:
        """The first CHURN plain filters of subscriber 0, hottest id
        first (Zipf puts most traffic on d0)."""
        out = []
        for i in range(0, self.ids, self.conns):
            for n in range(self.nums):
                if not self.is_shared(i, n):
                    out.append((i, n))
                    if len(out) == CHURN:
                        return out
        return out

    # a message's key is ("dev", i, n) or ("churn", j)
    @staticmethod
    def topic(key) -> str:
        if key[0] == "dev":
            return f"device/d{key[1]}/x/n{key[2]}/t"
        return f"churn/k{key[1]}/x/t"

    def matched_filters(self, key, churned: bool) -> list:
        if key[0] == "churn":
            return [f"churn/k{key[1]}/+/#"] if churned else []
        if churned and (key[1], key[2]) in self.dropped:
            return []
        return [self.filt(key[1], key[2])]

    def expect(self, key, churned: bool):
        """("none",) | ("one", sub) | ("either", sub_a, sub_b)."""
        if key[0] == "churn":
            return ("one", 0) if churned else ("none",)
        _, i, n = key
        c = i % self.conns
        if self.is_shared(i, n):
            return ("either", c, (c + 1) % self.conns)
        if churned and (i, n) in self.dropped:
            return ("none",)
        return ("one", c)


class Traffic:
    """Seeded message generator: device ids Zipf(1.3) as bench.py's
    device_topic_batch draws them, every 16th message aimed at the churn
    add set and every 16th at the churn drop set, so both stay in the
    stream before and after the churn."""

    def __init__(self, pop: Population, seed: int):
        self.pop = pop
        self.rng = np.random.RandomState(seed)
        self.drop = pop.churn_drop_set()

    def keys(self, n: int) -> list:
        pop, rng = self.pop, self.rng
        zipf = np.minimum(rng.zipf(1.3, size=n) - 1, pop.ids - 1)
        nums = rng.randint(0, pop.nums, n)
        pick = rng.randint(0, 1 << 30, n)
        out = []
        for k in range(n):
            if k % 16 == 5:
                out.append(("churn", int(pick[k] % CHURN)))
            elif k % 16 == 13 and self.drop:
                i, m = self.drop[int(pick[k] % len(self.drop))]
                out.append(("dev", i, m))
            else:
                out.append(("dev", int(zipf[k]), int(nums[k])))
        return out


class Ledger:
    """What was sent, what arrived, and the comparison."""

    def __init__(self, pop: Population, n_subs: int):
        self.pop = pop
        self.sent: dict = {}           # (pub, seq) -> (key, churned)
        self.got: dict = {}            # (pub, seq) -> [sub, ...]
        self.order: list = [dict() for _ in range(n_subs)]
        self.order_breaks = 0
        self.received = 0
        self.expected = 0
        self.checked = 0
        self.n_wrong = 0
        self.wrong: list = []          # the first few, for the report

    def note_sent(self, pub: int, seq: int, key, churned: bool) -> None:
        self.sent[(pub, seq)] = (key, churned)
        if self.pop.expect(key, churned)[0] != "none":
            self.expected += 1

    def note_received(self, sub: int, pkt) -> None:
        _phase, pub, seq = _PAY.unpack(pkt.payload)
        self.received += 1
        self.got.setdefault((pub, seq), []).append(sub)
        # per (publisher, topic, delivered qos) the sequence numbers only
        # grow — MQTT's ordered-topic guarantee. Across topics the engine
        # delivers a batch's clean rows before its dirty / rich rows,
        # and a QoS 0 message may pass a QoS 1 message parked behind a
        # full inflight window, so neither is asserted.
        last = self.order[sub]
        k = (pub, pkt.topic, pkt.qos)
        if last.get(k, -1) >= seq:
            self.order_breaks += 1
        last[k] = seq

    def verify(self) -> None:
        """Compare every not-yet-checked message with the oracle."""
        for ms, (key, churned) in self.sent.items():
            want = self.pop.expect(key, churned)
            have = self.got.get(ms, [])
            ok = (have == [] if want[0] == "none" else
                  have == [want[1]] if want[0] == "one" else
                  len(have) == 1 and have[0] in want[1:])
            self.checked += 1
            if not ok:
                self.n_wrong += 1
                if len(self.wrong) < 8:
                    self.wrong.append(
                        f"{self.pop.topic(key)} churned={churned} "
                        f"want={want} got={have}")
        self.sent = {}
        self.got = {}


def oracle_cross_check(node, pop: Population, keys, churned: bool) -> list:
    """The closed form against `utils.topic.match` and the host router
    (`broker.router.match`), over every distinct topic of a phase."""
    from emqx_tpu.utils import topic as T
    bad = []
    for key in set(keys):
        topic = pop.topic(key)
        want = pop.matched_filters(key, churned)
        if sorted(node.router.match(topic)) != sorted(want):
            bad.append(f"router.match({topic}) = "
                       f"{node.router.match(topic)} != {want}")
        if not all(T.match(topic, f) for f in want):
            bad.append(f"topic.match({topic}, {want}) is False")
        if key[0] == "dev" and T.match(
                topic, pop.filt(key[1], key[2] + 1)):
            bad.append(f"topic.match({topic}) hit a neighbour filter")
    return bad[:8]


def _metric_snapshot(node) -> dict:
    return dict(node.metrics.all())


def _compiles(node) -> dict:
    """{label: (trace events, executables, seconds)} per compile label
    of the node's telemetry."""
    by = node.pipeline_telemetry.snapshot()["compiles"]["by_shape"]
    return {k: (v["count"], v["executables"], v["total_s"])
            for k, v in by.items()}


def _in_path_compiles(before: dict, after: dict) -> dict:
    """What jit did inside a dispatch (every label the background warm
    passes did not issue): a trace event is a jit-cache miss on the
    serving path even when it builds nothing, an executable is a
    compile."""
    out = {}
    for k, (n, x, sec) in after.items():
        n0, x0, sec0 = before.get(k, (0, 0, 0.0))
        if not k.startswith("warm") and (n > n0 or x > x0):
            out[k] = {"trace_events": n - n0, "executables": x - x0,
                      "seconds": round(sec - sec0, 4)}
    return out


async def _wait_until(pred, timeout: float, step: float = 0.05) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        await asyncio.sleep(step)
    return pred()


def _engine_idle(eng) -> bool:
    """No background class warm or rebuild in flight."""
    if getattr(eng, "_fuse_warm_task", None) is not None:
        return False
    if getattr(eng, "_building", False):
        return False
    wt = getattr(eng, "_warm_thread", None)
    if wt is not None and wt.is_alive():
        return False
    rt = getattr(eng, "_rebuild_thread", None)
    return rt is None or not rt.is_alive()


async def drive(node, *, subs: int, msgs: int, seed: int,
                direct_batches: int = 8, warm_timeout_s: float = 900.0,
                require_exchange: bool = False) -> dict:
    """Drive `node` end to end and check what came out.

    Returns a report whose `failures` list is empty iff every delivery
    set, PUBACK, per-publisher order and "nothing hidden" counter held.
    Platform-independent: the TPU gate lives in `main()`, so tier-1 runs
    this on the CPU backend at a tiny size."""
    from emqx_tpu.broker.connection import Listener
    from emqx_tpu.broker.message import make
    from emqx_tpu.client import Client
    from emqx_tpu.mqtt import packet as P

    failures: list = []
    report: dict = {"failures": failures, "setup_s": {}}

    def check(name: str, ok: bool, detail="") -> None:
        if not ok:
            failures.append(f"{name}: {detail}" if detail != "" else name)
            say(f"CHECK FAILED {name}: {detail}")

    eng = node.device_engine
    pop = Population(subs, SUB_CONNS)
    if not pop.churn_drop_set():
        raise ValueError(f"subs={subs}: subscriber 0 owns no plain filter "
                         f"to drop in the churn; pick another size")
    traffic = Traffic(pop, seed)
    ledger = Ledger(pop, SUB_CONNS)
    report["filters"] = pop.n_filters

    lst = Listener(node, bind="127.0.0.1", port=0)
    await lst.start()
    sub_cl: list = []
    pub_cl: list = []
    drainers: list = []
    seqs = [0] * (PUB_CONNS + 1)     # per-publisher sequence; last = direct
    acks_wanted = 0
    acks_got = 0

    async def drain(c: int, cl) -> None:
        while True:
            ledger.note_received(c, await cl.messages.get())

    async def settle(what: str, timeout: float = 120.0) -> None:
        ok = await _wait_until(
            lambda: ledger.received >= ledger.expected, timeout)
        await asyncio.sleep(0.2)     # a duplicate would arrive late
        check(f"{what}: delivered == expected",
              ok and ledger.received == ledger.expected,
              f"delivered {ledger.received} expected {ledger.expected}")
        ledger.verify()

    async def flood(p: int, keys: list, phase: int, churned: bool) -> None:
        """One publisher's burst stream: QoS 0 pipelined with transport
        backpressure, every 4th message QoS 1 with pipelined PUBACKs."""
        nonlocal acks_wanted, acks_got
        cl = pub_cl[p]
        pending: list = []

        async def reap() -> None:
            nonlocal acks_got
            done = await asyncio.wait_for(
                asyncio.gather(*pending, return_exceptions=True), 120)
            acks_got += sum(1 for d in done
                            if not isinstance(d, BaseException))
            pending.clear()

        for k, key in enumerate(keys):
            qos = 1 if k % 4 == 0 else 0
            seq = seqs[p]
            seqs[p] += 1
            ledger.note_sent(p, seq, key, churned)
            fut = cl.publish_start(pop.topic(key),
                                   _PAY.pack(phase, p, seq), qos=qos)
            if fut is not None:
                acks_wanted += 1
                pending.append(fut)
            if len(pending) >= 256:
                await reap()
            if cl.needs_drain:
                await cl.drain()
            if k % 64 == 63:
                await asyncio.sleep(0)
        if pending:
            await reap()

    async def stream(n: int, phase: int, churned: bool) -> list:
        per = max(1, n // PUB_CONNS)
        all_keys = [traffic.keys(per) for _ in range(PUB_CONNS)]
        await asyncio.gather(*[flood(p, all_keys[p], phase, churned)
                               for p in range(PUB_CONNS)])
        return [k for ks in all_keys for k in ks]

    try:
        # ---- set-up: subscribe over the wire -------------------------
        t0 = time.monotonic()
        n_entries = 0
        for c in range(SUB_CONNS):
            cl = Client(port=lst.port, clientid=f"smoke-sub{c}")
            await cl.connect()
            entries = pop.subscriptions(c)
            n_entries += len(entries)
            for k in range(0, len(entries), 512):
                await cl.subscribe(entries[k:k + 512], timeout=120)
            sub_cl.append(cl)
            drainers.append(asyncio.ensure_future(drain(c, cl)))
        for p in range(PUB_CONNS):
            cl = Client(port=lst.port, clientid=f"smoke-pub{p}")
            await cl.connect()
            pub_cl.append(cl)
        report["setup_s"]["subscribe"] = round(time.monotonic() - t0, 1)
        report["subscriptions"] = n_entries
        say(f"subscribed: {pop.n_filters} filters, {n_entries} "
            f"subscriptions over {SUB_CONNS} connections in "
            f"{report['setup_s']['subscribe']}s")

        # ---- set-up: snapshot build + standard-class warm-up ---------
        # bursts over the wire reach the batcher, whose rebuild policy
        # captures, builds, uploads and warm-compiles in the background;
        # the host routes meanwhile (and those deliveries are checked)
        t0 = time.monotonic()
        m_start = _metric_snapshot(node)
        std = tuple(eng._STD_CLASSES)
        top = max(bp for _w, bp in std)

        def std_warm() -> bool:
            return eng.batch_class_warm(top) and _engine_idle(eng) and \
                (max(w for w, _b in std) == 1 or eng.max_fuse() > 1)

        keys_seen: list = []
        while not std_warm():
            if time.monotonic() - t0 > warm_timeout_s:
                break
            keys_seen += await stream(PUB_CONNS * 8, 0, False)
            await asyncio.sleep(0.5)
        check("standard classes warm", std_warm(),
              f"not warm after {warm_timeout_s}s")
        report["setup_s"]["build_and_warm"] = \
            round(time.monotonic() - t0, 1)
        say(f"snapshot built and standard classes warm in "
            f"{report['setup_s']['build_and_warm']}s")

        # demand warm-up: a slice of the real stream registers the
        # cached / compact classes it wants; wait out their compiles
        for _round in range(3):
            keys_seen += await stream(max(PUB_CONNS * 64, msgs // 16),
                                      0, False)
            await settle("warm-up")
            await _wait_until(lambda: _engine_idle(eng), warm_timeout_s)
        report["setup_s"]["demand_warm"] = round(
            time.monotonic() - t0 - report["setup_s"]["build_and_warm"], 1)
        check("oracle cross-check (warm-up)",
              not (bad := oracle_cross_check(node, pop, keys_seen, False)),
              bad)

        # ---- socket leg, first half ----------------------------------
        m0 = _metric_snapshot(node)
        c0 = _compiles(node)
        t_stream = time.monotonic()
        keys_a = await stream(msgs // 2, 1, False)
        await settle("stream A")
        check("oracle cross-check (A)",
              not (bad := oracle_cross_check(node, pop, keys_a, False)),
              bad)
        c1 = _compiles(node)

        # ---- churn: one subscriber adds 64 filters and drops 64 ------
        drop = pop.churn_drop_set()
        await sub_cl[0].subscribe(
            [(f"churn/k{j}/+/#", P.SubOpts(qos=0)) for j in range(CHURN)],
            timeout=60)
        await sub_cl[0].unsubscribe([pop.filt(i, n) for i, n in drop],
                                    timeout=60)
        pop.dropped = set(drop)
        # prime: let the delta-overlay classes this traffic wants compile
        # off the serving path before the second half is measured
        for _round in range(2):
            await stream(max(PUB_CONNS * 64, msgs // 16), 2, True)
            await settle("churn prime")
            await _wait_until(lambda: _engine_idle(eng), warm_timeout_s)

        # ---- socket leg, second half ---------------------------------
        c2 = _compiles(node)
        keys_b = await stream(msgs - msgs // 2, 3, True)
        await settle("stream B")
        check("oracle cross-check (B)",
              not (bad := oracle_cross_check(node, pop, keys_b, True)),
              bad)
        c3 = _compiles(node)
        m1 = _metric_snapshot(node)
        report["stream_s"] = round(time.monotonic() - t_stream, 1)

        def delta(name: str, a=m0, b=m1) -> int:
            return b.get(name, 0) - a.get(name, 0)

        in_path = {**_in_path_compiles(c0, c1), **_in_path_compiles(c2, c3)}
        bg = sum(c1[k][1] - c0.get(k, (0, 0))[1] for k in c1
                 if k.startswith("warm")) \
            + sum(c3[k][1] - c2.get(k, (0, 0))[1] for k in c3
                  if k.startswith("warm"))
        report["stream"] = {
            "sent": len(keys_a) + len(keys_b),
            "device_batches": delta("routing.device.batches"),
            "device_batches_with_warm_up":
                delta("routing.device.batches", m_start),
            "device_routed_msgs": delta("messages.routed.device"),
            "in_path_compiles": in_path,
            "background_warm_compiles": bg,
        }
        # over the whole socket leg, warm-up included: once it has both
        # costs the chooser may rightly keep a measured stream on the
        # host, but its first windows and its re-probes are the device's
        check("socket leg reached the device",
              delta("routing.device.batches", m_start) >= 1,
              "routing.device.batches did not move")
        check("no jit-cache miss or compile inside the measured stream",
              not in_path, in_path)
        check("every QoS 1 PUBACK arrived", acks_got == acks_wanted,
              f"{acks_got} of {acks_wanted}")

        # ---- direct leg: every standard class, whatever the chooser did
        m2 = _metric_snapshot(node)
        direct_pub = PUB_CONNS       # its own publisher id
        rr_last: dict = {}           # group -> last recipient, in order
        rr_breaks = 0
        count_wrong = 0
        direct_keys: list = []

        def direct_msgs(keys: list) -> list:
            out = []
            for k, key in enumerate(keys):
                seq = seqs[direct_pub]
                seqs[direct_pub] += 1
                qos = 1 if k % 4 == 0 else 0
                ledger.note_sent(direct_pub, seq, key, True)
                out.append((seq, key, make(
                    "smoke-direct", qos, pop.topic(key),
                    _PAY.pack(4, direct_pub, seq))))
            return out

        def want_count(key) -> int:
            return 0 if pop.expect(key, True)[0] == "none" else 1

        async def route_direct(lives: list) -> None:
            """lives: [[(seq, key, Message)]]; one batch, or a backlog
            the engine fuses into one W x B window."""
            nonlocal count_wrong, rr_breaks
            only = [[m for _s, _k, m in live] for live in lives]
            if len(only) == 1:
                counts = [eng.route_batch(only[0])]
            else:
                h = eng.prepare_window(only, gate_cold=False)
                check("fused window prepared", h is not None)
                if h is None:
                    return
                try:
                    eng.dispatch(h)
                    eng.materialize(h)
                except Exception:
                    eng.abandon(h)
                    raise
                counts = [eng.finish_sub(h, k, defer=False)
                          for k in range(len(only))]
            check("direct batch served by the device",
                  all(c is not None for c in counts))
            sent_now = [x for live in lives for x in live]
            for c_list, live in zip(counts, lives):
                for (_s, key, _m), c in zip(live, c_list or ()):
                    count_wrong += int(c) != want_count(key)
            await _wait_until(
                lambda: ledger.received >= ledger.expected, 120)
            for seq, key, _m in sent_now:
                direct_keys.append(key)
                if key[0] == "dev" and pop.is_shared(key[1], key[2]):
                    to = ledger.got.get((direct_pub, seq), [None])[0]
                    g = (key[1], key[2])
                    rr_breaks += rr_last.get(g) == to
                    rr_last[g] = to

        for w, bp in std:
            for _b in range(direct_batches if w == 1
                            else max(1, direct_batches // w) * 2):
                await route_direct([direct_msgs(traffic.keys(bp))
                                    for _k in range(w)])
            say(f"direct leg: class W{w}xB{bp} done")
        if require_exchange:
            # windows whose every message hits plain filters only are
            # the ones the exchanged per-destination plans may serve
            plain = [("dev", i, n) for i in range(pop.ids)
                     for n in range(pop.nums)
                     if not pop.is_shared(i, n)
                     and (i, n) not in pop.dropped][:top * 4]
            for _b in range(direct_batches):
                pick = traffic.rng.randint(0, len(plain), top)
                # the segment class follows an EWMA of what landed, so
                # warm whichever class this batch will ask for
                eng.warm_exchange(top)
                await route_direct(
                    [direct_msgs([plain[int(x)] for x in pick])])
        await settle("direct leg")
        m3 = _metric_snapshot(node)
        check("direct leg: per-message counts == oracle", count_wrong == 0,
              f"{count_wrong} messages")
        check("direct leg: round robin alternates members",
              rr_breaks == 0, f"{rr_breaks} repeats")
        check("direct leg grew routing.device.batches",
              delta("routing.device.batches", m2, m3) >= 1)
        check("oracle cross-check (direct)",
              not (bad := oracle_cross_check(node, pop, direct_keys, True)),
              bad)
        report["direct"] = {
            "messages": len(direct_keys),
            "device_batches": delta("routing.device.batches", m2, m3)}

        # ---- every delivery, every order ------------------------------
        check("delivery sets == oracle", ledger.n_wrong == 0,
              f"{ledger.n_wrong} of {ledger.checked}: {ledger.wrong}")
        check("per-publisher, per-topic order at every subscriber",
              ledger.order_breaks == 0, f"{ledger.order_breaks} breaks")
        report["messages_checked"] = ledger.checked

        # ---- nothing hidden -------------------------------------------
        m = _metric_snapshot(node)
        check("messages.routed.device > 0",
              m.get("messages.routed.device", 0) > 0)
        zero = ["routing.device.rebuild_failed",
                "routing.mesh.rebuild_failed",
                "routing.device.warm_failed",
                "routing.device.supervised_bypass",
                "routing.device.dispatch_failed",
                "supervise.replays", "supervise.faults",
                "pipeline.exchange.fallback.error"]
        zero += [k for k in m if k.startswith("supervise.faults.")]
        for k in zero:
            check(f"{k} == 0", m.get(k, 0) == 0, m.get(k, 0))
        sup = node.supervisor
        check("supervisor present", sup is not None)
        if sup is not None:
            states = {s: b.state for s, b in sup.breakers.items()}
            check("every breaker closed",
                  all(v == "closed" for v in states.values()), states)
        from emqx_tpu import native
        check("native codec loaded", native.available())
        report["device"] = node.device_info
        if require_exchange:
            check("exchange ring ran",
                  m.get("pipeline.exchange.rounds", 0) > 0)
            check("windows served from exchanged plans",
                  m.get("pipeline.exchange.windows", 0) > 0)

        snap = node.pipeline_telemetry.snapshot()
        report["split"] = {
            **dict(sorted(snap["decisions"].items())),
            "supervised_bypass": m.get("routing.device.supervised_bypass",
                                       0),
        }
        report["rebuild"] = snap.get("rebuild", {}).get("stages", {})
        report["compiles"] = snap["compiles"]
        report["jit_cache"] = snap.get("jit_cache", {})
        report["memory"] = snap.get("memory", {})
        report["exchange"] = snap.get("exchange", {})
        report["stats"] = eng.stats()
    finally:
        for t in drainers:
            t.cancel()
        for cl in pub_cl + sub_cl:
            try:
                await cl.disconnect()
            except Exception:  # noqa: BLE001 — teardown of a failed run
                pass
        await lst.stop()
        if node.publish_batcher is not None:
            await node.publish_batcher.stop()
            node.publish_batcher.close()
    return report


def print_report(report: dict) -> None:
    """What the run did, unjudged: set-up seconds, the chooser's split,
    compile accounting and the programs a real stream reached."""
    say(f"set-up seconds: {json.dumps(report.get('setup_s'))}")
    say(f"rebuild stages (ms): {json.dumps(report.get('rebuild'))}")
    say(f"stream: {json.dumps(report.get('stream'))} "
        f"in {report.get('stream_s')}s")
    say(f"direct: {json.dumps(report.get('direct'))}")
    say(f"chooser split (pipeline.batches.* and reasons; `host` "
        f"includes the chooser's host probes): "
        f"{json.dumps(report.get('split'))}")
    comp = report.get("compiles") or {}
    say(f"compiles: {comp.get('count')} in {comp.get('total_s')}s")
    for label, row in sorted((comp.get("by_shape") or {}).items()):
        say(f"  compile {label}: {row['executables']} executable(s), "
            f"{row['count']} trace events, {row['total_s']}s")
    say(f"compile_stats (jit-cache entries per route program): "
        f"{json.dumps(report.get('jit_cache'))}")
    if report.get("exchange"):
        say(f"exchange: {json.dumps(report['exchange'])}")
    mem = report.get("memory") or {}
    say(f"hbm ledger: live_bytes={mem.get('live_bytes')} "
        f"device={json.dumps(mem.get('device'))} "
        f"accounted_fraction={mem.get('accounted_fraction')}")
    say(f"messages checked against the oracle: "
        f"{report.get('messages_checked')}")


def check_memory(report: dict, failures: list) -> None:
    """memory_stats() present, and the ledger's live bytes inside it."""
    mem = report.get("memory") or {}
    dev = mem.get("device")
    if not dev or "bytes_in_use" not in dev:
        failures.append("memory_stats() is absent on this backend")
        return
    from emqx_tpu.broker.hbm_ledger import total_bytes_in_use
    in_use = total_bytes_in_use() or 0     # summed over every device
    live = mem.get("live_bytes", 0)
    if not 0 < live <= in_use:
        failures.append(f"hbm ledger live_bytes={live} outside "
                        f"(0, bytes_in_use={in_use}]")


def check_fold_kernel(node, seed: int, failures: list) -> None:
    """`shape_match_pallas`, compiled by Mosaic, against `shape_match`
    on the node's own snapshot tables, bit for bit."""
    import jax

    from emqx_tpu.ops.match import encode_topics_str
    from emqx_tpu.ops.shapes import shape_match, shape_match_pallas
    eng = node.device_engine
    b, tables = eng._built, eng._tables
    if b is None or b.backend != "shapes":
        failures.append(f"fold kernel: snapshot backend is "
                        f"{getattr(b, 'backend', None)}, not shapes")
        return
    pop = Population(len(b.fid_filter), SUB_CONNS)
    keys = Traffic(pop, seed + 1).keys(4096)
    enc, lens, dollar, _ = encode_topics_str(
        eng.intern, [pop.topic(k) for k in keys], eng.max_levels)
    t0 = time.monotonic()
    rx = shape_match(tables.shapes, enc, lens, dollar)
    rp = shape_match_pallas(tables.shapes, enc, lens, dollar)
    jax.block_until_ready((rx, rp))
    same = bool((np.asarray(rx.matches) == np.asarray(rp.matches)).all()
                and (np.asarray(rx.counts) == np.asarray(rp.counts)).all())
    say(f"fold kernel: Mosaic build of shape_match_pallas == shape_match "
        f"on {len(b.fid_filter)} filters, 4096 topics: {same} "
        f"(matched {int(np.asarray(rx.counts).sum())}, "
        f"{time.monotonic() - t0:.1f}s incl. compile)")
    if not same:
        failures.append("shape_match_pallas != shape_match on the chip")


def check_shards(node, failures: list) -> None:
    """Every device of the mesh holds a table shard."""
    import jax
    eng = node.device_engine
    holders: dict = {}
    for leaf in jax.tree.leaves(eng.tables):
        for sh in leaf.addressable_shards:
            holders[sh.device.id] = holders.get(sh.device.id, 0) \
                + sh.data.nbytes
    in_use = {d.id: int((d.memory_stats() or {}).get("bytes_in_use", 0))
              for d in jax.local_devices()}
    say(f"table shard bytes per device: {holders}; "
        f"bytes_in_use per device: {in_use}")
    for d in eng.mesh.devices.flat:
        if holders.get(d.id, 0) <= 0 or in_use.get(d.id, 0) <= 0:
            failures.append(f"device {d.id} holds no table shard")


def build_native() -> None:
    """`native/libemqx_native.so` from `native/emqx_native.cpp` (the .so
    is git-ignored, so a fresh checkout has none)."""
    so = os.path.join(HERE, "native", "libemqx_native.so")
    if not os.path.exists(so):
        r = subprocess.run(["make", "-C", os.path.join(HERE, "native")],
                           capture_output=True, text=True, timeout=300)
        if r.returncode != 0:
            raise SystemExit(f"chip_smoke: cannot build "
                             f"libemqx_native.so:\n{r.stdout}{r.stderr}")
    from emqx_tpu import native
    if not native.available():
        raise SystemExit("chip_smoke: libemqx_native.so did not load")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--subs", type=int, default=None,
                    help="filters to install (default: full scale, or "
                         "the time-limit cut; never below 100,000)")
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: route=4, then dp=2 x route=2")
    args = ap.parse_args()

    knobs = sorted(k for k in os.environ if k.startswith("EMQX_TPU_"))
    if knobs:
        print(f"chip_smoke: refusing to run with {knobs} set — the "
              f"smoke proves the default configuration", file=sys.stderr)
        return 2
    plats = os.environ.get("JAX_PLATFORMS", "").lower()
    if plats and all(p.strip() in ("cpu", "") for p in plats.split(",")):
        print(f"chip_smoke: JAX_PLATFORMS={plats!r} names no accelerator; "
              f"this script only passes on a TPU", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(HERE, "emqx_tpu")):
        print("chip_smoke: emqx_tpu/ is not next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)

    from emqx_tpu.utils.compile_cache import (compile_cache_entries,
                                              configure_compile_cache)
    cache_dir = configure_compile_cache()
    import jax
    import jaxlib
    devs = jax.devices()
    device = {"platform": devs[0].platform,
              "kind": devs[0].device_kind, "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {device}); there "
              f"is no CPU mode", file=sys.stderr)
        return 2
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "unknown"
    say(f"device: platform={device['platform']} "
        f"device_kind={device['kind']} count={device['count']} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}")
    cache_before = compile_cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} ({cache_before} entries before)")
    build_native()

    from emqx_tpu.broker.node import Node
    failures: list = []
    if args.mesh:
        if device["count"] < 4:
            print(f"chip_smoke --mesh needs 4 chips, found "
                  f"{device['count']}", file=sys.stderr)
            return 2
        subs = args.subs or MESH_SUBS
        layouts = [{"devices": 4, "dp": 1}, {"devices": 4, "dp": 2}]
    else:
        subs = args.subs or FULL_SUBS
        layouts = [None]
    if subs < MIN_SUBS:
        print(f"chip_smoke: --subs {subs} is below the {MIN_SUBS} floor",
              file=sys.stderr)
        return 2
    if subs < FULL_SUBS:
        say(f"CUT: {subs} filters instead of {FULL_SUBS} (time limit)")

    for layout in layouts:
        conf = None
        if layout is not None:
            say(f"=== mesh layout dp={layout['dp']} x "
                f"route={layout['devices'] // layout['dp']} ===")
            conf = {"broker": {"multichip": {"enable": True, **layout}}}
        node = Node(conf)
        report = asyncio.run(drive(node, subs=subs, msgs=MSGS,
                                   seed=args.seed,
                                   require_exchange=layout is not None))
        print_report(report)
        fails = report["failures"]
        if report.get("device", {}).get("platform") != "tpu":
            fails.append(f"node bound to {report.get('device')}")
        check_memory(report, fails)
        if layout is None:
            check_fold_kernel(node, args.seed, fails)
        else:
            check_shards(node, fails)
        failures += fails

    say(f"compile cache: {compile_cache_entries(cache_dir)} entries after "
        f"({cache_before} before)")
    if failures:
        say(f"FAILED ({len(failures)}):")
        for f in failures:
            say(f"  - {f}")
        print(json.dumps({"ok": False, "device": device,
                          "failures": failures[:32]}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
