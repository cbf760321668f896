#!/usr/bin/env python
"""Columnar-ingress end-to-end benchmark: the ISSUE-11 acceptance row.

Measures END-TO-END msgs/s — real TCP connections through the live
broker (Listener → Connection → FrameParser → Channel → PublishBatcher
→ route → deliver) — once per (connection count, ingress path):

  columnar=0   the per-packet path: parser.feed, one Packet +
               handle_in + publish per frame — the A/B baseline
  columnar=1   the columnar path: native burst decode → PublishBurst →
               handle_publish_burst → batcher.submit_burst, plus the
               SO_REUSEPORT acceptor lanes

This is the IoT-broker-benchmarking framing (arXiv:2603.21600,
PAPERS.md): committed messages per second under realistic
many-connection traffic, not isolated match throughput. Each
configuration runs in its OWN subprocess (a config must not inherit
the previous one's GC pressure or jit caches). The child reports msgs/s
plus the stage decomposition (pipeline telemetry snapshot) and the
`ingress` section, so a missed speedup target still ships the evidence
of where the wall is.

Correctness rides along: a subscriber counts its deliveries and the
parent asserts the columnar/per-packet twins delivered identical
counts.

Env knobs: INGRESS_CONNS ("64,256" sweep), INGRESS_MSGS_PER_CONN (400),
INGRESS_TOPICS (16), INGRESS_PAYLOAD (64 bytes), INGRESS_SUB_TOPICS (1:
subscriber covers bench/t0..t{n-1} — 1/16 of traffic by default so
egress cannot become the measured wall), INGRESS_TIMEOUT_S (240),
INGRESS_ONE_TIMEOUT_S (300).

Run directly or as `python bench.py` (its `cpu_ingress` row).
"""

import asyncio
import gc
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _blob(conn_id: int, n_msgs: int, n_topics: int, payload: int) -> bytes:
    """One publisher connection's whole flood, pre-serialized: CONNECT
    is sent separately; this is n_msgs QoS0 PUBLISH frames."""
    from emqx_tpu.mqtt import packet as P
    from emqx_tpu.mqtt.frame import serialize
    out = bytearray()
    pad = b"x" * max(0, payload - 16)
    for i in range(n_msgs):
        out += serialize(P.Publish(
            topic=f"bench/t{i % n_topics}",
            payload=b"%08d%08d" % (conn_id, i) + pad, qos=0), 4)
    return bytes(out)


async def _connect_raw(port: int, clientid: str):
    """CONNECT over a raw socket; returns (reader, writer) past the
    CONNACK (the flood writes pre-serialized frames, no client object)."""
    from emqx_tpu.mqtt import packet as P
    from emqx_tpu.mqtt.frame import FrameParser, serialize
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(serialize(P.Connect(proto_name="MQTT", proto_ver=4,
                                     clientid=clientid), 4))
    await writer.drain()
    parser = FrameParser(version=4)
    while True:
        data = await reader.read(64)
        if not data:
            raise RuntimeError("connection closed before CONNACK")
        if parser.feed(data):
            return reader, writer


async def _run_child(conns: int, columnar: bool) -> dict:
    from emqx_tpu.broker.connection import Listener
    from emqx_tpu.broker.node import Node
    from emqx_tpu.client import Client

    n_msgs = int(os.environ.get("INGRESS_MSGS_PER_CONN", 400))
    n_topics = int(os.environ.get("INGRESS_TOPICS", 16))
    payload = int(os.environ.get("INGRESS_PAYLOAD", 64))
    sub_topics = int(os.environ.get("INGRESS_SUB_TOPICS", 1))
    timeout_s = float(os.environ.get("INGRESS_TIMEOUT_S", 240))

    node = Node({"broker": {"columnar_ingress": columnar},
                 "log": {"enable": False}})
    lst = Listener(node, bind="127.0.0.1", port=0)
    await lst.start()

    sub = Client(port=lst.port, clientid="ingress-sub")
    await sub.connect()
    for k in range(sub_topics):
        await sub.subscribe(f"bench/t{k}", qos=0)
    delivered = [0]
    order_violations = [0]
    last_seq: dict = {}

    async def _drain_sub():
        # per-publisher order oracle: payload is b"%08d%08d" (conn,
        # seq) — within one publisher the seq must be monotone at the
        # subscriber, whatever the ingress path did
        while True:
            msg = await sub.messages.get()
            delivered[0] += 1
            head = bytes(msg.payload[:16])
            conn_id, seqno = int(head[:8]), int(head[8:])
            if last_seq.get(conn_id, -1) >= seqno:
                order_violations[0] += 1
            last_seq[conn_id] = seqno

    drain_task = asyncio.create_task(_drain_sub())

    async def flood(pairs, blobs):
        async def one(writer, blob):
            w = 0
            while w < len(blob):
                writer.write(blob[w:w + 65536])
                w += 65536
                await writer.drain()
        await asyncio.gather(*[one(w, b)
                               for (_r, w), b in zip(pairs, blobs)])

    async def settle(expect: int, deadline: float) -> bool:
        while time.perf_counter() < deadline:
            if node.metrics.val("messages.publish") >= expect:
                return True
            await asyncio.sleep(0.02)
        return False

    # warm pass: compiles, allocator, acceptor lanes — not timed. The
    # warm flood mirrors the timed flood's batch shape (full windows at
    # max_publish_batch) so the device route class the flood will use
    # compiles NOW, then we wait for the background warm to land —
    # otherwise every timed window cold-classes to the host path and
    # the bench measures the host trie, not the ingest stack.
    n_warm = min(conns, 8)
    warm_pairs = [await _connect_raw(lst.port, f"warm{i}")
                  for i in range(n_warm)]
    warm_blobs = [_blob(900 + i, n_msgs, n_topics, payload)
                  for i in range(n_warm)]
    await flood(warm_pairs, warm_blobs)
    await settle(n_warm * n_msgs, time.perf_counter() + 120)
    for _r, w in warm_pairs:
        w.close()
    eng = node.device_engine
    if eng is not None:
        bmax = node.publish_batcher.max_batch \
            if node.publish_batcher is not None else 1024
        deadline = time.perf_counter() + 90
        while time.perf_counter() < deadline:
            try:
                if eng.batch_class_warm(bmax):
                    break
            except Exception:  # noqa: BLE001 — engine without a snapshot
                break
            await asyncio.sleep(0.05)

    pairs = [await _connect_raw(lst.port, f"pub{i}")
             for i in range(conns)]
    blobs = [_blob(i, n_msgs, n_topics, payload) for i in range(conns)]
    base = node.metrics.val("messages.publish")
    total = conns * n_msgs
    gc.collect()
    t0 = time.perf_counter()
    await flood(pairs, blobs)
    ok = await settle(base + total, t0 + timeout_s)
    wall = time.perf_counter() - t0
    # let in-flight deliveries land before comparing twins: wait until
    # the delivered count stops moving (a fixed sleep raced the lanes
    # at the higher columnar rates)
    stable_at = delivered[0]
    quiet = 0
    deadline = time.perf_counter() + 30
    while quiet < 10 and time.perf_counter() < deadline:
        await asyncio.sleep(0.05)
        if delivered[0] == stable_at:
            quiet += 1
        else:
            stable_at = delivered[0]
            quiet = 0
    snap = node.pipeline_telemetry.snapshot()
    row = {
        "conns": conns,
        "columnar": bool(columnar),
        "msgs": total,
        "completed": ok,
        "wall_s": round(wall, 3),
        "msgs_per_s": round(total / wall) if ok and wall > 0 else 0,
        "delivered": delivered[0],
        "order_violations": order_violations[0],
        "ingress": snap.get("ingress"),
        "stages": snap.get("stages"),
        "decisions": snap.get("decisions"),
        "lanes": getattr(node, "ingress_lanes", None),
    }
    drain_task.cancel()
    for _r, w in pairs:
        w.close()
    await sub.close()
    await lst.stop()
    if node.publish_batcher is not None:
        await node.publish_batcher.stop()
    return row


def run_one(conns: int, columnar: bool) -> dict:
    return asyncio.run(_run_child(conns, columnar))


def run_ingress() -> dict:
    sweep = [int(x) for x in os.environ.get(
        "INGRESS_CONNS", "64,256").split(",")]
    one_timeout = int(os.environ.get("INGRESS_ONE_TIMEOUT_S", 300))
    rows = []
    for conns in sweep:
        for columnar in (0, 1):
            sp = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one",
                 str(conns), str(columnar)],
                capture_output=True, text=True, timeout=one_timeout)
            row = None
            for ln in reversed(sp.stdout.splitlines()):
                if ln.strip().startswith("{"):
                    row = json.loads(ln)
                    break
            if row is None:
                raise RuntimeError(
                    f"conns={conns} columnar={columnar} child failed "
                    f"rc={sp.returncode}: {sp.stderr[-300:]}")
            rows.append(row)
            log(f"conns={conns} columnar={columnar}: "
                f"{row['msgs_per_s'] / 1e3:.1f}k msgs/s "
                f"delivered={row['delivered']}")
    by = {(r["conns"], r["columnar"]): r for r in rows}
    twins = {}
    delivery_ok = True
    for conns in sweep:
        off, on = by[(conns, False)], by[(conns, True)]
        twins[str(conns)] = {
            "per_packet_msgs_per_s": off["msgs_per_s"],
            "columnar_msgs_per_s": on["msgs_per_s"],
            "speedup": round(on["msgs_per_s"]
                             / max(1, off["msgs_per_s"]), 2),
            "delivered": on["delivered"],
        }
        if on["delivered"] != off["delivered"] \
                or on["order_violations"] or off["order_violations"]:
            delivery_ok = False
    top = max(sweep)
    head = by[(top, True)]
    return {
        "metric": "ingress_msgs_per_sec",
        "unit": "msgs/s",
        "per_conns": twins,
        "best_per_s": head["msgs_per_s"],
        # ISSUE 11 acceptance: >= 3x the per-packet path at the
        # 256-connection CPU flood; the stage decomposition below is
        # the honest-number evidence either way
        "speedup": twins[str(top)]["speedup"],
        "delivery_twin_ok": delivery_ok,
        "ingress": head["ingress"],
        "stage_decomposition": head["stages"],
        "per_packet_stages": by[(top, False)]["stages"],
        "decisions": head["decisions"],
        "lanes": head["lanes"],
        "workload": {
            "conns_sweep": sweep,
            "msgs_per_conn": int(os.environ.get(
                "INGRESS_MSGS_PER_CONN", 400)),
            "topics": int(os.environ.get("INGRESS_TOPICS", 16)),
            "payload": int(os.environ.get("INGRESS_PAYLOAD", 64)),
            "qos": 0,
        },
    }


def main():
    if "--one" in sys.argv:
        i = sys.argv.index("--one")
        conns = int(sys.argv[i + 1])
        columnar = bool(int(sys.argv[i + 2]))
        print(json.dumps(run_one(conns, columnar)), flush=True)
        return
    print(json.dumps(run_ingress()), flush=True)


if __name__ == "__main__":
    main()
