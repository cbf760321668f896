#!/usr/bin/env python
"""Cluster chaos drive: node kills AND freezes under continuous QoS1
traffic.

The reference's failure story is tested with docker-compose node kills
(scripts/ + emqx_takeover_SUITE.erl); this is the sharper analog: a
3-OS-process cluster where, each cycle, a random non-seed node is either
SIGKILLed (crash) or SIGSTOPped (gray failure: TCP open, nothing
answers) mid-flood. Its clients re-home to a survivor (cross-node
takeover of the same clientid against the corpse/frozen owner), the
victim is restarted/thawed, and the invariants asserted every cycle:

  1. CONNECT to any survivor completes fast — a dead peer must never
     park the clientid lock; a FROZEN peer costs at most the bounded
     RPC timeouts (connect/handshake, lock, takeover).
  2. QoS1 publishes keep earning PUBACKs throughout the outage.
  3. The anchor subscriber (on the seed) resumes receiving within the
     bound — routes survive peer death.
  4. After heal/thaw, membership converges back to 3 running nodes.
  5. A node restarted at NEW dynamic ports is deliverable-to again
     (peer re-addressing + replication incarnation).

CHAOS_MODE=kill|freeze|mixed (default mixed), CHAOS_SEED, CHAOS_LAX,
CHAOS_QOS=2 (drive at QoS 2 and assert exactly-once), CHAOS_DEVICE=1.
Usage: python tools/chaos_cluster.py [cycles]    (default 6)

Exit 0 with "CHAOS OK" on success; assertion failure otherwise.
"""

import asyncio
import os
import random
import signal
import subprocess
import sys
import time

REPO = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

# latency-bound scale: the bounds separate "healthy" (<2s) from the
# 35s-stall bug class; under heavy CPU contention (full pytest suite +
# 5 broker processes on a small box) honest 2s bounds flake, so the
# in-suite wrapper runs with CHAOS_LAX=3
LAX = float(os.environ.get("CHAOS_LAX", "1"))
# CHAOS_QOS=2 runs the whole drive at QoS 2: the anchor then also
# asserts EXACTLY-once (a duplicate delivery fails the run)
QOS = int(os.environ.get("CHAOS_QOS", "1"))


def spawn(name, join=None):
    from test_two_process_cluster import _readline_deadline
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(REPO, "tools", "run_node.py"),
           "--name", name]
    if os.environ.get("CHAOS_DEVICE", "0") != "1":
        cmd.append("--no-device")   # CHAOS_DEVICE=1: serve through the
        # batcher + device engine (CPU backend) so kills/freezes also
        # exercise the fused serving path
    if join:
        cmd += ["--join", join]
    p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                         stderr=subprocess.DEVNULL, env=env)
    line = _readline_deadline(p, 60).strip()
    assert line.startswith("READY "), f"{name}: {line}"
    _, mqtt, rpc = line.split()
    rec = {"p": p, "mqtt": int(mqtt), "rpc": int(rpc), "name": name}
    _ALL_PROCS.append(rec)
    return rec


async def connect_fast(port, clientid, bound_s=None):
    """Invariant 1: CONNECT to a live node must complete inside bound_s
    even right after a peer died (pre-nodedown-detection window)."""
    bound_s = (bound_s or 2.0) * LAX
    from emqx_tpu.client import Client
    c = Client(port=port, clientid=clientid)
    t0 = time.monotonic()
    await c.connect(timeout=bound_s + 3)
    dt = time.monotonic() - t0
    assert dt < bound_s, f"CONNECT took {dt:.1f}s (> {bound_s}s) on :{port}"
    return c


async def main(cycles: int) -> None:
    from emqx_tpu.mqtt import packet as P

    seed = spawn("a@127.0.0.1")
    b = spawn("b@127.0.0.1", join=f"127.0.0.1:{seed['rpc']}")
    c = spawn("c@127.0.0.1", join=f"127.0.0.1:{seed['rpc']}")
    others = {"b@127.0.0.1": b, "c@127.0.0.1": c}
    procs = [seed, b, c]
    rng = random.Random(int(os.environ.get("CHAOS_SEED", 42)))
    clients: list = []

    anchor = await connect_fast(seed["mqtt"], "anchor")
    await anchor.subscribe([("chaos/#", P.SubOpts(qos=QOS))])

    # shared-group invariant members: one on the seed, one on a node the
    # chaos will kill/freeze — group dispatch (device picks under
    # CHAOS_DEVICE=1, incl. remote-member forwards) must stay
    # exactly-once-per-group at every steady state
    share1 = await connect_fast(seed["mqtt"], "share-1")
    await share1.subscribe([("$share/grp/shgrp/t", P.SubOpts(qos=QOS))])
    share2 = await connect_fast(b["mqtt"], "share-2")
    await share2.subscribe([("$share/grp/shgrp/t", P.SubOpts(qos=QOS))])
    shared_epoch = 0

    def drain_shared():
        got = []
        for s in (share1, share2):
            while not s.messages.empty():
                got.append(s.messages.get_nowait().payload)
        return got

    async def check_shared(pub_client, bound_s=None):
        """Invariant 6: a steady-state burst into the share group lands
        exactly once per message across the members. A settle probe
        first absorbs the post-heal transition (stale members purge,
        dirty slots, snapshot rebuild)."""
        nonlocal shared_epoch
        shared_epoch += 1
        bound_s = (bound_s or 8.0) * LAX
        drain_shared()
        t0 = time.monotonic()
        while time.monotonic() - t0 < bound_s:     # settle probe
            await pub_client.publish("shgrp/t", b"probe", qos=QOS,
                                     timeout=bound_s + 2)
            await asyncio.sleep(0.15)
            if b"probe" in drain_shared():
                break
        else:
            raise AssertionError("share group never resumed")
        mark = f"e{shared_epoch}-".encode()
        expected = [mark + str(i).encode() for i in range(10)]
        for p in expected:
            await pub_client.publish("shgrp/t", p, qos=QOS,
                                     timeout=bound_s + 2)
        got: list = []
        t0 = time.monotonic()
        while time.monotonic() - t0 < bound_s:
            got += [p for p in drain_shared() if p.startswith(mark)]
            if len(got) >= len(expected):
                break
            await asyncio.sleep(0.1)
        # grace drain: a late DUPLICATE must not escape the assertion by
        # arriving after the count was reached
        await asyncio.sleep(0.5 * LAX)
        got += [p for p in drain_shared() if p.startswith(mark)]
        assert sorted(got) == sorted(expected), \
            f"shared group: want {len(expected)} exactly-once, got {got}"

    seq = 0
    received: set = set()
    dupes: list = []

    async def drain_anchor():
        while not anchor.messages.empty():
            m = anchor.messages.get_nowait()
            n = int(m.payload)
            if n in received and QOS == 2:
                dupes.append(n)
            received.add(n)

    async def publish_burst(cl, n, bound_s=None):
        """Invariant 2: every QoS1 publish earns its PUBACK in bound."""
        bound_s = (bound_s or 3.0) * LAX
        nonlocal seq
        for _ in range(n):
            t0 = time.monotonic()
            await cl.publish("chaos/t", str(seq).encode(), qos=QOS,
                             timeout=bound_s + 2)
            dt = time.monotonic() - t0
            assert dt < bound_s, f"PUBACK took {dt:.1f}s"
            seq += 1
            await asyncio.sleep(0)

    async def wait_resume(deadline_s=None, bound_s=None):
        """Invariant 3: the anchor sees NEW messages within the bound."""
        deadline_s = (deadline_s or 8.0) * LAX
        start_seq = seq
        pub2 = await connect_fast(seed["mqtt"], "probe-pub",
                                  bound_s=bound_s)
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            await publish_burst(pub2, 1, bound_s=bound_s)
            await asyncio.sleep(0.1)
            await drain_anchor()
            if any(s >= start_seq for s in received):
                await pub2.disconnect()
                return
        raise AssertionError(f"anchor got nothing new in {deadline_s}s")

    async def wait_members(n, deadline_s=None):
        """Invariant 4: membership converges to n running nodes."""
        deadline_s = (deadline_s or 15.0) * LAX
        from emqx_tpu.cluster.rpc import RpcNode
        probe = RpcNode("probe@x", port=0)
        await probe.start()
        try:
            probe.add_peer("seed", "127.0.0.1", seed["rpc"])
            t0 = time.monotonic()
            last = None
            while time.monotonic() - t0 < deadline_s:
                try:
                    info = await probe.call("seed", "ekka.heartbeat",
                                            ["probe@x", None], timeout=2)
                    last = sorted(k for k, v in info.items()
                                  if v["status"] == "running"
                                  and not k.startswith("probe"))
                    if len(last) == n:
                        return
                except Exception:  # noqa: BLE001 — retry until deadline
                    pass
                await asyncio.sleep(0.3)
            raise AssertionError(f"membership stuck at {last}, want {n}")
        finally:
            await probe.stop()

    # steady state: publisher on b, extra subscriber on c
    pub = await connect_fast(b["mqtt"], "chaos-pub")
    extra = await connect_fast(c["mqtt"], "extra-sub")
    await extra.subscribe([("chaos/#", P.SubOpts(qos=1))])
    await publish_burst(pub, 20)
    await wait_resume()
    await check_shared(pub)

    for cycle in range(cycles):
        victim_name = rng.choice(list(others))
        victim = others[victim_name]

        # mixed mode: some cycles FREEZE (SIGSTOP — gray failure: TCP
        # open, nothing answers) instead of killing. Bounds are larger:
        # pre-detection, each RPC against the frozen node costs its
        # short timeout rather than failing instantly.
        mode = os.environ.get("CHAOS_MODE", "mixed")
        freeze = mode == "freeze" or (mode == "mixed"
                                      and cycle % 3 == 2)
        if freeze:
            print(f"[cycle {cycle}] SIGSTOP {victim_name}", flush=True)
            os.kill(victim["p"].pid, signal.SIGSTOP)
            try:
                if pub.port == victim["mqtt"]:
                    # re-home: same clientid, owner frozen — takeover
                    # must give up on the corpse within its bound
                    pub = await connect_fast(seed["mqtt"], "chaos-pub",
                                             bound_s=8.0)
                if extra.port == victim["mqtt"]:
                    extra = await connect_fast(seed["mqtt"], "extra-sub",
                                               bound_s=8.0)
                    await extra.subscribe([("chaos/#", P.SubOpts(qos=1))])
                # share2 is NOT re-homed on freeze: its socket to the
                # frozen node survives the thaw (deliveries buffer in
                # the socket), and a same-clientid reconnect would
                # leave a zombie member behind — the discard RPC times
                # out against the frozen owner
                probe = await connect_fast(seed["mqtt"],
                                           f"frz-{cycle}", bound_s=8.0)
                await probe.disconnect()
                await publish_burst(pub, 10, bound_s=8.0)
                await wait_resume(deadline_s=16.0, bound_s=8.0)
            finally:
                os.kill(victim["p"].pid, signal.SIGCONT)
            await wait_members(3)             # thaw: autoheal
            await publish_burst(pub, 10)
            await wait_resume()
            await check_shared(pub)           # invariant 6 after thaw
            print(f"[cycle {cycle}] thawed, seq={seq}, "
                  f"anchor_received={len(received)}", flush=True)
            continue

        print(f"[cycle {cycle}] kill -9 {victim_name}", flush=True)
        victim["p"].kill()
        victim["p"].wait(10)

        # clients that lived on the victim re-home to the seed with the
        # SAME clientid — exercises cross-node takeover while the old
        # owner is an undetected corpse
        if pub.port == victim["mqtt"]:
            pub = await connect_fast(seed["mqtt"], "chaos-pub")
        if extra.port == victim["mqtt"]:
            extra = await connect_fast(seed["mqtt"], "extra-sub")
            await extra.subscribe([("chaos/#", P.SubOpts(qos=1))])
        if share2.port == victim["mqtt"]:
            share2 = await connect_fast(seed["mqtt"], "share-2")
            await share2.subscribe(
                [("$share/grp/shgrp/t", P.SubOpts(qos=QOS))])

        await publish_burst(pub, 10)          # invariant 2 during outage
        await wait_resume()                   # invariant 3

        # heal: restart victim, rejoin
        fresh = spawn(victim_name, join=f"127.0.0.1:{seed['rpc']}")
        others[victim_name] = fresh
        procs.append(fresh)
        await wait_members(3)                 # invariant 4
        await publish_burst(pub, 10)
        await wait_resume()

        # invariant 5: the REJOINED node (new dynamic ports) must be
        # deliverable-to from survivors — the stale-peer regression
        # (add_peer keeping the old channel pool) made exactly this path
        # silently dead while everything else stayed green
        back = await connect_fast(fresh["mqtt"], f"back-{cycle}")
        await back.subscribe([(f"back/{cycle}", P.SubOpts(qos=1))])
        t0 = time.monotonic()
        got_back = False
        while time.monotonic() - t0 < 8.0 and not got_back:
            await pub.publish(f"back/{cycle}", b"x", qos=1, timeout=5)
            try:
                await asyncio.wait_for(back.messages.get(), 0.3)
                got_back = True
            except asyncio.TimeoutError:
                pass
        assert got_back, f"rejoined {victim_name} unreachable (stale peer)"
        await back.disconnect()
        await check_shared(pub)               # invariant 6 after heal
        print(f"[cycle {cycle}] healed, seq={seq}, "
              f"anchor_received={len(received)}", flush=True)

    await drain_anchor()
    # the anchor lives on the never-killed seed: everything published
    # while it was subscribed must have arrived (QoS1, local or relayed
    # from a LIVE publisher node — kills happen between bursts)
    missing = [s for s in range(seq) if s not in received]
    assert not missing, f"anchor lost {len(missing)} messages: " \
                        f"{missing[:10]}..."
    assert not dupes, f"QoS2 duplicates delivered: {dupes[:10]}"
    print(f"CHAOS OK: {cycles} cycles, {seq} published, "
          f"{len(received)} received, 0 lost", flush=True)
    for cl in (anchor, pub, extra):
        try:
            await cl.disconnect()
        except Exception:  # noqa: BLE001
            pass


def _reap():
    """Kill every node this drive spawned — an assertion failure must
    not leak broker processes onto the box (leaked nodes kept beating
    and skewed later benchmarks). SIGCONT first so a frozen victim's
    kill takes effect immediately."""
    for pr in _ALL_PROCS:
        if pr["p"].poll() is None:
            try:
                os.kill(pr["p"].pid, signal.SIGCONT)
            except ProcessLookupError:
                pass
            pr["p"].kill()
    for pr in _ALL_PROCS:
        try:
            pr["p"].wait(5)
        except Exception:  # noqa: BLE001
            pass


_ALL_PROCS: list = []


if __name__ == "__main__":
    try:
        asyncio.run(main(int(sys.argv[1]) if len(sys.argv) > 1 else 6))
    finally:
        _reap()
