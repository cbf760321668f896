"""Two-OS-process cluster: the deployment shape the reference tests with
scripts/start-two-nodes-in-docker.sh (SURVEY §4 "Multi-node" row).

Each node is a separate python process (tools/run_node.py) with its own
event loop, RPC listener, and MQTT listener; the harness wires a cluster
join, then drives real MQTT clients cross-node: subscribe on A, publish
on B → delivery must cross the node boundary over the RPC channel.
"""

import asyncio
import os
import signal
import subprocess
import sys

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")


def _readline_deadline(p, timeout_s):
    """readline with a deadline: a node that boots but never prints READY
    must fail the test, not hang pytest with an orphaned broker."""
    import selectors
    sel = selectors.DefaultSelector()
    sel.register(p.stdout, selectors.EVENT_READ)
    buf = b""
    import time
    deadline = time.monotonic() + timeout_s
    fd = p.stdout.fileno()
    while time.monotonic() < deadline:
        if not sel.select(timeout=0.2):
            continue
        chunk = os.read(fd, 4096)
        if not chunk:
            break
        buf += chunk
        if b"\n" in buf:
            return buf.split(b"\n", 1)[0].decode()
    p.kill()
    raise AssertionError(f"no READY line within {timeout_s}s: {buf!r}")


def _spawn(name, join=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(REPO, "tools", "run_node.py"),
           "--name", name, "--no-device"]
    if join:
        cmd += ["--join", join]
    p = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=None, env=env)
    try:
        line = _readline_deadline(p, 60).strip()
        assert line.startswith("READY "), \
            f"node {name} failed to boot: {line}"
        _, mqtt_port, rpc_port = line.split()
        return p, int(mqtt_port), int(rpc_port)
    except BaseException:
        p.kill()        # never orphan a half-booted broker
        raise


@pytest.fixture()
def two_nodes():
    a = b = None
    try:
        a = _spawn("a@127.0.0.1")
        b = _spawn("b@127.0.0.1", join=f"127.0.0.1:{a[2]}")
        yield a, b
    finally:
        for p in (x[0] for x in (a, b) if x):
            p.send_signal(signal.SIGTERM)
        for p in (x[0] for x in (a, b) if x):
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def test_chaos_cycles():
    """Bounded chaos run (tools/chaos_cluster.py): 3-node OS-process
    cluster, SIGKILL a random node per cycle under QoS1 traffic, assert
    fast CONNECT on survivors, PUBACK continuity, delivery resumption,
    membership re-convergence, and reachability of the rejoined node at
    its new dynamic ports. The long-form drive is the same tool with
    more cycles."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", CHAOS_LAX="3")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_cluster.py"),
         "2"],
        capture_output=True, text=True, timeout=240, env=env)
    assert r.returncode == 0, f"chaos failed:\n{r.stdout}\n{r.stderr}"
    assert "CHAOS OK" in r.stdout


def test_cross_process_pubsub(two_nodes):
    (pa, mqtt_a, _), (pb, mqtt_b, _) = two_nodes

    async def go():
        from emqx_tpu.client import Client

        sub = Client(port=mqtt_a, clientid="sub-a")
        await sub.connect()
        from emqx_tpu.mqtt import packet as P
        await sub.subscribe([("x/cross/#", P.SubOpts(qos=0))])

        pub = Client(port=mqtt_b, clientid="pub-b")
        await pub.connect()
        # replication is async: wait for the route to reach node B by
        # publishing until delivery lands (bounded)
        got = None
        for i in range(100):
            await pub.publish(f"x/cross/{i}", b"hello", qos=0)
            try:
                got = await asyncio.wait_for(sub.messages.get(), 0.2)
                break
            except asyncio.TimeoutError:
                continue
        assert got is not None, "cross-node delivery never arrived"
        assert got.topic.startswith("x/cross/")
        assert got.payload == b"hello"

        # reverse direction: subscribe on B, publish on A
        sub2 = Client(port=mqtt_b, clientid="sub-b")
        await sub2.connect()
        await sub2.subscribe([("y/back", P.SubOpts(qos=0))])
        pub2 = Client(port=mqtt_a, clientid="pub-a")
        await pub2.connect()
        got2 = None
        for _ in range(100):
            await pub2.publish("y/back", b"rsvp", qos=0)
            try:
                got2 = await asyncio.wait_for(sub2.messages.get(), 0.2)
                break
            except asyncio.TimeoutError:
                continue
        assert got2 is not None and got2.payload == b"rsvp"

        for c in (sub, pub, sub2, pub2):
            await c.disconnect()

    asyncio.run(go())


def test_autocluster_static_discovery(tmp_path):
    """Two processes with `cluster { discovery = static }` config and no
    explicit --join must find each other (run_node drives autocluster);
    proven by cross-node delivery."""
    import time

    confs = {}
    for name, my_rpc, peer_rpc in (("a", 17771, 17772),
                                   ("b", 17772, 17771)):
        c = tmp_path / f"{name}.conf"
        c.write_text(f"""
        listeners {{ t {{ type = tcp, bind = "127.0.0.1", port = 0 }} }}
        cluster {{ discovery = static,
                   nodes = ["127.0.0.1:{peer_rpc}"] }}
        """)
        confs[name] = (str(c), my_rpc)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = []
    try:
        ports = {}
        for name, (conf, rpc) in confs.items():
            p = subprocess.Popen(
                [sys.executable, os.path.join(REPO, "tools",
                                              "run_node.py"),
                 "--name", f"{name}@127.0.0.1", "--no-device",
                 "--config", conf, "--rpc-port", str(rpc)],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                env=env)
            procs.append(p)
            line = _readline_deadline(p, 60).strip()
            assert line.startswith("READY "), line
            ports[name] = int(line.split()[1])

        async def go():
            from emqx_tpu.client import Client
            from emqx_tpu.mqtt import packet as P
            sub = Client(port=ports["a"], clientid="s")
            await sub.connect()
            await sub.subscribe([("auto/#", P.SubOpts(qos=0))])
            pub = Client(port=ports["b"], clientid="p")
            await pub.connect()
            got = None
            for i in range(150):
                await pub.publish(f"auto/{i}", b"x", qos=0)
                try:
                    got = await asyncio.wait_for(sub.messages.get(), 0.2)
                    break
                except asyncio.TimeoutError:
                    pass
            assert got is not None, "autocluster never joined"
        asyncio.run(go())
    finally:
        for p in procs:
            p.kill()
        for p in procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                pass


def test_gray_failure_frozen_peer(two_nodes):
    """SIGSTOP (gray failure: TCP open, node unresponsive) must not park
    CONNECT on the survivor: the clientid-lock RPC and the heartbeat
    probe both bound their connect/handshake phase, so failure detection
    proceeds and the lock skips the frozen target within ~detection +
    one RPC timeout. Pre-fix this parked 25s+ (unbounded handshake wedged
    the beat loop, so nodedown never fired)."""
    import time

    (pa, mqtt_a, _), (pb, _mqtt_b, _) = two_nodes

    async def go():
        from emqx_tpu.client import Client
        from emqx_tpu.mqtt import packet as P

        warm = Client(port=mqtt_a, clientid="warm")
        await warm.connect()
        await warm.disconnect()

        os.kill(pb.pid, signal.SIGSTOP)
        try:
            await asyncio.sleep(0.3)
            t0 = time.monotonic()
            c = Client(port=mqtt_a, clientid="during-freeze")
            await c.connect(timeout=20)
            dt = time.monotonic() - t0
            assert dt < 15, f"gray failure parked CONNECT {dt:.1f}s"
            # the survivor still serves end-to-end during the freeze
            await c.subscribe([("gray/t", P.SubOpts(qos=1))])
            await c.publish("gray/t", b"ping", qos=1)
            got = await asyncio.wait_for(c.messages.get(), 10)
            assert got.payload == b"ping"
            await c.disconnect()
        finally:
            os.kill(pb.pid, signal.SIGCONT)

        await asyncio.sleep(2)            # thaw: autoheal
        c2 = Client(port=mqtt_a, clientid="after-thaw")
        await c2.connect(timeout=10)
        await c2.disconnect()

    asyncio.run(go())


def test_node_death_is_survivable(two_nodes):
    """Killing B must leave A serving: its clients still pub/sub locally."""
    (pa, mqtt_a, _), (pb, _mqtt_b, _) = two_nodes

    async def go():
        from emqx_tpu.client import Client
        from emqx_tpu.mqtt import packet as P

        pb.kill()
        pb.wait(timeout=10)
        await asyncio.sleep(0.2)

        c = Client(port=mqtt_a, clientid="local-a")
        await c.connect()
        await c.subscribe([("alive/check", P.SubOpts(qos=1))])
        await c.publish("alive/check", b"ping", qos=1)
        got = await asyncio.wait_for(c.messages.get(), 5)
        assert got.payload == b"ping"
        await c.disconnect()

    asyncio.run(go())
