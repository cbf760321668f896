"""Tests: alarms, $SYS broker, OS monitors, tracer, rate limiters.

Mirrors the reference suites emqx_alarm_SUITE, emqx_sys_SUITE,
emqx_os_mon_SUITE, emqx_tracer_SUITE and the limiter/force_shutdown
coverage in emqx_connection_SUITE.
"""

import asyncio
import time

import pytest

from emqx_tpu.apps.sys import SysBroker
from emqx_tpu.apps.tracer import Tracer
from emqx_tpu.broker.alarm import AlarmManager
from emqx_tpu.broker.connection import Listener
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.limiter import (ConnectionLimiter, ForceShutdownPolicy,
                                     QuotaLimiter, TokenBucket)
from emqx_tpu.broker.message import make
from emqx_tpu.broker.monitor import OsMon, cpu_load, proc_memory, sys_memory
from emqx_tpu.broker.node import Node
from emqx_tpu.client import Client
from emqx_tpu.mqtt import constants as C


class Sink:
    def __init__(self):
        self.got = []

    def deliver(self, f, m):
        self.got.append((f, m))
        return True


# ---------- alarms ----------

class TestAlarms:
    def test_lifecycle_and_hooks(self):
        h = Hooks()
        seen = []
        h.add("alarm.activated", lambda a: seen.append(("on", a["name"])))
        h.add("alarm.deactivated", lambda a: seen.append(("off", a["name"])))
        am = AlarmManager(h)
        assert am.activate("overload", {"v": 1}, "too hot")
        assert not am.activate("overload")       # already active
        assert am.is_active("overload")
        assert len(am.get_alarms("activated")) == 1
        assert am.deactivate("overload")
        assert not am.deactivate("overload")
        assert len(am.get_alarms("deactivated")) == 1
        assert seen == [("on", "overload"), ("off", "overload")]

    def test_history_cap_and_expiry(self):
        am = AlarmManager(None, size_limit=2, validity_period=0.05)
        for i in range(4):
            am.activate(f"a{i}")
            am.deactivate(f"a{i}")
        assert len(am.get_alarms("deactivated")) == 2
        time.sleep(0.06)
        am.tick()
        assert am.get_alarms("deactivated") == []

    def test_ensure_edge_trigger(self):
        am = AlarmManager(None)
        am.ensure("x", True)
        am.ensure("x", True)
        assert len(am.get_alarms("activated")) == 1
        am.ensure("x", False)
        assert not am.is_active("x")


# ---------- monitors ----------

class TestOsMon:
    def test_readings(self):
        used, total = sys_memory()
        assert total > 0 and 0 < used <= total
        assert proc_memory() > 0
        assert cpu_load() >= 0

    def test_watermark_alarm(self):
        am = AlarmManager(None)
        mon = OsMon(am, {"sysmem_high_watermark": 0.0000001,
                         "procmem_high_watermark": 2.0})
        mon.tick()
        assert am.is_active("high_system_memory_usage")
        assert not am.is_active("high_process_memory_usage")
        mon.sysmem_high = 2.0
        mon.tick()
        assert not am.is_active("high_system_memory_usage")


# ---------- $SYS ----------

class TestSysBroker:
    def test_heartbeat_and_stats_topics(self):
        node = Node({"broker": {"sys_heartbeat_interval": 0,
                                "sys_msg_interval": 0}})
        sys_app = node.register_app(SysBroker(node).load())
        sink = Sink()
        sid = node.broker.register(sink, "w")
        node.broker.subscribe(sid, "$SYS/#")
        sys_app.publish_heartbeat()
        topics = [m.topic for _, m in sink.got]
        assert "$SYS/brokers" in topics
        assert f"$SYS/brokers/{node.name}/version" in topics
        assert f"$SYS/brokers/{node.name}/uptime" in topics
        sink.got.clear()
        sys_app.publish_stats_metrics()
        topics = [m.topic for _, m in sink.got]
        assert any("/stats/connections.count" in t for t in topics)
        assert any("/metrics/messages.publish" in t for t in topics)

    def test_alarm_republish(self):
        node = Node()
        node.register_app(SysBroker(node).load())
        sink = Sink()
        sid = node.broker.register(sink, "w")
        node.broker.subscribe(sid, "$SYS/brokers/+/alarms/#")
        node.alarms.activate("boom", {}, "kapow")
        assert sink.got and sink.got[-1][1].topic.endswith("alarms/activate")
        node.alarms.deactivate("boom")
        assert sink.got[-1][1].topic.endswith("alarms/deactivate")


# ---------- tracer ----------

class TestTracer:
    def test_trace_clientid_and_topic(self, tmp_path):
        node = Node()
        tr = node.register_app(Tracer(node).load())
        f1 = tmp_path / "c1.log"
        f2 = tmp_path / "top.log"
        assert tr.start_trace("clientid", "c1", str(f1))
        assert not tr.start_trace("clientid", "c1", str(f1))
        assert tr.start_trace("topic", "t/#", str(f2))
        assert len(tr.lookup_traces()) == 2
        node.hooks.run("client.connected", ({"clientid": "c1"}, {}))
        node.broker.publish(make("c1", 1, "x/y", b"payload1"))
        node.broker.publish(make("other", 0, "t/1", b"payload2"))
        node.broker.publish(make("other", 0, "nope", b"payload3"))
        text1 = f1.read_text()
        assert "CONNECTED clientid=c1" in text1
        assert "topic=x/y" in text1
        text2 = f2.read_text()
        assert "topic=t/1" in text2 and "payload3" not in text2
        assert tr.stop_trace("topic", "t/#")
        assert not tr.stop_trace("topic", "t/#")
        assert len(tr.lookup_traces()) == 1


# ---------- limiters ----------

class TestTokenBucket:
    def test_burst_then_pace(self):
        tb = TokenBucket(rate=10, burst=5)
        now = time.monotonic()
        assert all(tb.consume(1, now) == 0 for _ in range(5))
        pause = tb.consume(1, now)
        assert pause > 0
        assert tb.consume(1, now + pause + 1e-6) == 0

    def test_quota(self):
        q = QuotaLimiter(rate=2, burst=2)
        assert q.check_publish() and q.check_publish()
        assert not q.check_publish()
        assert QuotaLimiter(None).check_publish()

    def test_conn_limiter(self):
        cl = ConnectionLimiter(msgs_rate=1, bytes_rate=None)
        assert cl.check(1, 100) == 0
        assert cl.check(1, 100) > 0
        assert ConnectionLimiter().check(1000, 10**9) == 0

    def test_force_shutdown(self):
        from emqx_tpu.broker.session import Session, SessionConf
        from emqx_tpu.broker.mqueue import MQueueOpts
        pol = ForceShutdownPolicy(max_mqueue_len=2)
        s = Session("c", SessionConf(max_inflight=1,
                                     mqueue=MQueueOpts(max_len=100)))
        assert pol.violated(s) is None
        s.deliver([(make("p", 1, "t", b"x"), {"qos": 1}) for _ in range(5)])
        assert pol.violated(s) == "mqueue_overflow"
        assert pol.violated(None) is None


class TestLimiterEndToEnd:
    @pytest.fixture()
    def loop(self):
        loop = asyncio.new_event_loop()
        yield loop
        loop.close()

    def test_quota_exceeded_rc(self, loop):
        node = Node({"rate_limit": {"quota_messages_routing": 2}})
        lst = Listener(node, bind="127.0.0.1", port=0)
        loop.run_until_complete(lst.start())

        async def go():
            c = Client(port=lst.port, clientid="q", proto_ver=C.MQTT_V5)
            await c.connect()
            rcs = []
            for i in range(4):
                ack = await c.publish("t", b"x", qos=1)
                rcs.append(ack.reason_code)
            assert C.RC_QUOTA_EXCEEDED in rcs
            assert rcs[0] != C.RC_QUOTA_EXCEEDED
            await c.disconnect()
        try:
            loop.run_until_complete(asyncio.wait_for(go(), 15))
        finally:
            loop.run_until_complete(lst.stop())

    def test_force_shutdown_kills_connection(self, loop):
        node = Node({"force_shutdown": {"max_mqueue_len": 3},
                     "mqtt": {"max_inflight": 1, "max_mqueue_len": 100}})
        lst = Listener(node, bind="127.0.0.1", port=0)
        loop.run_until_complete(lst.start())

        async def go():
            slow = Client(port=lst.port, clientid="slow")
            slow.auto_ack = False        # never acks → inflight stays full
            await slow.connect()
            await slow.subscribe("f/t", qos=1)
            pub = Client(port=lst.port, clientid="pub")
            await pub.connect()
            for i in range(8):
                await pub.publish("f/t", b"x", qos=1)
            # timer tick (1s) must detect the overflow and kill `slow`
            await asyncio.wait_for(slow.closed.wait(), 5)
            assert node.metrics.val("connection.force_shutdown") == 1
            await pub.disconnect()
        try:
            loop.run_until_complete(asyncio.wait_for(go(), 15))
        finally:
            loop.run_until_complete(lst.stop())


class TestCongestion:
    """emqx_congestion.erl analog: write-buffer congestion alarms with
    sustain-duration hysteresis."""

    def test_alarm_lifecycle(self):
        from emqx_tpu.broker.congestion import Congestion

        class FakeTransport:
            def __init__(self):
                self.size = 0

            def get_write_buffer_size(self):
                return self.size

        class FakeWriter:
            def __init__(self):
                self.transport = FakeTransport()

        node = Node(use_device=False)

        class Ch:
            clientid = "c1"
            clientinfo = {"username": "u1"}
            conninfo = {"peername": ("127.0.0.1", 1)}
            conn_state = "connected"

        w = FakeWriter()
        cg = Congestion(node, Ch(), w, enable_alarm=True,
                        min_alarm_sustain_duration=0.05)
        cg.check()
        assert not node.alarms.get_alarms("activated")  # not congested yet
        w.transport.size = 4096
        cg.check()
        acts = node.alarms.get_alarms("activated")
        assert any(a["name"] == "conn_congestion/c1/u1" for a in acts)
        # still congested: stays active
        cg.check()
        assert node.alarms.get_alarms("activated")
        # drained, but within sustain window: still active
        w.transport.size = 0
        cg.check()
        assert node.alarms.get_alarms("activated")
        time.sleep(0.06)
        cg.check()
        assert not node.alarms.get_alarms("activated")

    def test_disabled_noop(self):
        from emqx_tpu.broker.congestion import Congestion
        node = Node(use_device=False)

        class Ch:
            clientid = "c"
            clientinfo = {}
            conninfo = {}
            conn_state = "connected"

        class W:
            transport = None
        cg = Congestion(node, Ch(), W())
        cg.check()
        cg.cancel()
        assert not node.alarms.get_alarms("activated")


class TestLogFormatters:
    """emqx_logger_jsonfmt/textfmt + metadata scoping."""

    @pytest.fixture()
    def loop(self):
        loop = asyncio.new_event_loop()
        yield loop
        loop.close()

    def test_json_formatter_with_metadata(self):
        import json as _json
        import logging as _logging

        from emqx_tpu.utils import logger as L
        records = []

        class Cap(_logging.Handler):
            def emit(self, record):
                records.append(self.format(record))

        h = Cap()
        h.setFormatter(L.JsonFormatter())
        h.addFilter(L.MetadataFilter())
        lg = _logging.getLogger("emqx_tpu.testjson")
        lg.addHandler(h)
        lg.setLevel(_logging.INFO)
        try:
            L.set_metadata_clientid("cli-9")
            L.set_metadata_peername("10.0.0.9:1234")
            lg.info("client subscribed %s", "t/1")
            out = _json.loads(records[0])
            assert out["msg"] == "client subscribed t/1"
            assert out["level"] == "info"
            assert out["clientid"] == "cli-9"
            assert out["peername"] == "10.0.0.9:1234"
            assert isinstance(out["time"], int)
        finally:
            lg.removeHandler(h)
            L.clear_metadata()

    def test_json_formatter_unjsonable_extra(self):
        import json as _json
        import logging as _logging

        from emqx_tpu.utils import logger as L
        f = L.JsonFormatter()
        rec = _logging.makeLogRecord(
            {"msg": "x", "levelname": "INFO", "name": "n",
             "payload": b"\xff\xfe", "obj": object()})
        out = _json.loads(f.format(rec))
        assert "payload" in out and "obj" in out

    def test_text_formatter(self):
        import logging as _logging

        from emqx_tpu.utils import logger as L
        f = L.TextFormatter()
        rec = _logging.makeLogRecord(
            {"msg": "hello", "levelname": "WARNING", "name": "n"})
        rec.emqx_metadata = {"clientid": "c1", "peername": "1.2.3.4:5"}
        line = f.format(rec)
        assert "[warning]" in line and "c1@1.2.3.4:5:" in line \
            and "hello" in line

    def test_metadata_isolated_per_task(self, loop):
        from emqx_tpu.utils import logger as L

        async def task(cid, out):
            L.set_metadata_clientid(cid)
            await asyncio.sleep(0.01)
            out[cid] = dict(L._log_metadata.get())

        async def go():
            out = {}
            await asyncio.gather(task("a", out), task("b", out))
            assert out["a"]["clientid"] == "a"
            assert out["b"]["clientid"] == "b"
        loop.run_until_complete(go())


# ---------- pipeline telemetry: histograms ----------

class TestHistogram:
    """broker.metrics.Histogram — log2-bucket math edge cases."""

    def _h(self, **kw):
        from emqx_tpu.broker.metrics import Histogram
        return Histogram("t", **kw)

    def test_zero_and_min_land_in_first_bucket(self):
        h = self._h(lo=1e-6, n_buckets=4)
        h.observe(0.0)
        h.observe(1e-6)        # exactly the first bound: inclusive
        h.observe(-1.0)        # clamped, never a negative index
        assert h.counts[0] == 3 and h.count == 3

    def test_exact_bounds_are_inclusive(self):
        h = self._h(lo=1.0, n_buckets=4)       # bounds 1, 2, 4, 8
        for v in (1.0, 2.0, 4.0, 8.0):
            h.observe(v)
        assert h.counts[:4] == [1, 1, 1, 1]
        h2 = self._h(lo=1.0, n_buckets=4)
        h2.observe(2.0001)                     # just past a bound
        assert h2.counts[2] == 1

    def test_max_bound_and_overflow(self):
        h = self._h(lo=1.0, n_buckets=3)       # bounds 1, 2, 4
        h.observe(4.0)                         # last finite bucket
        h.observe(4.1)                         # overflow
        h.observe(1e12)                        # deep overflow
        assert h.counts[2] == 1
        assert h.counts[-1] == 2               # +Inf-only bucket
        cum = h.cumulative()
        assert cum[-1][0] == float("inf") and cum[-1][1] == 3
        assert cum[-2][1] == 1                 # finite cum excludes oflow

    def test_cumulative_monotone_and_count(self):
        h = self._h(lo=1e-6, n_buckets=10)
        import random
        rng = random.Random(5)
        for _ in range(500):
            h.observe(rng.uniform(0, 2e-4))
        cum = h.cumulative()
        vals = [c for _, c in cum]
        assert vals == sorted(vals)
        assert vals[-1] == h.count == 500

    def test_percentile(self):
        h = self._h(lo=1.0, n_buckets=8)
        assert h.percentile(0.99) == 0.0       # empty
        for _ in range(99):
            h.observe(1.5)                     # bucket le=2
        h.observe(100.0)                       # bucket le=128
        assert h.percentile(0.50) == 2.0
        assert h.percentile(0.99) == 2.0
        assert h.percentile(1.0) == 128.0

    def test_snapshot_fields(self):
        h = self._h(lo=1.0, n_buckets=4)
        h.observe(1.0)
        h.observe(3.0)
        s = h.snapshot()
        assert s["count"] == 2 and s["sum"] == 4.0 and s["mean"] == 2.0
        assert s["p50"] >= 1.0 and s["p99"] >= s["p50"]

    def test_metrics_registry(self):
        from emqx_tpu.broker.metrics import Metrics
        m = Metrics()
        h = m.hist("pipeline.stage.x.seconds")
        assert m.hist("pipeline.stage.x.seconds") is h
        h.observe(0.001)
        assert m.histograms()["pipeline.stage.x.seconds"].count == 1


class TestCompileAccounting:
    def test_jit_trace_attributed_to_context(self):
        import jax
        import jax.numpy as jnp

        from emqx_tpu.broker.telemetry import PipelineTelemetry
        tele = PipelineTelemetry()
        with tele.compile_context("W1xB17"):
            f = jax.jit(lambda x: x * 3 + 1)   # fresh fn: jit-cache miss
            f(jnp.zeros(17))
        snap = tele.snapshot()
        assert snap["compiles"]["count"] >= 1
        assert snap["compiles"]["total_s"] > 0
        assert "W1xB17" in snap["compiles"]["by_shape"]
        assert snap["compiles"]["by_shape"]["W1xB17"]["count"] >= 1
        # outside any context: not attributed to this instance
        before = tele.compiles
        g = jax.jit(lambda x: x - 2)
        g(jnp.zeros(13))
        assert tele.compiles == before

    def test_jit_cache_sizes_surface(self):
        from emqx_tpu.models.router_engine import compile_stats
        st = compile_stats()
        # ISSUE 15: mesh exchange programs (one per segment-capacity
        # class) fold into the same namespace under exchange_step_*
        named = {k for k in st if not k.startswith("exchange_step_")}
        assert named == {"route_step", "route_step_shapes",
                         "route_window_shapes", "route_window"}
        assert all(isinstance(v, int) for v in st.values())


# ---------- pipeline telemetry: the publish-path smoke test ----------

def _http_get(loop, port, path):
    import json as _json

    async def go():
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(f"GET {path} HTTP/1.1\r\nhost: x\r\n"
                     "connection: close\r\n\r\n".encode())
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(-1), 10)
        writer.close()
        head, _, body = raw.partition(b"\r\n\r\n")
        return head.split(b"\r\n")[0], body
    status, body = loop.run_until_complete(asyncio.wait_for(go(), 15))
    assert b"200" in status, status
    try:
        return _json.loads(body)
    except ValueError:
        return body


class TestPipelineSpans:
    """The acceptance-criterion smoke test: a pytest-driven publish burst
    through PublishBatcher + DeviceRouteEngine, then the snapshot and
    the REST endpoint report per-stage p50/p95/p99 and occupancy."""

    @pytest.fixture()
    def loop(self):
        loop = asyncio.new_event_loop()
        yield loop
        loop.close()

    def _burst_node(self, loop):
        from emqx_tpu.broker.message import make
        node = Node()          # device path on (CPU jax backend)
        b = node.broker
        sink = Sink()
        sid = b.register(sink, "w")
        for i in range(32):
            b.subscribe(sid, f"pt/{i}/+")
        # sync device route: compiles inline, exercises prepare →
        # dispatch → materialize → finish (occupancy + device stages)
        msgs = [make("p", 0, f"pt/{i % 32}/x", b"d") for i in range(16)]
        assert node.device_engine.route_batch(msgs) is not None

        async def burst():
            for _ in range(4):
                await asyncio.gather(*[
                    node.publish_async(make("p", 0, f"pt/{i % 32}/y", b"h"))
                    for i in range(48)])
            await node.publish_batcher.stop()
        loop.run_until_complete(asyncio.wait_for(burst(), 60))
        return node

    def test_snapshot_and_api_after_burst(self, loop):
        from emqx_tpu.mgmt.api import make_api
        node = self._burst_node(loop)
        snap = node.pipeline_telemetry.snapshot()
        # batched path stages all saw traffic
        for stage in ("enqueue", "batch_form", "total",
                      "dispatch", "materialize", "deliver"):
            assert snap["stages"].get(stage, {}).get("count", 0) > 0, stage
        for row in snap["stages"].values():
            assert row["p50_ms"] <= row["p95_ms"] <= row["p99_ms"]
        # occupancy recorded for the b64 shape class (16/64 fill)
        occ = [k for k in snap["occupancy"] if k.startswith("b")]
        assert occ, snap["occupancy"]
        assert 0 < snap["occupancy"][occ[0]]["mean_fill"] <= 1.0
        assert snap["decisions"]  # device/host decisions accounted
        assert snap["compiles"]["count"] >= 1  # route_batch cold compile

        # the REST surface serves the same schema
        srv = make_api(node, port=0)
        loop.run_until_complete(srv.start())
        try:
            doc = _http_get(loop, srv.port, "/api/v5/pipeline/stats")
        finally:
            loop.run_until_complete(srv.stop())
        assert doc["schema"] == snap["schema"]
        for stage, row in doc["stages"].items():
            assert {"p50_ms", "p95_ms", "p99_ms"} <= set(row), stage
        assert doc["occupancy"]

    def test_slow_batch_hook_and_trace(self, loop, tmp_path):
        from emqx_tpu.broker.message import make
        node = Node({"broker": {"slow_batch_threshold_ms": 1e-9}},
                    use_device=False)
        # host-only node still runs the batched pipeline? no — without a
        # batcher publishes go straight through; drive telemetry direct
        node.pipeline_telemetry.record_total(0.5, batch=8, path="host")
        assert node.metrics.val("pipeline.slow_batches") == 1

        # through the tracer: hook fires into slow_batch trace files
        tr = node.register_app(Tracer(node).load())
        path = tmp_path / "slow.log"
        assert tr.start_trace("slow_batch", "*", str(path))
        node.pipeline_telemetry.record_total(0.5, batch=4, path="device")
        text = path.read_text()
        assert "SLOW_BATCH" in text and "path=device" in text
        # slow_batch traces never capture ordinary publishes
        node.broker.publish(make("c", 0, "x/y", b"p"))
        assert "PUBLISH" not in path.read_text()
        assert tr.stop_trace("slow_batch", "*")


# ---------- exporters: Prometheus exposition validity ----------

def _parse_exposition(text):
    """Strict-enough exposition parser: returns {family: {type, samples}}
    and asserts one TYPE per family + family-contiguous samples."""
    import re
    families = {}
    current = None
    for line in text.rstrip("\n").split("\n"):
        if line.startswith("# HELP"):
            continue
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split(" ", 3)
            assert name not in families, f"duplicate TYPE for {name}"
            families[name] = {"type": kind, "samples": []}
            current = name
            continue
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})? (\S+)$", line)
        assert m, f"unparseable sample line: {line!r}"
        name, labels, value = m.groups()
        fam = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and \
                    name[: -len(suffix)] in families and \
                    families[name[: -len(suffix)]]["type"] == "histogram":
                fam = name[: -len(suffix)]
        assert fam in families, f"sample before TYPE: {line!r}"
        assert fam == current, \
            f"family {fam} not contiguous (current={current}): {line!r}"
        families[fam]["samples"].append((name, labels, value))
    return families


class TestPrometheusExposition:
    def test_valid_exposition_with_traffic(self):
        from emqx_tpu.apps.prometheus import collect
        node = Node(use_device=False)
        node.metrics.inc("messages.publish", 3)
        tele = node.pipeline_telemetry
        for v in (1e-5, 2e-4, 0.003, 0.04):
            tele.observe_stage("dispatch", v)
        tele.record_occupancy("b64", 0.25)
        fams = _parse_exposition(collect(node))

        fam = fams["emqx_pipeline_stage_dispatch_seconds"]
        assert fam["type"] == "histogram"
        les, cums = [], []
        saw_sum = saw_count = False
        for name, labels, value in fam["samples"]:
            if name.endswith("_bucket"):
                le = labels[len('{le="'):-2]
                les.append(float("inf") if le == "+Inf" else float(le))
                cums.append(int(value))
            elif name.endswith("_sum"):
                saw_sum = True
            elif name.endswith("_count"):
                saw_count = True
                assert int(value) == 4
        assert saw_sum and saw_count
        assert les == sorted(les) and les[-1] == float("inf")
        assert cums == sorted(cums) and cums[-1] == 4
        assert fams["emqx_pipeline_occupancy_b64"]["type"] == "histogram"

    def test_rule_families_one_type_and_escaped_labels(self):
        from emqx_tpu.apps.prometheus import collect
        from emqx_tpu.broker.message import make
        from emqx_tpu.rules import RuleEngine
        node = Node(use_device=False)
        eng = RuleEngine(node).load()
        eng.create_rule('SELECT * FROM "m/#"',
                        [{"name": "do_nothing", "params": {}}],
                        rule_id='r"quote\\slash')
        eng.create_rule('SELECT * FROM "m/#"',
                        [{"name": "do_nothing", "params": {}}],
                        rule_id="plain")
        node.broker.publish(make("p", 0, "m/1", b""))
        text = collect(node)
        fams = _parse_exposition(text)   # asserts single TYPE + grouping
        fam = fams["emqx_rule_sql_matched"]
        assert len(fam["samples"]) == 2  # both rules under ONE family
        assert '\\"' in text             # quote escaped in label value
        import re
        for _n, labels, _v in fam["samples"]:
            assert re.fullmatch(r'\{rule="(?:[^"\\\n]|\\.)*"\}', labels), \
                labels


# ---------- exporters: StatsD timers + final flush ----------

class TestStatsdPipeline:
    @pytest.fixture()
    def loop(self):
        loop = asyncio.new_event_loop()
        yield loop
        loop.close()

    def _recv_all(self, sock):
        out = ""
        while True:
            try:
                out += sock.recv(65536).decode()
            except BlockingIOError:
                return out

    def test_histogram_ms_timers(self, loop):
        import socket

        from emqx_tpu.apps.statsd import StatsdApp
        node = Node(use_device=False)

        async def go():
            sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            sock.bind(("127.0.0.1", 0))
            sock.setblocking(False)
            app = StatsdApp(node, {"host": "127.0.0.1",
                                   "port": sock.getsockname()[1],
                                   "interval": 60})
            app.load()
            h = node.metrics.hist("pipeline.stage.dispatch.seconds")
            h.observe(0.002)
            h.observe(0.004)
            app.flush()
            await asyncio.sleep(0.1)
            data = self._recv_all(sock)
            # interval mean 3ms, sample rate 1/2 observations
            assert "emqx.pipeline.stage.dispatch.seconds:3.000|ms|@0.5" \
                in data
            # second flush with no new observations: no timer line
            app.flush()
            await asyncio.sleep(0.1)
            data = self._recv_all(sock)
            assert "|ms" not in data
            app.unload()
            sock.close()
        loop.run_until_complete(asyncio.wait_for(go(), 15))

    def test_unload_flushes_final_interval(self, loop):
        import socket

        from emqx_tpu.apps.statsd import StatsdApp
        node = Node(use_device=False)

        async def go():
            sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            sock.bind(("127.0.0.1", 0))
            sock.setblocking(False)
            app = StatsdApp(node, {"host": "127.0.0.1",
                                   "port": sock.getsockname()[1],
                                   "interval": 3600})
            app.load()
            node.metrics.inc("messages.publish", 9)
            app.unload()             # NO explicit flush: unload must send
            await asyncio.sleep(0.1)
            data = self._recv_all(sock)
            assert "emqx.messages.publish:9|c" in data
            assert app._sock is None
            sock.close()
        loop.run_until_complete(asyncio.wait_for(go(), 15))


# ---------- $SYS pipeline topics ----------

class TestSysPipelineTopics:
    def test_pipeline_topics_published(self):
        import json as _json
        node = Node(use_device=False)
        tele = node.pipeline_telemetry
        tele.observe_stage("dispatch", 0.002)
        tele.record_occupancy("b64", 0.5)
        tele.record_decision("device", 3)
        sys_app = node.register_app(SysBroker(node).load())
        sink = Sink()
        sid = node.broker.register(sink, "w")
        node.broker.subscribe(sid, "$SYS/#")
        sys_app.publish_pipeline()
        by_topic = {m.topic: m.payload for _, m in sink.got}
        base = f"$SYS/brokers/{node.name}/pipeline"
        stage = _json.loads(by_topic[f"{base}/stages/dispatch"])
        assert stage["count"] == 1 and "p99_ms" in stage
        occ = _json.loads(by_topic[f"{base}/occupancy/b64"])
        assert occ["mean_fill"] == 0.5
        assert f"{base}/compiles" in by_topic
        dec = _json.loads(by_topic[f"{base}/decisions"])
        assert dec["device"] == 3
        # and the periodic stats/metrics publish carries them too
        sink.got.clear()
        sys_app.publish_stats_metrics()
        assert any(t.startswith(f"{base}/stages/")
                   for t, _ in ((m.topic, m) for _, m in sink.got))
