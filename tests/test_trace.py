"""Window-causal flight recorder (ISSUE 7).

Coverage, per the issue's satellite list:

- tracing on/off A/B shape equivalence (EMQX_TPU_TRACE=0 restores the
  pre-ISSUE-7 behavior exactly: no recorder object, identical delivery
  counts, identical snapshot schema minus the `trace` section)
- ring-buffer wraparound under sustained load (unit + live pipeline)
- Perfetto / Chrome trace-event JSON well-formedness, and the
  offline analyzer round-tripping through the dump
- Prometheus exposition of the new `trace.*` counter family
- the causal fix: a supervise window replay KEEPS its original trace
  id with the replay linked as a child span; a lane-worker restart
  keeps the plan's trace
- the doc-drift gate: every metric name cited in
  docs/OBSERVABILITY.md exists in the live registry (or the source),
  and exported observability families are documented
- the tracing-overhead guard: span recording costs <3% of a window at
  default sampling
"""

import asyncio
import json
import os
import re
import sys
import time

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))

from emqx_tpu.broker import supervise as S            # noqa: E402
from emqx_tpu.broker import trace as T                # noqa: E402
from emqx_tpu.broker.message import make              # noqa: E402
from emqx_tpu.broker.node import Node                 # noqa: E402


def run(coro, timeout=180):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()


class Sink:
    def __init__(self):
        self.got = []

    def deliver(self, topic_filter, msg):
        self.got.append((topic_filter, msg.topic, bytes(msg.payload)))
        return True


def _mk_node(**over):
    conf = {"device_fanout_cap": 16, "device_slot_cap": 4,
            "device_min_batch": 4, "batch_window_us": 1000,
            "deliver_lanes": 2}
    conf.update(over)
    return Node({"broker": conf})


def _subscribe(node, n=8):
    sinks = []
    for i in range(n):
        s = Sink()
        sid = node.broker.register(s, f"c{i}")
        node.broker.subscribe(sid, f"t/{i}/+", {"qos": 1})
        sinks.append(s)
    return sinks


async def _warm(node, n=8):
    """Warm the (1, b{n}) class (needs a running loop: the background
    warm tasks are spawned on it)."""
    node.device_engine.route_batch(
        [make("p", 0, f"t/{i}/w", b"") for i in range(n)])
    eng = node.device_engine
    deadline = time.monotonic() + 90
    while not eng.batch_class_warm(n) and time.monotonic() < deadline:
        eng._kick_class_warm()
        await asyncio.sleep(0.05)
    assert eng.batch_class_warm(n), "device classes never warmed"


async def _drive(node, windows=8, n=8, warm=True):
    if warm:
        await _warm(node, n)
    out = []
    for w in range(windows):
        out.extend(await asyncio.gather(*[
            node.publish_async(make("p", 1, f"t/{i}/x", b"m%d" % w))
            for i in range(n)]))
    # lanes settle before the loop closes
    pool = node.deliver_lanes
    if pool is not None and pool.busy():
        await pool.drain()
    return out


@pytest.fixture(scope="module")
def traced_run():
    """One warmed, traced pipeline run shared by the read-only tests:
    (node, delivered counts). trace_sample=1 so message spans are
    deterministic. The batcher's adaptive chooser legitimately host-
    routes most windows on CPU (the host trie IS faster at batch 8),
    so the device path is pinned on for half the windows to keep
    dispatch/materialize spans in the ring."""
    node = _mk_node(trace_sample=1)
    _subscribe(node)

    async def go():
        await _warm(node)
        node.publish_batcher._device_worth_it = lambda n: True
        out = await _drive(node, windows=6, warm=False)
        del node.publish_batcher.__dict__["_device_worth_it"]
        out += await _drive(node, windows=4, warm=False)
        return out
    counts = run(go())
    return node, counts


# ---------- knob resolution ----------

class TestKnobs:
    def test_config_beats_env_beats_default(self, monkeypatch):
        assert T.resolve_trace(None) is True
        monkeypatch.setenv("EMQX_TPU_TRACE", "0")
        assert T.resolve_trace(None) is False
        assert T.resolve_trace(True) is True     # config wins
        monkeypatch.setenv("EMQX_TPU_TRACE_SAMPLE", "17")
        assert T.resolve_trace_sample(None) == 17
        assert T.resolve_trace_sample(5) == 5
        with pytest.raises(ValueError):
            T.resolve_trace_sample(-1)

    def test_host_only_node_has_no_recorder(self):
        node = Node(use_device=False)
        assert node.flight_recorder is None


# ---------- the ring buffer ----------

class TestRing:
    def test_wraparound_keeps_newest(self):
        rec = T.FlightRecorder(cap=16, sample=0)
        tid = rec.new_trace()
        for i in range(40):
            rec.record(tid, f"s{i}", float(i), float(i) + 0.5)
        spans = rec.spans()
        assert len(spans) == 16
        # oldest were overwritten; order is monotone by span id
        names = [s.name for s in spans]
        assert names == [f"s{i}" for i in range(24, 40)]
        assert rec.recorded() == 40
        assert rec.dropped() == 24
        st = rec.state()
        assert st["cap"] == 16 and st["dropped"] == 24

    def test_sampling_cadence(self):
        rec = T.FlightRecorder(cap=16, sample=4)
        hits = [rec.sample_hit() for _ in range(12)]
        assert hits == [True, False, False, False] * 3
        assert not any(T.FlightRecorder(cap=16, sample=0).sample_hit()
                       for _ in range(8))

    def test_counters_ride_metrics(self):
        from emqx_tpu.broker.metrics import Metrics
        m = Metrics()
        rec = T.FlightRecorder(m, cap=16, sample=0)
        tid = rec.new_trace()
        for i in range(20):
            rec.record(tid, "s", 0.0, 1.0)
        assert m.val("trace.spans") == 20
        assert m.val("trace.windows") == 1
        assert m.val("trace.dropped") == 4


# ---------- the overlap/bubble analyzer ----------

def _span(tid, sid, name, t0, t1, track="pipeline", parent=0):
    return T.Span(tid, sid, parent, name, track, t0, t1, None)


class TestAnalyzer:
    def test_overlap_and_gap_attribution(self):
        spans = [
            # window 1: enqueue [0,1] dispatch [1,3] (gap 3..5 ends at
            # materialize -> device_stall) materialize [5,6]
            # deliver [6,6.5]
            _span(1, 1, "enqueue", 0.0, 1.0),
            _span(1, 2, "dispatch", 1.0, 3.0),
            _span(1, 3, "materialize", 5.0, 6.0),
            _span(1, 4, "deliver", 6.0, 6.5),
            # window 2's dispatch fully covers window 1's materialize:
            # overlap fraction must be 1.0
            _span(2, 5, "enqueue", 4.0, 4.5),
            _span(2, 6, "dispatch", 4.5, 6.5),
        ]
        a = T.analyze_spans(spans)
        assert a["windows"] == 2
        assert a["overlap"]["dispatch_materialize"] == 1.0
        assert a["overlap"]["materialize_s"] == pytest.approx(1.0)
        w1 = [w for w in a["last_windows"] if w["trace_id"] == 1][0]
        # the 3..5 gap is attributed to the device (readback pending)
        assert w1["bubbles"][0][0] == "device_stall"
        assert w1["bubbles"][0][1] == pytest.approx(2.0)
        assert a["bubbles"]["device_stall_s"] == pytest.approx(2.0)
        assert a["bubbles"]["top"][0][0] == "device_stall"
        # top list bounded at 3
        assert len(a["bubbles"]["top"]) <= 3

    def test_trailing_gap_attribution_follows_lanes(self):
        # with lane spans in the trace, settle-pending time is
        # lane_backpressure; without, it is the host consumer
        lanes = [
            _span(3, 1, "enqueue", 0.0, 1.0),
            _span(3, 2, "lane0", 1.0, 1.2, track="lane0"),
            _span(3, 3, "window", 0.0, 3.0, track="window"),
        ]
        a = T.analyze_spans(lanes)
        w = a["last_windows"][0]
        assert w["bubbles"][0][0] == "lane_backpressure"
        host = [
            _span(4, 4, "enqueue", 0.0, 1.0),
            _span(4, 5, "window", 0.0, 3.0, track="window"),
        ]
        a2 = T.analyze_spans(host)
        assert a2["last_windows"][0]["bubbles"][0][0] == "host_stall"

    def test_partial_overlap_fraction(self):
        spans = [
            _span(1, 1, "materialize", 0.0, 2.0),
            _span(2, 2, "dispatch", 1.0, 5.0),      # covers [1,2] of M
            _span(1, 3, "dispatch", 0.0, 2.0),      # SAME trace: ignored
        ]
        a = T.analyze_spans(spans)
        assert a["overlap"]["dispatch_materialize"] == \
            pytest.approx(0.5)


# ---------- Chrome / Perfetto export ----------

class TestChromeExport:
    def test_well_formed_and_round_trips(self, traced_run):
        node, _counts = traced_run
        rec = node.flight_recorder
        doc = rec.to_chrome()
        # JSON-serializable as a whole (Perfetto loads the same bytes)
        doc2 = json.loads(json.dumps(doc))
        evs = doc2["traceEvents"]
        assert evs, "no trace events recorded"
        tids_named = set()
        pids_named = set()
        for ev in evs:
            assert ev["ph"] in ("M", "X", "i")
            assert "pid" in ev and isinstance(ev["name"], str)
            if ev["ph"] == "M":
                if ev["name"] == "thread_name":
                    tids_named.add(ev["tid"])
                elif ev["name"] == "process_name":
                    pids_named.add(ev["pid"])
                continue
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
            assert ev["tid"] in tids_named
            assert ev["pid"] in pids_named
            assert "trace_id" in ev["args"]
            if ev["ph"] == "X":
                assert ev["dur"] >= 0
            else:
                assert ev["s"] in ("t", "p", "g")
        # the analyzer reads its own dump identically
        a_live = rec.analyze(per_window=10**6)
        a_dump = T.analyze_chrome(doc2)
        assert a_dump["windows"] == a_live["windows"]
        assert a_dump.get("overlap") == a_live.get("overlap")

    def test_dump_and_report(self, traced_run, tmp_path):
        node, _counts = traced_run
        path = node.flight_recorder.dump(str(tmp_path / "flight.json"))
        import trace_report
        rc = trace_report.main([path, "--json"])
        assert rc == 0
        rc2 = trace_report.main([path, "--top", "2", "--windows", "3"])
        assert rc2 == 0
        # an empty trace exits 2 so CI can assert capture happened
        empty = tmp_path / "empty.json"
        empty.write_text('{"traceEvents": []}')
        assert trace_report.main([str(empty)]) == 2


# ---------- the live pipeline: spans, sections, wraparound ----------

class TestPipelineTracing:
    def test_window_spans_cover_the_pipeline(self, traced_run):
        node, counts = traced_run
        assert all(c == 1 for c in counts)
        rec = node.flight_recorder
        names = {s.name for s in rec.spans()}
        # window-granularity always-on spans
        assert {"enqueue", "batch_form", "window"} <= names
        # the device path ran for at least some windows
        assert "dispatch" in names or "dispatch_cached" in names
        assert "materialize" in names and "deliver" in names
        # trace_sample=1: every settled window carries message spans
        assert "message" in names
        msg = next(s for s in rec.spans() if s.name == "message")
        assert msg.meta and msg.meta["topic"].startswith("t/")

    def test_live_ring_wraparound_under_sustained_load(self):
        node = _mk_node(trace_sample=1, trace_ring=16)
        _subscribe(node)
        counts = run(_drive(node, windows=10))
        assert all(c == 1 for c in counts)
        rec = node.flight_recorder
        # 10 windows x (several pipeline + 8 message spans) into a
        # 16-slot ring: wrapped, newest retained, nothing crashed and
        # the analyzer still runs on the partial tail
        assert rec.dropped() > 0
        assert len(rec.spans()) == rec.cap
        assert node.metrics.val("trace.dropped") == rec.dropped()
        rec.analyze()

    def test_causal_chain_parents(self, traced_run):
        node, _counts = traced_run
        spans = node.flight_recorder.spans()
        by_id = {s.span_id: s for s in spans}
        child = [s for s in spans
                 if s.name in ("batch_form", "message") and s.parent_id]
        assert child, "no parented spans in the ring"
        for s in child:
            p = by_id.get(s.parent_id)
            if p is not None:       # parent may have been overwritten
                assert p.trace_id == s.trace_id
                assert p.name == "enqueue"

    def test_snapshot_trace_section(self, traced_run):
        node, _counts = traced_run
        snap = node.pipeline_telemetry.snapshot()
        tr = snap["trace"]
        assert tr["schema"] == T.SCHEMA
        assert tr["ring"]["recorded"] > 0
        assert tr["windows"] > 0
        assert "overlap" in tr and "bubbles" in tr
        assert "dispatch_materialize" in tr["overlap"]
        assert tr["bubbles"]["top"], "no bubble attribution"
        assert len(tr["last_windows"]) <= 4
        for w in tr["last_windows"]:
            assert len(w["bubbles"]) <= 3
        json.dumps(snap)    # the whole document stays JSON-clean

    def test_sys_publishes_trace_section(self, traced_run):
        node, _counts = traced_run
        from emqx_tpu.apps.sys import SysBroker
        seen = {}

        class Spy(SysBroker):
            def _pub(self, suffix, payload):
                seen[suffix] = payload
        Spy(node).publish_pipeline()
        assert "pipeline/trace" in seen
        doc = json.loads(seen["pipeline/trace"])
        assert doc["ring"]["recorded"] > 0

    def test_prometheus_carries_trace_family(self, traced_run):
        node, _counts = traced_run
        from emqx_tpu.apps.prometheus import collect
        text = collect(node)
        assert "emqx_trace_spans" in text
        assert "emqx_trace_windows" in text
        for line in text.splitlines():
            if line.startswith("emqx_trace_spans "):
                assert int(line.split()[1]) > 0
                break
        else:
            raise AssertionError("emqx_trace_spans sample missing")

    def test_api_endpoint(self, traced_run):
        node, _counts = traced_run
        from emqx_tpu.mgmt import make_api

        async def _get(port, path):
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port)
            writer.write(f"GET {path} HTTP/1.1\r\nhost: x\r\n"
                         "connection: close\r\n\r\n".encode())
            await writer.drain()
            raw = await asyncio.wait_for(reader.read(-1), 10)
            writer.close()
            head, _, body = raw.partition(b"\r\n\r\n")
            assert b"200" in head.split(b"\r\n")[0], head
            return json.loads(body)

        async def go():
            srv = make_api(node, port=0)
            await srv.start()
            try:
                doc = await _get(srv.port, "/api/v5/pipeline/trace")
                assert doc["summary"]["windows"] > 0
                assert "ring" in doc
                doc2 = await _get(
                    srv.port, "/api/v5/pipeline/trace?format=perfetto")
                assert doc2["traceEvents"]
            finally:
                await srv.stop()
        run(go())


# ---------- A/B: EMQX_TPU_TRACE=0 restores current behavior ----------

class TestTraceOffAB:
    def test_off_means_no_recorder_and_same_results(self):
        node_off = _mk_node(trace=False)
        assert node_off.flight_recorder is None
        assert node_off.pipeline_telemetry.recorder is None
        _subscribe(node_off)
        counts_off = run(_drive(node_off, windows=6))
        node_on = _mk_node(trace=True, trace_sample=1)
        _subscribe(node_on)
        counts_on = run(_drive(node_on, windows=6))
        # delivery shape is bit-identical either way
        assert counts_off == counts_on
        # snapshot schema identical minus the trace section
        snap_off = node_off.pipeline_telemetry.snapshot()
        snap_on = node_on.pipeline_telemetry.snapshot()
        assert "trace" not in snap_off
        assert set(snap_off) == set(snap_on) - {"trace"}
        # no trace counters leak into the off registry
        assert node_off.metrics.val("trace.spans") == 0
        # handles carry no trace when off (engine-side A/B)
        h = node_off.device_engine.prepare(
            [make("p", 0, "t/0/z", b"")])
        if h is not None:
            assert h.trace == 0
            node_off.device_engine.abandon(h)

    def test_env_knob_off(self, monkeypatch):
        monkeypatch.setenv("EMQX_TPU_TRACE", "0")
        node = _mk_node()
        assert node.flight_recorder is None


# ---------- causal context survives replay + lane restart ----------

class TestReplaySurvival:
    def test_replay_keeps_trace_id_and_links_child_span(self):
        node = _mk_node(supervise_threshold=8, trace_sample=0)
        _subscribe(node)
        sup = node.supervisor
        assert sup is not None and sup.recorder is node.flight_recorder
        counts = run(self._drive_with_fault(node, sup))
        assert all(c == 1 for c in counts), "replay lost deliveries"
        rec = node.flight_recorder
        spans = rec.spans()
        replays = [s for s in spans if s.name == "replay"]
        assert replays, "no replay span recorded"
        rp = replays[0]
        # the replayed window KEEPS its original trace: its admit
        # (enqueue) span is on the same trace id
        same_trace = [s.name for s in spans
                      if s.trace_id == rp.trace_id]
        assert "enqueue" in same_trace
        # ... and the host re-route is the replay's CHILD span
        child = [s for s in spans if s.name == "host_route"
                 and s.parent_id == rp.span_id]
        assert child and child[0].trace_id == rp.trace_id
        # the window still settled (roll-up span present)
        assert "window" in same_trace
        assert node.metrics.val("supervise.replays") >= 1

    async def _drive_with_fault(self, node, sup):
        await _warm(node)
        # pin the device choice on: the CPU host trie outruns the jit
        # call at batch 8, so the adaptive chooser would route the
        # faulted window around the injection point
        node.publish_batcher._device_worth_it = lambda n: True
        out = []
        # a couple of healthy windows first, then arm one dispatch
        # exception — the faulted window must replay host-side
        out.extend(await asyncio.gather(*[
            node.publish_async(make("p", 1, f"t/{i}/x", b"a"))
            for i in range(8)]))
        sup.injector = S.FaultInjector(S.parse_faults(
            "dispatch:exception:count=1"))
        for w in range(6):
            out.extend(await asyncio.gather(*[
                node.publish_async(make("p", 1, f"t/{i}/x", b"b"))
                for i in range(8)]))
            if sup.injector.faults[0].fired:
                break
        pool = node.deliver_lanes
        if pool is not None and pool.busy():
            await pool.drain()
        return out

    def test_lane_restart_keeps_plan_trace(self):
        node = _mk_node(deliver_lanes=2, supervise_threshold=8)
        sup = node.supervisor
        sup.wd_floor_s = 0.1
        sup.wd_mult = 0.0
        pool = node.deliver_lanes
        rec = node.flight_recorder
        s = Sink()
        sid = node.broker.register(s, "c1")

        async def go():
            pool.ensure_loop()
            pool.pause()
            # plan1 is popped and HELD at the gate when the workers
            # die (surrendered, lost-but-accounted); plan2 stays
            # queued with its trace — only the drain watchdog's
            # revival can deliver it
            p1 = pool.new_plan([make("p", 0, "a/1", b"one")])
            p1.trace = rec.new_trace()
            p1.register_fast([0])
            p1.add_rows_py(0, [(sid, 0, "a/+")])
            pool.submit(p1)
            tid = rec.new_trace()
            p2 = pool.new_plan([make("p", 0, "a/2", b"two")])
            p2.trace = tid
            p2.register_fast([0])
            p2.add_rows_py(0, [(sid, 0, "a/+")])
            pool.submit(p2)
            await asyncio.sleep(0.05)
            for w in pool._workers:
                w.cancel()          # simulated worker death
            await asyncio.sleep(0.05)
            pool.resume()
            await pool.drain()      # watchdog revives + drains
            return tid, p2.done
        tid, done = run(go(), timeout=60)
        assert done
        assert node.metrics.val("supervise.restarts") >= 1
        # the revived worker recorded its lane span on the ORIGINAL
        # trace (causal context rode the plan, not the dead task)...
        lane_spans = [sp for sp in rec.spans()
                      if sp.name.startswith("lane")
                      and sp.trace_id == tid]
        assert lane_spans, "lane span lost across worker restart"
        # ... and the restart itself is on the node-scope timeline
        assert any(sp.name == "restart" and sp.trace_id == 0
                   for sp in rec.spans())


# ---------- doc-drift gate (CI satellite) ----------

_DOC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "docs", "OBSERVABILITY.md")

# a backticked token counts as a metric name when it is dotted,
# lowercase and not a file / config / code / JSON-path reference.
# Metric roots are the registry's actual top-level families — a token
# rooted anywhere else (`stages.dispatch.p99_ms`, `node.x`, `jax.y`)
# is a snapshot path or code reference, not a metric name.
_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_{}*]+)+$")
_METRIC_ROOTS = ("pipeline", "routing", "supervise", "match_cache",
                 "trace", "messages", "packets", "bytes", "delivery",
                 "client", "session", "authorization", "deliver")
_NOT_METRICS_SUFFIX = (".py", ".md", ".erl", ".json")

# observability-owned families that must be documented when exported
_FAMILY_PREFIXES = ("pipeline.", "routing.", "supervise.",
                    "match_cache.", "trace.")


def _doc_metric_names():
    with open(_DOC) as f:
        text = f.read()
    names = set()
    for tok in re.findall(r"`([^`\n]+)`", text):
        tok = tok.strip()
        if not _NAME_RE.match(tok):
            continue
        if tok.split(".")[0] not in _METRIC_ROOTS \
                or tok.endswith(_NOT_METRICS_SUFFIX):
            continue
        names.add(tok)
    return names, text


@pytest.fixture(scope="module")
def source_blob():
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "emqx_tpu")
    parts = []
    for dirpath, _dirs, files in os.walk(root):
        if "__pycache__" in dirpath:
            continue
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(dirpath, fn)) as f:
                    parts.append(f.read())
    return "\n".join(parts)


class TestDocDrift:
    def test_documented_metrics_exist(self, traced_run, source_blob):
        """Every metric name docs/OBSERVABILITY.md cites must exist —
        in the live registry of a traced pipeline run, or (for names
        whose traffic the run can't produce: churn, faults, compact
        overflow) as a literal in the source. A doc citing a renamed/
        deleted metric fails here."""
        node, _counts = traced_run
        live = set(node.metrics.all()) | set(node.metrics.histograms())
        live |= set(node.stats.sample())
        names, _text = _doc_metric_names()
        assert names, "doc parser found no metric names at all"
        missing = []
        for name in sorted(names):
            probe = name.split("{")[0].split("*")[0].rstrip(".")
            if name in live or probe in live:
                continue
            if any(n.startswith(probe) for n in live):
                continue        # templated family (deliver_lane{i})
            if f'"{probe}' in source_blob \
                    or f"'{probe}" in source_blob:
                continue        # literal (or literal prefix) in code
            # dynamic leaf (f"match_cache.{k}"): the FAMILY literal
            # must still exist in code — whole-family renames fail
            fam = ".".join(probe.split(".")[:-1])
            if fam and (f'"{fam}.' in source_blob
                        or f"'{fam}." in source_blob):
                continue
            missing.append(name)
        assert not missing, (
            f"docs/OBSERVABILITY.md cites metrics that exist nowhere "
            f"(rename drift?): {missing}")

    def test_exported_families_are_documented(self, traced_run):
        """The reverse direction: every observability family this run
        actually exported must appear in the doc — a new family landing
        without documentation fails here."""
        node, _counts = traced_run
        _names, text = _doc_metric_names()
        live = [n for n, v in node.metrics.all().items() if v]
        live += list(node.metrics.histograms())
        undocumented = set()
        for name in live:
            if not name.startswith(_FAMILY_PREFIXES):
                continue
            fam = ".".join(name.split(".")[:2])
            if fam not in text:
                undocumented.add(fam)
        assert not undocumented, (
            f"exported observability families missing from "
            f"docs/OBSERVABILITY.md: {sorted(undocumented)}")


# ---------- tracing-overhead guard ----------

class TestOverheadGuard:
    def test_span_recording_under_3pct_of_window(self, traced_run):
        """The guard is deterministic, not a wall-clock race: measure
        the per-record cost of the recorder primitive, count the spans
        an average window actually records (from the live ring), and
        bound overhead = spans/window * cost/record against 3% of the
        measured mean window span. A hot-path regression (e.g. an
        analysis call leaking into record()) fails this immediately;
        scheduler noise cannot."""
        node, _counts = traced_run
        rec = node.flight_recorder
        probe = type(rec)(cap=4096, sample=rec.sample)
        tid = probe.new_trace()
        n = 4000
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _i in range(n):
                probe.record(tid, "x", 0.0, 1.0, track="p",
                             meta={"k": 1})
            best = min(best, (time.perf_counter() - t0) / n)
        a = rec.analyze(per_window=10**6)
        wins = a["last_windows"]
        assert wins
        mean_span = sum(w["span_s"] for w in wins) / len(wins)
        # spans per window: ring spans belonging to window traces
        spans = [s for s in rec.spans() if s.trace_id > 0]
        per_window = len(spans) / max(1, len({s.trace_id
                                              for s in spans}))
        overhead = per_window * best
        assert overhead < 0.03 * mean_span, (
            f"tracing records {per_window:.1f} spans/window at "
            f"{best * 1e6:.2f}us each = {overhead * 1e3:.3f}ms, vs "
            f"window span {mean_span * 1e3:.1f}ms — over the 3% budget")

    def test_ab_wall_clock_sanity(self):
        """Loose A/B backstop (gross regressions only — the 3% claim
        is carried by the deterministic bound above): tracing on must
        not cost more than 25% wall clock on the sync route_batch +
        publish path."""
        def bench(trace_on: bool) -> float:
            node = _mk_node(trace=trace_on, deliver_lanes=0,
                            batch_window_us=0)
            _subscribe(node)

            async def go():
                await _warm(node)
                t0 = time.perf_counter()
                for w in range(12):
                    await asyncio.gather(*[
                        node.publish_async(
                            make("p", 0, f"t/{i}/x", b"m"))
                        for i in range(8)])
                return time.perf_counter() - t0
            return run(go())
        off = min(bench(False), bench(False))
        on = min(bench(True), bench(True))
        assert on <= off * 1.25 + 0.05, (off, on)


# ---------- ISSUE 24: one span call, three sinks ----------

def _profile(tmp_path, coro_fn):
    """Run `coro_fn()` under a jax.profiler session on the CPU backend;
    returns (result, {thread line name: [(name, start, end, stats)]})
    of the host plane."""
    import glob

    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tdir = str(tmp_path / "prof")
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        out = run(coro_fn())
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        tdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    lines: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for k, line in enumerate(plane.lines):
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats)) for ev in line.events
                   if ev.name.startswith("emqx:")
                   or ev.name == "route_step"]
            if evs:
                lines[f"{line.name}#{k}"] = evs
    return out, lines


def _events(lines, name):
    return [e for evs in lines.values() for e in evs if e[0] == name]


class TestOneSpanCall:
    def test_unit_three_sinks_and_release(self, tmp_path):
        """Spans.span feeds the stage histogram, the ring (with trace
        id and parent) and an emqx: annotation carrying the trace id;
        released() splits the annotation, not histogram or ring."""
        from emqx_tpu.broker.telemetry import PipelineTelemetry
        tele = PipelineTelemetry(track_compiles=False)
        rec = T.FlightRecorder(tele.metrics, cap=64, sample=0)
        spans = T.Spans(tele, rec)
        tid = rec.new_trace()

        async def go():
            root = spans.record("enqueue", tid, time.perf_counter(),
                                stage="enqueue", track="batcher")
            with spans.span("host_route", tid, track="host",
                            parent=root, meta={"k": 3}) as sp:
                with sp.released():
                    await asyncio.sleep(0.002)
            with spans.span("finish_sub", tid, stage="deliver"):
                pass
            with spans.span("prepare_window"):      # no trace: no ring
                pass
            return root, sp
        (root, sp), lines = _profile(tmp_path, go)
        ring = {s.name: s for s in rec.spans()}
        assert set(ring) == {"enqueue", "host_route", "deliver"}
        assert ring["host_route"].parent_id == root == ring[
            "enqueue"].span_id
        assert ring["host_route"].span_id == sp.sid
        assert ring["host_route"].meta == {"k": 3}
        assert ring["host_route"].dur == pytest.approx(sp.dur) \
            and sp.dur >= 0.002
        stages = tele.snapshot()["stages"]
        assert {k: v["count"] for k, v in stages.items()} == {
            "enqueue": 1, "host_route": 1, "deliver": 1}
        hr = _events(lines, "emqx:host_route")
        assert len(hr) == 2                     # split at the await
        assert all(e[3] == {"k": 3, "trace_id": tid} for e in hr)
        # the two pieces leave the wait out
        assert sum(e[2] - e[1] for e in hr) < (sp.dur - 0.0015) * 1e9
        assert [e[3] for e in _events(lines, "emqx:finish_sub")] == [
            {"trace_id": tid}]
        assert [e[3] for e in _events(lines, "emqx:prepare_window")] \
            == [{}]

    def test_pipeline_spans_reach_any_profiler_session(self, tmp_path):
        """Nobody calls start_device_trace: a profiler session started
        from outside sees the stages as emqx:* events with their
        window's trace id, and every dispatch as a route_step step
        whose step_num is that id."""
        node = _mk_node(trace_sample=0)
        _subscribe(node)
        assert not hasattr(node.device_engine, "_tracing")

        async def go():
            await _warm(node)
            node.publish_batcher._device_worth_it = lambda n: True
            n0 = len(node.flight_recorder.spans())
            out = await _drive(node, windows=4, warm=False)
            return n0, out
        (n0, counts), lines = _profile(tmp_path, go)
        assert sum(counts) > 0
        ring = node.flight_recorder.spans()[n0:]
        by_name: dict = {}
        for s in ring:
            by_name.setdefault(s.name, set()).add(s.trace_id)
        # (the warm pass's direct route_batch carries no trace: its
        # spans are there too, with no trace_id and step_num 0)
        assert all({"W", "B", "cached"} <= set(e[3])
                   for e in _events(lines, "emqx:dispatch"))
        disp = [e for e in _events(lines, "emqx:dispatch")
                if "trace_id" in e[3]]
        assert {e[3]["trace_id"] for e in disp} == by_name["dispatch"]
        steps = [e for e in _events(lines, "route_step")
                 if e[3]["step_num"]]
        assert {e[3]["step_num"] for e in steps} == by_name["dispatch"]
        for st in steps:        # the step nests inside emqx:dispatch
            assert any(d[1] <= st[1] and st[2] <= d[2] for d in disp)
        for name, ring_name in (("emqx:materialize", "materialize"),
                                ("emqx:finish_sub", "deliver"),
                                ("emqx:batch_form", "batch_form"),
                                ("emqx:settle", "settle")):
            got = {e[3]["trace_id"] for e in _events(lines, name)
                   if "trace_id" in e[3]}
            assert got and got <= by_name[ring_name], name
        lanes = _events(lines, "emqx:lane")
        assert lanes and all(e[3]["lane"] in (0, 1) for e in lanes)
        assert _events(lines, "emqx:prepare_window")
        # the histograms moved with them (the one call's first sink)
        st = node.pipeline_telemetry.snapshot()["stages"]
        assert st["dispatch"]["count"] >= len(by_name["dispatch"])
        assert st["materialize"]["count"] >= 1 \
            and st["deliver"]["count"] >= 1

    def test_loop_thread_spans_never_enclose_an_await(self, tmp_path):
        """Two publishers interleave on the loop (host windows of 200
        messages yield every 64; device windows ride the lanes): on
        every thread, any two emqx: spans are disjoint or nested —
        a span left open across an await would partially overlap the
        other coroutine's."""
        node = _mk_node(trace_sample=0, max_publish_batch=256)
        _subscribe(node)
        flip = [0]

        def alternate(n):
            flip[0] += 1
            return flip[0] % 2 == 0

        async def pub(tag):
            out = []
            for w in range(3):
                out += await asyncio.gather(*[
                    node.publish_async(make(
                        tag, 1, f"t/{i % 8}/x", b"%d" % w))
                    for i in range(200)])
                await asyncio.sleep(0)
            return out

        async def go():
            await _warm(node)
            node.publish_batcher._device_worth_it = alternate
            a, b = await asyncio.gather(pub("p1"), pub("p2"))
            pool = node.deliver_lanes
            if pool is not None and pool.busy():
                await pool.drain()
            return a + b
        counts, lines = _profile(tmp_path, go)
        assert len(counts) == 1200 and all(c >= 1 for c in counts)
        pieces = _events(lines, "emqx:host_route")
        hosts = [s for s in node.flight_recorder.spans()
                 if s.name == "host_route"]
        assert hosts and len(pieces) > len(hosts)   # released at yields
        checked = 0
        for evs in lines.values():
            evs = sorted((e for e in evs if e[0].startswith("emqx:")),
                         key=lambda e: (e[1], -e[2]))
            stack = []
            for name, s, e, _st in evs:
                while stack and stack[-1][2] <= s:
                    stack.pop()
                if stack:
                    assert e <= stack[-1][2], (
                        f"{name} [{s}, {e}] partially overlaps "
                        f"{stack[-1][0]} [{stack[-1][1]}, "
                        f"{stack[-1][2]}]")
                stack.append((name, s, e))
                checked += 1
        assert checked > 20


class TestGcAndChooserCounters:
    def test_gc_callback_lives_from_start_to_stop(self):
        import gc

        from emqx_tpu.broker.connection import Listener
        node = _mk_node()
        cb = node.gc_watch._on_gc
        assert cb not in gc.callbacks

        async def go():
            lst = Listener(node, bind="127.0.0.1", port=0)
            await lst.start()
            node.start_timers(30.0)
            assert gc.callbacks.count(cb) == 1
            m0 = dict(node.metrics.all())
            n0 = len([s for s in node.flight_recorder.spans()
                      if s.name == "gc"])
            gc.collect(0)
            gc.collect(2)
            m1 = dict(node.metrics.all())
            node.stop_timers()
            assert gc.callbacks.count(cb) == 1      # a listener is up
            await lst.stop()
            return m0, m1, n0
        m0, m1, n0 = run(go())
        assert cb not in gc.callbacks

        def d(k):
            return m1.get(k, 0) - m0.get(k, 0)
        assert d("runtime.gc.pauses.gen0") >= 1
        assert d("runtime.gc.pauses.gen2") >= 1
        assert d("runtime.gc.pause_us") > 0
        gcs = [s for s in node.flight_recorder.spans() if s.name == "gc"]
        assert len(gcs) - n0 >= 1 and gcs[-1].trace_id == T.NODE_TRACE
        assert gcs[-1].meta == {"generation": 2} and gcs[-1].dur > 0
        before = dict(node.metrics.all())
        gc.collect(2)                               # gone after stop
        assert node.metrics.all().get("runtime.gc.pauses.gen2") \
            == before.get("runtime.gc.pauses.gen2")

    def test_gen2_collection_is_a_span_on_the_profiler(self, tmp_path):
        import gc
        node = _mk_node()

        async def go():
            node.gc_watch.start()
            try:
                gc.collect(1)
                gc.collect(2)
            finally:
                node.gc_watch.stop()
        _out, lines = _profile(tmp_path, go)
        assert [e[3] for e in _events(lines, "emqx:gc")] \
            == [{"generation": 2}]

    def test_each_chooser_verdict_is_counted(self):
        node = _mk_node()
        pb = node.publish_batcher
        m = node.metrics

        def verdicts():
            return {k.rsplit(".", 1)[1]: v for k, v in m.all().items()
                    if k.startswith("routing.chooser.")}
        assert pb._device_worth_it(8) is True           # nothing measured
        assert verdicts() == {"first": 1}
        pb._dev_batch_s = 0.004
        assert pb._device_worth_it(8) is False          # no host cost yet
        pb._host_msg_s = 0.001
        pb._since_host_probe = pb.host_probe_every
        assert pb._device_worth_it(8) is False
        assert verdicts()["host_probe"] == 2
        pb._since_probe = 64
        assert pb._device_worth_it(8) is True
        assert verdicts()["device_probe"] == 1
        assert pb._device_worth_it(8) is True           # 4 ms <= 8 x 1 ms
        assert pb.chooser_margin == pytest.approx(0.5)
        assert pb._device_worth_it(2) is False          # 4 ms > 2 x 1 ms
        assert pb.chooser_margin == pytest.approx(2.0)
        assert verdicts() == {"first": 1, "host_probe": 2,
                              "device_probe": 1, "cost_device": 1,
                              "cost_host": 1}
        assert m.val("routing.device.bypassed") == 1
        # what the probes routed is counted beside the verdicts, not
        # among them (a reader sums `routing.chooser.*` as verdicts)
        assert m.val("routing.host_probe.msgs") == 16
        ch = node.pipeline_telemetry.snapshot()["chooser"]
        assert ch == {"dev_batch_ms": 4.0, "host_msg_us": 1000.0,
                      "margin": 2.0, "probe_gap": 32,
                      "verdicts": verdicts()}


# ---------- ISSUE 24: named scopes change metadata only ----------

def _scope_fixture():
    import numpy as np

    from emqx_tpu.models import router_engine as RE
    from emqx_tpu.ops import intern as I
    from emqx_tpu.ops.delta import build_delta_tables
    from emqx_tpu.ops.fanout import build_subtable
    from emqx_tpu.ops.match import encode_topics
    from emqx_tpu.ops.shapes import build_shape_tables
    from emqx_tpu.ops.trie import build_tables
    from emqx_tpu.utils import topic as TP
    filters = ["dev/+/t", "dev/#", "q/job", "+/x/+"]
    intern = I.InternTable()
    rows = np.zeros((len(filters), 8), np.int32)
    lens = np.zeros(len(filters), np.int64)
    for fid, f in enumerate(filters):
        w = intern.encode_filter(TP.words(f))
        rows[fid, :len(w)] = w
        lens[fid] = len(w)
    subs = build_subtable(len(filters),
                          {0: [(1, 1)], 1: [(2, 2)], 3: [(3, 1)]},
                          {2: [0]}, {0: [(50, 1), (51, 1), (52, 1)]})
    dw = intern.encode_filter(TP.words("n/+/m"))
    delta = build_delta_tables([(dw, 900, [(7, 1), (8, 0)])],
                               row_cap=8, level_cap=8)
    rng = np.random.RandomState(11)
    W, B = 4, 8
    names = ["dev/a/t", "q/job", "n/x/m", "dev/b/c", "none"]
    enc, ln, dol = [], [], []
    for _k in range(W):
        e, l, d, too_long = encode_topics(
            intern, [TP.words(names[rng.randint(len(names))])
                     for _ in range(B)], 8)
        assert not too_long.any()
        enc.append(e), ln.append(l), dol.append(d)
    return {
        "RE": RE, "W": W, "B": B,
        "trie": RE.RouterTables(trie=build_tables(rows, lens), subs=subs),
        "shapes": RE.ShapeRouterTables(
            shapes=build_shape_tables(rows, lens), subs=subs),
        "delta": delta,
        "enc": np.stack(enc), "lens": np.stack(ln), "dol": np.stack(dol),
        "hash": rng.randint(0, 1 << 30, size=(W, B)).astype(np.int32),
        "strat": np.int32(0), "cur": np.zeros(1, np.int32),
    }


def _plain_steps(fx, match):
    """The reference: the same ops, each called on its own outside any
    route program (no scope anywhere), W sequential steps threading the
    cursors; every RouteResult field stacked [W, ...]."""
    import numpy as np

    from emqx_tpu.ops.fanout import fanout_normal, shared_slots
    from emqx_tpu.ops.shared import pick_members
    RE, subs = fx["RE"], fx["trie"].subs
    cur, out = fx["cur"], []
    for k in range(fx["W"]):
        mr = match(fx["enc"][k], fx["lens"][k], fx["dol"][k])
        fr = fanout_normal(subs, mr.matches, fanout_cap=8)
        sids, so = shared_slots(subs, mr.matches, slot_cap=4)
        sp = pick_members(subs, cur, sids, fx["strat"], fx["hash"][k])
        out.append(RE.RouteResult(
            matches=mr.matches, match_counts=mr.counts, rows=fr.rows,
            opts=fr.opts, fan_counts=fr.counts, shared_sids=sids,
            shared_rows=sp.rows, shared_opts=sp.opts,
            overflow=mr.overflow | fr.overflow | so,
            new_cursors=sp.new_cursors, occur=sp.occur,
            match_overflow=mr.overflow, nfa_wide_steps=mr.wide_steps,
            fanout_overflow=fr.overflow))
        cur = sp.new_cursors
    return RE.RouteResult(*[
        None if out[0][i] is None
        else np.stack([np.asarray(r[i]) for r in out])
        for i in range(len(out[0]))])


def _first(stacked):
    """Sub-batch 0 of a window-stacked RouteResult."""
    return type(stacked)(*[x if x is None else x[0] for x in stacked])


def _same(got, want):
    import jax
    import numpy as np
    g, w = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(g) == len(w)
    for a, b in zip(g, w):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert (a == b).all()


def _dedup_plan(fx, by, dmatch=None):
    """A match-cache plan over the fixture's window, as the engine's
    `_plan_window` makes one: the lanes collapse to their unique
    topics, every other unique topic is a cache hit (its base row
    filled from the reference match, and from the overlay's matcher
    where `dmatch` is given), the rest are the miss lanes. Returns
    (WindowPlan, overlay base rows or None, the matcher's result on the
    miss lanes)."""
    import numpy as np
    RE, W, B = fx["RE"], fx["W"], fx["B"]
    flat = (fx["enc"].reshape(W * B, -1), fx["lens"].reshape(W * B),
            fx["dol"].reshape(W * B))
    keys = np.concatenate([flat[0], flat[1][:, None],
                           flat[2][:, None].astype(np.int32)], axis=1)
    _u, first, inv = np.unique(keys, axis=0, return_index=True,
                               return_inverse=True)
    Bu = len(first)
    assert 2 <= Bu <= B
    uniq = tuple(a[first] for a in flat)
    hit = np.arange(Bu) % 2 == 0
    miss_u = np.flatnonzero(~hit)
    Bm = B
    miss = (np.zeros((Bm,) + flat[0].shape[1:], np.int32),
            np.zeros(Bm, np.int32), np.zeros(Bm, bool))
    for dst, src in zip(miss, uniq):
        dst[:len(miss_u)] = src[miss_u]
    pos = np.full(Bm, B, np.int32)              # pad = B: dropped
    pos[:len(miss_u)] = miss_u

    def base_rows(match):
        mr = match(*uniq)
        m = np.full((B,) + mr.matches.shape[1:], -1, np.int32)
        c, o = np.zeros(B, np.int32), np.zeros(B, bool)
        m[:Bu][hit] = np.asarray(mr.matches)[hit]
        c[:Bu][hit] = np.asarray(mr.counts)[hit]
        o[:Bu][hit] = np.asarray(mr.overflow)[hit]
        return m, c, o
    plan = RE.WindowPlan(*miss, *base_rows(by), pos,
                         inv.reshape(W, B).astype(np.int32))
    return (plan, None if dmatch is None else base_rows(dmatch),
            by(*miss))


def _window_cases():
    out = ["step", "step_shapes"]
    for backend in ("shapes", "trie"):
        for plan in ("", "_plan"):
            for delta in ("", "_delta"):
                for compact in ("", "_compact"):
                    out.append(f"window{plan}{delta}{compact}.{backend}")
    return out + ["window_padded.trie"]


@pytest.mark.parametrize("family", _window_cases())
def test_route_outputs_bit_equal_with_scopes(family):
    """jax.named_scope changes HLO metadata only, and `route_window`'s
    optional stages compose without touching one another: the two step
    programs and every combination of the window's stages (the match
    cache's plan x the delta overlay x the CSR readback, on either
    backend, and a trie window with padding sub-batches) return bit for
    bit what the same ops return when called one by one with no scope
    around them (W sequential steps threading the cursors, then the
    unfused `delta_overlay` and `compact_result`); and the scopes are
    in the lowered program's metadata. A trie program reports
    `nfa_wide_steps` (a window with a plan walks once, row 0), which a
    shape-hash program's result does not have."""
    import jax
    import numpy as np

    from emqx_tpu.ops.compact import compact_result
    from emqx_tpu.ops.delta import delta_match, delta_overlay
    from emqx_tpu.ops.match import match_batch
    from emqx_tpu.ops.shapes import shape_match
    fx = _scope_fixture()
    RE, W, B = fx["RE"], fx["W"], fx["B"]
    caps = dict(fanout_cap=8, slot_cap=4)

    def by_trie(e, l, d):
        return match_batch(fx["trie"].trie, e, l, d, frontier_cap=16,
                           match_cap=64)

    def by_shapes(e, l, d):
        return shape_match(fx["shapes"].shapes, e, l, d)

    scopes = {"match", "fanout", "shared"}
    if family in ("step", "step_shapes"):
        trie = family == "step"
        fn = RE.route_step if trie else RE.route_step_shapes
        kw = dict(caps, frontier_cap=16, match_cap=64) if trie else caps
        args = (fx["trie" if trie else "shapes"], fx["cur"],
                fx["enc"][0], fx["lens"][0], fx["dol"][0], fx["hash"][0],
                fx["strat"])
        want = _first(_plain_steps(dict(fx, W=1),
                                   by_trie if trie else by_shapes))
    else:
        stages, backend = family.split(".")
        trie = backend == "trie"
        if "_padded" in stages:
            # the last two sub-batches are the window class's padding: a
            # trie window skips the NFA there and returns what it returns
            fx = dict(fx, lens=fx["lens"].copy())
            fx["lens"][2:] = 0
        tables, by = (fx["trie"], by_trie) if trie \
            else (fx["shapes"], by_shapes)
        kw = dict(caps, frontier_cap=16, match_cap=64) if trie else caps
        flat = (fx["enc"].reshape(W * B, -1), fx["lens"].reshape(W * B),
                fx["dol"].reshape(W * B))
        want = _plain_steps(fx, by)
        scopes |= {"scan"}
        lanes, plan, delta, dbase = (fx["enc"], fx["lens"], fx["dol"]), \
            None, None, None
        if "_plan" in stages:
            dev_delta = jax.device_put(fx["delta"])
            plan, dbase, probe = _dedup_plan(
                fx, by, (lambda e, l, d: delta_match(
                    dev_delta, e, l, d, match_cap=4))
                if "_delta" in stages else None)
            lanes = (None, None, None)
            if trie:    # one walk over the miss lanes, reported in row 0
                want = want._replace(nfa_wide_steps=np.array(
                    [probe.wide_steps] + [0] * (W - 1), np.int32))
        if "_delta" in stages:
            delta = RE.WindowDelta(fx["delta"], dbase)
            kw = dict(kw, delta_match_cap=4, delta_fanout_cap=8)
            dp = delta_overlay(fx["delta"], *flat, match_cap=4,
                               fanout_cap=8)
            want = want._replace(delta=type(dp)(*[
                np.asarray(x).reshape((W, B) + x.shape[1:]) for x in dp]))
            scopes |= {"delta"}
        if "_compact" in stages:
            kw = dict(kw, payload_cap=256)
            r = want
            want = want._replace(compact=compact_result(
                r.matches, r.rows, r.opts, r.fan_counts, r.shared_sids,
                r.shared_rows, r.shared_opts, payload_cap=256,
                match_holes=not trie))
            if delta is not None:
                kw["d_payload_cap"] = 64
                dp = want.delta
                want = want._replace(d_compact=compact_result(
                    dp.fids, dp.rows, dp.opts, dp.fan_counts,
                    np.full((W, B, 1), -1, np.int32),
                    np.zeros((W, B, 1), np.int32),
                    np.zeros((W, B, 1), np.int8),
                    payload_cap=64, match_holes=False))
            scopes |= {"compact"}
        fn = RE.route_window
        args = (tables, fx["cur"]) + lanes + (fx["hash"], fx["strat"],
                                              plan, delta)
    got = fn(*args, **kw)
    assert isinstance(got, RE.RouteResult)
    for name, g, w in zip(got._fields, got, want):
        assert (g is None) == (w is None), name
    _same(got, want)
    assert (got.nfa_wide_steps is not None) == trie
    ops = [ln for ln in fn.lower(*args, **kw).compile().as_text()
           .splitlines() if "op_name=" in ln]
    for name in scopes:
        assert any(f"/{name}/" in ln for ln in ops), name


def test_no_span_is_opened_per_message(tmp_path):
    """1,500 PUBLISHes on 1,500 distinct topics in one write arrive as
    a few read bursts: the spans count with the bursts, the windows
    and the lane items, never with the messages (an authz check that
    released its span for every new topic once made 17,000 spans a
    second of `emqx:ingress`)."""
    from emqx_tpu.broker.connection import Listener
    from emqx_tpu.client import Client
    from emqx_tpu.mqtt import packet as P
    from emqx_tpu.mqtt.frame import serialize
    node = _mk_node(trace_sample=0)
    n = 1500

    async def go():
        lst = Listener(node, bind="127.0.0.1", port=0)
        await lst.start()
        sub = Client(port=lst.port, clientid="sub")
        await sub.connect()
        await sub.subscribe("t/#", qos=0)
        pub = Client(port=lst.port, clientid="pub")
        await pub.connect()
        blob = bytearray()
        for i in range(n):
            blob += serialize(P.Publish(topic=f"t/{i}", payload=b"x",
                                        qos=0), 4)
        pub._writer.write(bytes(blob))
        await pub._writer.drain()
        for _ in range(n):
            await asyncio.wait_for(sub.messages.get(), 30)
        bursts = node.metrics.val("pipeline.ingress.bursts")
        await pub.close()
        await sub.close()
        await lst.stop()
        await node.publish_batcher.stop()
        return bursts
    bursts, lines = _profile(tmp_path, go)
    assert node.metrics.val("pipeline.ingress.rows") >= n - 64
    ingress = _events(lines, "emqx:ingress")
    # a decode span a read, and a burst's hand-off split at its
    # 64-row yields
    assert bursts <= len(ingress) <= 4 * bursts + n // 64 + 64
    spans = [e for evs in lines.values() for e in evs
             if e[0].startswith("emqx:")]
    assert len(spans) < n / 4


# ---------- ISSUE 29: the NFA's steps, and how many ran narrow ----------

def test_engine_counts_the_nfa_steps_and_the_narrow_ones():
    """`routing.device.nfa_steps` is `max_levels + 1` a sub-batch the
    NFA walked (none for a padding sub-batch, whose walk is skipped; one
    walk for a window that took the match-cache plan),
    `nfa_narrow_steps` those that ran below `frontier_cap`: all of them
    where a topic has a few live paths, not where `+`s fan a topic out
    over more than `ops/match.NARROW_WIDTHS` holds."""
    from tools.workloads import shape_spread_filters
    spread = shape_spread_filters(24, tail_hash=True)
    # a tail of its own each, so that none covers another
    fan = ["w/" + "/".join(x if (m >> i) & 1 else "+"
                           for i, x in enumerate("abc")) + f"/t{m}"
           for m in range(8)]
    deep = [make("p", 0, f.replace("+", "x").replace("#", "y"), b"")
            for f in spread]
    wide = make("p", 0, "w/a/b/c/t7", b"")   # 8 live paths into level 4

    def trie_node(**conf):
        node = _mk_node(**conf)
        node.device_engine.shape_cap = 1      # before the first build
        sid = node.broker.register(Sink(), "c")
        for f in spread + fan:
            node.broker.subscribe(sid, f, {"qos": 0})
        node.device_engine.rebuild()
        assert node.device_engine.stats()["backend"] == "trie"
        return node, node.device_engine

    def route(eng, window):
        h = eng.prepare_window(window, gate_cold=False)
        eng.dispatch(h)
        eng.materialize(h)
        for k in range(len(h.subs)):
            eng.finish_sub(h, k)
        return h

    def counted(node):
        st = node.device_engine.stats()
        got = (node.metrics.val("routing.device.nfa_steps"),
               node.metrics.val("routing.device.nfa_narrow_steps"))
        assert (st["nfa_steps"], st["nfa_narrow_steps"]) == got
        return got
    node, eng = trie_node(topic_dedup=False)
    steps = eng.max_levels + 1
    assert counted(node) == (0, 0)
    # three sub-batches in a window class of eight: five walks skipped
    h = route(eng, [deep[:8], deep[8:16], deep[16:]])
    assert h.enc[0].shape[0] == 8
    assert list(h.res.nfa_wide_steps) == [0] * 8
    assert counted(node) == (3 * steps, 3 * steps)
    h = route(eng, [[wide, deep[0]]])
    assert list(h.res.nfa_wide_steps) == [1]
    assert counted(node) == (4 * steps, 4 * steps - 1)
    # the match-cache plan walks once, over the window's distinct topics
    node, eng = trie_node()
    h = route(eng, [[wide] * 40 + [deep[0]] * 40])
    assert h.plan is not None
    assert counted(node) == (steps, steps - 1)
    # a shape-hash snapshot counts none
    node = _mk_node()
    _subscribe(node)
    node.device_engine.route_batch([make("p", 0, "t/1/x", b"")])
    assert node.device_engine.stats()["backend"] == "shapes"
    assert node.metrics.val("routing.device.windows") == 1
    assert counted(node) == (0, 0)
