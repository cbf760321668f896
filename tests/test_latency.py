"""End-to-end message latency SLO observatory (ISSUE 13).

Coverage, per the issue's satellite list:

- knob matrix: broker.latency_observatory / EMQX_TPU_LATENCY and
  broker.slo_route_p99_ms / EMQX_TPU_SLO_ROUTE_P99_MS
  (config-beats-env-beats-default, malformed fails loudly)
- knob-off A/B twin: EMQX_TPU_LATENCY=0 ⇒ no observatory object, no
  `latency` snapshot section, REST 404, bit-identical delivery counts
  and per-publisher order
- per-path attribution oracle: device / host / a FORCED host-fallback
  window (prepare_window declines) / a journal replay (injected
  dispatch fault) each land in their own (qos, path) series
- burst-vs-per-packet ingress-stamp equivalence (the PR 11 twins)
- the sub-millisecond Histogram mode (substeps) unit behavior + the
  stage-family migration (names unchanged, quarter-octave ladder)
- SLO engine: burn-rate windows, breach exemplars linked to the
  flight-recorder trace of the exact slow message, hook throttling
- exporter expositions (snapshot section, $SYS, Prometheus, REST)
- deterministic <3%-per-message overhead guard at default sampling
- tools/latency_report.py: report + the exit-2 CI gate against a
  p99-less bench row
"""

import asyncio
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))

from emqx_tpu.broker import latency as L                  # noqa: E402
from emqx_tpu.broker import supervise as S                # noqa: E402
from emqx_tpu.broker.hooks import Hooks                   # noqa: E402
from emqx_tpu.broker.message import Message, make         # noqa: E402
from emqx_tpu.broker.metrics import Histogram, Metrics    # noqa: E402
from emqx_tpu.broker.node import Node                     # noqa: E402
from emqx_tpu.broker.trace import FlightRecorder          # noqa: E402
from emqx_tpu.mqtt import constants as C                  # noqa: E402
from emqx_tpu.mqtt import packet as P                     # noqa: E402
from emqx_tpu.mqtt.frame import (FrameParser, PublishBurst,  # noqa: E402
                                 serialize)


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
        self.got.append((msg.topic, bytes(msg.payload)))
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


def _stamped(from_, qos, topic, payload=b""):
    """A publish message carrying a real ingress stamp — what the
    frame parser + channel produce for socket traffic."""
    m = make(from_, qos, topic, payload)
    m.ingress_ns = time.perf_counter_ns()
    return m


async def _warm(node, n=8):
    node.device_engine.route_batch(
        [make("p", 0, f"t/{i}/w", b"") for i in range(n)])
    eng = node.device_engine
    deadline = time.monotonic() + 90
    while not eng.batch_class_warm(n) and time.monotonic() < deadline:
        eng._kick_class_warm()
        await asyncio.sleep(0.05)
    assert eng.batch_class_warm(n), "device classes never warmed"


async def _drive(node, windows=4, n=8, qos=1, warm=True, tag="x"):
    if warm:
        await _warm(node, n)
    out = []
    for w in range(windows):
        out.extend(await asyncio.gather(*[
            node.publish_async(
                _stamped("p", qos, f"t/{i}/{tag}", b"m%d" % w))
            for i in range(n)]))
    pool = node.deliver_lanes
    if pool is not None and pool.busy():
        await pool.drain()
    return out


def _routed_paths(node):
    """The (leg, qos, path) series the observatory actually recorded."""
    return {key for key, h in
            node.latency_observatory._hist.items() if h.count}


# ---------- knob resolution ----------

class TestKnobs:
    def test_observatory_default_on(self, monkeypatch):
        monkeypatch.delenv("EMQX_TPU_LATENCY", raising=False)
        assert L.resolve_latency_observatory() is True

    def test_env_off(self, monkeypatch):
        monkeypatch.setenv("EMQX_TPU_LATENCY", "0")
        assert L.resolve_latency_observatory() is False

    def test_config_beats_env(self, monkeypatch):
        monkeypatch.setenv("EMQX_TPU_LATENCY", "0")
        assert L.resolve_latency_observatory(True) is True
        monkeypatch.setenv("EMQX_TPU_LATENCY", "1")
        assert L.resolve_latency_observatory(False) is False

    def test_objective_default(self, monkeypatch):
        monkeypatch.delenv("EMQX_TPU_SLO_ROUTE_P99_MS", raising=False)
        assert L.resolve_slo_route_p99_ms() == 2.0

    def test_objective_env_and_config(self, monkeypatch):
        monkeypatch.setenv("EMQX_TPU_SLO_ROUTE_P99_MS", "5.5")
        assert L.resolve_slo_route_p99_ms() == 5.5
        # config beats env
        assert L.resolve_slo_route_p99_ms(1.25) == 1.25

    def test_objective_malformed_fails_loudly(self, monkeypatch):
        monkeypatch.setenv("EMQX_TPU_SLO_ROUTE_P99_MS", "fast")
        with pytest.raises(ValueError):
            L.resolve_slo_route_p99_ms()
        with pytest.raises(ValueError):
            L.resolve_slo_route_p99_ms(0)
        with pytest.raises(ValueError):
            L.resolve_slo_route_p99_ms(-3)

    def test_node_env_knob_off(self, monkeypatch):
        monkeypatch.setenv("EMQX_TPU_LATENCY", "0")
        node = _mk_node()
        assert node.latency_observatory is None
        assert node.pipeline_telemetry.observatory is None
        assert node.broker.latency_obs is None
        assert node.publish_batcher.obs is None


# ---------- sub-millisecond Histogram mode (satellite 2) ----------

class TestFineHistogram:
    def test_bounds_quarter_octave(self):
        h = Histogram("x", lo=1e-6, n_buckets=16, substeps=4)
        for a, b in zip(h.bounds, h.bounds[1:]):
            assert b / a == pytest.approx(2 ** 0.25)
        # every 4th bound is an exact octave of lo
        assert h.bounds[4] == pytest.approx(2e-6)
        assert h.bounds[8] == pytest.approx(4e-6)

    def test_index_matches_reference(self):
        h = Histogram("x", lo=1e-6, n_buckets=40, substeps=4)

        def ref_index(v):
            if v <= h.lo:
                return 0
            for i, b in enumerate(h.bounds):
                if v <= b:
                    return i
            return len(h.bounds)

        import random
        rng = random.Random(7)
        probes = [0.0, 1e-9, 1e-6, 2e-6, 0.002, 0.5]
        probes += list(h.bounds)                      # exact bounds
        probes += [b * 1.0001 for b in h.bounds]      # just above
        probes += [rng.uniform(0, 2e-4) for _ in range(200)]
        for v in probes:
            assert h._index(v) == ref_index(v), v

    def test_resolves_2ms(self):
        """The satellite's point: a 2ms SLO objective falls between
        quarter-octave bounds ~19% apart, not the plain ladder's
        1.024ms/2.048ms factor-2 gap."""
        h = Histogram("x", lo=1e-6, n_buckets=112, substeps=4)
        below = max(b for b in h.bounds if b <= 0.002)
        above = min(b for b in h.bounds if b > 0.002)
        assert above / below <= 2 ** 0.25 + 1e-9
        # percentile over-estimates by at most one sub-step
        for _ in range(100):
            h.observe(0.0019)
        assert h.percentile(0.99) <= 0.0019 * 2 ** 0.25

    def test_substeps_1_unchanged(self):
        """The classic octave ladder is bit-identical to before."""
        h = Histogram("x", lo=1e-6, n_buckets=28)
        assert h.substeps == 1
        assert h.bounds == [1e-6 * (1 << i) for i in range(28)]
        h.observe(0.5e-6)
        h.observe(1e-6)
        h.observe(1.1e-6)
        assert h.counts[0] == 2 and h.counts[1] == 1

    def test_overflow_and_cumulative(self):
        h = Histogram("x", lo=1e-6, n_buckets=8, substeps=4)
        h.observe(1.0)                 # far beyond the last bound
        h.observe(1e-6)
        cum = h.cumulative()
        assert cum[-1][0] == float("inf") and cum[-1][1] == 2
        assert h.counts[-1] == 1

    def test_stage_families_migrated(self):
        """pipeline.stage.* ride the fine ladder with names unchanged
        (the PR 7 doc-drift gate keys on the names)."""
        node = _mk_node()
        h = node.metrics.histograms()["pipeline.stage.dispatch.seconds"]
        assert h.substeps == 4
        assert any(abs(b - 0.002) / 0.002 < 0.10 for b in h.bounds), \
            "no bound within 10% of the 2ms objective"
        # the watchdog deadline source still reads these names
        assert "pipeline.stage.materialize.seconds" in \
            node.metrics.histograms()


# ---------- SLO engine unit behavior ----------

class TestSloEngine:
    def _obs(self, objective_ms=2.0, hooks=None, recorder=None):
        return L.LatencyObservatory(Metrics(), hooks=hooks,
                                    recorder=recorder,
                                    objective_ms=objective_ms)

    def test_burn_rates(self):
        obs = self._obs()
        sid = int(time.monotonic() / L._SLOT_S)
        # 100 samples, 2 breaches in the current slot: burn = 2%/1% = 2
        obs._slots.append([sid, 100, 2])
        burn = obs.burn_rates()
        assert burn["1m"] == pytest.approx(2.0)
        assert burn["5m"] == pytest.approx(2.0)
        assert burn["30m"] == pytest.approx(2.0)
        # an old slot outside the 1m window but inside 30m
        obs._slots.appendleft([sid - 12, 100, 0])
        burn = obs.burn_rates()
        assert burn["1m"] == pytest.approx(2.0)
        assert burn["30m"] == pytest.approx(1.0)

    def test_verdict_and_merged_p99(self):
        obs = self._obs(objective_ms=2.0)
        m = Message(topic="a", qos=1)
        for _ in range(200):
            obs.record_routed(m, "device", 0.0005)
        sec = obs.section()
        assert sec["slo"]["verdict"] == "met"
        assert sec["slo"]["routed_p99_ms"] <= 2.0
        assert sec["routed"]["q1.device"]["count"] == 200
        for _ in range(200):
            obs.record_routed(m, "replay", 0.05)
        sec = obs.section()
        # the merged p99 now sits in the replay tail
        assert sec["slo"]["verdict"] == "breached"
        assert sec["slo"]["routed_p99_ms"] > 2.0
        assert set(sec["routed"]) == {"q1.device", "q1.replay"}

    def test_no_data_verdict(self):
        sec = self._obs().section()
        assert sec["slo"]["verdict"] == "no_data"

    def test_exemplar_trace_link_and_hook_throttle(self):
        hooks = Hooks()
        seen = []
        hooks.add("latency.breach", lambda ex: seen.append(ex))
        rec = FlightRecorder(Metrics(), cap=64)
        obs = self._obs(objective_ms=1.0, hooks=hooks, recorder=rec)
        tid = rec.new_trace()
        m = Message(topic="slow/one", qos=1)
        for _ in range(5):
            obs.record_routed(m, "replay", 0.25, trace=tid)
        # exemplars recorded for every breach, hook throttled to one
        assert len(obs.exemplars) == 5
        ex = obs.exemplars[0]
        assert ex["trace_id"] == tid and ex["path"] == "replay"
        assert len(seen) == 1 and seen[0]["topic"] == "slow/one"
        assert obs.hook_fires == 1 and obs.hook_throttled == 4
        # the slow message's trace carries the slo_breach event
        marks = [s for s in rec.spans()
                 if s.name == "slo_breach" and s.trace_id == tid]
        assert marks and marks[0].meta["path"] == "replay"

    def test_section_json_clean(self):
        obs = self._obs()
        obs.record_routed(Message(topic="a", qos=0), "host", 0.01)
        obs.record_delivered(Message(topic="a", qos=0), "host", 0.02)
        json.dumps(obs.section())


# ---------- the window form (ISSUE 45) ----------

class _Clock:
    """The two clocks the observatory reads, held still inside a
    window so that both sides of a comparison meet the same slot and
    the same hook throttle."""

    def __init__(self):
        self.now = 1000.0

    def monotonic(self):
        return self.now

    def time(self):
        return 1.7e9 + self.now


def _window(w, n, stamps, t_ns, over):
    """`n` messages of window `w`: `stamps` read bursts, every 4th row
    QoS 1 and every 10th QoS 2, every 7th unstamped; burst b waited
    0.3 ms + b x 0.1 ms where b >= `over`, else 300 ms + b ms (the
    objective is 2 ms). The last burst's rows come in two runs with
    another burst's between them."""
    msgs = []
    for i in range(n):
        b = i * stamps // n
        if stamps > 2 and b == stamps - 2 and i % 2:
            b = stamps - 1
        m = Message(topic=f"w{w}/b{b}/{i}",
                    qos=2 if i % 10 == 9 else 1 if i % 4 == 3 else 0)
        if i % 7 != 6:
            wait = 300_000_000 + b * 1_000_000 if b < over \
                else 300_000 + b * 100_000
            m.ingress_ns = t_ns - wait
        msgs.append(m)
    return msgs


def _per_message(obs, leg, msgs, path, t_ns, trace=0):
    """The loop the batcher ran before ISSUE 45, one call a message."""
    for m in msgs:
        ing = m.ingress_ns
        if ing:
            if leg == "routed":
                obs.record_routed(m, path, (t_ns - ing) / 1e9,
                                  trace=trace)
            else:
                obs.record_delivered(m, path, (t_ns - ing) / 1e9)


class TestWindowForm:
    def _obs(self, clamp):
        hooks = Hooks()
        seen = []
        hooks.add("latency.breach", lambda ex: seen.append(ex))
        rec = FlightRecorder(Metrics(), cap=8192)
        obs = L.LatencyObservatory(Metrics(), hooks=hooks, recorder=rec,
                                   objective_ms=2.0)
        obs.clamp = clamp
        return obs, rec, seen

    @pytest.mark.parametrize("clamp", [1, 16])
    @pytest.mark.parametrize("n,stamps,over", [
        (840, 7, 7),        # a flood's window: every group breaches
        (500, 9, 4),        # some groups under, some over
        (60, 3, 1),         # fewer than 16 breaches a window at clamp 16
        (20, 1, 1),         # one burst, near 16 breaches a window
        (40, 5, 0),         # nothing breaches
    ])
    def test_a_window_equals_the_per_message_loop(
            self, monkeypatch, clamp, n, stamps, over):
        clock = _Clock()
        monkeypatch.setattr(L, "time", clock)
        a, rec_a, seen_a = self._obs(clamp)
        b, rec_b, seen_b = self._obs(clamp)
        traces = []
        # three windows in a row: the second in the first's slot and
        # inside the hook's second, the third in a slot of its own
        for w, at in enumerate((1000.0, 1000.5, 1011.0)):
            clock.now = at
            t_ns = 5_000_000_000 + w * 40_000_000
            msgs = _window(w, n + w, stamps, t_ns, over)
            path = ("device", "device_cached", "host")[w]
            ta, tb = rec_a.new_trace(), rec_b.new_trace()
            late = [(t_ns - m.ingress_ns) / 1e9 for m in msgs
                    if m.ingress_ns
                    and (t_ns - m.ingress_ns) / 1e9 > 0.002]
            traces.append((ta, len(late), path, late))
            for leg in L.LEGS:
                a.record_window(leg, msgs, path, t_ns, trace=ta)
                _per_message(b, leg, msgs, path, t_ns, trace=tb)
        assert a._hist.keys() == b._hist.keys()
        for key, hb in b._hist.items():
            ha = a._hist[key]
            assert ha.counts == hb.counts and ha.count == hb.count, key
            assert ha.sum == pytest.approx(hb.sum, abs=1e-9), key
        assert b.samples > 0 and a.samples == b.samples
        assert a.breaches == b.breaches
        assert a.metrics.val("pipeline.latency.breaches") \
            == b.metrics.val("pipeline.latency.breaches") == b.breaches
        assert list(a._slots) == list(b._slots) and len(a._slots) == 2
        assert a.burn_rates() == b.burn_rates()
        assert a.clamped == b.clamped and (a.clamped > 0) == (clamp > 1)
        assert (a._clamp_tick, a._clamp_tick_d) \
            == (b._clamp_tick, b._clamp_tick_d)
        assert (a.hook_fires, a.hook_throttled) \
            == (b.hook_fires, b.hook_throttled)
        assert len(seen_a) == len(seen_b) == b.hook_fires
        # the deque holds what the loop left in it, dict for dict
        # (the trace ids are each side's own)
        def untraced(exs):
            return [{k: v for k, v in ex.items() if k != "trace_id"}
                    for ex in exs]
        assert untraced(a.exemplars) == untraced(b.exemplars)
        # one `slo_breach` a breaching window, for all it holds; the
        # loop left one a message
        if clamp == 1:
            marks = [s for s in rec_a.spans() if s.name == "slo_breach"]
            assert [(s.trace_id, s.meta) for s in marks] == [
                (t, {"count": c, "path": p,
                     "latency_ms": round(max(late) * 1000, 3)})
                for t, c, p, late in traces if c]
            assert sum(s.name == "slo_breach" for s in rec_b.spans()) \
                == b.breaches
        # the record block: one window a routed call that held a stamp
        rec_doc = a.section()["slo"]["record"]
        assert rec_doc["windows"] == 3 == a.metrics.val(
            "pipeline.latency.windows")
        assert rec_doc["groups"] == a.metrics.val(
            "pipeline.latency.groups") <= 3 * 3 * stamps
        # the one-message form counts a group a sample and no window
        assert b.groups == b.samples and b.windows == 0

    def test_breach_event_names_the_worst_group(self):
        rec = FlightRecorder(Metrics(), cap=64)
        obs = L.LatencyObservatory(Metrics(), recorder=rec,
                                   objective_ms=2.0)
        t_ns = 9_000_000_000
        msgs = _window(0, 100, 4, t_ns, 4)
        tid = rec.new_trace()
        obs.record_window("routed", msgs, "replay", t_ns, trace=tid)
        (mark,) = [s for s in rec.spans() if s.name == "slo_breach"]
        stamped = [m for m in msgs if m.ingress_ns]
        assert mark.trace_id == tid and mark.meta == {
            "latency_ms": 303.0, "path": "replay", "count": len(stamped)}
        # at most 16 exemplars, the window's last, oldest first
        assert [ex["topic"] for ex in obs.exemplars] \
            == [m.topic for m in stamped[-16:]]
        assert len({ex["ts"] for ex in obs.exemplars}) == 1

    def test_unstamped_window_records_nothing(self):
        obs = L.LatencyObservatory(Metrics(), objective_ms=2.0)
        msgs = [Message(topic="sys/x", qos=0) for _ in range(5)]
        for leg in L.LEGS:
            obs.record_window(leg, msgs, "host", 1_000_000)
            obs.record_window(leg, [], "host", 1_000_000)
        assert not obs._hist and not obs._slots and obs.windows == 0
        assert obs.section()["slo"]["verdict"] == "no_data"

    def test_window_guard_under_a_tenth_of_the_breach_path(self):
        """The guard ISSUE 45 would have been caught by, deterministic
        like `test_overhead_guard_under_3pct`: the path every flood
        window takes (all over the objective, a recorder and a hook
        chain in place) against the loop's budget, not the path under
        the objective against a latency. A 1,024-message window of 8
        stamps on both legs: under 0.3 us a message, and under a tenth
        of the one-message breach path measured here."""
        rec = FlightRecorder(Metrics(), cap=4096)
        tid = rec.new_trace()
        t_ns = time.perf_counter_ns()
        msgs = []
        for i in range(1024):
            m = Message(topic=f"t/{i}", qos=0)
            m.ingress_ns = t_ns - 300_000_000 - (i // 128) * 1_000_000
            msgs.append(m)

        def best(fn, reps, rounds=5):
            out = float("inf")
            for _ in range(rounds):
                t0 = time.perf_counter()
                for _i in range(reps):
                    fn()
                out = min(out, (time.perf_counter() - t0)
                          / (reps * len(msgs)))
            return out

        win = L.LatencyObservatory(Metrics(), hooks=Hooks(),
                                   recorder=rec)
        one = L.LatencyObservatory(Metrics(), hooks=Hooks(),
                                   recorder=rec)

        def window():
            for leg in L.LEGS:
                win.record_window(leg, msgs, "device", t_ns, trace=tid)

        def loop():
            for leg in L.LEGS:
                _per_message(one, leg, msgs, "device", t_ns, trace=tid)
        per_win, per_one = best(window, 40), best(loop, 2)
        assert win.breaches == win.samples > 0 and win.groups \
            == 8 * win.windows
        assert per_win < 0.3e-6, (
            f"a window's record costs {per_win * 1e6:.3f} us a message")
        assert per_win < 0.1 * per_one, (
            f"window form {per_win * 1e6:.3f} us a message against "
            f"{per_one * 1e6:.3f} us one by one")


class TestServedWindow:
    def test_a_burst_is_one_window_form_call_a_leg(self):
        """One read burst through a listener into a node with lanes:
        the batcher hands the observatory the window once a leg, the
        two counters move, and the flight recorder holds ONE
        `slo_breach` for the window, on its trace."""
        from emqx_tpu.broker.connection import Listener
        from emqx_tpu.client import Client
        # an objective nothing meets, so the window breaches whole
        node = Node({"broker": {"deliver_lanes": 2,
                                "slo_route_p99_ms": 1e-4}})
        obs = node.latency_observatory
        calls = []
        real = obs.record_window

        def spy(leg, msgs, path, t_ns, trace=0):
            calls.append((leg, len(msgs), trace))
            real(leg, msgs, path, t_ns, trace=trace)
        obs.record_window = spy
        obs.record_routed = obs.record_delivered = None     # not the path
        n = 96

        async def go():
            lst = Listener(node, bind="127.0.0.1", port=0)
            await lst.start()
            sub = Client(port=lst.port, clientid="sub")
            await sub.connect()
            await sub.subscribe("t/#", qos=0)
            pub = Client(port=lst.port, clientid="pub")
            await pub.connect()
            blob = b"".join(
                serialize(P.Publish(topic=f"t/{i}", payload=b"x" * 48,
                                    qos=0), C.MQTT_V4)
                for i in range(n))
            assert len(blob) > FrameParser.BURST_SCAN_MIN
            pub._writer.write(blob)
            await pub._writer.drain()
            got = [(await sub.recv()).topic for _ in range(n)]
            pool = node.deliver_lanes
            if pool.busy():
                await pool.drain()
            await pub.close()
            await sub.close()
            await lst.stop()
            return got
        got = run(go(), timeout=120)
        assert got == [f"t/{i}" for i in range(n)]
        routed = [c for c in calls if c[0] == "routed"]
        delivered = [c for c in calls if c[0] == "delivered"]
        # a leg a window, every message in exactly one window
        assert len(routed) == len(delivered) >= 1
        assert sum(c[1] for c in routed) == n \
            == sum(c[1] for c in delivered)
        m = node.metrics
        assert m.val("pipeline.latency.windows") == len(routed)
        assert len(routed) <= m.val("pipeline.latency.groups") < n
        assert obs.samples == obs.breaches == n
        sec = node.pipeline_telemetry.snapshot()["latency"]
        assert sec["slo"]["record"] == {
            "windows": len(routed),
            "groups": m.val("pipeline.latency.groups")}
        marks = [s for s in node.flight_recorder.spans()
                 if s.name == "slo_breach"]
        assert [(s.trace_id, s.meta["count"]) for s in marks] \
            == [(tr, k) for _leg, k, tr in routed]
        assert all(tr for _leg, _k, tr in routed)


# ---------- ingress stamp: burst vs per-packet equivalence ----------

class TestIngressStamp:
    def _frames(self, n=220, payload=b"p" * 24):
        return b"".join(
            serialize(P.Publish(topic=f"s/t{i % 7}", payload=payload,
                                qos=1, packet_id=(i % 60000) + 1),
                      C.MQTT_V4)
            for i in range(n))

    def test_per_packet_feed_stamps_publishes(self):
        p = FrameParser(version=C.MQTT_V4)
        pkts = p.feed(self._frames(8))
        assert len(pkts) == 8
        assert all(pk.ingress_ns > 0 for pk in pkts)
        # non-PUBLISH frames stay unstamped (Publish-only attribute)
        p2 = FrameParser(version=C.MQTT_V4)
        (ping,) = p2.feed(serialize(P.Pingreq(), C.MQTT_V4))
        assert getattr(ping, "ingress_ns", 0) == 0

    def test_burst_one_clock_read_per_row_attribution(self):
        data = self._frames()
        assert len(data) > FrameParser.BURST_SCAN_MIN
        pc = FrameParser(version=C.MQTT_V4)
        items = pc.feed_columnar(data)
        bursts = [it for it in items if type(it) is PublishBurst]
        assert bursts, "columnar path produced no burst"
        for b in bursts:
            assert b.ingress_ns > 0
        # equivalence with the per-packet twin: same rows, and every
        # row of either path carries a stamp taken at frame decode
        pp = FrameParser(version=C.MQTT_V4)
        pkts = pp.feed(data)
        assert sum(len(b) for b in bursts) == len(pkts)
        assert [t for b in bursts for t in b.topics] == \
            [pk.topic for pk in pkts]
        assert all(pk.ingress_ns > 0 for pk in pkts)

    def test_stamp_rides_message_both_paths(self):
        """Channel-level: the burst hand-off and the per-packet path
        plant the same ingress_ns onto the Message."""
        m = make("c", 1, "a/b", b"x")
        assert m.ingress_ns == 0        # internal publishes unstamped
        m.ingress_ns = 123
        assert m.ingress_ns == 123
        # the burst constructor path (Channel.handle_publish_burst)
        mm = Message.__new__(Message)
        mm.__dict__ = {"topic": "a", "payload": b"", "qos": 0,
                       "from_": "c", "flags": {}, "headers": {},
                       "id": 1, "ts": 1, "extra": {},
                       "ingress_ns": 456}
        assert mm.ingress_ns == 456


# ---------- knob-off A/B twin ----------

class TestOffTwin:
    def test_off_is_pre_issue13_exactly(self):
        node_off = _mk_node(latency_observatory=False)
        assert node_off.latency_observatory is None
        sinks_off = _subscribe(node_off)
        counts_off = run(_drive(node_off))
        node_on = _mk_node(latency_observatory=True)
        assert node_on.latency_observatory is not None
        sinks_on = _subscribe(node_on)
        counts_on = run(_drive(node_on))
        # bit-identical delivery counts AND per-publisher order
        assert counts_off == counts_on
        assert [s.got for s in sinks_off] == [s.got for s in sinks_on]
        # snapshot schema identical minus the latency section
        snap_off = node_off.pipeline_telemetry.snapshot()
        snap_on = node_on.pipeline_telemetry.snapshot()
        assert "latency" not in snap_off
        assert set(snap_off) == set(snap_on) - {"latency"}
        # no latency metric leaks into the off registry
        assert not [n for n in node_off.metrics.histograms()
                    if n.startswith("pipeline.latency.")]
        assert node_off.metrics.val("pipeline.latency.breaches") == 0

    def test_rest_404_when_off(self):
        node = _mk_node(latency_observatory=False)
        from emqx_tpu.mgmt import make_api

        async def go():
            srv = make_api(node, port=0)
            await srv.start()
            try:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", srv.port)
                writer.write(b"GET /api/v5/pipeline/latency HTTP/1.1"
                             b"\r\nhost: x\r\nconnection: close\r\n\r\n")
                await writer.drain()
                raw = await asyncio.wait_for(reader.read(-1), 10)
                writer.close()
                assert b"404" in raw.split(b"\r\n")[0]
            finally:
                await srv.stop()
        run(go(), timeout=60)


# ---------- per-path attribution oracle ----------

class TestPathAttribution:
    @pytest.fixture(scope="class")
    def attributed_run(self):
        node = _mk_node(supervise_threshold=8)
        _subscribe(node)

        async def go():
            await _warm(node)
            pb = node.publish_batcher
            eng = node.device_engine
            out = []
            # (a) trickle host: one lone message is below
            # device_min_batch and takes the inline host path
            out.append(await node.publish_async(
                _stamped("p", 1, "t/0/h")))
            # (b) device: pinned chooser, full windows
            pb._device_worth_it = lambda n: True
            out += await _drive(node, windows=3, warm=False, tag="d")
            assert node.metrics.val("pipeline.batches.device") \
                or node.metrics.val("pipeline.batches.device_cached"), \
                "device path never engaged"
            # (c) FORCED host-fallback: the device path is chosen but
            # prepare_window declines (mid-rebuild shape)
            real_prepare = eng.prepare_window
            eng.prepare_window = lambda lives: None
            out += await _drive(node, windows=1, warm=False, tag="f")
            eng.prepare_window = real_prepare
            # (d) journal replay: one injected dispatch exception
            sup = node.supervisor
            sup.injector = S.FaultInjector(S.parse_faults(
                "dispatch:exception:count=1"))
            for _w in range(6):
                out += await _drive(node, windows=1, warm=False,
                                    tag="r")
                if sup.injector.faults[0].fired:
                    break
            assert sup.injector.faults[0].fired, \
                "injected dispatch fault never fired"
            del pb.__dict__["_device_worth_it"]
            return out
        counts = run(go())
        return node, counts

    def test_each_rung_is_its_own_series(self, attributed_run):
        node, counts = attributed_run
        assert all(c == 1 for c in counts), "a rung lost deliveries"
        paths = {p for (leg, _q, p), h in
                 node.latency_observatory._hist.items()
                 if leg == "routed" and h.count}
        assert "host" in paths
        assert "device" in paths or "device_cached" in paths
        assert "host_fallback" in paths, \
            "forced prepare_window decline not attributed"
        assert "replay" in paths, "journal replay not attributed"

    def test_delivered_leg_mirrors_routed(self, attributed_run):
        node, _counts = attributed_run
        series = node.latency_observatory._hist
        for (leg, q, p), h in series.items():
            if leg != "routed" or not h.count:
                continue
            hd = series.get(("delivered", q, p))
            assert hd is not None and hd.count == h.count, \
                f"delivered leg missing for q{q}.{p}"

    def test_replay_breach_exemplar_names_injected_stage(
            self, attributed_run):
        """The acceptance drive's tier-1 twin: the slow (replayed)
        window's breach exemplar links the flight-recorder trace whose
        causal chain carries the replay event attributing the latency
        to the injected dispatch stage."""
        node, _counts = attributed_run
        obs = node.latency_observatory
        rec = node.flight_recorder
        assert obs.breaches > 0, \
            "replayed windows never breached the objective"
        tids = {ex["trace_id"] for ex in obs.exemplars
                if ex["trace_id"]}
        assert tids
        replayed = [s for s in rec.spans()
                    if s.name == "replay" and s.trace_id in tids]
        assert replayed, \
            "no breach exemplar links a trace with a replay event"
        assert replayed[0].meta["stage"] == "dispatch"

    def test_snapshot_and_exporters(self, attributed_run):
        node, _counts = attributed_run
        snap = node.pipeline_telemetry.snapshot()
        lat = snap["latency"]
        assert lat["schema"] == L.SCHEMA
        assert lat["slo"]["samples"] == \
            sum(r["count"] for r in lat["routed"].values())
        json.dumps(snap)
        # $SYS
        from emqx_tpu.apps.sys import SysBroker
        seen = {}

        class Spy(SysBroker):
            def _pub(self, suffix, payload):
                seen[suffix] = payload
        Spy(node).publish_pipeline()
        assert "pipeline/latency" in seen
        assert json.loads(seen["pipeline/latency"])["slo"]
        # Prometheus histogram families
        from emqx_tpu.apps.prometheus import collect
        text = collect(node)
        assert "emqx_pipeline_latency_routed_q1_" in text
        assert "emqx_pipeline_latency_delivered_q1_" in text

    def test_rest_endpoint(self, attributed_run):
        node, _counts = attributed_run
        from emqx_tpu.mgmt import make_api

        async def go():
            srv = make_api(node, port=0)
            await srv.start()
            try:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", srv.port)
                writer.write(b"GET /api/v5/pipeline/latency HTTP/1.1"
                             b"\r\nhost: x\r\nconnection: close\r\n\r\n")
                await writer.drain()
                raw = await asyncio.wait_for(reader.read(-1), 10)
                writer.close()
                head, _, body = raw.partition(b"\r\n\r\n")
                assert b"200" in head.split(b"\r\n")[0]
                doc = json.loads(body)
                assert doc["schema"] == L.SCHEMA and doc["routed"]
            finally:
                await srv.stop()
        run(go(), timeout=60)

    def test_overhead_guard_under_3pct(self, attributed_run):
        """Deterministic, the PR 7/8 shape: measure the per-record
        cost of the observatory primitive, double it (two legs per
        message), and bound it against 3% of the MEASURED mean
        ingress→delivered latency of this live run. A hot-path
        regression (say, section() leaking into record) fails
        immediately; scheduler noise cannot."""
        node, _counts = attributed_run
        obs = node.latency_observatory
        probe = L.LatencyObservatory(Metrics(), objective_ms=1e9)
        m = Message(topic="t/overhead", qos=1)
        n = 4000
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _i in range(n):
                probe.record_routed(m, "device", 1e-4)
                probe.record_delivered(m, "device", 1e-4)
            best = min(best, (time.perf_counter() - t0) / n)
        hs = [h for (leg, _q, _p), h in obs._hist.items()
              if leg == "delivered" and h.count]
        mean_lat = sum(h.sum for h in hs) / sum(h.count for h in hs)
        assert best < 0.03 * mean_lat, (
            f"observatory records cost {best * 1e6:.2f}us/message vs "
            f"mean e2e latency {mean_lat * 1e3:.2f}ms — over the 3% "
            f"budget")


# ---------- host-only node (no batcher) still measures ----------

class TestHostOnlyNode:
    def test_host_path_records_both_legs(self):
        node = Node({"broker": {"device_route": False}},
                    use_device=False)
        assert node.publish_batcher is None
        assert node.latency_observatory is not None
        s = Sink()
        sid = node.broker.register(s, "c0")
        node.broker.subscribe(sid, "h/+", {"qos": 1})

        async def go():
            return [await node.publish_async(_stamped("p", 1, "h/a"))
                    for _ in range(16)]
        counts = run(go())
        assert all(c == 1 for c in counts)
        sec = node.latency_observatory.section()
        assert sec["routed"]["q1.host"]["count"] == 16
        assert sec["delivered"]["q1.host"]["count"] == 16


# ---------- offline report + CI gate ----------

class TestLatencyReport:
    def _section(self):
        obs = L.LatencyObservatory(Metrics(), objective_ms=2.0)
        m = Message(topic="a/b", qos=1)
        for _ in range(100):
            obs.record_routed(m, "device", 0.001)
            obs.record_delivered(m, "device", 0.0015)
        return obs.section()

    def test_report_renders_and_exits_0(self, tmp_path, capsys):
        import latency_report
        doc = {"cpu_latency0": {"metric": "x",
                                "latency": self._section()},
               "e2e_host": {"latency": self._section()}}
        path = tmp_path / "bench.json"
        path.write_text(json.dumps(doc))
        assert latency_report.main([str(path)]) == 0
        out = capsys.readouterr().out
        assert "ingress→routed" in out and "q1.device" in out
        assert "SLO" in out and "MET" in out

    def test_exit_2_on_p99less_row(self, tmp_path, capsys):
        """The CI gate: a bench row WITHOUT a latency section cannot
        silently commit a p99-less headline."""
        import latency_report
        doc = {"cpu_latency0": {"metric": "x", "value": 123},
               "e2e_device": {"per_sec": 1}}
        path = tmp_path / "bench.json"
        path.write_text(json.dumps(doc))
        assert latency_report.main([str(path)]) == 2
        assert "NO latency section" in capsys.readouterr().err

    def test_require_pins_rows(self, tmp_path, capsys):
        import latency_report
        doc = {"cpu_latency0": {"latency": self._section()}}
        path = tmp_path / "bench.json"
        path.write_text(json.dumps(doc))
        assert latency_report.main([str(path)]) == 0
        # --require pins a row the artifact lacks -> gate fires
        assert latency_report.main(
            ["--require", "cpu_latency0,e2e_device", str(path)]) == 2

    def test_exit_1_on_garbage(self, tmp_path):
        import latency_report
        path = tmp_path / "junk.json"
        path.write_text("not json")
        assert latency_report.main([str(path)]) == 1
        assert latency_report.main([]) == 1
