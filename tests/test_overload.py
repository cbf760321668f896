"""Adaptive overload protection — the graded load-shed ladder (ISSUE 14).

Coverage, per the issue:

- knob matrix: broker.overload / EMQX_TPU_OVERLOAD
  (config-beats-env-beats-default-on)
- governor unit: signal→grade voting, hysteresis on both edges (a
  flapping signal cannot oscillate the ladder), one-grade-per-interval
  climbs and recoveries, ordered action arm/unwind with full state
  restoration, the overload/$SYS alarm lifecycle, the loop-lag probe
- the QoS1/2-never-shed invariant: at grade critical QoS0 drops at
  batcher admit while QoS1 delivery counts and per-session order stay
  bit-identical to the unloaded twin
- CONNECT admission gate: new CONNECTs answered with v5 0x97 while
  pause_connects is armed; re-admitted on recovery
- top-offender disconnect: limiter debt outranks volume, the volume
  fallback is floored, the offender gets DISCONNECT 0x97
- knob-off A/B twin: EMQX_TPU_OVERLOAD=0 ⇒ no governor object, no
  `overload` snapshot section (even at full=True), REST 404,
  bit-identical delivery counts and order
- overload chaos cells (chaos marker): signal_spike climbs/sheds/
  recovers, stuck_grade raises the overload_stuck alarm — via the
  tools/chaos_bench.py cells, mirroring the PR 6 matrix pattern
- real-TCP drive: a small overdrive flood with tightened thresholds —
  grade reaches critical, only QoS0 sheds, zero accepted-QoS1 loss,
  per-publisher order holds, the ladder recovers to normal
- satellites: TokenBucket debt mode (take(n) past capacity charges
  into negative balance and returns the full repay pause),
  congestion alarm hysteresis (re-arm on every congested observation,
  deactivate only after min_alarm_sustain_duration clean), the
  3.10-compatible utils/aio.timeout_after the cluster RPC now uses,
  retained-replay deferral
"""

import asyncio
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))

from emqx_tpu.broker import overload as O                 # noqa: E402
from emqx_tpu.broker import supervise as S                # noqa: E402
from emqx_tpu.broker.congestion import Congestion         # noqa: E402
from emqx_tpu.broker.limiter import (ConnectionLimiter,   # noqa: E402
                                     TokenBucket)
from emqx_tpu.broker.message import make                  # noqa: E402
from emqx_tpu.broker.node import Node                     # noqa: E402
from emqx_tpu.mqtt import constants as C                  # noqa: E402
from emqx_tpu.mqtt import packet as P                     # noqa: E402
from emqx_tpu.mqtt.frame import FrameParser, serialize    # noqa: E402
from emqx_tpu.utils.aio import timeout_after              # noqa: E402


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


def _force_grade(gov, grade, signal="queue_fill"):
    """Deterministically walk the governor to `grade` (and hold it):
    monkeypatch-free signal override + one poll per climb."""
    vals = {0: 0.0, 1: 0.55, 2: 0.80, 3: 0.95}
    gov.sample_signals = lambda: {signal: vals[grade]}
    gov.up_sustain = 1
    gov.down_sustain = 1
    for _ in range(4):
        gov.poll()
        if gov.grade == grade:
            break
    assert gov.grade == grade, (gov.grade, gov.last_signals)


# ---------- knob resolution ----------

class TestKnob:
    def test_default_on(self, monkeypatch):
        monkeypatch.delenv("EMQX_TPU_OVERLOAD", raising=False)
        assert O.resolve_overload() is True

    def test_env_off(self, monkeypatch):
        monkeypatch.setenv("EMQX_TPU_OVERLOAD", "0")
        assert O.resolve_overload() is False

    def test_config_beats_env(self, monkeypatch):
        monkeypatch.setenv("EMQX_TPU_OVERLOAD", "0")
        assert O.resolve_overload(True) is True
        monkeypatch.delenv("EMQX_TPU_OVERLOAD", raising=False)
        assert O.resolve_overload(False) is False

    def test_node_env_knob_off(self, monkeypatch):
        monkeypatch.setenv("EMQX_TPU_OVERLOAD", "0")
        node = _mk_node()
        assert node.overload_governor is None
        assert node.pipeline_telemetry.overload_state_fn is None


# ---------- governor unit ----------

class TestGovernorUnit:
    def test_grade_votes(self):
        node = _mk_node()
        gov = node.overload_governor
        assert gov._grade_of({}) == 0
        assert gov._grade_of({"queue_fill": 0.3}) == 0
        assert gov._grade_of({"queue_fill": 0.55}) == 1
        assert gov._grade_of({"queue_fill": 0.80}) == 2
        assert gov._grade_of({"queue_fill": 0.95}) == 3
        # max vote wins across signals
        assert gov._grade_of({"queue_fill": 0.55,
                              "hbm_fill": 0.96}) == 3
        # a tier with no threshold never votes it
        assert gov._grade_of({"inflight_fill": 50.0}) == 1
        # multi-window burn: page-level needs both windows
        assert gov._grade_of({"burn_1m": 5.0}) == 1
        assert gov._grade_of({"burn_page": 20.0}) == 2
        assert gov._grade_of({"burn_page": 60.0}) == 3

    def test_hysteresis_up(self):
        node = _mk_node()
        gov = node.overload_governor
        gov.up_sustain = 3
        gov.sample_signals = lambda: {"queue_fill": 0.95}
        gov.poll()
        gov.poll()
        assert gov.grade == 0          # 2 < up_sustain polls
        gov.poll()
        assert gov.grade == 1          # one grade per interval, no jump

    def test_flapping_signal_cannot_oscillate(self):
        node = _mk_node()
        gov = node.overload_governor
        gov.up_sustain = 2
        gov.down_sustain = 2
        flip = [0.95, 0.0]
        gov.sample_signals = lambda: {"queue_fill": flip[0]}
        for _ in range(12):
            gov.poll()
            flip.reverse()
        # alternating saturated/idle polls never sustain either edge
        assert gov.grade == 0
        assert node.metrics.val("pipeline.overload.grade_changes") == 0

    def test_climb_and_recover_one_grade_per_interval(self):
        node = _mk_node()
        gov = node.overload_governor
        gov.up_sustain = 1
        gov.down_sustain = 2
        gov.sample_signals = lambda: {"queue_fill": 0.95}
        trail = []
        for _ in range(3):
            gov.poll()
            trail.append(gov.grade)
        assert trail == [1, 2, 3]
        gov.sample_signals = lambda: {"queue_fill": 0.0}
        for _ in range(6):
            gov.poll()
            trail.append(gov.grade)
        assert trail == [1, 2, 3, 3, 2, 2, 1, 1, 0]

    def test_rebreach_backoff_damps_oscillation(self):
        node = _mk_node()
        gov = node.overload_governor
        gov.up_sustain = 1
        gov.down_sustain = 2
        # sustained flood: signals read healthy exactly when shedding
        # (grade critical), saturated when not — the oscillation trap
        gov.sample_signals = lambda: {
            "queue_fill": 0.0 if gov.grade >= 3 else 0.95}
        downs_between_rebreaches = []
        last_down = None
        for i in range(200):
            g0 = gov.grade
            gov.poll()
            if gov.grade < g0:
                if last_down is not None:
                    downs_between_rebreaches.append(i - last_down)
                last_down = i
        assert node.metrics.val("pipeline.overload.rebreaches") >= 2
        # each easing attempt that re-breached made the next one
        # exponentially later
        assert len(downs_between_rebreaches) >= 2
        assert downs_between_rebreaches[-1] > \
            downs_between_rebreaches[0]
        assert gov._down_mult > 1

    def test_full_recovery_resets_backoff(self):
        node = _mk_node()
        gov = node.overload_governor
        gov.up_sustain = 1
        gov.down_sustain = 1
        gov._down_mult = 16
        gov.sample_signals = lambda: {"queue_fill": 0.95}
        gov.poll()
        assert gov.grade == 1
        gov.sample_signals = lambda: {"queue_fill": 0.0}
        for _ in range(20):
            gov.poll()
        assert gov.grade == 0
        assert gov._down_mult == 1

    def test_actions_arm_unwind_and_restore(self):
        node = _mk_node()
        gov = node.overload_governor
        rec = node.flight_recorder
        obs = node.latency_observatory
        b = node.publish_batcher
        sample0, depth0 = rec.sample, b.dispatch_depth
        _force_grade(gov, 3)
        assert list(gov._armed) == list(O.ACTIONS)
        assert rec.sample == sample0 * O.CLAMP_FACTOR
        assert obs.clamp == O.CLAMP_FACTOR
        assert b.dispatch_depth == 1
        assert gov.shed_qos0 and gov.connects_paused \
            and gov.retained_deferred
        assert node.metrics.val("pipeline.overload.sheds") == \
            len(O.ACTIONS)
        _force_grade(gov, 0)
        assert gov._armed == []
        assert rec.sample == sample0
        assert obs.clamp == 1
        assert b.dispatch_depth == depth0
        assert not (gov.shed_qos0 or gov.connects_paused
                    or gov.retained_deferred)
        assert gov._saved == {}

    def test_alarm_lifecycle(self):
        node = _mk_node()
        gov = node.overload_governor
        _force_grade(gov, 2)
        assert node.alarms.is_active("overload")
        details = [a for a in node.alarms.get_alarms("activated")
                   if a["name"] == "overload"][0]["details"]
        assert details["grade"] == "overload"
        _force_grade(gov, 3)
        details = [a for a in node.alarms.get_alarms("activated")
                   if a["name"] == "overload"][0]["details"]
        assert details["grade"] == "critical"   # refreshed per change
        _force_grade(gov, 0)
        assert not node.alarms.is_active("overload")

    def test_loop_lag_probe_cadence_drift(self):
        node = _mk_node()
        gov = node.overload_governor
        gov.poll_interval_s = 0.1
        gov.up_sustain = 1
        t0 = time.monotonic()
        gov.poll(now=t0)
        gov.poll(now=t0 + 0.1)       # on cadence: no lag
        assert gov.loop_lag_s < 1e-9
        gov.poll(now=t0 + 0.2 + 2.0)  # 2s late: the loop was wedged
        assert 1.9 < gov.loop_lag_s < 2.1
        # the NEXT poll votes on the measured lag (critical >= 1.0s)
        gov.poll(now=t0 + 2.3 + 2.0)
        assert gov.last_signals["loop_lag_s"] >= 1.0
        assert gov.grade >= 1

    def test_hook_fires_per_arm(self):
        node = _mk_node()
        seen = []
        node.hooks.add("overload.shed", lambda info: seen.append(info))
        gov = node.overload_governor
        _force_grade(gov, 1)
        assert [i["action"] for i in seen] == ["clamp_sampling"]
        assert seen[0]["armed"] is True
        _force_grade(gov, 0)
        assert seen[-1] == {"action": "clamp_sampling", "armed": False,
                            "grade": "normal"}


# ---------- QoS0 shed at batcher admit (the never-shed invariant) ----

class TestShedQos0:
    def _world(self, node, n=4):
        sinks = []
        for i in range(n):
            s = Sink()
            sid = node.broker.register(s, f"c{i}")
            node.broker.subscribe(sid, f"t/{i}/+", {"qos": 1})
            sinks.append(s)
        return sinks

    async def _drive(self, node, windows=3, n=4):
        counts = []
        for w in range(windows):
            counts.append(await asyncio.gather(*[
                node.publish_async(
                    make("pub", qos, f"t/{i}/x", b"w%dq%d" % (w, qos)))
                for i in range(n) for qos in (0, 1)]))
        pool = node.deliver_lanes
        if pool is not None and pool.busy():
            await pool.drain()
        return counts

    def test_critical_sheds_only_qos0_order_identical_to_twin(self):
        node = _mk_node()
        gov = node.overload_governor
        sinks = self._world(node)
        _force_grade(gov, 3)
        counts = run(self._drive(node))
        twin = _mk_node()           # governor on, grade normal
        tsinks = self._world(twin)
        tcounts = run(self._drive(twin))
        # QoS0 rows: count 0 on the shed node, delivered on the twin
        for w in counts:
            assert w[0::2] == [0] * 4       # qos0 slots all shed
            assert all(c >= 1 for c in w[1::2])   # qos1 delivered
        for w in tcounts:
            assert all(c >= 1 for c in w)
        assert node.metrics.val("pipeline.overload.qos0_shed") == 12
        assert twin.metrics.val("pipeline.overload.qos0_shed") == 0
        # per-session QoS1 sequences bit-identical to the twin
        for s, t in zip(sinks, tsinks):
            q1 = [g for g in s.got if not g[1].endswith(b"q0")]
            tq1 = [g for g in t.got if not g[1].endswith(b"q0")]
            assert q1 == tq1
            # and nothing QoS0 leaked through the shed
            assert not [g for g in s.got if g[1].endswith(b"q0")]

    def test_publish_nowait_accepts_and_sheds(self):
        node = _mk_node()
        gov = node.overload_governor
        _force_grade(gov, 3)

        async def go():
            node.publish_batcher._kick()     # bind queues to this loop
            assert node.publish_nowait(make("p", 0, "t/0/x", b"")) \
                is True                      # accepted-and-shed: the
            return True                      # caller must NOT fall
        run(go())                            # back to awaiting submit
        assert node.metrics.val("pipeline.overload.qos0_shed") == 1

    def test_recovery_readmits_qos0(self):
        node = _mk_node()
        gov = node.overload_governor
        self._world(node)
        _force_grade(gov, 3)
        _force_grade(gov, 0)

        async def go():
            return await node.publish_async(make("p", 0, "t/0/x", b""))
        assert run(go()) >= 1
        assert node.metrics.val("pipeline.overload.qos0_shed") == 0

    def test_burst_rows_shed_qos0_only(self):
        node = _mk_node()
        gov = node.overload_governor
        self._world(node)
        _force_grade(gov, 3)

        async def go():
            pb = node.publish_batcher
            rows = [(make("p", 0, "t/0/x", b"a"), False),
                    (make("p", 1, "t/1/x", b"b"), True),
                    (make("p", 0, "t/2/x", b"c"), False)]
            futs = pb.submit_burst(rows)
            assert set(futs) == {1}          # only the QoS1 row waits
            return await futs[1]
        assert run(go()) >= 1
        assert node.metrics.val("pipeline.overload.qos0_shed") == 2


# ---------- CONNECT admission gate (v5 0x97) -------------------------

async def _raw_connect(port, clientid, proto_ver=5):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(serialize(P.Connect(proto_name="MQTT",
                                     proto_ver=proto_ver,
                                     clientid=clientid), proto_ver))
    await writer.drain()
    parser = FrameParser(version=proto_ver)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            data = await asyncio.wait_for(reader.read(512), 10)
        except ConnectionError:
            # a refused CONNECT's close can land as RST once the
            # CONNACK was already consumed — only bytes matter here
            raise RuntimeError("reset before CONNACK")
        if not data:
            raise RuntimeError("closed before CONNACK")
        pkts = parser.feed(data)
        if pkts:
            return reader, writer, pkts[0]
    raise RuntimeError("no CONNACK")


class TestConnectGate:
    def test_paused_connects_get_quota_exceeded_then_recover(self):
        from emqx_tpu.broker.connection import Listener
        # one acceptor lane: lane 0 always accepts (the 0x97 CONNACK
        # is ITS half of pause_connects; the extra-lane close is
        # covered by test_paused_lane_refuses_at_accept)
        node = _mk_node(ingress_lanes=1)
        gov = node.overload_governor

        async def go():
            lst = Listener(node, bind="127.0.0.1", port=0)
            await lst.start()
            try:
                _r, w, ack = await _raw_connect(lst.port, "ok1")
                assert isinstance(ack, P.Connack)
                assert ack.reason_code == C.RC_SUCCESS
                w.close()
                _force_grade(gov, 2)    # pause_connects arms
                _r2, w2, ack2 = await _raw_connect(lst.port, "no1")
                assert ack2.reason_code == C.RC_QUOTA_EXCEEDED
                w2.close()
                assert node.metrics.val(
                    "pipeline.overload.connects_rejected") == 1
                _force_grade(gov, 0)    # recovery re-admits
                _r3, w3, ack3 = await _raw_connect(lst.port, "ok2")
                assert ack3.reason_code == C.RC_SUCCESS
                w3.close()
            finally:
                await lst.stop()
        run(go(), timeout=60)

    def test_paused_lane_refuses_at_accept(self):
        from emqx_tpu.broker.connection import Listener
        node = _mk_node()
        gov = node.overload_governor
        _force_grade(gov, 2)
        lst = Listener(node, bind="127.0.0.1", port=0)
        closed = []

        class W:
            def close(self):
                closed.append(True)
        # a lane > 0 handler refuses at accept while paused; lane 0
        # keeps accepting (so the CONNACK 0x97 can go out)
        run(lst._lane_handler(2)(None, W()))
        assert closed == [True]
        assert node.metrics.val(
            "pipeline.overload.accepts_paused") == 1


# ---------- top-offender disconnect ----------------------------------

class TestOffenderDisconnect:
    def test_debt_outranks_volume_and_floor_gates(self):
        from emqx_tpu.broker.connection import Listener
        node = _mk_node()
        gov = node.overload_governor

        async def go():
            lst = Listener(node, bind="127.0.0.1", port=0)
            await lst.start()
            try:
                r1, w1, _ = await _raw_connect(lst.port, "quiet")
                r2, w2, _ = await _raw_connect(lst.port, "flood")
                await asyncio.sleep(0.05)
                conns = {c.channel.clientid: c
                         for c in gov._conns if c.channel.clientid}
                assert set(conns) == {"quiet", "flood"}
                # below the volume floor nobody qualifies
                conns["quiet"].shed_rows = 10.0
                assert conns["quiet"].shed_score() == 0.0
                # a flooder's decayed volume qualifies it
                conns["flood"].shed_rows = 5000.0
                assert conns["flood"].shed_score() == 5000.0
                # configured-limiter debt outranks ANY volume
                conns["quiet"].limiter = ConnectionLimiter(10.0, None)
                conns["quiet"].limiter.msgs.take(500)
                assert conns["quiet"].shed_score() > \
                    conns["flood"].shed_score()
                conns["quiet"].limiter = ConnectionLimiter(None, None)
                _force_grade(gov, 3)
                gov.poll()      # disconnect_offenders fires per poll
                await asyncio.sleep(0.1)
                assert node.metrics.val(
                    "pipeline.overload.disconnects") == 1
                # the flooder got the v5 DISCONNECT 0x97 and the close
                parser = FrameParser(version=5)
                data = await asyncio.wait_for(r2.read(512), 10)
                pkts = parser.feed(data)
                assert any(isinstance(p, P.Disconnect)
                           and p.reason_code == C.RC_QUOTA_EXCEEDED
                           for p in pkts)
                assert not await asyncio.wait_for(r2.read(512), 10)
                w1.close()
                w2.close()
            finally:
                await lst.stop()
        run(go(), timeout=60)


# ---------- knob-off A/B twin ----------------------------------------

class TestOffTwin:
    def _world(self, node, n=4):
        sinks = []
        for i in range(n):
            s = Sink()
            sid = node.broker.register(s, f"c{i}")
            node.broker.subscribe(sid, f"t/{i}/+", {"qos": 1})
            sinks.append(s)
        return sinks

    async def _drive(self, node, n=4):
        out = []
        for w in range(3):
            out.extend(await asyncio.gather(*[
                node.publish_async(make("p", 1, f"t/{i}/x",
                                        b"m%d" % w))
                for i in range(n)]))
        pool = node.deliver_lanes
        if pool is not None and pool.busy():
            await pool.drain()
        return out

    def test_off_is_pre_issue14_exactly(self):
        node_off = _mk_node(overload=False)
        assert node_off.overload_governor is None
        sinks_off = self._world(node_off)
        counts_off = run(self._drive(node_off))
        node_on = _mk_node(overload=True)
        assert node_on.overload_governor is not None
        sinks_on = self._world(node_on)
        counts_on = run(self._drive(node_on))
        # bit-identical delivery counts AND per-publisher order
        assert counts_off == counts_on
        assert [s.got for s in sinks_off] == [s.got for s in sinks_on]
        # no `overload` section on the off twin — even at full=True
        snap_off = node_off.pipeline_telemetry.snapshot(full=True)
        snap_on = node_on.pipeline_telemetry.snapshot(full=True)
        assert "overload" not in snap_off
        assert "overload" in snap_on
        assert set(snap_off) == set(snap_on) - {"overload"}
        # no overload metric leaks into the off registry
        assert not [k for k in node_off.metrics.all()
                    if k.startswith("pipeline.overload.")]

    def test_rest_404_when_off_200_when_on(self):
        from emqx_tpu.mgmt import make_api

        async def probe(node, expect):
            srv = make_api(node, port=0)
            await srv.start()
            try:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", srv.port)
                writer.write(b"GET /api/v5/pipeline/overload HTTP/1.1"
                             b"\r\nhost: x\r\nconnection: close\r\n\r\n")
                await writer.drain()
                raw = await asyncio.wait_for(reader.read(-1), 10)
                writer.close()
                assert expect in raw.split(b"\r\n")[0]
                return raw
            finally:
                await srv.stop()
        run(probe(_mk_node(overload=False), b"404"), timeout=60)
        raw = run(probe(_mk_node(overload=True), b"200"), timeout=60)
        assert b'"grade"' in raw

    def test_snapshot_section_and_counters_after_shed(self):
        node = _mk_node()
        _force_grade(node.overload_governor, 3)
        snap = node.pipeline_telemetry.snapshot()
        ov = snap["overload"]
        assert ov["state"]["grade"] == "critical"
        assert ov["state"]["actions"] == list(O.ACTIONS)
        assert ov["sheds"] == len(O.ACTIONS)
        assert ov["actions_armed_counts"]["shed_qos0"] == 1
        assert ov["state"]["signals"]["raw"] == 3


# ---------- chaos cells (the PR 6 matrix pattern) --------------------

@pytest.mark.chaos
class TestOverloadChaos:
    @pytest.mark.parametrize("point", ("signal_spike", "stuck_grade"))
    def test_cell(self, point):
        import chaos_bench as CB
        case = CB.run_overload_case(point)
        bad = CB.grade_overload(case, point)
        assert not bad, bad

    def test_points_in_grammar(self):
        faults = S.parse_faults(
            "signal_spike:corrupt:count=2,stuck_grade:corrupt")
        assert [f.point for f in faults] == ["signal_spike",
                                             "stuck_grade"]
        assert "signal_spike" in S.FAULT_POINTS
        assert "stuck_grade" in S.FAULT_POINTS


# ---------- real-TCP overdrive drive ---------------------------------

class TestDrive:
    def test_flood_sheds_qos0_holds_qos1_and_recovers(self):
        from emqx_tpu.broker.connection import Listener
        node = _mk_node()
        gov = node.overload_governor
        # tighten so a small flood overdrives deterministically on CI
        gov.up_sustain = 1
        gov.down_sustain = 3
        gov.thresholds = dict(gov.thresholds,
                              queue_fill=(0.005, 0.01, 0.02))
        got_q1 = []
        got_q0 = [0]

        class Tally:
            def deliver(self, topic_filter, msg):
                if msg.topic.startswith("ov/q1/"):
                    got_q1.append(bytes(msg.payload))
                else:
                    got_q0[0] += 1
                return True
        sid = node.broker.register(Tally(), "tally")
        node.broker.subscribe(sid, "ov/#", {"qos": 1})

        def blob(cid, n, base):
            out = bytearray()
            pid = 0
            for i in range(n):
                seq = base + i
                if i % 4 == 3:
                    pid = pid % 65535 + 1
                    out += serialize(P.Publish(
                        topic="ov/q1/t", qos=1, packet_id=pid,
                        payload=b"%04d%06d" % (cid, seq)), 4)
                else:
                    out += serialize(P.Publish(
                        topic="ov/q0/t", qos=0,
                        payload=b"%04d%06d" % (cid, seq)), 4)
            return bytes(out)

        async def go():
            lst = Listener(node, bind="127.0.0.1", port=0)
            await lst.start()
            node.start_timers(0.02)
            grade_max = 0
            try:
                pairs = [await _raw_connect(lst.port, f"p{i}",
                                            proto_ver=4)
                         for i in range(4)]

                async def sink(r):
                    try:
                        while await r.read(65536):
                            pass
                    except (ConnectionError, OSError):
                        pass
                sinks = [asyncio.get_running_loop().create_task(
                    sink(r)) for r, _w, _a in pairs]
                for k in range(6):     # sustained: 6 waves x 4 conns
                    await asyncio.gather(*[
                        _write(w, blob(i, 200, k * 200))
                        for i, (_r, w, _a) in enumerate(pairs)])
                    grade_max = max(grade_max, gov.grade)
                    await asyncio.sleep(0.05)
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    grade_max = max(grade_max, gov.grade)
                    recv = node.metrics.val("messages.qos1.received")
                    if recv and len(got_q1) >= recv \
                            and gov.grade == 0 and not gov._armed:
                        break
                    await asyncio.sleep(0.05)
                for t in sinks:
                    t.cancel()
                for _r, w, _a in pairs:
                    w.close()
                return grade_max
            finally:
                node.stop_timers()
                await lst.stop()
                if node.publish_batcher is not None:
                    await node.publish_batcher.stop()

        grade_max = run(go(), timeout=180)
        m = node.metrics
        # the ladder engaged hard enough to shed
        assert grade_max >= 3, grade_max
        shed = m.val("pipeline.overload.qos0_shed")
        assert shed > 0
        # zero accepted-QoS1 loss: every QoS1 the broker accepted was
        # delivered (some publishers may have been offender-shed)
        assert len(got_q1) == m.val("messages.qos1.received")
        assert len(got_q1) > 0
        # per-publisher QoS1 order: seq monotone per conn
        last = {}
        for payload in got_q1:
            cid, seq = int(payload[:4]), int(payload[4:10])
            assert last.get(cid, -1) < seq, (cid, seq)
            last[cid] = seq
        # conservation: nothing vanished silently — every accepted
        # QoS0 was either delivered or is accounted as shed
        assert got_q0[0] + shed == m.val("messages.qos0.received")
        # full recovery: normal grade, all actions unwound
        assert gov.grade == 0 and gov._armed == []


async def _write(writer, blob):
    try:
        writer.write(blob)
        await writer.drain()
    except (ConnectionError, OSError):
        pass


# ---------- retained-replay deferral ---------------------------------

class TestRetainedDefer:
    def test_deferred_then_replayed_on_recovery(self):
        from emqx_tpu.apps.retainer import Retainer
        node = _mk_node()
        gov = node.overload_governor
        ret = Retainer(node)
        dispatched = []
        ret._dispatch_retained = \
            lambda ci, t, so: dispatched.append((ci, t, so))
        _force_grade(gov, 2)    # defer_retained armed
        ret.on_session_subscribed({"clientid": "c1"}, "a/+",
                                  {"qos": 1, "is_new": True})
        assert dispatched == []
        assert len(ret._deferred) == 1
        assert node.metrics.val(
            "pipeline.overload.retained_deferred") == 1
        ret.tick()              # still deferred while armed
        assert dispatched == []
        _force_grade(gov, 0)
        ret.tick()              # first healthy tick drains the lot
        assert [d[1] for d in dispatched] == ["a/+"]
        assert ret._deferred == []

    def test_defer_parking_is_bounded(self):
        from emqx_tpu.apps.retainer import Retainer
        node = _mk_node()
        gov = node.overload_governor
        ret = Retainer(node)
        ret._DEFER_CAP = 5
        _force_grade(gov, 2)
        for i in range(9):
            ret.on_session_subscribed({"clientid": f"c{i}"}, f"f/{i}",
                                      {"qos": 0, "is_new": True})
        assert len(ret._deferred) == 5
        # oldest dropped, newest kept
        assert [d[1] for d in ret._deferred] == \
            [f"f/{i}" for i in range(4, 9)]


# ---------- satellite: TokenBucket debt mode -------------------------

class TestTokenBucketDebt:
    def test_take_past_capacity_charges_debt_and_full_repay_pause(self):
        b = TokenBucket(10.0, burst=5.0)
        t0 = time.monotonic()
        pause = b.take(20.0, now=t0)
        # 5 tokens existed; 20 taken => balance -15; repay at 10/s
        assert b.tokens == pytest.approx(-15.0)
        assert pause == pytest.approx(1.5)
        assert b.debt(now=t0) == pytest.approx(15.0)
        # refill repays the debt linearly
        assert b.debt(now=t0 + 1.0) == pytest.approx(5.0)
        assert b.debt(now=t0 + 1.5) == pytest.approx(0.0)

    def test_try_take_never_goes_negative(self):
        b = TokenBucket(10.0, burst=5.0)
        t0 = time.monotonic()
        assert b.try_take(20.0, now=t0) is False
        assert b.tokens == pytest.approx(5.0)
        assert b.debt(now=t0) == 0.0

    def test_connection_limiter_debt_in_repay_seconds(self):
        lim = ConnectionLimiter(10.0, 1000.0)
        t0 = time.monotonic()
        lim.msgs.take(25.0, now=t0)        # 15 tokens of debt @ 10/s
        lim.bytes.take(1500.0, now=t0)     # 500 of debt @ 1000/s
        # worst bucket in repay-seconds: msgs 1.5s vs bytes 0.5s
        lim.msgs._t = lim.bytes._t = t0    # pin refill clock
        assert lim.debt() == pytest.approx(1.5, abs=0.05)
        assert ConnectionLimiter(None, None).debt() == 0.0


# ---------- satellite: congestion alarm hysteresis -------------------

class _FakeTransport:
    def __init__(self):
        self.pending = 0

    def get_write_buffer_size(self):
        return self.pending


class _FakeWriter:
    def __init__(self):
        self.transport = _FakeTransport()


class TestCongestionHysteresis:
    def _cong(self, sustain=0.15):
        node = _mk_node()
        writer = _FakeWriter()

        class Ch:
            clientid = "c1"
            clientinfo = {"username": "u"}
            conninfo = {"peername": ("127.0.0.1", 1)}
            conn_state = "connected"
        cong = Congestion(node, Ch(), writer, enable_alarm=True,
                          min_alarm_sustain_duration=sustain)
        return node, writer, cong

    def test_rearm_on_every_congested_observation(self, monkeypatch):
        # the module's clock, stepped by hand: a sleep of 0.10 s that
        # the scheduler stretched past the sustain failed this case
        from types import SimpleNamespace

        from emqx_tpu.broker import congestion
        clock = SimpleNamespace(now=1000.0)
        clock.monotonic = lambda: clock.now
        monkeypatch.setattr(congestion, "time", clock)
        node, writer, cong = self._cong(sustain=0.15)
        writer.transport.pending = 100
        cong.check()
        name = cong._alarm_name()
        assert node.alarms.is_active(name)
        # congested again right before the sustain would have elapsed:
        # the deactivation clock RESTARTS (re-arm on every congested
        # observation — emqx_congestion's WontClearIn)
        clock.now += 0.10
        cong.check()                       # still congested: re-arms
        writer.transport.pending = 0
        clock.now += 0.10                  # 0.10 < sustain since last
        cong.check()                       # congested observation
        assert node.alarms.is_active(name)
        clock.now += 0.06                  # now 0.16 >= sustain clean
        cong.check()
        assert not node.alarms.is_active(name)

    def test_deactivates_only_after_sustained_clean(self):
        node, writer, cong = self._cong(sustain=0.1)
        writer.transport.pending = 1
        cong.check()
        name = cong._alarm_name()
        writer.transport.pending = 0
        cong.check()                       # clean but not sustained
        assert node.alarms.is_active(name)
        time.sleep(0.12)
        cong.check()
        assert not node.alarms.is_active(name)
        # cancel() is idempotent once deactivated
        cong.cancel()
        assert not node.alarms.is_active(name)

    def test_no_alarm_when_disabled(self):
        node, writer, cong = self._cong()
        cong.enable = False
        writer.transport.pending = 100
        cong.check()
        assert node.alarms.get_alarms("activated") == []


# ---------- satellite: the 3.10 timeout helper (cluster rpc) ---------

class TestTimeoutAfter:
    def test_converts_deadline_cancel_to_timeout(self):
        async def go():
            with pytest.raises(asyncio.TimeoutError):
                async with timeout_after(0.05):
                    await asyncio.sleep(5)
        run(go(), timeout=30)

    def test_fast_body_passes_value_through(self):
        async def go():
            async with timeout_after(5):
                await asyncio.sleep(0)
            return "ok"
        assert run(go(), timeout=30) == "ok"

    def test_external_cancel_not_swallowed(self):
        async def body():
            async with timeout_after(5):
                await asyncio.sleep(5)

        async def go():
            task = asyncio.get_running_loop().create_task(body())
            await asyncio.sleep(0.05)
            task.cancel()
            with pytest.raises(asyncio.CancelledError):
                await task
        run(go(), timeout=30)

    def test_none_disables_deadline(self):
        async def go():
            async with timeout_after(None):
                await asyncio.sleep(0)
            return "ok"
        assert run(go(), timeout=30) == "ok"

    def test_cluster_rpc_uses_it(self):
        # the 3.10 regression this satellite fixes: importing the rpc
        # module (and its timeout sites) must not require 3.11's
        # asyncio.timeout
        import emqx_tpu.cluster.rpc as rpc
        import inspect
        src = inspect.getsource(rpc)
        assert "asyncio.timeout(" not in src
        assert "timeout_after(" in src
