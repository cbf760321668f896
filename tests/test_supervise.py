"""Fault-domain supervision for the device route pipeline (ISSUE 6).

The chaos acceptance criteria, as tests:

- **Injection matrix** (marked `chaos`): for each injection point ×
  fault kind, the twin-engine oracle shows zero lost QoS≥1 deliveries,
  per-session order bit-identical to the fault-free run, degradation to
  the next ladder rung within one window (threshold 1 here), and the
  breaker re-closing after the half-open probe.
- **EMQX_TPU_SUPERVISE=0** reproduces the pre-ISSUE-6 behavior exactly
  (no supervisor object anywhere; the old unwind paths untouched).
- **Watchdogs**: a hung dispatch/materialize trips the stall detector
  instead of wedging the consumer; a dead lane worker is restarted by
  the drain watchdog and drains its queue in order.
- Plus the satellite coverage for error paths that had none: compact
  payload overflow concurrent with a snapshot swap, a delta-overlay
  overflow racing `_overlay_sync`, and `pool.drain()` after loop
  teardown — and the task-hygiene static pass wired as a tier-1 gate.
"""

import asyncio
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))

import chaos_bench as CB                                    # noqa: E402
import check_task_hygiene as hygiene                        # noqa: E402

from emqx_tpu.broker import device_engine as DE             # noqa: E402
from emqx_tpu.broker import supervise as S                  # noqa: E402
from emqx_tpu.broker.message import make                    # noqa: E402
from emqx_tpu.broker.node import Node                       # noqa: E402


def run(coro, timeout=120):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()


def mkmsg(topic, payload=b"x", qos=1):
    return make("pub", qos, topic, payload)


class Rec:
    def __init__(self):
        self.got = []

    def deliver(self, topic_filter, msg):
        self.got.append((topic_filter, msg.topic, bytes(msg.payload)))
        return True


# ---------- fault spec grammar + injector determinism ----------

class TestFaultSpec:
    def test_grammar(self):
        faults = S.parse_faults(
            "dispatch:exception,materialize:hang:after=2:count=3:"
            "hang_s=0.25, lane_deliver:resource")
        assert [(f.point, f.kind) for f in faults] == [
            ("dispatch", "exception"), ("materialize", "hang"),
            ("lane_deliver", "resource")]
        assert faults[1].after == 2 and faults[1].count == 3
        assert faults[1].hang_s == 0.25
        assert S.parse_faults(None) == [] and S.parse_faults("") == []

    @pytest.mark.parametrize("bad", [
        "dispatch",                    # no kind
        "nosuchpoint:exception",       # unknown point
        "dispatch:nosuchkind",         # unknown kind
        "dispatch:exception:after",    # option not k=v
        "dispatch:exception:welp=1",   # unknown option
    ])
    def test_malformed_specs_fail_loudly(self, bad):
        with pytest.raises(ValueError):
            S.parse_faults(bad)

    def test_after_count_window(self):
        inj = S.FaultInjector(S.parse_faults(
            "dispatch:exception:after=2:count=2"))
        fired = []
        for _ in range(6):
            try:
                inj.fire("dispatch")
                fired.append(False)
            except S.InjectedFault:
                fired.append(True)
        # traversals 3 and 4 fire, nothing before or after
        assert fired == [False, False, True, True, False, False]

    def test_resource_kind_reads_like_oom(self):
        inj = S.FaultInjector(S.parse_faults("materialize:resource"))
        with pytest.raises(S.InjectedResourceExhausted) as ei:
            inj.fire("materialize")
        assert "RESOURCE_EXHAUSTED" in str(ei.value)

    def test_corrupt_decays_to_exception_unless_handled(self):
        inj = S.FaultInjector(S.parse_faults(
            "dispatch:corrupt,materialize:corrupt"))
        with pytest.raises(S.InjectedFault):
            inj.fire("dispatch")            # corrupt_ok=False: raises
        assert inj.fire("materialize", corrupt_ok=True) == "corrupt"

    def test_unarmed_is_free(self):
        sup = S.PipelineSupervisor(Node(use_device=False).metrics,
                                   injector=S.FaultInjector([]))
        assert sup.fire("dispatch") is None


# ---------- circuit breaker state machine ----------

class TestBreaker:
    def test_open_after_threshold_consecutive(self):
        t = [0.0]
        br = S.CircuitBreaker("dispatch", threshold=3, cooldown_s=1.0,
                              clock=lambda: t[0])
        assert br.allow()
        br.record_fault()
        br.record_ok()              # a success resets the streak
        br.record_fault()
        br.record_fault()
        assert br.allow() and br.state == "closed"
        assert br.record_fault()    # third consecutive: opens
        assert br.state == "open" and not br.allow() and br.trips == 1

    def test_half_open_probe_cycle_with_backoff(self):
        t = [0.0]
        br = S.CircuitBreaker("dispatch", threshold=1, cooldown_s=1.0,
                              max_cooldown_s=4.0, clock=lambda: t[0])
        br.record_fault()
        assert not br.probe_due()
        t[0] = 1.5
        assert br.probe_due()
        br.begin_probe()
        assert br.state == "half_open" and not br.allow()
        br.probe_fail()             # still broken: cooldown doubles
        assert br.state == "open" and br.cooldown_s == 2.0
        t[0] = 4.0
        br.begin_probe()
        br.probe_ok()
        assert br.state == "closed" and br.allow()
        assert br.cooldown_s == 1.0     # reset on close

    def test_faults_while_open_do_not_restack(self):
        br = S.CircuitBreaker("x", threshold=1)
        assert br.record_fault()
        assert not br.record_fault()    # already open: no second trip
        assert br.trips == 1


# ---------- the ladder ----------

class TestLadder:
    def _sup(self):
        return S.PipelineSupervisor(
            Node(use_device=False).metrics,
            injector=S.FaultInjector([]), threshold=1)

    def test_rungs(self):
        sup = self._sup()
        assert sup.rung() == S.RUNG_FULL
        assert sup.allow_device() and sup.reuse_enabled()
        sup.note_fault("cache_insert")
        assert sup.rung() == S.RUNG_DEVICE_PLAIN
        assert sup.allow_device() and not sup.reuse_enabled()
        sup.note_fault("materialize")
        assert sup.rung() == S.RUNG_HOST and not sup.allow_device()

    def test_open_lane_breaker_defers_inline_fallback_until_drained(self):
        """An open lane_deliver breaker must NOT flip the pool inactive
        while plans are still in flight — an immediate inline fallback
        could reorder a session's stream against its queued lane rows.
        New plans stop only once the lanes have drained."""
        node = Node({"broker": {"deliver_lanes": 2,
                                "supervise_threshold": 1,
                                "device_fanout_cap": 16,
                                "device_slot_cap": 4}})
        pool = node.deliver_lanes
        sup = node.supervisor
        assert pool.active()
        sup.note_fault("lane_deliver")      # breaker opens
        assert not sup.lanes_enabled()
        assert not pool.active()            # idle: inline is order-safe
        pool._live_plans = 1                # in-flight lane work
        assert pool.active()                # keep routing through lanes
        pool._live_plans = 0
        assert not pool.active()

    def test_lane_swap_mesh_gates_are_orthogonal_to_the_rung(self):
        sup = self._sup()
        sup.note_fault("lane_deliver")
        sup.note_fault("snapshot_swap")
        sup.note_fault("mesh_exchange")
        assert sup.rung() == S.RUNG_FULL
        assert not sup.lanes_enabled()
        assert not sup.rebuild_enabled()
        assert not sup.mesh_enabled()


# ---------- guard_task / spawn (the done-callback satellite) ----------

class TestTaskGuard:
    def test_guarded_death_is_logged_and_counted(self):
        node = Node(use_device=False)
        seen = []

        async def go():
            async def boom():
                raise RuntimeError("lane died")
            t = S.guard_task(asyncio.get_running_loop().create_task(
                boom()), "test-task", node.metrics,
                on_error=seen.append)
            await asyncio.sleep(0.05)
            assert t.done()
        before = S.task_error_count()
        run(go())
        assert S.task_error_count() == before + 1
        assert node.metrics.val("supervise.task_errors") == 1
        assert len(seen) == 1 and "lane died" in str(seen[0])

    def test_cancel_and_success_are_silent(self):
        node = Node(use_device=False)

        async def go():
            async def ok():
                return 1

            async def forever():
                await asyncio.sleep(60)
            t1 = S.guard_task(asyncio.get_running_loop().create_task(
                ok()), "t1", node.metrics)
            t2 = S.guard_task(asyncio.get_running_loop().create_task(
                forever()), "t2", node.metrics)
            await asyncio.sleep(0.02)
            t2.cancel()
            await asyncio.sleep(0.02)
            assert t1.done() and t2.cancelled()
        run(go())
        assert node.metrics.val("supervise.task_errors") == 0

    def test_spawn_holds_and_guards(self):
        node = Node(use_device=False)

        async def go():
            async def boom():
                raise ValueError("x")
            t = S.spawn(boom(), "spawned", node.metrics)
            assert t is not None
            await asyncio.sleep(0.05)
        run(go())
        assert node.metrics.val("supervise.task_errors") == 1

    def test_spawn_without_loop_closes_coro(self):
        async def never():
            raise AssertionError("must not run")
        assert S.spawn(never(), "no-loop") is None


# ---------- knob resolution + the A/B-off contract ----------

class TestKnob:
    def test_config_beats_env_beats_default(self, monkeypatch):
        monkeypatch.delenv("EMQX_TPU_SUPERVISE", raising=False)
        assert S.resolve_supervise(None) is True
        monkeypatch.setenv("EMQX_TPU_SUPERVISE", "0")
        assert S.resolve_supervise(None) is False
        assert S.resolve_supervise(True) is True    # config wins
        monkeypatch.setenv("EMQX_TPU_SUPERVISE", "1")
        assert S.resolve_supervise(False) is False

    def test_supervise_off_restores_pre_issue6_shape(self):
        node = Node({"broker": {"supervise": False,
                                "device_fanout_cap": 16,
                                "device_slot_cap": 4}})
        assert node.supervisor is None
        assert node.device_engine.sup is None
        if node.deliver_lanes is not None:
            assert node.deliver_lanes.sup is None
        assert node.publish_batcher.sup is None
        assert node.pipeline_telemetry.supervise_state_fn is None
        # and the old unwind still works: a consume error fails the
        # window's publishers (no replay machinery)
        s = Rec()
        sid = node.broker.register(s, "c1")
        node.broker.subscribe(sid, "t/+", {"qos": 1})

        async def go():
            return await node.publish_async(mkmsg("t/1"))
        assert run(go()) == 1
        assert "supervise" not in node.pipeline_telemetry.snapshot()

    def test_host_only_node_has_no_supervisor(self):
        assert Node(use_device=False).supervisor is None


# ---------- watchdog deadlines ----------

class TestWatchdogDeadline:
    def test_deadline_tracks_stage_p99(self):
        node = Node(use_device=False)
        sup = S.PipelineSupervisor(
            node.metrics, telemetry=node.pipeline_telemetry,
            injector=S.FaultInjector([]),
            watchdog_floor_s=0.1, watchdog_cap_s=10.0, watchdog_mult=4)
        # cold histogram: the floor holds
        assert sup.deadline("dispatch") == pytest.approx(0.1)
        for _ in range(100):
            node.pipeline_telemetry.observe_stage("dispatch", 0.2)
        d = sup.deadline("dispatch")
        # p99 of a 0.2s-dominated histogram is the 0.25-ish log2 bucket
        assert 0.4 <= d <= 4.0
        # the cap bounds a pathological history
        for _ in range(100):
            node.pipeline_telemetry.observe_stage("dispatch", 100.0)
        assert sup.deadline("dispatch") == 10.0


# ---------- the chaos injection matrix (the acceptance criterion) ----

@pytest.fixture(scope="module")
def twin():
    return CB.run_twin()


@pytest.fixture(scope="module")
def twin_delta():
    return CB.run_twin(delta=True)


@pytest.mark.chaos
class TestChaosMatrix:
    @pytest.mark.parametrize("point", CB.MATRIX_POINTS)
    @pytest.mark.parametrize("kind", S.FAULT_KINDS)
    def test_cell(self, point, kind, twin, twin_delta):
        case = CB.run_case(point, kind)
        oracle = twin_delta if point == "overlay_apply" else twin
        bad = CB.grade(case, oracle, point, kind)
        assert not bad, bad
        # hangs at watchdogged stages must be STALLS (tripped, not
        # wedged); raising kinds at pipeline stages must REPLAY
        if kind == "hang" and point in CB.WATCHDOGGED:
            assert case["stalls"] >= 1
        if kind in ("exception", "resource", "corrupt") \
                and point in ("dispatch", "materialize"):
            assert case["replays"] >= 1


@pytest.mark.chaos
class TestMeshChaos:
    def test_mesh_exchange_fault_replays_and_recovers(self):
        node = Node({"broker": {
            "multichip": {"enable": True, "devices": 2,
                          "max_batch": 64},
            "deliver_lanes": 0, "device_min_batch": 4,
            "batch_window_us": 2000, "supervise": True,
            "supervise_threshold": 1, "device_fanout_cap": 16,
            "device_slot_cap": 4}})
        sup = node.supervisor
        for br in sup.breakers.values():
            br.base_cooldown_s = br.cooldown_s = 0.05
        srv = node.device_engine
        b = node.broker
        sinks = {}
        for i in range(4):
            s = Rec()
            sid = b.register(s, f"c{i}")
            sinks[sid] = s
            b.subscribe(sid, f"t/{i}/+", {"qos": 1})
        srv.route_batch([mkmsg(f"t/{i}/w") for i in range(4)] * 2,
                        wait=True)
        import time as _time
        deadline = _time.monotonic() + 60
        while not srv.batch_class_warm(8) \
                and _time.monotonic() < deadline:
            srv._kick_class_warm()
            _time.sleep(0.05)
        assert srv.batch_class_warm(8), "mesh classes never warmed"
        sup.injector = S.FaultInjector(S.parse_faults(
            "mesh_exchange:exception:count=1"))

        async def go():
            outs = []
            for w in range(10):
                outs.extend(await asyncio.gather(*[
                    node.publish_async(mkmsg(f"t/{i}/x", b"m%d%d"
                                             % (w, i)))
                    for i in range(4) for _ in range(2)]))
                await asyncio.sleep(0.06)
                if sup.breakers["mesh_exchange"].state == "closed" \
                        and sup.injector.faults[0].fired:
                    break
            # the half-open probe runs on an executor thread: under a
            # loaded machine it can outlast the ten rounds above, so
            # wait for its verdict, not for the wall clock
            deadline = _time.monotonic() + 60
            while sup.breakers["mesh_exchange"].state != "closed" \
                    and _time.monotonic() < deadline:
                await asyncio.sleep(0.05)
            return outs
        outs = run(go(), timeout=180)
        assert all(c == 1 for c in outs)
        m = node.metrics
        assert m.val("supervise.faults.mesh_exchange") == 1
        assert sup.breakers["mesh_exchange"].state == "closed"
        assert m.val("messages.dropped") == 0


# ---------- lane-worker death + drain watchdog recovery ----------

class TestLaneRecovery:
    def test_dead_workers_revived_by_drain_watchdog_in_order(self):
        node = Node({"broker": {"deliver_lanes": 2,
                                "supervise_threshold": 8,
                                "device_fanout_cap": 16,
                                "device_slot_cap": 4}})
        sup = node.supervisor
        sup.wd_floor_s = 0.1
        sup.wd_mult = 0.0
        pool = node.deliver_lanes
        b = node.broker
        s = Rec()
        sid = b.register(s, "c1")      # even sid? force lane 0 rows
        lane_sid = sid if sid % 2 == 0 else sid + 0
        assert pool is not None

        async def go():
            pool.ensure_loop()
            pool.pause()
            msgs1 = [mkmsg("a/1", b"one")]
            msgs2 = [mkmsg("a/2", b"two")]
            p1 = pool.new_plan(msgs1)
            p1.register_fast([0])
            p1.add_rows_py(0, [(lane_sid, 0, "a/+")])
            pool.submit(p1)
            p2 = pool.new_plan(msgs2)
            p2.register_fast([0])
            p2.add_rows_py(0, [(lane_sid, 0, "a/+")])
            pool.submit(p2)
            await asyncio.sleep(0.05)   # workers hold plan1 at the gate
            for w in pool._workers:
                w.cancel()              # simulated worker death
            await asyncio.sleep(0.05)
            assert all(w.done() for w in pool._workers)
            pool.resume()
            # plan2's item is still queued with NO live worker: only the
            # drain watchdog's revival can complete it
            await pool.drain()
            return p1.done, p2.done
        d1, d2 = run(go(), timeout=30)
        assert d1 and d2
        m = node.metrics
        assert m.val("supervise.restarts") >= 1
        assert m.val("supervise.stalls.lane_deliver") >= 1
        # plan2's delivery survived the dead worker, in queue order
        assert (b"two" in [p for _f, _t, p in s.got])


# ---------- window journal ----------

class TestJournal:
    def test_depth_tracks_inflight_and_settles_to_zero(self):
        node = Node({"broker": {"deliver_lanes": 2,
                                "device_fanout_cap": 16,
                                "device_slot_cap": 4,
                                "device_min_batch": 4,
                                "batch_window_us": 1000}})
        sup = node.supervisor
        b = node.broker
        s = Rec()
        sid = b.register(s, "c1")
        b.subscribe(sid, "t/+", {"qos": 1})
        eng = node.device_engine
        eng.rebuild()

        async def go():
            eng._kick_class_warm()
            if eng._fuse_warm_task is not None:
                await eng._fuse_warm_task
            pool = node.deliver_lanes
            pool.ensure_loop()
            pool.pause()
            futs = [asyncio.ensure_future(
                node.publish_async(mkmsg(f"t/{i}"))) for i in range(8)]
            for _ in range(100):
                await asyncio.sleep(0.02)
                if sup.journal_depth() > 0 and pool.busy():
                    break
            depth_mid = sup.journal_depth()
            pool.resume()
            outs = await asyncio.gather(*futs)
            await pool.drain()
            return depth_mid, outs
        depth_mid, outs = run(go())
        assert depth_mid >= 1       # in-flight window was journaled
        assert outs == [1] * 8
        assert sup.journal_depth() == 0


# ---------- satellite: error paths that had no coverage ----------

class TestErrorPaths:
    def test_compact_overflow_concurrent_with_snapshot_swap(self):
        """A window whose payload class overflows (dense fallback) while
        a finished background rebuild waits on the handle pin: the
        overflow must not corrupt delivery, and the swap must apply the
        moment the handle releases."""
        node = Node({"broker": {"deliver_lanes": 0,
                                "device_fanout_cap": 64,
                                "device_slot_cap": 4}})
        b = node.broker
        sinks = []
        for i in range(30):
            s = Rec()
            sid = b.register(s, f"c{i}")
            sinks.append(s)
            b.subscribe(sid, "f/+", {"qos": 1 if i % 2 else 0})
        eng = node.device_engine
        eng.rebuild()
        eng.rebuild_threshold = 1
        # force the smallest payload class so 30-wide fan-out overflows
        eng._pay_ewma[64] = 4.0

        async def go():
            msgs = [mkmsg(f"f/{i}") for i in range(16)]
            h = eng.prepare(msgs, gate_cold=False)
            assert h is not None and h.pcap is not None
            old_sid = eng._built.sid
            # churn a BUILT filter past the threshold: a background
            # compaction starts while h pins the snapshot
            s2 = Rec()
            sid2 = b.register(s2, "late")
            b.subscribe(sid2, "f/+", {"qos": 0})
            assert eng.maybe_background_rebuild()
            for _ in range(600):
                if eng._pending_swap is not None:
                    break
                await asyncio.sleep(0.02)
            assert eng._pending_swap is not None   # gated by the pin
            assert eng._built.sid == old_sid
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, eng.dispatch, h)
            await loop.run_in_executor(None, eng.materialize, h)
            counts = eng.finish_sub(h, 0, defer=False)
            return old_sid, counts
        old_sid, counts = run(go(), timeout=180)
        # the dirty filter delivered host-side against live membership
        assert counts == [31] * 16
        assert node.metrics.val("routing.device.compact_overflow") >= 1
        # handle released -> the gated swap applied
        assert eng._built.sid != old_sid
        assert not eng._building

    def test_delta_overlay_overflow_racing_overlay_sync(self,
                                                        monkeypatch):
        """More delta filters than the overlay holds, with an overlay
        refresh racing an in-flight handle: the pinned version serves
        its rows, the uncovered tail host-routes, nothing is lost or
        double-delivered."""
        monkeypatch.setattr(DE, "_OVERLAY_MAX", 4)
        node = Node({"broker": {"deliver_lanes": 0,
                                "device_fanout_cap": 16,
                                "device_slot_cap": 4}})
        b = node.broker
        base = Rec()
        sid = b.register(base, "base")
        b.subscribe(sid, "t/+", {"qos": 1})
        eng = node.device_engine
        eng.rebuild()
        sinks = {}
        for i in range(6):          # 4 fit the overlay, 2 overflow
            s = Rec()
            dsid = b.register(s, f"d{i}")
            sinks[i] = s
            b.subscribe(dsid, f"d{i}/+", {"qos": 1})
        msgs = [mkmsg(f"d{i}/x") for i in range(6)] + [mkmsg("t/x")]
        h = eng.prepare(msgs, gate_cold=False)
        assert h is not None
        assert eng._overlay_uncovered == 2
        assert eng._compaction_reason() == "overflow"
        # race: churn + a fresh overlay version while h is in flight
        s7 = Rec()
        dsid7 = b.register(s7, "d7")
        b.subscribe(dsid7, "d7/+", {"qos": 1})
        eng._overlay_sync()
        eng.dispatch(h)
        eng.materialize(h)
        counts = eng.finish(h)
        assert counts == [1] * 7
        for i, s in sinks.items():
            assert [t for _f, t, _p in s.got] == [f"d{i}/x"]
        assert [t for _f, t, _p in base.got] == ["t/x"]
        assert node.metrics.val("routing.device.host_delta") >= 1

    def test_pool_drain_after_loop_teardown(self):
        """Plans stranded on a dead loop: a drain() from a NEW loop must
        finalize them (releasing pinned snapshot handles) and return —
        not hang on a wake event nobody can set."""
        node = Node({"broker": {"deliver_lanes": 2,
                                "device_fanout_cap": 16,
                                "device_slot_cap": 4}})
        b = node.broker
        s = Rec()
        sid = b.register(s, "c1")
        b.subscribe(sid, "t/+", {"qos": 1})
        eng = node.device_engine
        eng.rebuild()
        pool = node.deliver_lanes

        async def strand():
            pool.ensure_loop()
            pool.pause()
            msgs = [mkmsg(f"t/{i}") for i in range(4)]
            h = eng.prepare(msgs, gate_cold=False)
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, eng.dispatch, h)
            await loop.run_in_executor(None, eng.materialize, h)
            counts = eng.finish_sub(h, 0)   # defer=True: plan queued
            assert pool.busy()
            return counts
        run(strand())                        # loop A dies here
        assert eng._outstanding == 1         # handle pinned by the plan

        async def teardown_drain():
            await pool.drain()               # loop B
        run(teardown_drain(), timeout=30)
        assert not pool.busy()
        assert eng._outstanding == 0         # pin released: swaps free
        # stranded deliveries are LOST by contract (the loop died), but
        # accounted — never silently leaked
        assert node.metrics.val("messages.dropped.no_subscribers") >= 1


# ---------- satellite: task-hygiene static pass (tier-1 gate) ---------

class TestTaskHygiene:
    def test_flags_fire_and_forget(self):
        src = ("import asyncio\n"
               "async def f():\n"
               "    asyncio.create_task(g())\n"
               "    asyncio.ensure_future(g())\n")
        got = hygiene.check_source("x.py", src)
        assert [f.kind for f in got] == ["fire-and-forget"] * 2

    def test_accepts_held_or_guarded(self):
        src = ("import asyncio\n"
               "async def f():\n"
               "    t = asyncio.create_task(g())\n"
               "    ts.append(asyncio.ensure_future(g()))\n"
               "    await asyncio.create_task(g())\n"
               "    guard_task(asyncio.create_task(g()), 'n')\n")
        assert hygiene.check_source("x.py", src) == []

    def test_flags_commentless_except_pass(self):
        src = ("try:\n    f()\nexcept Exception:\n    pass\n")
        got = hygiene.check_source("x.py", src)
        assert [f.kind for f in got] == ["except-pass"]
        ok = ("try:\n    f()\n"
              "except Exception:  # noqa: BLE001 — best-effort close\n"
              "    pass\n")
        assert hygiene.check_source("x.py", ok) == []
        narrow = ("try:\n    f()\nexcept ValueError:\n    pass\n")
        assert hygiene.check_source("x.py", narrow) == []

    def test_repo_is_clean(self):
        """The tier-1 gate: emqx_tpu/ has zero hygiene findings."""
        root = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "emqx_tpu")
        findings = hygiene.check(root)
        assert findings == [], "\n".join(map(repr, findings))


# ---------- telemetry: the supervise section + exporters ----------

class TestSuperviseTelemetry:
    def test_snapshot_section(self):
        node = Node({"broker": {"device_fanout_cap": 16,
                                "device_slot_cap": 4}})
        sup = node.supervisor
        assert sup is not None
        sup.note_fault("dispatch")
        sup.note_stall("materialize")
        sup.note_replay()
        snap = node.pipeline_telemetry.snapshot()["supervise"]
        assert snap["faults"] == 2          # fault + stall's fault
        assert snap["replays"] == 1
        assert snap["stalls"] == 1
        assert snap["faults_by_point"] == {"dispatch": 1,
                                           "materialize": 1}
        assert snap["stalls_by_stage"] == {"materialize": 1}
        st = snap["state"]
        assert st["rung"] == 0
        assert set(st["breakers"]) == set(S.FAULT_POINTS)
        assert st["journal_depth"] == 0
        assert "watchdog" in st

    def test_prometheus_carries_supervise_counters(self):
        from emqx_tpu.apps.prometheus import collect
        node = Node({"broker": {"device_fanout_cap": 16,
                                "device_slot_cap": 4}})
        node.supervisor.note_fault("dispatch")
        text = collect(node)
        assert "emqx_supervise_faults" in text
        assert "emqx_supervise_faults_dispatch" in text

    def test_sys_publishes_supervise_section(self):
        from emqx_tpu.apps.sys import SysBroker
        node = Node({"broker": {"device_fanout_cap": 16,
                                "device_slot_cap": 4}})
        node.supervisor.note_fault("dispatch")
        published = {}
        app = SysBroker(node)
        app._pub = lambda topic, payload: published.update(
            {topic: payload})
        app.publish_pipeline()
        assert "pipeline/supervise" in published
        doc = json.loads(published["pipeline/supervise"])
        assert doc["faults"] == 1
