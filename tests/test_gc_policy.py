"""The long-lived heap out of the collector's way (ISSUE 31):
`trace.HeapFreeze`, the process's one owner of `gc.freeze()`, and the
`GcWatch` of each node that reports to it.

The policy cases are driven by events against a stand-in collector and
a stand-in loop: no wall clock decides anything here. The cases that
need the real collector (a frozen cycle, a frozen snapshot) give the
watch an owner of their own, so the worker's own heap is thawed again
when they end.
"""

import asyncio
import gc
import json
import os
import subprocess
import sys
import weakref

import pytest

from emqx_tpu.broker import trace as T
from emqx_tpu.broker.message import make
from emqx_tpu.broker.metrics import Metrics
from emqx_tpu.broker.node import Node

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LONG = 4 * T.FREEZE_PAUSE_FLOOR_S         # a pause well over the floor
FREEZE = ["freeze", "collect"]            # then the little that is younger
REEVALUATE = ["unfreeze", "collect", "freeze", "collect"]


def _reclaimed_in(seconds: float) -> int:
    """As many objects as a collection reclaims in `seconds` of its
    pause."""
    return int(seconds / T.FREEZE_RECLAIM_S)


class FakeGc:
    """What `HeapFreeze` takes from the `gc` module."""

    def __init__(self, live=100_000):
        self.calls = []
        self.live, self.permanent = live, 0

    def freeze(self):
        self.calls.append("freeze")
        self.permanent += self.live
        self.live = 0

    def unfreeze(self):
        self.calls.append("unfreeze")
        self.live += self.permanent
        self.permanent = 0

    def collect(self):
        self.calls.append("collect")
        return 0

    def get_freeze_count(self):
        return self.permanent

    thresholds = (700, 10, 10)

    def get_threshold(self):
        return self.thresholds

    def set_threshold(self, *t):
        self.thresholds = t


class FakeLoop:
    """The one call a watch makes on its loop; `turn()` is its next
    turn."""

    def __init__(self):
        self.due = []
        self.closed = False

    def is_closed(self):
        return self.closed

    def call_soon_threadsafe(self, fn, *args):
        self.due.append((fn, args))

    def turn(self):
        due, self.due = self.due, []
        for fn, args in due:
            fn(*args)


def _watch(heap, rec=None):
    """A started watch on a stand-in loop, reporting to `heap`."""
    w = T.GcWatch(Metrics(), T.Spans(None, rec))
    w.heap = heap
    w._loop = FakeLoop()
    w.start()
    return w


def _heap(**kw):
    fake = FakeGc(**kw)
    return T.HeapFreeze(fake), fake


def run(coro, timeout=120):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()


class TestPolicy:
    @pytest.mark.parametrize("pause_s,reclaimed,freezes", [
        # long on what survived: the heap is long-lived
        (LONG, 0, 1),
        (LONG, _reclaimed_in(LONG - 1.1 * T.FREEZE_PAUSE_FLOOR_S), 1),
        (1.01 * T.FREEZE_PAUSE_FLOOR_S, 10, 1),
        # short: a small heap is never frozen
        (0.9 * T.FREEZE_PAUSE_FLOOR_S, 0, 0),
        (0.001, 0, 0),
        # long, but on what it reclaimed: the pause was earning its keep
        (LONG, _reclaimed_in(LONG - 0.9 * T.FREEZE_PAUSE_FLOOR_S), 0),
        (LONG, 10_000_000, 0),
    ])
    def test_engages_after_a_long_unproductive_collection_only(
            self, pause_s, reclaimed, freezes):
        heap, fake = _heap()
        w = _watch(heap)
        heap.collected(w, pause_s, reclaimed)
        w._loop.turn()
        assert fake.calls.count("freeze") == freezes
        assert w.metrics.val("runtime.gc.freezes") == freezes
        assert heap.frozen == (100_000 if freezes else 0)
        w.stop()

    def test_freezes_on_the_loops_next_turn_not_in_the_callback(self):
        heap, fake = _heap()
        w = _watch(heap)
        heap.collected(w, LONG, 0)
        assert fake.calls == [] and heap.frozen == 0     # only scheduled
        assert len(w._loop.due) == 1
        w._loop.turn()
        assert fake.calls == FREEZE
        w.stop()

    def test_engages_again_after_the_heap_has_grown(self):
        heap, fake = _heap()
        w = _watch(heap)
        heap.collected(w, LONG, 0)
        w._loop.turn()
        assert heap.frozen == 100_000
        fake.live = 40_000                 # a subscribe storm since
        heap.collected(w, 0.5 * T.FREEZE_PAUSE_FLOOR_S, 0)
        w._loop.turn()
        assert fake.calls.count("freeze") == 1      # not yet long
        fake.live = 400_000
        heap.collected(w, LONG, 3)
        w._loop.turn()
        assert fake.calls.count("freeze") == 2
        assert heap.frozen == 500_000
        assert w.metrics.val("runtime.gc.freezes") == 2
        w.stop()

    def test_a_freeze_that_took_nothing_off_the_pause_is_not_repeated(self):
        """What is in flight is walked by every full collection, frozen
        or not: the first pause after a freeze is the base."""
        heap, fake = _heap()
        w = _watch(heap)
        heap.collected(w, LONG, 0)
        w._loop.turn()
        for _k in range(5):                 # as long as before: in flight
            heap.collected(w, LONG, 0)
            w._loop.turn()
        heap.collected(w, LONG + 0.9 * T.FREEZE_PAUSE_FLOOR_S, 0)
        w._loop.turn()
        assert fake.calls == FREEZE
        heap.collected(w, LONG + 1.1 * T.FREEZE_PAUSE_FLOOR_S, 0)   # grown
        w._loop.turn()
        assert fake.calls == FREEZE * 2
        heap.collected(w, 0.001, 0)         # it helped: a low base again
        heap.collected(w, T.FREEZE_PAUSE_FLOOR_S + 0.002, 0)
        w._loop.turn()
        assert fake.calls == FREEZE * 3
        w.stop()

    def test_one_collection_reported_twice_is_one_freeze(self):
        heap, fake = _heap()
        a, b = _watch(heap), _watch(heap)
        heap.collected(a, LONG, 0)          # both nodes' callbacks see
        heap.collected(b, LONG, 0)          # the same collection
        assert len(a._loop.due) == 1 and b._loop.due == []
        a._loop.turn()
        b._loop.turn()
        assert fake.calls == FREEZE
        # the heap is the process's: both nodes count the freeze
        assert a.metrics.val("runtime.gc.freezes") == 1
        assert b.metrics.val("runtime.gc.freezes") == 1
        a.stop()
        b.stop()

    def test_only_generation_2_reaches_the_policy(self, monkeypatch):
        heap, fake = _heap()
        w = _watch(heap)
        clock = iter([0.0, 9.0, 10.0, 19.0, 20.0, 29.0])
        monkeypatch.setattr(T.time, "perf_counter", lambda: next(clock))
        for gen in (0, 1):                  # nine seconds each, young
            w._on_gc("start", {"generation": gen})
            w._on_gc("stop", {"generation": gen, "collected": 0})
        assert w._loop.due == []
        w._on_gc("start", {"generation": 2})
        w._on_gc("stop", {"generation": 2, "collected": 0})
        w._loop.turn()
        assert fake.calls == FREEZE
        assert w.metrics.val("runtime.gc.pauses.gen2") == 1
        assert w.metrics.val("runtime.gc.pause_us") == 27_000_000
        w.stop()

    def test_a_collection_on_another_thread_cannot_wedge_the_policy(self):
        """A collection on an executor thread schedules the freeze from
        there, and the loop may run it before that thread has come back
        from the call: the debt is booked first."""
        class EagerLoop(FakeLoop):
            def call_soon_threadsafe(self, fn, *args):
                fn(*args)               # the loop thread won the race
        heap, fake = _heap()
        w = _watch(heap)
        w._loop = EagerLoop()
        heap.collected(w, LONG, 0)
        assert fake.calls == FREEZE and heap._due is None
        heap.collected(w, 0.001, 0)         # the base
        heap.collected(w, LONG, 0)          # and the next one still counts
        assert fake.calls == FREEZE * 2 and heap._due is None
        w.stop()

    def test_a_watch_without_a_loop_is_counted_and_never_frozen(self):
        heap, fake = _heap()
        w = T.GcWatch(Metrics(), T.Spans(None, None))
        w.heap = heap
        w.start()                           # no loop is running here
        assert w._loop is None
        heap.collected(w, LONG, 0)
        assert heap._due is None and fake.calls == []
        w._loop = FakeLoop()
        w._loop.closed = True               # nor on a loop that is gone
        heap.collected(w, LONG, 0)
        assert heap._due is None and fake.calls == []
        w.stop()


class TestOwnership:
    def test_two_nodes_share_one_owner(self):
        a, b = Node(use_device=False), Node(use_device=False)
        assert a.gc_watch.heap is T.HEAP and b.gc_watch.heap is T.HEAP
        assert a.gc_watch is not b.gc_watch

    def test_the_last_stop_in_the_process_unfreezes(self):
        heap, fake = _heap()
        a, b = _watch(heap), _watch(heap)
        a.start()                           # a second listener of a's
        heap.collected(b, LONG, 0)
        b._loop.turn()
        assert heap.frozen == 100_000
        a.stop()
        b.stop()
        assert "unfreeze" not in fake.calls         # a still serves
        a.stop()
        assert fake.calls == FREEZE + ["unfreeze"]
        assert heap.frozen == 0 and fake.permanent == 0
        a.stop()                            # one stop too many: ignored
        assert fake.calls == FREEZE + ["unfreeze"]

    def test_the_old_generation_waits_longer_while_the_heap_is_frozen(self):
        """Frozen, the collector's quarter rule is always met: the owner
        of the freeze spaces the full collections out instead, leaves
        the young thresholds alone and hands the interpreter's back."""
        heap, fake = _heap()
        fake.thresholds = (900, 12, 10)
        a, b = _watch(heap), _watch(heap)
        assert fake.thresholds == (900, 12, 10)
        heap.collected(a, LONG, 0)
        a._loop.turn()
        assert fake.thresholds == (900, 12, T.FROZEN_OLD_THRESHOLD)
        heap.collected(a, 0.001, 0)
        heap.collected(a, LONG, 0)          # a second freeze, and a
        a._loop.turn()                      # re-evaluation: still the
        heap.reevaluate()                   # interpreter's to restore
        assert fake.thresholds == (900, 12, T.FROZEN_OLD_THRESHOLD)
        a.stop()
        assert fake.thresholds == (900, 12, T.FROZEN_OLD_THRESHOLD)
        b.stop()
        assert fake.thresholds == (900, 12, 10)

    def test_a_process_never_frozen_is_not_thawed(self):
        heap, fake = _heap()
        w = _watch(heap)
        heap.collected(w, 0.5 * T.FREEZE_PAUSE_FLOOR_S, 0)
        w.stop()
        assert fake.calls == [] and fake.thresholds == (700, 10, 10)

    def test_stopping_the_node_that_owes_the_freeze_cancels_it(self):
        heap, fake = _heap()
        a, b = _watch(heap), _watch(heap)
        heap.collected(a, LONG, 0)
        a.stop()                            # lets go of its loop, too
        assert a._loop is None and heap._due is None
        heap.collected(b, LONG, 0)          # the next one is b's to do
        b._loop.turn()
        assert fake.calls == FREEZE
        b.stop()

    def test_a_real_loop_runs_the_freeze(self):
        heap, fake = _heap()
        w = T.GcWatch(Metrics(), T.Spans(None, None))
        w.heap = heap

        async def go():
            w.start()
            heap.collected(w, LONG, 0)
            assert fake.calls == []
            await asyncio.sleep(0)
            await asyncio.sleep(0)
            assert fake.calls == FREEZE
            w.stop()
        run(go())
        assert fake.calls == FREEZE + ["unfreeze"]


class TestReevaluation:
    def _frozen(self, live=220_000):
        heap, fake = _heap(live=live)
        w = _watch(heap)
        heap.collected(w, LONG, 0)
        w._loop.turn()
        return heap, fake, w

    def test_churn_below_the_share_costs_no_pause(self):
        heap, fake, w = self._frozen()
        due = int(T.REEVALUATE_CHURN_SHARE * 220_000
                  / T.CONTAINERS_PER_SUBSCRIPTION)          # 1,000
        w.housekeeping(closed=10, subscriptions=5_000)
        w.housekeeping(closed=10 + due // 2, subscriptions=5_000)
        assert fake.calls == FREEZE
        assert w.metrics.val("runtime.gc.reevaluations") == 0
        w.stop()

    def test_churn_past_the_share_thaws_collects_and_freezes(self):
        heap, fake, w = self._frozen()
        w.housekeeping(closed=0, subscriptions=5_000)
        w.housekeeping(closed=400, subscriptions=5_000)     # connections
        assert fake.calls == FREEZE
        w.housekeeping(closed=400, subscriptions=4_300)     # + removals
        assert fake.calls == FREEZE + REEVALUATE
        assert w.metrics.val("runtime.gc.reevaluations") == 1
        assert heap.churn == 0 and heap.frozen == 220_000
        # subscriptions that came are not churn
        w.housekeeping(closed=400, subscriptions=9_000)
        assert fake.calls == FREEZE + REEVALUATE
        w.stop()

    def test_its_own_collection_does_not_schedule_a_freeze(self):
        heap, fake, w = self._frozen()

        def collect():
            fake.calls.append("collect")
            heap.collected(w, LONG, 0)      # the callback, mid-collect
            return 0
        fake.collect = collect
        heap.reevaluate()
        assert w._loop.due == [] and heap._due is None
        assert fake.calls == FREEZE + REEVALUATE
        w.stop()

    def test_a_node_that_is_not_serving_reports_no_churn(self):
        heap, fake, w = self._frozen()
        idle = T.GcWatch(Metrics(), T.Spans(None, None))
        idle.heap = heap
        idle.housekeeping(closed=10_000, subscriptions=0)
        assert fake.calls == FREEZE
        w.stop()

    def test_the_nodes_sweep_is_the_housekeeping_pass(self):
        node = Node(use_device=False)
        heap, fake = _heap(live=2_200)
        w = node.gc_watch
        w.heap = heap
        w._loop = FakeLoop()
        w.start()
        heap.collected(w, LONG, 0)
        w._loop.turn()
        sid = node.broker.register(object(), "c")
        for i in range(40):
            node.broker.subscribe(sid, f"a/{i}", {"qos": 0})
        node.sweep()
        assert fake.calls == FREEZE
        for i in range(40):
            node.broker.unsubscribe(sid, f"a/{i}")
        node.sweep()                        # 40 x 22 >= 10 % of 2,200
        assert fake.calls == FREEZE + REEVALUATE
        assert node.metrics.val("runtime.gc.reevaluations") == 1
        w.stop()

    def test_a_frozen_cycle_is_reclaimed_by_a_reevaluation_only(self):
        class Knot:
            pass
        heap = T.HeapFreeze()               # the real collector
        w = _watch(heap)
        try:
            a, b = Knot(), Knot()
            a.other, b.other = b, a
            ref = weakref.ref(a)
            heap.collected(w, LONG, 0)
            w._loop.turn()
            assert heap.frozen > 0 and gc.get_freeze_count() > 0
            del a, b
            gc.collect()
            assert ref() is not None        # frozen: no collection sees it
            heap.reevaluate()
            assert ref() is None
            assert w.metrics.val("runtime.gc.reevaluations") == 1
        finally:
            w.stop()
        assert gc.get_freeze_count() == 0
        assert gc.get_threshold()[2] != T.FROZEN_OLD_THRESHOLD


class TestFrozenSnapshot:
    def test_a_superseded_snapshot_that_was_frozen_is_released_at_swap(self):
        """The engine's snapshot graph holds no cycle: once the last
        handle drops, the superseded tables go by reference count and
        the HBM ledger's bytes come back, frozen or not."""
        from emqx_tpu.broker import hbm_ledger as H
        node = Node({"broker": {"device_fanout_cap": 16,
                                "device_slot_cap": 4,
                                "device_min_batch": 1, "deliver_lanes": 0}})
        eng, led = node.device_engine, node.hbm_ledger
        eng.delta_overlay = False           # one new filter = a rebuild
        sid = node.broker.register(_Sink(), "c")
        for i in range(24):
            node.broker.subscribe(sid, f"gcp/{i}/+", {"qos": 0})
        heap = T.HeapFreeze()
        w = node.gc_watch
        w.heap = heap

        async def go():
            w.start()
            for _k in range(3):
                await asyncio.gather(*[
                    node.publish_async(make("p", 0, f"gcp/{i}/x", b"m"))
                    for i in range(24)])
            assert eng._built is not None
            gc.collect()
            base = led.live_bytes()
            old = [weakref.ref(x) for x in H._leaves(eng._tables)]
            assert base > 0 and old
            heap.collected(w, LONG, 0)
            await asyncio.sleep(0)
            await asyncio.sleep(0)
            assert node.metrics.val("runtime.gc.freezes") == 1
            assert heap.frozen > 0
            gc.disable()                    # reference counts alone
            try:
                eng.rebuild_threshold = 1
                node.broker.subscribe(sid, "gcp/extra/+", {"qos": 0})
                assert eng.maybe_background_rebuild()
                for _ in range(12000):
                    if not eng._building:
                        break
                    await asyncio.sleep(0.005)
                assert not eng._building
                await node.publish_async(make("p", 0, "gcp/extra/x", b"m"))
                alive = sum(r() is not None for r in old)
                live = led.live_bytes("snapshot_tables") \
                    + led.live_bytes("snapshot_cursors")
                held = sum(int(x.nbytes) for tree in (eng._tables,
                                                      eng._cursors)
                           for x in H._leaves(tree))
            finally:
                gc.enable()
            return base, alive, live, held
        try:
            base, alive, live, held = run(go(), timeout=300)
        finally:
            w.stop()
        assert alive == 0
        # the ledger holds the serving snapshot and nothing of the old
        assert base > 0 and live == pytest.approx(held, rel=0.01)
        assert gc.get_freeze_count() == 0


class _Sink:
    def deliver(self, topic_filter, msg):
        return True


class TestDeliveredWindows:
    def test_a_delivered_window_dies_by_reference_count(self):
        """A finished `DeliveryPlan` lets go of its `LaneCounts` (they
        pointed at each other, so every delivered window's messages
        waited for a full collection: 20,000-70,000 objects each, my
        chip runs, PR 31). With the collector off, the messages of a
        lane-delivered window are gone once it has settled."""
        import time
        node = Node({"broker": {"device_fanout_cap": 16,
                                "device_slot_cap": 4,
                                "device_min_batch": 4,
                                "batch_window_us": 1000,
                                "deliver_lanes": 2}})
        eng = node.device_engine
        for i in range(8):
            sid = node.broker.register(_Sink(), f"dw{i}")
            node.broker.subscribe(sid, f"dw/{i}/+", {"qos": 1})

        async def go():
            eng.route_batch([make("p", 0, f"dw/{i}/w", b"") for i in range(8)])
            deadline = time.monotonic() + 90
            while not eng.batch_class_warm(8) \
                    and time.monotonic() < deadline:
                eng._kick_class_warm()
                await asyncio.sleep(0.05)
            assert eng.batch_class_warm(8)
            node.publish_batcher._device_worth_it = lambda n: True
            plans0 = node.metrics.val("pipeline.deliver.plans")
            gc.collect()
            gc.disable()
            try:
                refs = []
                for w in range(4):
                    msgs = [make("p", 1, f"dw/{i}/x", b"m%d" % w)
                            for i in range(8)]
                    refs += [weakref.ref(m) for m in msgs]
                    await asyncio.gather(*[node.publish_async(m)
                                           for m in msgs])
                    del msgs
                pool = node.deliver_lanes
                if pool.busy():
                    await pool.drain()
                await asyncio.sleep(0.05)
                alive = sum(r() is not None for r in refs)
            finally:
                gc.enable()
            return alive, node.metrics.val("pipeline.deliver.plans") - plans0
        alive, plans = run(go(), timeout=240)
        assert plans >= 1                   # the lanes did deliver them
        assert alive == 0


class TestCounters:
    def _node(self):
        node = Node(use_device=False)
        heap, fake = _heap(live=4_400)
        w = node.gc_watch
        w.heap = heap
        w._loop = FakeLoop()
        w.start()
        return node, heap, w

    def test_counters_and_gauge_in_metrics_and_stats(self):
        node, heap, w = self._node()
        m = node.metrics.all()
        assert m["runtime.gc.freezes"] == 0          # listed from start
        assert m["runtime.gc.reevaluations"] == 0
        assert node.stats.sample()["runtime.gc.frozen_objects"] == 0
        heap.collected(w, LONG, 0)
        w._loop.turn()
        heap.reevaluate()
        m = node.metrics.all()
        assert m["runtime.gc.freezes"] == 1
        assert m["runtime.gc.reevaluations"] == 1
        assert node.stats.sample()["runtime.gc.frozen_objects"] == 4_400
        w.stop()
        assert node.stats.sample()["runtime.gc.frozen_objects"] == 0

    def test_exporters_carry_them(self):
        from emqx_tpu.apps.prometheus import collect
        from emqx_tpu.apps.statsd import StatsdApp
        node, heap, w = self._node()
        heap.collected(w, LONG, 0)
        w._loop.turn()
        text = collect(node)
        assert "emqx_runtime_gc_freezes 1" in text
        assert "emqx_runtime_gc_reevaluations 0" in text
        assert "emqx_runtime_gc_frozen_objects 4400" in text
        lines = StatsdApp(node).render()
        assert "emqx.runtime.gc.freezes:1|c" in lines
        assert "emqx.runtime.gc.frozen_objects:4400|g" in lines
        w.stop()

    def test_a_freeze_is_an_event_on_the_node_trace(self):
        rec = T.FlightRecorder(Metrics(), cap=64, sample=0)
        heap, fake = _heap()
        w = _watch(heap, rec)
        heap.collected(w, 0.4321, 7)
        w._loop.turn()
        heap.reevaluate()
        evs = {s.name: s for s in rec.spans()}
        fz = evs["gc_freeze"]
        assert fz.trace_id == T.NODE_TRACE and fz.track == "runtime"
        assert fz.meta == {"pause_ms": 432.1, "frozen_objects": 100_000}
        assert fz.t0 == fz.t1                        # an instant event
        assert evs["gc_reevaluate"].meta == {
            "frozen_before": 100_000, "frozen_objects": 100_000}
        w.stop()

    def test_docs_name_every_counter(self):
        with open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")) as f:
            doc = f.read()
        for name in ("runtime.gc.freezes", "runtime.gc.frozen_objects",
                     "runtime.gc.reevaluations", "gc_freeze"):
            assert name in doc, name


class TestNoKnob:
    def test_nothing_in_the_program_sets_the_collector(self):
        """The collector is set in one place, by the owner of the
        freeze: the freeze itself and, while it lasts, the old
        generation's threshold. Nothing disables it, no young threshold
        moves, and there is no environment variable or config field."""
        import re
        hits = []
        for d, _dirs, files in os.walk(os.path.join(ROOT, "emqx_tpu")):
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                with open(os.path.join(d, fn)) as f:
                    src = f.read()
                for m in re.finditer(
                        r"gc\.(set_threshold|disable|freeze|unfreeze)\(",
                        src):
                    hits.append((os.path.relpath(os.path.join(d, fn), ROOT),
                                 m.group(1)))
        assert hits and {p for p, _ in hits} == {"emqx_tpu/broker/trace.py"}
        assert {k for _, k in hits} == {"freeze", "unfreeze",
                                        "set_threshold"}
        with open(os.path.join(ROOT, "emqx_tpu", "broker", "trace.py")) as f:
            src = f.read()
        cut = src[src.index("class HeapFreeze"):src.index("class GcWatch")]
        assert "environ" not in cut and "config" not in cut


def test_served_path_stays_exact_across_freezes_and_churn():
    """A rehearsal-size flood of `plus-100k.flood` through real sockets
    with the floor below zero, so every full collection but the base freezes:
    a churn client subscribes, unsubscribes, drops and reconnects
    between two freezes, the housekeeping pass re-evaluates, and the
    harness's comparison of every delivery stays exact."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("EMQX_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "gc_policy_drive.py"),
         "--workload", "plus-100k.flood", "--seed", str(2**31 + 31),
         "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 100
    rep = [ln for ln in r.stderr.splitlines() if ln.startswith("GCPOLICY ")]
    assert len(rep) == 1, r.stderr[-3000:]
    rep = json.loads(rep[0][len("GCPOLICY "):])
    first, second = rep["freezes"]
    assert first >= 1 and second > first            # churn lay between
    assert rep["reevaluations"] >= 1
    assert rep["frozen_objects"] > 0
    # the churn client got the flood's messages on both connections
    assert rep["churn_received"][0] > 0 and rep["churn_received"][1] > 0
