"""Depth-twin A/B contract for the double-buffered window pipeline
(ISSUE 9).

The tentpole changes WHEN dispatch/materialize run (up to
``dispatch_depth`` windows' stages in flight concurrently), never WHAT
settles or in what order. These tests pin that contract:

- **Twin runs** over clean/shared/dirty/churn interleavings: depth-1 vs
  depth-2 runs of the same deterministic schedule produce bit-identical
  per-session delivery order and settle counts.
- **Mid-pipeline fault**: dispatch(W+1) is in flight when
  materialize(W) dies — both windows replay through the journal with
  zero QoS≥1 loss and FIFO order preserved, while ≥2 windows were
  measurably in flight when the fault hit.
- **Depth-1 guard** (tier-1): ``EMQX_TPU_DISPATCH_DEPTH=1`` restores
  the pre-ISSUE-9 synchronous consumer EXACTLY — the pipelined ring is
  never entered, and the flight-recorder span structure matches the
  synchronous shape.
- **Knob resolution**: config beats env beats default 2; malformed
  values fail loudly.
"""

import asyncio
import os
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))

from emqx_tpu.broker import supervise as S                  # noqa: E402
from emqx_tpu.broker.batcher import (PublishBatcher,        # noqa: E402
                                     resolve_dispatch_depth)
from emqx_tpu.broker.message import make                    # noqa: E402
from emqx_tpu.broker.node import Node                       # noqa: E402

N_FILTERS = 6
BATCH = 48
WINDOWS = 6


def run(coro, timeout=180):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()


class Rec:
    def __init__(self):
        self.got = []

    def deliver(self, topic_filter, msg):
        self.got.append((topic_filter, msg.topic, bytes(msg.payload)))
        return True


def build_node(depth: int, *, lanes: int = 0,
               supervise: bool = True) -> Node:
    node = Node({"broker": {
        "dispatch_depth": depth,
        "device_fanout_cap": 16, "device_slot_cap": 4,
        "deliver_lanes": lanes, "device_min_batch": 4,
        "batch_window_us": 2000, "supervise": supervise,
        "supervise_threshold": 1,
        # one schedule burst = one window, so a back-to-back submit
        # keeps dispatch_depth windows genuinely in the ring
        "max_publish_batch": BATCH + 1}})
    # pin the adaptive chooser to the device: the depth contract under
    # test is the DEVICE window pipeline, not the host-probe cadence
    node.publish_batcher._device_worth_it = lambda n: True
    return node


def build_world(node: Node, mode: str) -> dict:
    """Deterministic world per interleaving mode. Every session
    subscribes exactly ONE filter, so its delivered sequence is the
    publish-order subsequence of its topic — path-independent by
    construction, the same oracle ground as tools/chaos_bench.py."""
    b = node.broker
    sinks = {}
    for i in range(N_FILTERS):
        for q in (0, 1):
            s = Rec()
            sid = b.register(s, f"c{i}-{q}")
            sinks[sid] = s
            b.subscribe(sid, f"t/{i}/+", {"qos": q})
    if mode == "shared":
        # shared groups exercise the cursor state machine: the
        # round-robin pick of window W+1 depends on W's new_cursors, so
        # any adoption/readback race between in-flight windows would
        # show up as diverged picks between the depth twins
        for i in range(N_FILTERS):
            for m in range(2):
                s = Rec()
                sid = b.register(s, f"g{i}-{m}")
                sinks[sid] = s
                b.subscribe(sid, f"$share/g{i}/t/{i}/+", {"qos": 1})
    return sinks


def schedule(windows: int = WINDOWS, batch: int = BATCH) -> list:
    wins = []
    seq = 0
    for _w in range(windows):
        msgs = [(f"t/{(seq + i) % N_FILTERS}/x", b"m%06d" % (seq + i))
                for i in range(batch)]
        seq += batch
        wins.append(msgs)
    return wins


async def _warm(node: Node) -> None:
    eng = node.device_engine
    eng.rebuild()
    eng._kick_class_warm()
    if eng._fuse_warm_task is not None:
        await eng._fuse_warm_task


async def _drive(node: Node, wins, mode: str) -> list:
    """Publish the schedule in back-to-back window bursts WITHOUT
    awaiting settle between windows — at depth ≥ 2 consecutive windows
    genuinely coexist in the ring (the synchronous depth-1 twin drains
    them one at a time). Segmented only at churn points: a mid-run
    (un)subscribe lands at a fully-settled message boundary, so the
    world state every message observes is deterministic across the
    depth twins."""
    b = node.broker
    counts: list = [None] * len(wins)
    pending: list = []      # (window index, its publish futures)
    churn_sid = None

    async def flush():
        for w, futs in pending:
            counts[w] = await asyncio.gather(*futs)
        pending.clear()
        pool = node.deliver_lanes
        if pool is not None:
            await pool.drain()

    for w, msgs in enumerate(wins):
        if mode in ("dirty", "churn") and w == 2:
            # a post-snapshot filter makes the overlay dirty mid-run —
            # the interleaving where in-flight windows and delta state
            # coexist
            await flush()
            s = Rec()
            churn_sid = b.register(s, "cd")
            b.subscribe(churn_sid, "d/+", {"qos": 1})
        if mode == "churn" and w == 4 and churn_sid is not None:
            await flush()
            b.unsubscribe(churn_sid, "d/+")
            churn_sid = None
        if churn_sid is not None:
            msgs = msgs + [("d/x", b"d%03d" % w)]
        pending.append((w, [
            asyncio.ensure_future(node.publish_async(
                make("pub", 1, t, p))) for t, p in msgs]))
    await flush()
    return counts


def run_depth(depth: int, mode: str, *, lanes: int = 0) -> dict:
    node = build_node(depth, lanes=lanes)
    sinks = build_world(node, mode)
    wins = schedule()

    async def go():
        await _warm(node)
        return await _drive(node, wins, mode)

    counts = run(go())
    assert node.publish_batcher.dispatch_depth == depth
    assert node.device_engine.dispatch_depth == depth
    # sids are deterministic (same registration order both runs), so
    # the sid-keyed order oracle compares across the depth twins
    return {
        "counts": [list(c) for c in counts],
        "order": {sid: s.got for sid, s in sinks.items()},
        "device_windows":
            node.metrics.val("routing.device.batches"),
    }


# ---------- knob resolution ----------

class TestKnob:
    def test_config_beats_env_beats_default(self, monkeypatch):
        monkeypatch.delenv("EMQX_TPU_DISPATCH_DEPTH", raising=False)
        assert resolve_dispatch_depth(None) == 2
        monkeypatch.setenv("EMQX_TPU_DISPATCH_DEPTH", "3")
        assert resolve_dispatch_depth(None) == 3
        assert resolve_dispatch_depth(1) == 1      # config wins
        assert resolve_dispatch_depth("4") == 4

    @pytest.mark.parametrize("bad", ["zero", "", 0, -1, "1.5"])
    def test_malformed_fails_loudly(self, bad):
        with pytest.raises(ValueError):
            resolve_dispatch_depth(bad)

    def test_batcher_and_engine_share_resolution(self, monkeypatch):
        monkeypatch.delenv("EMQX_TPU_DISPATCH_DEPTH", raising=False)
        node = build_node(3)
        assert node.publish_batcher.dispatch_depth == 3
        assert node.device_engine.dispatch_depth == 3
        assert node.device_engine._pipelined


# ---------- the depth-twin A/B contract ----------

@pytest.mark.slow
class TestDepthTwins:
    @pytest.mark.parametrize("mode",
                             ["clean", "shared", "dirty", "churn"])
    def test_bit_identical_delivery(self, mode):
        a = run_depth(1, mode)
        b = run_depth(2, mode)
        assert a["counts"] == b["counts"], \
            f"{mode}: settle counts diverged between depths"
        assert a["order"] == b["order"], \
            f"{mode}: per-session delivery order diverged"

    def test_depth2_with_lanes_clean(self):
        # the lanes (ISSUE 5) and the settle ring (ISSUE 9) compose:
        # plan hand-off order is the settle order, which stays FIFO
        a = run_depth(1, "clean", lanes=2)
        b = run_depth(2, "clean", lanes=2)
        assert a["counts"] == b["counts"]
        assert a["order"] == b["order"]


# ---------- mid-pipeline fault: two windows in flight ----------

class TestMidPipelineFault:
    def test_materialize_death_with_dispatch_in_flight(self,
                                                       monkeypatch):
        """dispatch(W+1) is in flight when materialize(W) dies: both
        windows settle through the journal with zero QoS≥1 loss, FIFO
        order intact — and the run PROVES ≥2 windows were concurrently
        in flight when the fault fired."""
        node = build_node(2)
        sup = node.supervisor
        for br in sup.breakers.values():
            br.base_cooldown_s = br.cooldown_s = 0.05
        sinks = build_world(node, "clean")
        wins = schedule(windows=8)

        # concurrency witness: count stage tasks alive inside
        # _run_stages; record the high-water mark and the in-flight
        # level at the moment the armed fault fires
        live = {"n": 0, "peak": 0, "at_fault": 0}
        orig = PublishBatcher._run_stages

        async def counted(self, entry, loop):
            live["n"] += 1
            live["peak"] = max(live["peak"], live["n"])
            try:
                return await orig(self, entry, loop)
            finally:
                live["n"] -= 1
        monkeypatch.setattr(PublishBatcher, "_run_stages", counted)

        orig_fire = S.FaultInjector.fire

        def spy_fire(inj, point, **kw):
            try:
                return orig_fire(inj, point, **kw)
            except BaseException:
                live["at_fault"] = max(live["at_fault"], live["n"])
                raise
        monkeypatch.setattr(S.FaultInjector, "fire", spy_fire)

        async def go():
            await _warm(node)
            sup.injector = S.FaultInjector(S.parse_faults(
                "materialize:exception:after=1:count=1"))
            return await _drive(node, wins, "clean")

        counts = run(go())
        m = node.metrics
        assert sum(f.fired for f in sup.injector.faults) == 1, \
            "armed fault never fired"
        assert live["peak"] >= 2, \
            f"never ≥2 windows in flight (peak {live['peak']})"
        assert live["at_fault"] >= 2, \
            "fault did not hit while a second window was in flight"
        assert m.val("supervise.replays") >= 1
        assert m.val("messages.dropped") == 0
        # zero QoS≥1 loss: every settled count equals the fan-out (2)
        for w, cs in enumerate(counts):
            assert all(c == 2 for c in cs), f"window {w}: lost delivery"
        # per-session order: payload sequence strictly increasing per
        # topic (the publish-order subsequence — FIFO preserved through
        # the replay)
        for sid, s in sinks.items():
            pays = [p for _f, _t, p in s.got]
            assert pays == sorted(pays), f"sid {sid}: order broke"
        assert sup.journal_depth() == 0

    def test_chaos_matrix_cell_at_depth2(self):
        """One full chaos-harness cell runs green at depth 2 (the whole
        matrix runs at the session's default depth via
        tests/test_supervise.py; this pins the depth explicitly)."""
        import chaos_bench as CB
        old = os.environ.pop("EMQX_TPU_DISPATCH_DEPTH", None)
        try:
            twin = CB.run_twin()
            case = CB.run_case("materialize", "exception")
            bad = CB.grade(case, twin, "materialize", "exception")
            assert not bad, bad
            assert case["replays"] >= 1
        finally:
            if old is not None:
                os.environ["EMQX_TPU_DISPATCH_DEPTH"] = old


# ---------- depth-1 guard: pre-ISSUE-9 behavior, exactly ----------

class TestDepth1Guard:
    def test_synchronous_loop_never_enters_the_ring(self, monkeypatch):
        """At depth 1 the pipelined consumer is dead code: entering it
        would mean the A/B baseline is no longer the pre-ISSUE-9 code
        path."""
        def boom(self):
            raise AssertionError(
                "depth-1 node entered _consume_pipelined")
        monkeypatch.setattr(PublishBatcher, "_consume_pipelined", boom)

        node = build_node(1)
        eng = node.device_engine
        assert not eng._pipelined

        sinks = build_world(node, "clean")
        wins = schedule(windows=4)

        async def go():
            await _warm(node)
            return await _drive(node, wins, "clean")

        counts = run(go())
        assert all(c == 2 for cs in counts for c in cs)
        assert node.metrics.val("supervise.task_errors") == 0
        assert len(sinks) == 2 * N_FILTERS

    def test_depth1_trace_shape_matches_synchronous(self):
        """The flight-recorder span structure at depth 1 is the
        synchronous per-window shape: within every device window,
        materialize begins only after ITS OWN dispatch ended, and the
        consumer settles windows strictly one at a time (no window's
        materialize starts before the previous window settled its
        stages). Cross-window dispatch overlap is NOT asserted either
        way: the producer has launched dispatch-at-admit since the
        round-2 pipelined serving path — ISSUE 9's ring moves the
        MATERIALIZE launch ahead of the previous settle, which is
        exactly what the ordering below pins to the old behavior."""
        node = build_node(1)
        build_world(node, "clean")
        wins = schedule()

        async def go():
            await _warm(node)
            return await _drive(node, wins, "clean")

        run(go())
        rec = node.flight_recorder
        assert rec is not None
        spans = rec.spans()
        by_trace = {}
        for sp in spans:
            by_trace.setdefault(sp.trace_id, {})[sp.name] = sp
        mats = []
        for tid, names in by_trace.items():
            if "dispatch" in names and "materialize" in names:
                assert names["materialize"].t0 >= names["dispatch"].t1
                mats.append(names["materialize"])
        # depth 1 = one materialize at a time, in settle order
        mats.sort(key=lambda sp: sp.t0)
        for a, b in zip(mats, mats[1:]):
            assert b.t0 >= a.t1, \
                "depth-1 run overlapped two windows' materialize"
        assert len(mats) >= 2, "schedule produced <2 device windows"
