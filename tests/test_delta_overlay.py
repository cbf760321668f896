"""Device-resident delta overlay + journal-driven rebuilds (ISSUE 4).

The overlay must be INVISIBLE except for speed: under subscribe /
unsubscribe / shared-group churn, an overlay engine (which matches
post-snapshot filters ON DEVICE and demotes full rebuilds to rare
compactions) must deliver exactly the same result set as an oracle
engine that is freshly full-rebuilt before every batch — across trie
and shapes backends, the cached and compact program twins, the overlay
overflow → compaction path, and the mesh. Plus: journal replay ordering
at swap, the delta-aware match-cache invalidation, the knob surface
(EMQX_TPU_DELTA_OVERLAY / broker.delta_overlay A/B exactness,
EMQX_TPU_REBUILD_THRESHOLD validation), and the rebuild telemetry
section.
"""

import numpy as np
import pytest

from emqx_tpu.broker import device_engine as DE
from emqx_tpu.broker.message import make
from emqx_tpu.broker.node import Node


class Sink:
    def __init__(self):
        self.got = []

    def deliver(self, topic_filter, msg):
        self.got.append((topic_filter, msg.topic))
        return True


def mkmsg(topic, payload=b"x"):
    return make("pub", 0, topic, payload)


def _mk_twins(**over):
    """(overlay node, oracle node): identical config except the oracle
    runs with the overlay OFF and is explicitly full-rebuilt by the
    churn driver before every compared batch — the ground truth the
    overlay must match bit-for-bit (in delivered (filter, topic) sets
    and per-message counts)."""
    ov = Node({"broker": {"delta_overlay": True}})
    oracle = Node({"broker": {"delta_overlay": False}})
    for k, v in over.items():
        setattr(ov.device_engine, k, v)
        setattr(oracle.device_engine, k, v)
    return ov, oracle


def _route_both(ov, oracle, topics):
    """Route one batch through both engines; oracle rebuilds first so
    its snapshot reflects the live state exactly."""
    oracle.device_engine.rebuild()
    c1 = ov.device_engine.route_batch([mkmsg(t) for t in topics])
    c2 = oracle.device_engine.route_batch([mkmsg(t) for t in topics])
    assert c1 is not None and c2 is not None
    assert c1 == c2, (c1, c2)
    return c1


def _drain(sink):
    got = sorted(sink.got)
    sink.got = []
    return got


class TestChurnOracle:
    """Twin-engine delivery oracle under subscribe/unsubscribe churn."""

    def _seed(self, node, n=12):
        b = node.broker
        s = Sink()
        sid = b.register(s, "seed")
        for i in range(n):
            b.subscribe(sid, f"dev/{i}/+", {"qos": 1})
        return s, sid

    def _churn_sequence(self, ov, oracle, s_ov, s_or):
        b_ov, b_or = ov.broker, oracle.broker
        c_ov = Sink()
        c_or = Sink()
        sid_ov = b_ov.register(c_ov, "churn")
        sid_or = b_or.register(c_or, "churn")
        topics = [f"dev/{i % 12}/t" for i in range(8)] \
            + ["fresh/1/x"] * 4 + ["deep/a/b/c"] * 2 + ["no/match"] * 2

        # round 1: steady state (no delta filters anywhere)
        _route_both(ov, oracle, topics)
        assert _drain(s_ov) == _drain(s_or)

        # round 2: subscribe NEW filters after the build
        for b, sid in ((b_ov, sid_ov), (b_or, sid_or)):
            b.subscribe(sid, "fresh/+/x", {"qos": 0})
            b.subscribe(sid, "deep/#", {"qos": 1})
        _route_both(ov, oracle, topics)
        assert _drain(s_ov) == _drain(s_or)
        assert _drain(c_ov) == _drain(c_or)

        # round 3: membership change on a delta filter (second member)
        d_ov, d_or = Sink(), Sink()
        for b, snk in ((b_ov, d_ov), (b_or, d_or)):
            sid2 = b.register(snk, "late")
            b.subscribe(sid2, "fresh/+/x", {"qos": 2})
        _route_both(ov, oracle, topics)
        assert _drain(d_ov) == _drain(d_or)
        assert _drain(c_ov) == _drain(c_or)

        # round 4: unsubscribe (delta delete) + shared group churn on a
        # delta filter
        for b, sid in ((b_ov, sid_ov), (b_or, sid_or)):
            b.unsubscribe(sid, "deep/#")
            b.subscribe(sid, "$share/g/fresh/+/x", {"qos": 0})
        _route_both(ov, oracle, topics)
        assert _drain(s_ov) == _drain(s_or)
        assert _drain(c_ov) == _drain(c_or)

        return c_ov, c_or

    def test_shapes_backend(self):
        ov, oracle = _mk_twins()
        s_ov, sid_ov = self._seed(ov)
        s_or, sid_or = self._seed(oracle)
        self._churn_sequence(ov, oracle, s_ov, s_or)
        # unsubscribe a BUILT filter (snapshot tombstone): host-side
        # dirty delivery on the overlay engine, absent on the oracle
        ov.broker.unsubscribe(sid_ov, "dev/2/+")
        oracle.broker.unsubscribe(sid_or, "dev/2/+")
        _route_both(ov, oracle, ["dev/2/t", "dev/3/t"])
        assert _drain(s_ov) == _drain(s_or)
        assert ov.device_engine.stats()["backend"] == "shapes"
        # the overlay actually engaged and kept the device path hot
        assert ov.device_engine.stats()["overlay"] is not None
        assert ov.metrics.val("routing.device.host_delta") == 0
        # the oracle (overlay off) paid full rebuilds every round; the
        # overlay engine kept its first snapshot
        assert ov.metrics.val("routing.device.rebuilds") == 1

    def test_trie_backend(self):
        ov, oracle = _mk_twins(shape_cap=2)
        for node in (ov, oracle):
            b = node.broker
            s = Sink()
            sid = b.register(s, "t")
            for f in ["a", "a/b", "a/+/c", "+/b/#", "x/y/z/w"]:
                b.subscribe(sid, f, {"qos": 0})
        oracle.device_engine.rebuild()
        _route_both(ov, oracle, ["a/b", "x/y/z/w", "a/q/c"])
        assert ov.device_engine.stats()["backend"] == "trie"
        # churn: new filter matched on device via route_window_delta
        for node in (ov, oracle):
            b = node.broker
            sid2 = b.register(Sink(), "t2")
            b.subscribe(sid2, "new/+", {"qos": 0})
        _route_both(ov, oracle, ["new/1", "a/b", "no/match"])
        assert ov.metrics.val("routing.device.host_delta") == 0
        assert ov.device_engine.stats()["overlay"]["rows"] == 1

    def test_cached_and_compact_twins(self):
        """Churn under the dedup/cache plan + CSR readback: the delta
        planes merge through the cached base rows and ride their own
        CSR, still delivery-identical to the fresh-rebuild oracle."""
        ov, oracle = _mk_twins()
        s_ov, _ = self._seed(ov, 8)
        s_or, _ = self._seed(oracle, 8)
        # >64 lanes, few uniques: the plan engages (Bm=64 < Bp=256)
        topics = ["dev/3/t"] * 40 + ["dev/5/t"] * 30 + ["hot/x"] * 20 \
            + ["no/match"] * 10
        _route_both(ov, oracle, topics)
        for node in (ov, oracle):
            b = node.broker
            sid = b.register(Sink(), "late")
            b.subscribe(sid, "hot/+", {"qos": 0})
        for rnd in range(3):    # repeat: cache-hit rounds incl. delta
            _route_both(ov, oracle, topics)
            assert _drain(s_ov) == _drain(s_or), rnd
        eng = ov.device_engine
        assert eng._match_cache is not None and len(eng._match_cache)
        assert ov.metrics.val("routing.device.cached_windows") > 0
        assert ov.metrics.val("routing.device.host_delta") == 0

    def test_overlay_overflow_triggers_compaction(self, monkeypatch):
        """Past the top overlay class the engine compacts (full rebuild
        folding the delta filters into the snapshot) and the compaction
        reason is counted; deliveries stay correct throughout."""
        monkeypatch.setattr(DE, "_DELTA_CLASSES", (4,))
        monkeypatch.setattr(DE, "_OVERLAY_MAX", 4)
        ov, oracle = _mk_twins()
        s_ov, _ = self._seed(ov, 6)
        s_or, _ = self._seed(oracle, 6)
        _route_both(ov, oracle, ["dev/1/t"])
        sinks = []
        for node in (ov, oracle):
            b = node.broker
            snk = Sink()
            sid = b.register(snk, "many")
            sinks.append(snk)
            for i in range(6):      # > overlay max of 4
                b.subscribe(sid, f"bulk/{i}/+", {"qos": 0})
        topics = [f"bulk/{i}/z" for i in range(6)] + ["dev/2/t"]
        _route_both(ov, oracle, topics)
        assert sorted(sinks[0].got) == sorted(sinks[1].got)
        assert ov.metrics.val("routing.device.compactions") >= 1
        assert ov.metrics.val(
            "routing.device.compaction.overflow") >= 1
        # compaction folded the delta set into the snapshot
        assert ov.device_engine.stats()["delta_filters"] == 0
        _route_both(ov, oracle, topics)
        assert sorted(sinks[0].got) == sorted(sinks[1].got)

    def test_mesh_churn_keeps_sweep_and_guard(self):
        """Mesh churn path (per-shard rebuild): subscribe-after-build
        delivers via the per-shard update; the knob surfaces in stats;
        deliveries match a repeat route after the shard update."""
        MC = {"broker": {"multichip": {"enable": True, "devices": 4,
                                       "dp": 2, "max_batch": 16},
                         "device_min_batch": 1}}
        node = Node(MC)
        b = node.broker
        s = Sink()
        sid = b.register(s, "c")
        for i in range(6):
            b.subscribe(sid, f"dev/{i}/+", {"qos": 0})
        eng = node.device_engine
        assert eng.route_batch([mkmsg("dev/1/x")], wait=True) == [1]
        assert eng.stats()["delta_overlay"] == "per-shard-rebuild"
        s2 = Sink()
        sid2 = b.register(s2, "late")
        b.subscribe(sid2, "fresh/+", {"qos": 0})
        b.subscribe(sid2, "$share/g/fresh/+", {"qos": 0})
        counts = eng.route_batch([mkmsg("fresh/1"), mkmsg("dev/2/x")],
                                 wait=True)
        assert counts == [2, 1]
        assert ("fresh/+", "fresh/1") in s2.got


class TestJournalReplay:
    """Mutations racing a background capture must converge to the live
    state at swap — including subscribe+unsubscribe of the SAME filter
    (and shared-group member join/leave) landing mid-capture."""

    def _engine(self):
        node = Node()
        b = node.broker
        s = Sink()
        sid = b.register(s, "c")
        for i in range(4):
            b.subscribe(sid, f"base/{i}/+", {"qos": 0})
        node.device_engine.rebuild()
        return node, b, s, sid

    def _race(self, node, b, sid, mutate):
        """Capture → mutate (the mid-build race) → build → swap with
        journal replay, exactly the background rebuild's sequence."""
        eng = node.device_engine
        eng._building = True
        eng._journal = []
        capture = eng._capture_state_sync() \
            if not eng._can_capture_incremental() \
            else eng._capture_state_incremental()
        mutate()
        result = eng._build_from_capture(capture)
        eng._pending_swap = (result,)
        eng._try_swap()
        assert not eng._building and eng._journal is None

    def test_sub_unsub_same_filter_mid_capture(self):
        node, b, s, sid = self._engine()
        s2 = Sink()
        sid2 = b.register(s2, "r")

        def mutate():
            b.subscribe(sid2, "race/+", {"qos": 0})
            b.unsubscribe(sid2, "race/+")
            b.subscribe(sid2, "race/+", {"qos": 0})

        self._race(node, b, sid, mutate)
        # live state HAS race/+ (sub-unsub-sub): it must deliver
        assert node.device_engine.route_batch([mkmsg("race/9")]) == [1]
        assert ("race/+", "race/9") in s2.got

    def test_unsub_wins_when_final_state_absent(self):
        node, b, s, sid = self._engine()
        s2 = Sink()
        sid2 = b.register(s2, "r")
        b.subscribe(sid2, "gone/+", {"qos": 0})

        def mutate():
            b.unsubscribe(sid2, "gone/+")
            b.subscribe(sid2, "gone/+", {"qos": 0})
            b.unsubscribe(sid2, "gone/+")

        self._race(node, b, sid, mutate)
        assert node.device_engine.route_batch([mkmsg("gone/1")]) == [0]
        assert s2.got == []

    def test_shared_member_join_leave_mid_capture(self):
        node, b, s, sid = self._engine()
        m1, m2 = Sink(), Sink()
        sida = b.register(m1, "m1")
        sidb = b.register(m2, "m2")
        b.subscribe(sida, "$share/g/job/q", {"qos": 0})
        node.device_engine.rebuild()

        def mutate():
            b.subscribe(sidb, "$share/g/job/q", {"qos": 0})
            b.unsubscribe(sida, "$share/g/job/q")

        self._race(node, b, sid, mutate)
        for _ in range(4):
            assert node.device_engine.route_batch(
                [mkmsg("job/q")]) == [1]
        # only the surviving member may receive
        assert m1.got == [] and len(m2.got) == 4


class TestIncrementalCapture:
    def test_incremental_equals_full_capture(self):
        node = Node()
        b = node.broker
        s = Sink()
        sid = b.register(s, "c")
        for i in range(10):
            b.subscribe(sid, f"f/{i}/+", {"qos": 0})
        b.subscribe(sid, "$share/g/f/0/+", {"qos": 0})
        eng = node.device_engine
        eng.rebuild()
        assert eng._last_capture is not None
        # churn: touch some filters, add + delete others
        b.subscribe(sid, "f/3/+", {"qos": 1})       # opts update
        b.unsubscribe(sid, "f/7/+")
        b.subscribe(sid, "newly/+", {"qos": 0})
        inc = eng._capture_state_incremental()
        exact, wild, subs, shared = inc
        full = (list(b.router.exact), list(b.router.wildcards),
                {f: list(b.subs[f].items())
                 for f in list(b.router.exact) + list(b.router.wildcards)
                 if b.subs.get(f)}, None)
        assert sorted(wild) == sorted(full[1])
        for f, v in full[2].items():
            assert subs.get(f) == v, f
        assert "f/7/+" not in [k for k, v in subs.items() if v]
        # journal consumed: a second incremental capture re-walks ~only
        # the shared set
        assert eng.journal_depth() == 0

    def test_compaction_counts_and_uses_journal(self):
        node = Node({"broker": {"rebuild_threshold": 3}})
        b = node.broker
        s = Sink()
        sid = b.register(s, "c")
        for i in range(8):
            b.subscribe(sid, f"f/{i}/+", {"qos": 0})
        eng = node.device_engine
        assert eng.route_batch([mkmsg("f/1/x")]) == [1]
        # membership churn on BUILT filters past the threshold → the
        # "churn" compaction fires on the next route
        s2 = Sink()
        sid2 = b.register(s2, "d")
        for i in range(4):
            b.subscribe(sid2, f"f/{i}/+", {"qos": 0})
        assert eng.staleness() >= 3
        assert eng.route_batch([mkmsg("f/2/x")]) == [2]
        assert eng.staleness() == 0
        assert node.metrics.val("routing.device.compactions") >= 1
        assert node.metrics.val("routing.device.compaction.churn") >= 1


class TestTombstonePolicy:
    def test_deleted_built_filters_use_ratio_not_churn_trigger(self):
        """Rolling unsubscribe churn on built filters must not drip the
        churn staleness over the threshold (overlay on): tombstones
        deliver nothing and are governed by the delete-tombstone RATIO
        trigger instead."""
        node = Node({"broker": {"rebuild_threshold": 4}})
        b = node.broker
        s = Sink()
        sid = b.register(s, "c")
        for i in range(10):
            b.subscribe(sid, f"f/{i}/+", {"qos": 0})
        eng = node.device_engine
        assert eng.route_batch([mkmsg("f/1/x")]) == [1]
        for i in range(6):
            b.unsubscribe(sid, f"f/{i}/+")
        assert len(eng._built_deleted) == 6
        assert eng.staleness() == 0
        assert eng._compaction_reason() is None
        # deliveries stay correct: deleted filters deliver nothing
        assert eng.route_batch([mkmsg("f/1/x"), mkmsg("f/8/x")]) \
            == [0, 1]
        # overlay OFF keeps the pre-ISSUE-4 accounting
        node2 = Node({"broker": {"rebuild_threshold": 4,
                                 "delta_overlay": False}})
        b2 = node2.broker
        sid2 = b2.register(Sink(), "c")
        for i in range(10):
            b2.subscribe(sid2, f"f/{i}/+", {"qos": 0})
        node2.device_engine.route_batch([mkmsg("f/1/x")])
        for i in range(6):
            b2.unsubscribe(sid2, f"f/{i}/+")
        assert node2.device_engine.staleness() == 6


class TestUncoveredDeltaFilters:
    def test_too_deep_filter_counts_toward_rebuild_and_heals(self):
        """A post-snapshot filter deeper than max_levels cannot ride
        the overlay: it serves host-side AND must keep counting toward
        the rebuild trigger (like the overlay-off path) so the
        degradation heals at the threshold instead of persisting
        forever."""
        node = Node({"broker": {"rebuild_threshold": 2}})
        b = node.broker
        s = Sink()
        sid = b.register(s, "c")
        for i in range(4):
            b.subscribe(sid, f"d/{i}/+", {"qos": 0})
        eng = node.device_engine
        assert eng.route_batch([mkmsg("d/1/x")]) == [1]
        deep = "/".join(["lvl"] * 17) + "/#"        # > max_levels=16
        b.subscribe(sid, deep, {"qos": 0})
        eng._overlay_sync()
        assert eng._overlay_uncovered == 1
        assert eng.staleness() == 1
        assert eng.rebuild_state()["overlay_uncovered"] == 1
        # a second uncovered filter crosses the threshold: the next
        # route compacts and the deep filters fold into the snapshot
        b.subscribe(sid, "/".join(["deep"] * 18), {"qos": 0})
        eng._overlay_sync()
        assert eng.staleness() >= 2
        assert eng.route_batch([mkmsg("d/2/x")]) == [1]
        assert eng.staleness() == 0 and eng._overlay_uncovered == 0
        assert node.metrics.val("routing.device.compactions") >= 1
        # fast consume is provable-clean again (no pending delta)
        assert not eng._delta_pending(None) or eng._delta_filter


class TestDeltaAwareCacheInvalidation:
    def test_drop_where_stack_memoized_across_changes(self):
        """Consecutive overlay changes without cache content changes
        reuse one columnar stack (the churn regime runs several route
        changes per batch window)."""
        node = Node()
        b = node.broker
        s = Sink()
        sid = b.register(s, "c")
        for i in range(8):
            b.subscribe(sid, f"dev/{i}/+", {"qos": 0})
        eng = node.device_engine
        assert eng.route_batch(
            [mkmsg("dev/1/t")] * 40 + [mkmsg("a/x")] * 30) is not None
        cache = eng._match_cache
        b.subscribe(sid, "zz/1/+", {"qos": 0})     # no cached topic hit
        st1 = cache._stack
        assert st1 is not None
        b.subscribe(sid, "zz/2/+", {"qos": 0})     # still no drops
        assert cache._stack is st1                  # reused
        b.subscribe(sid, "dev/1/#", {"qos": 0})    # drops dev/1/t
        assert cache._stack is None                 # content changed
    def test_new_filter_drops_only_matching_topics(self):
        node = Node()
        b = node.broker
        s = Sink()
        sid = b.register(s, "c")
        for i in range(8):
            b.subscribe(sid, f"dev/{i}/+", {"qos": 0})
        eng = node.device_engine
        topics = ["dev/1/t"] * 40 + ["dev/2/t"] * 30 + ["other/x"] * 20
        assert eng.route_batch([mkmsg(t) for t in topics]) is not None
        cache = eng._match_cache
        assert len(cache) >= 3
        before = len(cache)
        inv0 = cache.delta_invalidated
        # new filter matching ONLY dev/1/t
        b.subscribe(sid, "dev/1/#", {"qos": 0})
        assert cache.delta_invalidated == inv0 + 1  # just that topic
        assert len(cache) == before - 1
        # and the fresh filter delivers on the formerly-cached topic
        assert eng.route_batch([mkmsg("dev/1/t")]) == [2]

    def test_delete_drops_matching_topics(self):
        node = Node()
        b = node.broker
        s = Sink()
        sid = b.register(s, "c")
        for i in range(8):
            b.subscribe(sid, f"dev/{i}/+", {"qos": 0})
        eng = node.device_engine
        assert eng.route_batch([mkmsg("dev/1/t")] * 40
                               + [mkmsg("other/x")] * 30) is not None
        b.subscribe(sid, "dev/1/#", {"qos": 0})     # delta insert
        assert eng.route_batch([mkmsg("dev/1/t")] * 40
                               + [mkmsg("other/x")] * 30) is not None
        cache = eng._match_cache
        n0 = len(cache)
        b.unsubscribe(sid, "dev/1/#")               # delta delete
        assert len(cache) < n0      # dev/1/t rows dropped again
        assert eng.route_batch([mkmsg("dev/1/t")]) == [1]


class TestKnobs:
    def test_overlay_off_restores_host_fallback(self):
        node = Node({"broker": {"delta_overlay": False}})
        b = node.broker
        s = Sink()
        sid = b.register(s, "c")
        for i in range(6):
            b.subscribe(sid, f"dev/{i}/+", {"qos": 0})
        eng = node.device_engine
        assert not eng.delta_overlay
        assert eng.route_batch([mkmsg("dev/1/x")]) == [1]
        b.subscribe(sid, "late/+", {"qos": 0})
        # pre-overlay contract: delta filters count toward staleness,
        # deliveries come from the host trie, host_delta counts them,
        # cache rows stay 3-tuples
        assert eng.staleness() == 1
        h = eng.prepare([mkmsg("late/1")] * 4, gate_cold=False)
        assert h.delta is None
        eng.dispatch(h)
        eng.materialize(h)
        assert eng.finish(h) == [1] * 4
        assert node.metrics.val("routing.device.host_delta") > 0
        assert eng.stats()["overlay"] is None
        cache = eng._match_cache
        with cache._lock:
            rows = list(cache._rows.values())
        assert all(len(r) == 3 for r in rows)

    def test_env_delta_knob_wiring(self, monkeypatch):
        monkeypatch.setattr(DE, "_ENV_DELTA", False)
        node = Node()
        assert not node.device_engine.delta_overlay
        monkeypatch.setattr(DE, "_ENV_DELTA", True)
        node2 = Node()
        assert node2.device_engine.delta_overlay
        # config beats env
        node3 = Node({"broker": {"delta_overlay": False}})
        assert not node3.device_engine.delta_overlay

    def test_rebuild_threshold_env(self, monkeypatch):
        monkeypatch.delenv("EMQX_TPU_REBUILD_THRESHOLD", raising=False)
        assert DE.resolve_rebuild_threshold() == 256
        assert DE.resolve_rebuild_threshold(64) == 64
        monkeypatch.setenv("EMQX_TPU_REBUILD_THRESHOLD", "512")
        assert DE.resolve_rebuild_threshold() == 512
        assert DE.resolve_rebuild_threshold(64) == 64   # config wins
        monkeypatch.setenv("EMQX_TPU_REBUILD_THRESHOLD", "0")
        with pytest.raises(ValueError):
            DE.resolve_rebuild_threshold()
        monkeypatch.setenv("EMQX_TPU_REBUILD_THRESHOLD", "lots")
        with pytest.raises(ValueError):
            DE.resolve_rebuild_threshold()
        monkeypatch.setenv("EMQX_TPU_REBUILD_THRESHOLD", "128")
        node = Node()
        assert node.device_engine.rebuild_threshold == 128
        assert node.router.rebuild_threshold == 128


class TestRebuildTelemetry:
    def test_snapshot_rebuild_section_and_exporters(self):
        node = Node()
        b = node.broker
        s = Sink()
        sid = b.register(s, "c")
        for i in range(4):
            b.subscribe(sid, f"d/{i}/+", {"qos": 0})
        eng = node.device_engine
        assert eng.route_batch([mkmsg("d/1/x")]) == [1]
        b.subscribe(sid, "late/+", {"qos": 0})
        assert eng.route_batch([mkmsg("late/1")]) == [1]
        snap = node.pipeline_telemetry.snapshot()
        rb = snap["rebuild"]
        assert rb["rebuilds"] >= 1
        assert rb["delta_applies"] >= 1
        assert {"capture", "build", "swap", "delta_apply"} \
            <= set(rb["stages"])
        assert rb["state"]["delta_overlay"] is True
        assert rb["state"]["overlay_rows"] == 1
        assert "journal_depth" in rb["state"]
        # prometheus carries the rebuild-stage histograms via the
        # shared registry
        from emqx_tpu.apps.prometheus import collect
        text = collect(node)
        assert "pipeline_rebuild_capture_seconds" in text
        assert "routing_device_delta_applies" in text

    def test_host_delta_counter_closes(self):
        """The before/after counter of the hole ISSUE 4 closes: overlay
        off routes delta filters host-side (counter grows); overlay on
        keeps it at zero for the same traffic."""
        for overlay, expect_zero in ((False, False), (True, True)):
            node = Node({"broker": {"delta_overlay": overlay}})
            b = node.broker
            s = Sink()
            sid = b.register(s, "c")
            for i in range(4):
                b.subscribe(sid, f"d/{i}/+", {"qos": 0})
            eng = node.device_engine
            assert eng.route_batch([mkmsg("d/1/x")]) == [1]
            b.subscribe(sid, "late/+", {"qos": 0})
            assert eng.route_batch([mkmsg("late/1")] * 3) == [1] * 3
            v = node.metrics.val("routing.device.host_delta")
            assert (v == 0) if expect_zero else (v > 0), (overlay, v)


class TestDeltaOpOracle:
    def test_np_filter_match_equals_host_trie(self):
        from emqx_tpu.ops import intern as I
        from emqx_tpu.ops.delta import np_filter_match
        from emqx_tpu.ops.trie import HostTrie
        from emqx_tpu.utils import topic as T
        t = I.InternTable()
        filters = ["a/b", "a/+", "a/#", "#", "+/b", "$sys/+", "a/b/c"]
        host = HostTrie()
        for fid, f in enumerate(filters):
            host.insert(t.encode_filter(T.tokens(f)), fid)
        topics = ["a/b", "a/x", "a", "b", "$sys/n", "a/b/c", "q"]
        L = 4
        for topic in topics:
            ws = T.tokens(topic)
            ids = t.encode_topic(ws)
            enc = np.zeros((1, L), np.int32)
            enc[0, :len(ids)] = ids
            lens = np.asarray([len(ids)])
            dol = np.asarray([topic.startswith("$")])
            want = set(host.match(ids, bool(dol[0])))
            for fid, f in enumerate(filters):
                got = bool(np_filter_match(
                    t.encode_filter(T.tokens(f)), enc, lens, dol)[0])
                assert got == (fid in want), (topic, f)
