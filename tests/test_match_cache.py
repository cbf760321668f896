"""Snapshot-keyed match cache + in-window topic dedup (ISSUE 2).

The device route path's reuse layers must be INVISIBLE except for speed:
a deduplicated (and cache-backed) dispatch returns the same RouteResult,
bit for bit, as the un-deduplicated step on the same batch — including
overflow lanes, padding lanes and shared-subscription cursor threading —
and the cache must die wholesale with its snapshot. These tests pin that
equivalence with a twin-engine oracle (one node with the layers on, one
with them off, identical subscription state), plus the cache lifecycle
and the telemetry counters the exporters carry.
"""

import numpy as np
import pytest

from emqx_tpu.broker.match_cache import MatchCache
from emqx_tpu.broker.message import make
from emqx_tpu.broker.node import Node

PLAIN_CONF = {"broker": {"topic_dedup": False}}


class Sink:
    def __init__(self):
        self.got = []

    def deliver(self, topic_filter, msg):
        self.got.append((topic_filter, msg.topic))
        return True


def mkmsg(topic, payload=b"x"):
    return make("pub", 0, topic, payload)


def _twin_nodes(setup, **engine_over):
    """Two nodes with identical subscription state: `fast` has dedup +
    cache on (default), `plain` has both layers off — the bit-for-bit
    oracle. `setup(broker) -> sinks` runs against each. Both twins pin
    the DENSE readback: this oracle compares raw np_res planes, which
    the CSR readback replaces wholesale; the compact-vs-dense oracle
    (incl. the dedup/cache interplay) lives in
    tests/test_compact_readback.py."""
    fast = Node({"broker": {"compact_readback": False}})
    plain = Node({"broker": {**PLAIN_CONF["broker"],
                             "compact_readback": False}})
    assert fast.device_engine.dedup
    assert fast.device_engine._match_cache is not None
    assert not plain.device_engine.dedup
    assert plain.device_engine._match_cache is None
    for k, v in engine_over.items():
        setattr(fast.device_engine, k, v)
        setattr(plain.device_engine, k, v)
    return fast, setup(fast.broker), plain, setup(plain.broker)


def _np_res(node, msgs, *, window=None):
    """prepare/dispatch/materialize one batch (or window) and return the
    raw host-side RouteResult planes + the handle."""
    eng = node.device_engine
    if window is None:
        h = eng.prepare(msgs, gate_cold=False)
    else:
        h = eng.prepare_window(window, gate_cold=False)
    assert h is not None
    eng.dispatch(h)
    eng.materialize(h)
    return h


def _assert_bit_identical(hf, hp):
    for i, (a, b) in enumerate(zip(hf.np_res, hp.np_res)):
        np.testing.assert_array_equal(a, b, err_msg=f"np_res plane {i}")
    # match_counts is only materialized for cache population; compare
    # the device plane directly so the oracle still covers it
    np.testing.assert_array_equal(np.asarray(hf.res.match_counts),
                                  np.asarray(hp.res.match_counts),
                                  err_msg="match_counts")


def _finish_all(node, h):
    """Consume every sub-batch (releases the handle); concatenated
    per-message delivery counts."""
    out = []
    for k in range(len(h.subs)):
        out.extend(node.device_engine.finish_sub(h, k))
    return out


class TestDedupOracle:
    def _setup(self, broker):
        sinks = [Sink() for _ in range(3)]
        sids = [broker.register(s, f"c{i}") for i, s in enumerate(sinks)]
        broker.subscribe(sids[0], "dev/+/temp", {"qos": 1})
        broker.subscribe(sids[1], "dev/7/temp", {"qos": 0})
        broker.subscribe(sids[2], "exact/topic", {"qos": 2})
        broker.subscribe(sids[0], "$share/g/job/q", {"qos": 0})
        broker.subscribe(sids[1], "$share/g/job/q", {"qos": 0})
        return sinks

    def test_dedup_scatter_bit_identical(self):
        """Duplicate-heavy batch: the deduplicated dispatch's RouteResult
        equals the plain route step's bit for bit."""
        fast, fs, plain, ps = _twin_nodes(self._setup)
        # >64 lanes of 4 unique topics: the miss class (64) quantizes
        # BELOW the batch class (256), so the plan engages on first touch
        msgs = ([mkmsg("dev/7/temp")] * 30 + [mkmsg("job/q")] * 25
                + [mkmsg("exact/topic")] * 10 + [mkmsg("no/match")] * 5)
        hf = _np_res(fast, msgs)
        hp = _np_res(plain, msgs)
        assert hf.plan is not None, "dedup plan did not engage"
        assert hp.plan is None
        _assert_bit_identical(hf, hp)
        _finish_all(fast, hf)
        _finish_all(plain, hp)
        assert sorted(len(s.got) for s in fs) == \
            sorted(len(s.got) for s in ps)

    def test_cache_hit_bit_identical_to_cold_match(self):
        """A fully-cached repeat batch returns the identical RouteResult
        a cold match produces (and the same planes as the layer-off
        engine routing the same traffic history)."""
        fast, _fs, plain, _ps = _twin_nodes(self._setup)
        msgs = [mkmsg("dev/7/temp")] * 40 + [mkmsg("job/q")] * 30
        h1 = _np_res(fast, msgs)
        cold = tuple(np.array(p) for p in h1.np_res)
        _finish_all(fast, h1)
        _finish_all(plain, _np_res(plain, msgs))
        h2 = _np_res(fast, msgs)        # all unique topics now cached
        hp = _np_res(plain, msgs)
        assert h2.plan is not None and h2.plan.n_hit > 0
        _assert_bit_identical(h2, hp)
        for i, p in enumerate(cold):
            # matches/rows/opts/shared planes equal; occur/cursor planes
            # advance with the round-robin state, so compare the pure
            # match planes only against the cold run
            if i in (0, 1, 2, 6):      # matches, rows, opts, overflow
                np.testing.assert_array_equal(np.array(h2.np_res[i]), p)
        _finish_all(fast, h2)
        _finish_all(plain, hp)

    def test_overflow_lanes_bit_identical(self):
        """Capacity overflow (host-fallback lanes) survives the dedup
        scatter and the cache round trip unchanged."""
        def setup(broker):
            sinks = [Sink() for _ in range(8)]
            # two filters of four: narrow rows alone pass the cap of 4
            for i, s in enumerate(sinks):
                broker.subscribe(broker.register(s, f"o{i}"),
                                 "big/+" if i < 4 else "big/#", {"qos": 0})
            return sinks

        fast, _, plain, _ = _twin_nodes(setup, fanout_cap=4)
        msgs = [mkmsg("big/t")] * 40 + [mkmsg("big/u")] * 30
        hf, hp = _np_res(fast, msgs), _np_res(plain, msgs)
        assert hf.plan is not None
        assert hf.np_res[6].any(), "expected overflow lanes"
        _assert_bit_identical(hf, hp)
        cf = _finish_all(fast, hf)
        cp = _finish_all(plain, hp)
        assert cf == cp
        # repeat: overflow rides the cache now
        hf2, hp2 = _np_res(fast, msgs), _np_res(plain, msgs)
        assert hf2.plan is not None and hf2.plan.n_hit > 0
        _assert_bit_identical(hf2, hp2)
        _finish_all(fast, hf2)
        _finish_all(plain, hp2)

    def test_full_unique_array_bit_identical(self):
        """Bu == Bp edge: every base-array row is live, so a wrapping
        pad scatter index would clobber unique row Bp-1 (jax wraps
        negative dynamic indices — the pad must be an out-of-range
        POSITIVE index). Seed the cache, then route a batch whose
        unique count fills the entire Bp-wide unique array."""
        def setup(broker):
            s = Sink()
            sid = broker.register(s, "c")
            for i in range(300):
                broker.subscribe(sid, f"full/{i}", {"qos": 0})
            return [s]

        fast, fs, plain, ps = _twin_nodes(setup)
        seed = [mkmsg(f"full/{i}") for i in range(226)]
        _finish_all(fast, _np_res(fast, seed))
        _finish_all(plain, _np_res(plain, seed))
        # 255 unique topics + the pad sentinel = 256 = Bp: all-unique
        # batch, mostly cache-hit, miss class 64 < 256 -> engages
        msgs = [mkmsg(f"full/{i}") for i in range(255)]
        hf, hp = _np_res(fast, msgs), _np_res(plain, msgs)
        assert hf.plan is not None and hf.plan.n_hit > 0
        _assert_bit_identical(hf, hp)
        cf = _finish_all(fast, hf)
        cp = _finish_all(plain, hp)
        assert cf == cp == [1] * 255

    def test_a_plan_engages_only_where_it_takes_two_fifths_off(self):
        """100 real lanes of a 256 class whose misses fit the 64 class:
        with 62 distinct new topics (38 % repeats) the window dispatches
        plain and still seeds the cache; with 58 (42 %) it engages; and
        the first topics, now hits, engage whatever their repeats."""
        def setup(broker):
            # every device's name a word some filter holds: a word no
            # filter has encodes as one "unknown", and topics that
            # differ only there are one topic to the match
            sink = Sink()
            sid = broker.register(sink, "c0")
            for i in range(160):
                broker.subscribe(sid, f"dev/{i}/temp", {"qos": 0})
            return [sink]

        fast, _fs, plain, _ps = _twin_nodes(setup)

        def msgs(base, uniq):
            return [mkmsg(f"dev/{base + i % uniq}/temp")
                    for i in range(100)]

        for base, uniq, engaged in ((0, 62, False), (100, 58, True),
                                    (0, 62, True)):
            hf, hp = _np_res(fast, msgs(base, uniq)), \
                _np_res(plain, msgs(base, uniq))
            assert (hf.plan is not None) == engaged, (base, uniq)
            if base == 0 and not engaged:
                assert hf.cache_info is not None    # misses still seed
            _assert_bit_identical(hf, hp)
            _finish_all(fast, hf)
            _finish_all(plain, hp)

    def test_underfilled_window_pads_collapse(self):
        """Fused window with an under-filled sub-batch: every padding
        lane collapses onto one sentinel entry and the stacked
        RouteResult still equals the plain window program's."""
        fast, fs, plain, ps = _twin_nodes(self._setup)
        # half of the real lanes repeat: a plan engages only where it
        # takes two fifths of them off the match
        win = [[mkmsg("dev/7/temp"), mkmsg("dev/9/temp"),
                mkmsg("dev/7/temp")], [mkmsg("dev/7/temp")]]
        hf = _np_res(fast, [m for w in win for m in w], window=win)
        hp = _np_res(plain, None, window=win)
        assert hf.plan is not None
        # 4 real lanes on 2 topics + the pad sentinel
        assert hf.plan.n_miss + hf.plan.n_hit == 2
        _assert_bit_identical(hf, hp)
        _finish_all(fast, hf)
        _finish_all(plain, hp)

    def test_shared_cursors_advance_identically(self):
        """Round-robin cursors thread through cached matches exactly as
        through cold ones: distribution and occur planes match the
        layer-off engine batch for batch."""
        def setup(broker):
            sinks = [Sink() for _ in range(3)]
            for i, s in enumerate(sinks):
                broker.subscribe(broker.register(s, f"m{i}"),
                                 "$share/rr/work/q", {"qos": 0})
            return sinks

        fast, fs, plain, ps = _twin_nodes(setup)
        for rounds in range(3):          # round 2+ is fully cached
            msgs = [mkmsg("work/q", str(i).encode()) for i in range(72)]
            hf, hp = _np_res(fast, msgs), _np_res(plain, msgs)
            _assert_bit_identical(hf, hp)
            assert _finish_all(fast, hf) == _finish_all(plain, hp)
        assert [len(s.got) for s in fs] == [len(s.got) for s in ps]
        assert sorted(len(s.got) for s in fs) == [72, 72, 72]
        assert fast.device_engine.stats()["match_cache"]["hits"] > 0

    def test_trie_backend_dedup_and_cache(self):
        """The trie-NFA fallback backend gets the same reuse layers
        (route_window_cached, W = 1), bit-identical to the plain trie
        step."""
        def setup(broker):
            s = Sink()
            sid = broker.register(s, "c")
            for f in ["a", "a/b", "a/+/c", "+/b/#", "x/y/z/w"]:
                broker.subscribe(sid, f, {"qos": 0})
            return [s]

        fast, _, plain, _ = _twin_nodes(setup, shape_cap=2)
        assert fast.device_engine is not None
        msgs = [mkmsg("a/b")] * 50 + [mkmsg("x/y/z/w")] * 20
        hf, hp = _np_res(fast, msgs), _np_res(plain, msgs)
        assert fast.device_engine.stats()["backend"] == "trie"
        assert hf.plan is not None
        _assert_bit_identical(hf, hp)
        _finish_all(fast, hf)
        _finish_all(plain, hp)
        hf2, hp2 = _np_res(fast, msgs), _np_res(plain, msgs)
        assert hf2.plan is not None and hf2.plan.n_hit > 0
        _assert_bit_identical(hf2, hp2)
        _finish_all(fast, hf2)
        _finish_all(plain, hp2)


class TestSnapshotLifecycle:
    def test_swap_invalidates_wholesale(self):
        node = Node()
        b = node.broker
        s = Sink()
        sid = b.register(s, "c")
        b.subscribe(sid, "a/+", {"qos": 0})
        eng = node.device_engine
        msgs = [mkmsg("a/1")] * 70    # > smallest class: analysis runs
        eng.route_batch(msgs)
        eng.route_batch(msgs)
        st = eng.stats()["match_cache"]
        assert st["hits"] > 0 and st["size"] > 0
        sid_before = st["snapshot_id"]
        b.subscribe(sid, "b/+", {"qos": 0})
        eng.rebuild()                      # snapshot swap
        st = eng.stats()["match_cache"]
        assert st["size"] == 0, "swap must invalidate wholesale"
        assert st["invalidations"] == 1
        assert st["snapshot_id"] != sid_before
        # nothing stale served: fresh rows under the NEW snapshot route
        # the new filter correctly
        assert eng.route_batch([mkmsg("a/1")] * 3 + [mkmsg("b/2")] * 3) \
            == [1] * 6
        assert len([1 for _f, t in s.got if t == "b/2"]) == 3

    def test_cache_never_crosses_snapshot_ids(self):
        """Unit-level: get/put against a stale snapshot id are inert."""
        mc = MatchCache(capacity=4)
        mc.attach(1)
        row = (np.array([3, -1], np.int32), 1, False)
        mc.put_many(1, [(b"k1", row)])
        assert mc.get_many(1, [b"k1"])[0] is not None
        # reader pinned to snapshot 1 while the cache moved to 2
        mc.attach(2)
        assert mc.get_many(1, [b"k1"]) == [None]
        mc.put_many(1, [(b"k1", row)])     # in-flight insert: dropped
        assert len(mc) == 0
        assert mc.get_many(2, [b"k1"]) == [None]

    def test_lru_eviction(self):
        mc = MatchCache(capacity=2)
        mc.attach(7)
        row = (np.zeros(2, np.int32), 0, False)
        mc.put_many(7, [(b"a", row), (b"b", row)])
        mc.get_many(7, [b"a"])             # touch a -> b is LRU
        mc.put_many(7, [(b"c", row)])
        assert mc.evictions == 1
        hits = [r is not None for r in mc.get_many(7, [b"a", b"b", b"c"])]
        assert hits == [True, False, True]

    def test_disabled_layers(self):
        node = Node({"broker": {"topic_dedup": False}})
        eng = node.device_engine
        b = node.broker
        b.subscribe(b.register(Sink(), "c"), "t/+", {"qos": 0})
        assert eng.route_batch([mkmsg("t/1")] * 4) == [1] * 4
        h = eng.prepare([mkmsg("t/1")] * 4, gate_cold=False)
        assert h.plan is None and h.cache_info is None
        eng.abandon(h)
        assert eng.stats()["match_cache"] is None
        # cache off, dedup on: in-window dedup still engages
        node2 = Node({"broker": {"match_cache_size": 0}})
        eng2 = node2.device_engine
        b2 = node2.broker
        b2.subscribe(b2.register(Sink(), "c"), "t/+", {"qos": 0})
        assert eng2._match_cache is None and eng2.dedup
        assert eng2.route_batch([mkmsg("t/1")] * 80) == [1] * 80
        h2 = eng2.prepare([mkmsg("t/1")] * 80, gate_cold=False)
        assert h2.plan is not None and h2.plan.n_hit == 0
        eng2.abandon(h2)


class TestTelemetry:
    def test_warm_route_exposes_match_cache_counters(self):
        """Tier-1 exporter guard (ISSUE 2 satellite): after a warm route
        the telemetry snapshot must expose nonzero match_cache.* and
        dedup counters — the same snapshot all four exporters and
        bench.py embed, so a regression here fails fast."""
        node = Node()
        b = node.broker
        b.subscribe(b.register(Sink(), "c"), "hot/+", {"qos": 0})
        msgs = [mkmsg("hot/1")] * 50 + [mkmsg("hot/2")] * 20
        node.device_engine.route_batch(msgs)
        node.device_engine.route_batch(msgs)    # warm: cache hits
        snap = node.pipeline_telemetry.snapshot()
        assert snap["match_cache"]["hits"] > 0
        assert snap["match_cache"]["inserts"] > 0
        assert 0 < snap["match_cache"]["hit_rate"] <= 1
        assert snap["dedup"]["lanes"] > snap["dedup"]["unique"] > 0
        assert 0 < snap["dedup"]["ratio"] < 1
        assert snap["decisions"]["routing.device.cached_windows"] >= 1
        # the raw counters ride the shared Metrics registry, which is
        # what Prometheus/StatsD/$SYS export — assert they are there too
        assert node.metrics.val("match_cache.hits") > 0
        assert node.metrics.val("routing.dedup.lanes") > 0
        # cached dispatches land in their own stage histogram
        assert snap["stages"].get("dispatch_cached", {}).get("count", 0) \
            >= 1

    def test_fold_backend_effective_flag(self):
        from emqx_tpu.ops import shapes as SHP
        assert SHP.fold_backend_effective() is True
