"""Compacted device→host readback (ISSUE 3): CSR payload classes.

The CSR readback must be INVISIBLE except for bytes: a compacted window
produces the identical deliveries and per-message counts as the dense
readback of the same traffic — including overflow/host-fallback lanes,
the payload-class overflow fallback, under-filled fused windows, shared
slots, and the match cache populated from CSR views — and the byte
accounting the exporters carry must reflect the actual transfer.
"""

import numpy as np
import pytest

from emqx_tpu.broker.device_engine import _CsrRes
from emqx_tpu.broker.message import make
from emqx_tpu.broker.node import Node

DENSE_CONF = {"broker": {"compact_readback": False}}


class Sink:
    def __init__(self):
        self.got = []

    def deliver(self, topic_filter, msg):
        self.got.append((topic_filter, msg.topic))
        return True


def mkmsg(topic, payload=b"x"):
    return make("pub", 0, topic, payload)


def _twin_nodes(setup, **engine_over):
    """Two nodes with identical subscription state: `comp` reads back
    CSR (default), `dense` the padded planes — the delivery oracle.
    (Raw-plane comparison is meaningless across the two paths — the CSR
    readback replaces the planes — so the oracle is deliveries+counts,
    with the per-plane CSR decode pinned in TestCsrDecode.)"""
    comp = Node()
    dense = Node(DENSE_CONF)
    assert comp.device_engine.compact_readback
    assert not dense.device_engine.compact_readback
    for k, v in engine_over.items():
        setattr(comp.device_engine, k, v)
        setattr(dense.device_engine, k, v)
    return comp, setup(comp.broker), dense, setup(dense.broker)


def _setup_mixed(broker):
    sinks = [Sink() for _ in range(3)]
    sids = [broker.register(s, f"c{i}") for i, s in enumerate(sinks)]
    broker.subscribe(sids[0], "dev/+/temp", {"qos": 1})
    broker.subscribe(sids[1], "dev/7/temp", {"qos": 0})
    broker.subscribe(sids[2], "exact/topic", {"qos": 2})
    broker.subscribe(sids[0], "$share/g/job/q", {"qos": 0})
    broker.subscribe(sids[1], "$share/g/job/q", {"qos": 0})
    return sinks


def _mixed_msgs():
    return ([mkmsg("dev/7/temp")] * 30 + [mkmsg("job/q")] * 25
            + [mkmsg("exact/topic")] * 10 + [mkmsg("no/match")] * 5)


def _route_csr(node, msgs, *, window=None):
    """prepare/dispatch/materialize and return the handle (caller
    finishes); asserts the COMPACT path actually engaged."""
    eng = node.device_engine
    h = eng.prepare(msgs, gate_cold=False) if window is None \
        else eng.prepare_window(window, gate_cold=False)
    assert h is not None
    eng.dispatch(h)
    eng.materialize(h)
    return h


def _finish_all(node, h):
    out = []
    for k in range(len(h.subs)):
        out.extend(node.device_engine.finish_sub(h, k))
    return out


class TestCompactOracle:
    def test_mixed_batch_identical_three_rounds(self):
        """Shared slots + wildcard + exact + no-match traffic, repeated
        so round 2+ serves from the CSR-populated match cache: counts
        and deliveries equal the dense engine's every round, and the
        round-robin shared distribution threads identically."""
        comp, cs, dense, ds = _twin_nodes(_setup_mixed)
        for rnd in range(3):
            hc = _route_csr(comp, _mixed_msgs())
            hd = _route_csr(dense, _mixed_msgs())
            assert isinstance(hc.np_res, _CsrRes), "compact did not engage"
            assert not isinstance(hd.np_res, _CsrRes)
            assert _finish_all(comp, hc) == _finish_all(dense, hd), rnd
        assert [s.got for s in cs] == [s.got for s in ds]
        assert comp.metrics.val("pipeline.readback.windows.compact") == 3
        assert comp.device_engine.stats()["match_cache"]["hits"] > 0

    def test_trie_backend(self):
        """The trie-NFA fallback backend compacts through
        route_window_full_compact / route_window_cached_compact at W = 1,
        bit-identically."""
        def setup(broker):
            s = Sink()
            sid = broker.register(s, "c")
            for f in ["a", "a/b", "a/+/c", "+/b/#", "x/y/z/w"]:
                broker.subscribe(sid, f, {"qos": 0})
            return [s]

        comp, cs, dense, ds = _twin_nodes(setup, shape_cap=2)
        msgs = [mkmsg("a/b")] * 50 + [mkmsg("x/y/z/w")] * 20
        for rnd in range(2):       # round 2: cached trie plan + compact
            hc = _route_csr(comp, [mkmsg(m.topic) for m in msgs])
            hd = _route_csr(dense, [mkmsg(m.topic) for m in msgs])
            assert comp.device_engine.stats()["backend"] == "trie"
            assert isinstance(hc.np_res, _CsrRes)
            assert _finish_all(comp, hc) == _finish_all(dense, hd), rnd
        assert [s.got for s in cs] == [s.got for s in ds]

    def test_underfilled_window(self):
        """Fused window with an under-filled sub-batch: padding lanes
        contribute zero payload entries and deliveries match."""
        comp, cs, dense, ds = _twin_nodes(_setup_mixed)
        win = [[mkmsg("dev/7/temp"), mkmsg("dev/9/temp")],
               [mkmsg("dev/7/temp")]]
        hc = _route_csr(comp, None, window=[[mkmsg(m.topic) for m in w]
                                            for w in win])
        hd = _route_csr(dense, None, window=[[mkmsg(m.topic) for m in w]
                                             for w in win])
        assert isinstance(hc.np_res, _CsrRes)
        assert _finish_all(comp, hc) == _finish_all(dense, hd)
        assert [s.got for s in cs] == [s.got for s in ds]

    def test_payload_overflow_falls_back_dense(self):
        """A window outgrowing its payload class reads the dense planes
        of the SAME dispatch: deliveries identical, counter fires, and
        the EWMA resizes the next window's class up."""
        comp, cs, dense, ds = _twin_nodes(_setup_mixed)
        eng = comp.device_engine
        real = eng._choose_payload_cap
        eng._choose_payload_cap = lambda Bp: 8   # absurdly small class
        hc = _route_csr(comp, _mixed_msgs())
        assert not isinstance(hc.np_res, _CsrRes), \
            "overflow must fall back to the dense readback"
        assert comp.metrics.val("routing.device.compact_overflow") == 1
        hd = _route_csr(dense, _mixed_msgs())
        assert _finish_all(comp, hc) == _finish_all(dense, hd)
        assert [s.got for s in cs] == [s.got for s in ds]
        # the overflow window's offsets seeded the EWMA: the un-mocked
        # chooser now picks a class that fits
        eng._choose_payload_cap = real
        assert eng._pay_ewma, "overflow fallback must still feed the EWMA"
        hc2 = _route_csr(comp, _mixed_msgs())
        assert isinstance(hc2.np_res, _CsrRes)
        hd2 = _route_csr(dense, _mixed_msgs())
        assert _finish_all(comp, hc2) == _finish_all(dense, hd2)

    def test_fanout_overflow_lanes_host_fallback(self):
        """Per-message capacity overflow (fan-out cap) survives
        compaction: the lane is flagged, host-fallback routes it, and
        counts match the dense engine."""
        def setup(broker):
            sinks = [Sink() for _ in range(8)]
            # two filters of four: narrow rows alone pass the cap of 4
            for i, s in enumerate(sinks):
                broker.subscribe(broker.register(s, f"o{i}"),
                                 "big/+" if i < 4 else "big/#", {"qos": 0})
            return sinks

        comp, cs, dense, ds = _twin_nodes(setup, fanout_cap=4)
        msgs = [mkmsg("big/t")] * 40 + [mkmsg("big/u")] * 30
        hc = _route_csr(comp, [mkmsg(m.topic) for m in msgs])
        hd = _route_csr(dense, [mkmsg(m.topic) for m in msgs])
        assert isinstance(hc.np_res, _CsrRes)
        assert hc.np_res.overflow.any(), "expected overflow lanes"
        assert _finish_all(comp, hc) == _finish_all(dense, hd)
        assert sorted(len(s.got) for s in cs) == \
            sorted(len(s.got) for s in ds)


class TestCsrDecode:
    def test_csr_slices_equal_dense_planes(self):
        """Per-plane decode oracle: every message's CSR slices carry
        exactly the dense planes' valid entries, in order (matches may
        drop interior holes — the shapes backend's slot layout — which
        is the documented hole-insensitivity contract)."""
        from emqx_tpu.ops.compact import csr_slices
        comp, _cs, dense, _ds = _twin_nodes(_setup_mixed)
        hc = _route_csr(comp, _mixed_msgs())
        hd = _route_csr(dense, _mixed_msgs())
        nr = hc.np_res
        assert isinstance(nr, _CsrRes)
        (m_d, r_d, o_d, ss_d, sr_d, so_d, ovf_d, occ_d) = hd.np_res
        np.testing.assert_array_equal(nr.overflow, ovf_d)
        np.testing.assert_array_equal(nr.occur, occ_d)
        W, B = ovf_d.shape
        for w in range(W):
            for i in range(B):
                m, r, o, ss, sr, so = csr_slices(nr.off[w], nr.c3[w],
                                                 nr.pay[w], i)
                md = m_d[w, i]
                np.testing.assert_array_equal(m, md[md >= 0])
                cf = len(r)
                np.testing.assert_array_equal(r, r_d[w, i][:cf])
                np.testing.assert_array_equal(o, o_d[w, i][:cf])
                sd = ss_d[w, i]
                cs_n = int((sd >= 0).sum())
                np.testing.assert_array_equal(ss, sd[sd >= 0])
                np.testing.assert_array_equal(sr, sr_d[w, i][:cs_n])
                np.testing.assert_array_equal(so, so_d[w, i][:cs_n])
        _finish_all(comp, hc)
        _finish_all(dense, hd)


class TestHoleClosingOnlyWhereHolesAre:
    """`compact_result(match_holes=False)` is the same CSR wherever the
    match rows are packed prefixes (the trie NFA's, a covering
    snapshot's: `models/router_engine._match_holes`), and not where a
    matcher leaves holes."""

    @staticmethod
    def _planes(seed, holes):
        rng = np.random.RandomState(seed)
        W, B, M, D, K = 3, 16, 64, 24, 6
        cm = rng.randint(0, 5, size=(W, B))
        matches = np.full((W, B, M), -1, np.int32)
        for w in range(W):
            for b in range(B):
                at = rng.choice(M, cm[w, b], replace=False) if holes \
                    else np.arange(cm[w, b])
                matches[w, b, np.sort(at)] = rng.randint(
                    0, 1000, size=cm[w, b])
        cf = rng.randint(0, D + 1, size=(W, B)).astype(np.int32)
        lane = np.arange(D)[None, None, :] < cf[..., None]
        rows = np.where(lane, rng.randint(0, 99, (W, B, D)), -1).astype(
            np.int32)
        opts = np.where(lane, rng.randint(0, 4, (W, B, D)), 0).astype(
            np.int8)
        cs = rng.randint(0, 3, size=(W, B))
        slot = np.arange(K)[None, None, :] < cs[..., None]
        sids = np.where(slot, rng.randint(0, 9, (W, B, K)), -1).astype(
            np.int32)
        srows = np.where(slot, rng.randint(0, 99, (W, B, K)), -1).astype(
            np.int32)
        sopts = np.where(slot, 1, 0).astype(np.int8)
        return matches, rows, opts, cf, sids, srows, sopts

    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_packed_rows_compact_alike_either_way(self, seed):
        from emqx_tpu.ops.compact import compact_result
        planes = self._planes(seed, holes=False)
        a = compact_result(*planes, payload_cap=2048, match_holes=True)
        b = compact_result(*planes, payload_cap=2048, match_holes=False)
        for name, x, y in zip(a._fields, a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=name)
        assert np.asarray(a.counts3)[..., 0].max() >= 3
        assert not np.asarray(a.row_overflow).any()

    def test_rows_with_holes_need_the_closing(self):
        """The control: on the shape-hash matcher's own rows the two
        differ, and only the closing one keeps every valid id."""
        from emqx_tpu.ops.compact import compact_result, csr_slices
        planes = self._planes(4, holes=True)
        a = compact_result(*planes, payload_cap=2048, match_holes=True)
        b = compact_result(*planes, payload_cap=2048, match_holes=False)
        assert (np.asarray(a.payload) != np.asarray(b.payload)).any()
        off, c3, pay = (np.asarray(x) for x in (a.offsets, a.counts3,
                                                a.payload))
        for w in range(planes[0].shape[0]):
            for i in range(planes[0].shape[1]):
                row = planes[0][w, i]
                np.testing.assert_array_equal(
                    csr_slices(off[w], c3[w], pay[w], i)[0],
                    row[row >= 0])


class TestCachePopulationFromCsr:
    def test_rows_equivalent_to_dense_population(self):
        """A cache row built from the CSR view carries the same valid
        filter ids (in order), the same count, and the same overflow
        flag as the dense-populated row for the same topic."""
        comp, _cs, dense, _ds = _twin_nodes(_setup_mixed)
        _finish_all(comp, _route_csr(comp, _mixed_msgs()))
        _finish_all(dense, _route_csr(dense, _mixed_msgs()))
        cc = comp.device_engine._match_cache
        dc = dense.device_engine._match_cache
        assert len(cc) == len(dc) > 0
        with dc._lock:
            dense_rows = dict(dc._rows)
        with cc._lock:
            comp_rows = dict(cc._rows)
        assert set(comp_rows) == set(dense_rows)
        for key, row in comp_rows.items():
            m, c, o = row[:3]
            md, cd, od = dense_rows[key][:3]
            assert m.shape == md.shape      # full match width both ways
            np.testing.assert_array_equal(m[m >= 0], md[md >= 0])
            assert (c, o) == (cd, od)
            # the delta-overlay fields (ISSUE 4) ride the same rows:
            # topic encoding identical on both populate paths
            if len(row) > 3:
                np.testing.assert_array_equal(row[6],
                                              dense_rows[key][6])
                assert row[7:] == dense_rows[key][7:]
        assert comp.metrics.val("match_cache.inserts") > 0


class TestByteAccounting:
    def test_compact_bytes_exact_and_reduced(self):
        """pipeline.readback.bytes.* count the actual transferred host
        arrays, and at fan-out ~1 the compact transfer is >= 4x smaller
        per window (the ISSUE 3 acceptance regime)."""
        comp, _cs, dense, _ds = _twin_nodes(_setup_mixed)
        hc = _route_csr(comp, _mixed_msgs())
        nr = hc.np_res
        assert isinstance(nr, _CsrRes)
        expect = (nr.off.nbytes + nr.c3.nbytes + nr.pay.nbytes
                  + nr.overflow.nbytes + nr.occur.nbytes)
        assert comp.metrics.val("pipeline.readback.bytes.compact") \
            == expect
        _finish_all(comp, hc)

        hd = _route_csr(dense, _mixed_msgs())
        dense_expect = sum(a.nbytes for a in hd.np_res)
        if hd.np_counts is not None:
            dense_expect += hd.np_counts.nbytes
        assert dense.metrics.val("pipeline.readback.bytes.dense") \
            == dense_expect
        _finish_all(dense, hd)
        assert dense_expect >= 4 * expect, \
            f"compaction won only {dense_expect / expect:.1f}x"

    def test_snapshot_readback_section(self):
        """The telemetry snapshot (the schema all four exporters and
        bench.py embed) derives per-window bytes for each path."""
        comp, _cs, _dense, _ds = _twin_nodes(_setup_mixed)
        _finish_all(comp, _route_csr(comp, _mixed_msgs()))
        snap = comp.pipeline_telemetry.snapshot()
        rb = snap["readback"]
        assert rb["windows_compact"] == 1
        assert rb["bytes_per_window_compact"] == rb["bytes_compact"]
        # raw counters ride the shared Metrics registry — what the
        # Prometheus/StatsD exporters emit verbatim
        assert comp.metrics.val("pipeline.readback.bytes.compact") > 0
        from emqx_tpu.apps.prometheus import collect
        text = collect(comp)
        assert "emqx_pipeline_readback_bytes_compact" in text

    def test_a_payload_class_is_held_on_a_step_of_the_ladder(self):
        """An EWMA that swings across a step (2 x 4,096 = the 8 x 1024
        class) does not change class every few windows: up at once,
        down only where the smaller class has a quarter to spare."""
        node = Node()
        b = node.broker
        b.subscribe(b.register(Sink(), "c"), "t/+", {"qos": 0})
        eng = node.device_engine
        eng.rebuild()
        assert 128 < eng._dense_msg_entries()
        picks = []
        for ew in (3900.0, 4700.0, 3900.0, 3200.0, 3000.0, 3900.0,
                   20000.0, 4700.0, 2000.0):
            eng._pay_ewma[1024] = ew
            picks.append(eng._choose_payload_cap(1024) // 1024)
        assert picks == [8, 32, 32, 32, 8, 8, 128, 32, 8]
        # each batch class holds its own; past the ladder it is dense
        eng._pay_ewma[64] = 200.0
        assert eng._choose_payload_cap(64) == 8 * 64
        eng._pay_ewma[1024] = 1e6
        assert eng._choose_payload_cap(1024) is None
        eng._pay_ewma[1024] = 3900.0
        assert eng._choose_payload_cap(1024) == 8 * 1024

    def test_disabled_knob(self):
        node = Node(DENSE_CONF)
        b = node.broker
        b.subscribe(b.register(Sink(), "c"), "t/+", {"qos": 0})
        eng = node.device_engine
        assert not eng.compact_readback
        assert eng.route_batch([mkmsg("t/1")] * 70) == [1] * 70
        assert node.metrics.val("pipeline.readback.windows.compact") == 0
        assert node.metrics.val("pipeline.readback.windows.dense") > 0


class TestMeshCompact:
    def test_mesh_compact_identical_and_guarded(self):
        """Mesh readback compaction: deliveries equal the dense mesh,
        and the per-slot staleness guard host-dispatches a pick whose
        member left the group mid-batch instead of delivering to the
        stale session."""
        MC = {"broker": {"multichip": {"enable": True, "devices": 4,
                                       "dp": 2, "max_batch": 16},
                         "device_min_batch": 1}}
        MCD = {"broker": {**MC["broker"], "compact_readback": False}}
        comp, dense = Node(MC), Node(MCD)

        def setup(node):
            b = node.broker
            sinks = [Sink() for _ in range(3)]
            sids = [b.register(s, f"c{i}") for i, s in enumerate(sinks)]
            for i in range(8):
                b.subscribe(sids[i % 3], f"dev/{i}/+", {"qos": 0})
            b.subscribe(sids[0], "$share/g/job/q", {"qos": 0})
            b.subscribe(sids[1], "$share/g/job/q", {"qos": 0})
            return sinks, sids

        cs, c_sids = setup(comp)
        ds, _d_sids = setup(dense)
        msgs = [mkmsg(f"dev/{i % 8}/x") for i in range(10)] \
            + [mkmsg("job/q"), mkmsg("no/match")]
        eng = comp.device_engine
        # pre-warm the payload class so the compact path engages on the
        # first batch (production: the background warm thread does this)
        eng.route_batch([mkmsg(m.topic) for m in msgs], wait=True)
        Bp = eng._batch_class(len(msgs))
        P = eng._choose_pcap(Bp)
        assert P is not None
        eng._compact_warm.add((Bp, P))
        for rnd in range(3):
            c1 = eng.route_batch([mkmsg(m.topic) for m in msgs],
                                 wait=True)
            c2 = dense.device_engine.route_batch(
                [mkmsg(m.topic) for m in msgs], wait=True)
            assert c1 == c2, rnd
        assert comp.metrics.val("pipeline.readback.windows.compact") > 0
        # equalize: run the dense node the extra warm batch the compact
        # node got, then compare distributions by count
        dense.device_engine.route_batch([mkmsg(m.topic) for m in msgs],
                                        wait=True)
        assert sorted(len(s.got) for s in cs) == \
            sorted(len(s.got) for s in ds)

        # staleness guard: single-member group, member leaves AFTER the
        # pick is materialized but before consume — without the guard
        # the stale session (still alive) would receive the delivery
        b = comp.broker
        lone = Sink()
        sid_l = b.register(lone, "lone")
        b.subscribe(sid_l, "$share/s/solo/q", {"qos": 0})
        eng.route_batch([mkmsg("solo/q")] * 4, wait=True)  # warm shard
        n_before = len(lone.got)
        h = eng.prepare([mkmsg("solo/q")] * 4)
        assert h is not None
        eng.dispatch(h)
        eng.materialize(h)
        b.unsubscribe(sid_l, "$share/s/solo/q")   # leaves mid-batch
        counts = eng.finish(h)
        assert len(lone.got) == n_before, \
            "stale pick delivered to a member that left the group"
        assert counts == [0] * 4


# ---------- ISSUE 32: a wide filter travels by reference ----------

N_WIDE_SINKS = 2000
# (filter, subscribers, subopts); widths 1..2,000 around the default
# fanout_cap of 128. `subid` is a rich option (host dict path), `nl` /
# `rap` ride the packed byte
WIDE_SPEC = [
    ("fleet/#", 2000, {"qos": 0}),
    ("fleet/+/x", 1280, {"qos": 1}),
    ("fleet/a/#", 160, {"qos": 0}),
    ("fleet/a/x", 129, {"qos": 2}),
    ("+/a/x", 1, {"qos": 0}),
    ("fleet/+/y", 128, {"qos": 0}),
    ("fleet/b/y", 127, {"qos": 1}),
    ("fleet/b/+", 3, {"qos": 0}),
    ("edge/+", 126, {"qos": 0}),
    ("edge/#", 129, {"qos": 0, "nl": 1, "rap": 1}),
    ("edge/k", 2, {"qos": 1, "nl": 1}),
    ("rich/#", 200, {"qos": 1, "subid": 7}),
    ("rich/+", 2, {"qos": 0}),
]
# clean topics first, then the slow ones (narrow rows of 128 + 127 + 3
# that pass the cap, which a roomier twin serves as clean; then a rich
# filter): the device path delivers a window's clean messages before
# its slow ones
WIDE_TOPICS = ["fleet/a/x", "fleet/c/y", "fleet/b/z", "edge/k", "edge/q",
               "fleet/q/x", "none/at/all"]
SLOW_TOPICS = ["fleet/b/y", "rich/r"]


class OptSink:
    """Records (filter, topic, payload, packed subopts) a delivery."""

    def __init__(self):
        self.got = []

    def deliver(self, topic_filter, msg):
        o = msg.headers.get("subopts", {})
        self.got.append((topic_filter, msg.topic, bytes(msg.payload),
                         tuple(int(o.get(k, 0) or 0)
                               for k in ("qos", "nl", "rap", "rh"))))
        return True


def _setup_wide(seed, extra=()):
    def setup(broker):
        rng = np.random.RandomState(seed)
        sinks = [OptSink() for _ in range(N_WIDE_SINKS)]
        sids = [broker.register(s, f"w{i}") for i, s in enumerate(sinks)]
        spec = WIDE_SPEC + [
            (f"rnd/{k}/#", int(rng.randint(1, 2001)), {"qos": k % 3})
            for k in range(3)] + list(extra)
        for f, width, opts in spec:
            for i in rng.choice(N_WIDE_SINKS, size=width, replace=False):
                broker.subscribe(sids[int(i)], f, dict(opts))
        return sinks
    return setup


def _wide_window(seed, n=96):
    rng = np.random.RandomState(seed + 1)
    fast = WIDE_TOPICS + [f"rnd/{k}/t" for k in range(3)]
    topics = [fast[rng.randint(len(fast))] for _ in range(n - 8)] \
        + sorted(SLOW_TOPICS[rng.randint(2)] for _ in range(8))
    return [make("w5" if i % 7 == 0 else "pub", 0, t, b"m%04d" % i)
            for i, t in enumerate(topics)]


def _on_device(got):
    """A session's log without the lanes the device path hands to the
    host route (narrow rows past the cap): there the filters of one
    message come in the host trie's order, not in match order."""
    return [d for d in got if d[1] != "fleet/b/y"]


def _by_message(got):
    """A session's log as (payload, sorted deliveries of that message)
    runs: the sequence of messages, and each message's set."""
    out = []
    for f, _t, p, o in got:
        if out and out[-1][0] == p:
            out[-1][1].append((f, o))
        else:
            out.append((p, [(f, o)]))
    return [(p, sorted(d)) for p, d in out]


class TestWideByReference:
    @pytest.mark.parametrize("seed", [5, 2**31 + 11])
    def test_widths_1_to_2000_equal_the_host_route(self, seed):
        """Widths 1..2,000 mixed in one window (several wide filters on
        one topic, wide and narrow interleaved in match order, 127 /
        128 / 129, a wide filter with `nl` / `rap`, a rich one): the
        device path, CSR or dense, delivers what a twin with a cap no
        filter passes delivers, delivery for delivery in every
        session's order, and what the host route delivers (the same
        messages in the same order a session, the same (filter, opts)
        set a message); only the lanes whose NARROW rows pass the cap
        go to the host."""
        setup = _setup_wide(seed)
        comp, cs, dense, ds = _twin_nodes(setup)
        roomy, rs, host, hs = _twin_nodes(setup, fanout_cap=4096)
        # two windows: the first one's totals size the payload class
        n_slow, total = 0, 0
        for rnd in range(2):
            msgs = _wide_window(seed + rnd)

            def clone():
                return [make(m.from_, 0, m.topic, m.payload) for m in msgs]
            want = [host.broker._route(m,
                                       host.broker.router.match(m.topic))
                    for m in clone()]
            hc, hd, hr = (_route_csr(n, clone())
                          for n in (comp, dense, roomy))
            assert not isinstance(hd.np_res, _CsrRes)
            slow = sum(m.topic == "fleet/b/y" for m in msgs)
            assert int(hd.np_res[6].sum()) == slow > 0
            assert not hr.np_res[6].any()
            for node, h in ((comp, hc), (dense, hd), (roomy, hr)):
                assert _finish_all(node, h) == want
            n_slow, total = n_slow + slow, total + sum(want)
        assert isinstance(hc.np_res, _CsrRes)
        for a, b, c, d in zip(cs, ds, rs, hs):
            assert a.got == b.got
            assert _on_device(a.got) == _on_device(c.got)
            assert _by_message(a.got) == _by_message(c.got) \
                == _by_message(d.got)
        assert total > 100_000
        for node in (comp, dense):
            m = node.metrics
            assert m.val("routing.device.host_fallback") == n_slow
            assert m.val("routing.device.fanout_overflow") == n_slow
            # everything but the host-fallback lanes and the rich
            # filter's dict walk came off the device window
            assert m.val("routing.device.wide_rows") > 0.8 * total
            assert m.val("routing.device.wide_segments") > 2 * len(msgs)
            st = node.device_engine.stats()
            assert st["wide_rows"] == m.val("routing.device.wide_rows")
            assert st["fanout_overflow"] == n_slow
        assert roomy.metrics.val("routing.device.wide_segments") == 0
        # 4 B a wide filter: 2,000 + 1,280 + 160 + 129 subscribers a
        # message and the window's payload is what its narrow rows take
        assert max(comp.device_engine._pay_ewma.values()) < 96 * 260

    def test_fused_window_and_cached_rows(self):
        """A fused window of three sub-batches, twice (the second from
        the match cache's rows): wide segments are attributed inside
        each sub-batch and deliveries equal the roomy twin's."""
        setup = _setup_wide(7)
        comp, cs, roomy, rs = _twin_nodes(setup)
        roomy.device_engine.fanout_cap = 4096
        for rnd in range(2):
            lives = [_wide_window(20 + k, n=40) for k in range(3)]
            hc = _route_csr(comp, None, window=lives)
            hr = _route_csr(roomy, None, window=[
                [make(m.from_, 0, m.topic, m.payload) for m in sub]
                for sub in lives])
            assert _finish_all(comp, hc) == _finish_all(roomy, hr), rnd
        assert [_on_device(s.got) for s in cs] == \
            [_on_device(s.got) for s in rs]
        assert [_by_message(s.got) for s in cs] == \
            [_by_message(s.got) for s in rs]
        assert comp.device_engine.stats()["match_cache"]["hits"] > 0
        assert comp.metrics.val("routing.device.wide_segments") > 0

    @pytest.mark.parametrize("width,wide", [(127, 0), (128, 0), (129, 1)])
    def test_the_cap_itself_is_narrow(self, width, wide):
        def setup(broker):
            sinks = [OptSink() for _ in range(width)]
            for i, s in enumerate(sinks):
                broker.subscribe(broker.register(s, f"e{i}"), "t/+",
                                 {"qos": 0})
            return sinks
        comp, cs, dense, ds = _twin_nodes(setup)
        for node in (comp, dense):
            assert node.device_engine.route_batch(
                [mkmsg("t/a"), mkmsg("t/b")]) == [width, width]
            m = node.metrics
            assert m.val("routing.device.wide_segments") == 2 * wide
            assert m.val("routing.device.wide_rows") == 2 * wide * width
            assert m.val("routing.device.host_fallback") == 0
            assert m.val("messages.routed.device") == 2 * width
        assert all(len(s.got) == 2 for s in cs + ds)
