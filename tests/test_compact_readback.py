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
            for i, s in enumerate(sinks):
                broker.subscribe(broker.register(s, f"o{i}"), "big/+",
                                 {"qos": 0})
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
