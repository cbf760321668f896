"""Fan-out + shared-subscription selection + fused route step tests.

Oracle: brute-force topic.match over the filter list, subscriber lists as
python dicts, sequential round-robin for shared groups (the reference's
per-group counter semantics, emqx_shared_sub.erl round_robin :284-290).
"""

import numpy as np
import pytest

from emqx_tpu.models.router_engine import RouterTables, route_step
from emqx_tpu.ops import intern as I
from emqx_tpu.ops.fanout import build_subtable, fanout_normal, shared_slots
from emqx_tpu.ops.match import encode_topics, match_batch
from emqx_tpu.ops.shared import STRATEGY_ROUND_ROBIN, pick_members
from emqx_tpu.ops.trie import build_tables
from emqx_tpu.utils import topic as T


def build_fixture(filters, normal, filter_slots=None, shared_members=None,
                  max_levels=8):
    """filters: list[str]; normal: fid -> [(row, opts)]; returns full setup."""
    intern = I.InternTable()
    rows = np.zeros((len(filters), max_levels), np.int32)
    lens = np.zeros(len(filters), np.int64)
    for fid, f in enumerate(filters):
        w = intern.encode_filter(T.words(f))
        rows[fid, :len(w)] = w
        lens[fid] = len(w)
    trie = build_tables(rows, lens)
    subs = build_subtable(len(filters), normal, filter_slots or {},
                          shared_members or {})
    return intern, RouterTables(trie=trie, subs=subs)


def encode(intern, topics, max_levels=8):
    tw = [T.words(t) for t in topics]
    enc, lens, dollar, too_long = encode_topics(intern, tw, max_levels)
    assert not too_long.any()
    return enc, lens, dollar


class TestFanout:
    def test_basic_fanout(self):
        filters = ["a/+", "a/#", "b"]
        normal = {0: [(10, 1), (11, 2)], 1: [(12, 0)], 2: [(13, 1)]}
        intern, tables = build_fixture(filters, normal)
        enc, lens, dollar = encode(intern, ["a/x", "b", "zzz"])
        mr = match_batch(tables.trie, enc, lens, dollar)
        fr = fanout_normal(tables.subs, mr.matches)
        got0 = sorted(int(r) for r in fr.rows[0] if r >= 0)
        assert got0 == [10, 11, 12]
        assert int(fr.counts[0]) == 3
        got1 = sorted(int(r) for r in fr.rows[1] if r >= 0)
        assert got1 == [13]
        assert int(fr.counts[2]) == 0
        # opts travel with rows
        opts0 = {int(r): int(o) for r, o in zip(fr.rows[0], fr.opts[0]) if r >= 0}
        assert opts0 == {10: 1, 11: 2, 12: 0}

    def test_fanout_overflow(self):
        filters = ["t"]
        normal = {0: [(i, 0) for i in range(40)]}
        intern, tables = build_fixture(filters, normal)
        enc, lens, dollar = encode(intern, ["t"])
        mr = match_batch(tables.trie, enc, lens, dollar)
        fr = fanout_normal(tables.subs, mr.matches, fanout_cap=16)
        assert bool(fr.overflow[0])
        assert int(fr.counts[0]) == 40  # true count still reported

    def test_fanout_overflow_stays_without_wide_by_ref(self):
        """Off (the mesh's programs, the step programs): a filter wider
        than the cap overflows its lane as before."""
        subs = build_subtable(2, {0: [(i, 0) for i in range(40)],
                                  1: [(99, 1)]}, {}, {})
        m = np.array([[0, 1, -1]], np.int32)
        off = fanout_normal(subs, m, fanout_cap=16)
        assert bool(off.overflow[0]) and int(off.counts[0]) == 41
        on = fanout_normal(subs, m, fanout_cap=16, wide_by_ref=True)
        assert not bool(on.overflow[0]) and int(on.counts[0]) == 1
        assert [int(r) for r in on.rows[0] if r >= 0] == [99]

    def test_empty_filter_no_subscribers(self):
        filters = ["a", "b"]
        normal = {0: [(1, 0)]}  # filter 1 has no subscribers
        intern, tables = build_fixture(filters, normal)
        enc, lens, dollar = encode(intern, ["b"])
        mr = match_batch(tables.trie, enc, lens, dollar)
        fr = fanout_normal(tables.subs, mr.matches)
        assert int(fr.counts[0]) == 0


def _random_csr(rng, widths):
    """A SubTable whose filter k has `widths[k]` subscribers, with the
    python dict it was built from."""
    normal, row = {}, 0
    for fid, w in enumerate(widths):
        normal[fid] = [(row + j, int(rng.randint(0, 64))) for j in range(w)]
        row += w
    return build_subtable(len(widths), normal, {}, {}), normal


def _rows_by_reference(fr, b, matches, normal, cap):
    """Lane b's deliveries as the host rebuilds them: the plane's rows
    for each narrow matched filter, the CSR's own for a wide one, at
    the filter's place in match order."""
    out, col = [], 0
    rows, opts = np.asarray(fr.rows[b]), np.asarray(fr.opts[b])
    for fid in matches[b]:
        if fid < 0:
            continue
        seg = normal[int(fid)]
        if len(seg) > cap:
            out += seg
        else:
            out += list(zip(rows[col:col + len(seg)].tolist(),
                            opts[col:col + len(seg)].tolist()))
            col += len(seg)
    assert (rows[col:] == -1).all()
    return out


class TestWideByReference:
    """`fanout_normal(wide_by_ref=True)`: a filter with more than
    `fanout_cap` subscribers takes no slot of its lane's row, and the
    lane's deliveries, rebuilt from the planes and the CSR, are still
    the concatenation of the matched filters' segments in match
    order."""

    @pytest.mark.parametrize("seed", [3, 17, 2**31 + 5])
    def test_widths_1_to_2000_mixed_in_one_batch(self, seed):
        rng = np.random.RandomState(seed % (2**32))
        cap = 128
        widths = [1, 2, 5, 127, 128, 129, 160, 1280, 2000] + \
            [int(w) for w in rng.randint(1, 2001, size=9)] + [3, 40, 60]
        subs, normal = _random_csr(rng, widths)
        B, M = 48, 8
        matches = np.full((B, M), -1, np.int32)
        for b in range(B):
            k = rng.randint(0, M + 1)
            at = np.sort(rng.choice(M, size=k, replace=False))
            matches[b, at] = rng.choice(len(widths), size=k, replace=False)
        # several wide filters on one topic, wide and narrow interleaved
        matches[0] = [7, 0, 8, -1, 1, 6, 2, -1]
        matches[1] = [3, 5, -1, -1, -1, -1, -1, -1]     # 127, 129
        matches[2] = [5, -1, 4, -1, -1, -1, -1, -1]     # 129, 128
        fr = fanout_normal(subs, matches, fanout_cap=cap, wide_by_ref=True)
        for b in range(B):
            fids = [int(f) for f in matches[b] if f >= 0]
            narrow = sum(widths[f] for f in fids if widths[f] <= cap)
            assert int(fr.counts[b]) == narrow
            assert bool(fr.overflow[b]) == (narrow > cap)
            if narrow > cap:
                continue        # the lane goes to the host route
            want = [e for f in fids for e in normal[f]]
            assert _rows_by_reference(fr, b, matches, normal, cap) == want
        assert not np.asarray(fr.overflow[:3]).any()
        assert [int(c) for c in fr.counts[:3]] == [1 + 2 + 5, 127, 128]

    @pytest.mark.parametrize("width,wide", [(127, False), (128, False),
                                            (129, True)])
    def test_the_cap_itself_is_narrow(self, width, wide):
        rng = np.random.RandomState(width)
        subs, normal = _random_csr(rng, [width, 1])
        m = np.array([[1, 0]], np.int32)
        fr = fanout_normal(subs, m, fanout_cap=128, wide_by_ref=True)
        assert int(fr.counts[0]) == (1 if wide else width + 1)
        # 128 + 1 rows of narrow filters pass the row: still an overflow
        assert bool(fr.overflow[0]) == (width == 128)
        if width != 128:
            assert _rows_by_reference(fr, 0, m, normal, 128) == \
                normal[1] + normal[0]

    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_narrow_lanes_are_bit_equal_with_and_without(self, seed):
        """No matched filter wider than the cap: every plane is what
        the parent's program returned (overflow lanes included)."""
        rng = np.random.RandomState(seed)
        widths = [int(w) for w in rng.randint(0, 9, size=40)]
        subs, _normal = _random_csr(rng, widths)
        matches = rng.randint(-30, 40, size=(64, 6)).clip(-1) \
            .astype(np.int32)
        a = fanout_normal(subs, matches, fanout_cap=16)
        b = fanout_normal(subs, matches, fanout_cap=16, wide_by_ref=True)
        assert np.asarray(a.overflow).any() \
            and not np.asarray(a.overflow).all()
        for x, y in zip(a, b):
            x, y = np.asarray(x), np.asarray(y)
            assert x.dtype == y.dtype and (x == y).all()


class TestSharedPick:
    def setup_tables(self):
        # filter 0 = "job/+" in group slot 0 (3 members), slot 1 (2 members)
        filters = ["job/+"]
        normal = {}
        filter_slots = {0: [0, 1]}
        shared_members = {0: [(100, 0), (101, 0), (102, 0)],
                          1: [(200, 1), (201, 1)]}
        return build_fixture(filters, normal, filter_slots, shared_members)

    def test_round_robin_within_batch(self):
        intern, tables = self.setup_tables()
        enc, lens, dollar = encode(intern, ["job/1", "job/2", "job/3", "job/4"])
        mr = match_batch(tables.trie, enc, lens, dollar)
        sids, oflow = shared_slots(tables.subs, mr.matches)
        assert not bool(oflow.any())
        cursors = np.zeros(2, np.int32)
        sp = pick_members(tables.subs, cursors, sids,
                          np.int32(STRATEGY_ROUND_ROBIN), np.zeros(4, np.int32))
        # slot 0: members 100,101,102 → picks cycle in batch order
        picks0 = [int(r) for r in sp.rows[:, 0]]
        assert picks0 == [100, 101, 102, 100]
        picks1 = [int(r) for r in sp.rows[:, 1]]
        assert picks1 == [200, 201, 200, 201]
        assert list(np.asarray(sp.new_cursors)) == [4, 4]

    def test_round_robin_across_batches(self):
        intern, tables = self.setup_tables()
        enc, lens, dollar = encode(intern, ["job/1"])
        mr = match_batch(tables.trie, enc, lens, dollar)
        sids, _ = shared_slots(tables.subs, mr.matches)
        cursors = np.zeros(2, np.int32)
        seen = []
        for _ in range(4):
            sp = pick_members(tables.subs, cursors, sids,
                              np.int32(STRATEGY_ROUND_ROBIN),
                              np.zeros(1, np.int32))
            seen.append(int(sp.rows[0, 0]))
            cursors = np.asarray(sp.new_cursors)
        assert seen == [100, 101, 102, 100]

    def test_hash_strategy_stable(self):
        from emqx_tpu.ops.shared import STRATEGY_HASH_TOPIC
        intern, tables = self.setup_tables()
        enc, lens, dollar = encode(intern, ["job/1", "job/1"])
        mr = match_batch(tables.trie, enc, lens, dollar)
        sids, _ = shared_slots(tables.subs, mr.matches)
        h = np.array([77, 77], np.int32)  # same topic hash
        sp = pick_members(tables.subs, np.zeros(2, np.int32), sids,
                          np.int32(STRATEGY_HASH_TOPIC), h)
        assert int(sp.rows[0, 0]) == int(sp.rows[1, 0])  # sticky per hash
        assert list(np.asarray(sp.new_cursors)) == [0, 0]  # no advance

    def test_sticky_strategy_affinity(self):
        """Sticky: the cursor is the affinity pointer (seeded host-side
        with the sticky member's index, emqx_shared_sub.erl:269-283);
        every message in every batch picks it and it never advances."""
        from emqx_tpu.ops.shared import STRATEGY_STICKY
        intern, tables = self.setup_tables()
        enc, lens, dollar = encode(intern, ["job/1", "job/2", "job/3"])
        mr = match_batch(tables.trie, enc, lens, dollar)
        sids, _ = shared_slots(tables.subs, mr.matches)
        cursors = np.array([1, 0], np.int32)   # slot0 stuck on member 101
        sp = pick_members(tables.subs, cursors, sids,
                          np.int32(STRATEGY_STICKY), np.zeros(3, np.int32))
        assert [int(r) for r in sp.rows[:, 0]] == [101, 101, 101]
        assert [int(r) for r in sp.rows[:, 1]] == [200, 200, 200]
        assert list(np.asarray(sp.new_cursors)) == [1, 0]  # no advance
        # next batch keeps the affinity
        sp2 = pick_members(tables.subs, np.asarray(sp.new_cursors), sids,
                           np.int32(STRATEGY_STICKY),
                           np.zeros(3, np.int32))
        assert [int(r) for r in sp2.rows[:, 0]] == [101, 101, 101]


class TestRouteStep:
    def test_fused_step(self):
        filters = ["s/+", "s/#", "q/job"]
        normal = {0: [(1, 1)], 1: [(2, 2)]}
        filter_slots = {2: [0]}
        shared = {0: [(50, 1), (51, 1)]}
        intern, tables = build_fixture(filters, normal, filter_slots, shared)
        enc, lens, dollar = encode(intern, ["s/a", "q/job", "q/job"])
        cursors = np.zeros(1, np.int32)
        res = route_step(tables, cursors, enc, lens, dollar,
                         np.zeros(3, np.int32), np.int32(STRATEGY_ROUND_ROBIN))
        # topic 0: normal rows {1, 2}, no shared
        assert sorted(int(r) for r in res.rows[0] if r >= 0) == [1, 2]
        assert int(res.shared_rows[0].max()) == -1
        # topics 1,2: shared picks round-robin over {50,51}
        assert int(res.shared_rows[1, 0]) == 50
        assert int(res.shared_rows[2, 0]) == 51
        assert list(np.asarray(res.new_cursors)) == [2]
        assert not bool(res.overflow.any())


class TestRankOccurOracle:
    """Randomized oracle for the sort-based rank/occur kernel (rewritten
    round-3 with unique-index scatters): rank must equal the number of
    earlier occurrences in flattened batch order, occur the per-slot
    totals — the invariants round-robin fairness rests on."""

    @pytest.mark.parametrize("impl", ["sorted", "blocked"])
    def test_matches_bruteforce(self, impl):
        import numpy as np

        from emqx_tpu.ops import shared as S
        fn = (S._rank_and_occur_sorted if impl == "sorted"
              else S._rank_and_occur_blocked)
        rng = np.random.RandomState(3)
        for _ in range(5):
            B, K, G = 64, 3, 17
            sids = rng.randint(-1, G, size=(B, K)).astype(np.int32)
            rank, occur = fn(sids, G)
            rank = np.asarray(rank)
            occur = np.asarray(occur)
            flat = sids.reshape(-1)
            seen: dict = {}
            want_rank = np.zeros_like(flat)
            for i, s in enumerate(flat):
                if s < 0:
                    continue
                want_rank[i] = seen.get(int(s), 0)
                seen[int(s)] = want_rank[i] + 1
            assert (rank.reshape(-1)[flat >= 0]
                    == want_rank[flat >= 0]).all()
            want_occur = np.bincount(flat[flat >= 0], minlength=G)
            assert (occur == want_occur).all()

    @pytest.mark.parametrize("block", [8, 32, 256])
    def test_blocked_any_width(self, block):
        """The block width is a sweepable static arg (tpu_matrix sweeps
        it on hardware); every width must agree with the sorted impl,
        including widths that leave a ragged final block."""
        import numpy as np

        from emqx_tpu.ops import shared as S
        rng = np.random.RandomState(11)
        B, K, G = 37, 3, 13          # B*K not a multiple of any block
        sids = rng.randint(-1, G, size=(B, K)).astype(np.int32)
        want_rank, want_occur = S._rank_and_occur_sorted(sids, G)
        rank, occur = S._rank_and_occur_blocked(sids, G, block=block)
        valid = sids >= 0          # -1 ranks are documented as unused
        assert (np.asarray(rank)[valid]
                == np.asarray(want_rank)[valid]).all()
        assert (np.asarray(occur) == np.asarray(want_occur)).all()


class TestRouteWindow:
    """The W-fused window step (one dispatch per W batches) must be
    bit-identical to W sequential route_step_shapes calls: same digests,
    same threaded cursors."""

    def test_window_equals_sequential(self):
        from emqx_tpu.models.router_engine import (ShapeRouterTables,
                                                   route_digest,
                                                   route_step_shapes,
                                                   route_window_shapes)
        from emqx_tpu.ops.shapes import build_shape_tables

        filters = ["dev/+/t", "dev/#", "q/job", "+/x/+"]
        intern = I.InternTable()
        rows = np.zeros((len(filters), 8), np.int32)
        lens = np.zeros(len(filters), np.int64)
        for fid, f in enumerate(filters):
            w = intern.encode_filter(T.words(f))
            rows[fid, :len(w)] = w
            lens[fid] = len(w)
        st = build_shape_tables(rows, lens)
        normal = {0: [(1, 1)], 1: [(2, 2)], 3: [(3, 1)]}
        shared = {0: [(50, 1), (51, 1), (52, 1)]}
        subs = build_subtable(len(filters), normal, {2: [0]}, shared)
        tables = ShapeRouterTables(shapes=st, subs=subs)

        rng = np.random.RandomState(11)
        W, B = 4, 8
        topics = ["dev/a/t", "q/job", "n/x/m", "dev/b/c", "none"]
        batches = [[topics[rng.randint(len(topics))] for _ in range(B)]
                   for _ in range(W)]
        encs = [encode(intern, bt) for bt in batches]
        hashes = rng.randint(0, 1 << 30, size=(W, B)).astype(np.int32)
        strat = np.int32(STRATEGY_ROUND_ROBIN)

        # sequential reference
        cur = np.zeros(1, np.int32)
        want = []
        for k in range(W):
            enc, lens_, dol = encs[k]
            r = route_step_shapes(tables, cur, enc, lens_, dol, hashes[k],
                                  strat, fanout_cap=8, slot_cap=4)
            want.append(int(route_digest(r)))
            cur = r.new_cursors

        stacked = tuple(np.stack([encs[k][i] for k in range(W)])
                        for i in range(3))
        new_cur, digests = route_window_shapes(
            tables, np.zeros(1, np.int32), stacked[0], stacked[1],
            stacked[2], hashes, strat, fanout_cap=8, slot_cap=4)
        assert list(np.asarray(digests)) == want
        assert list(np.asarray(new_cur)) == list(np.asarray(cur))
