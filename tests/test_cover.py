"""Subscription covering (ISSUE 18): match the covering set, expand at
fan-out.

Covering must be INVISIBLE except for speed. The proof obligations:

- `covers_pair` (the pure-python covering oracle) against BRUTE-FORCE
  topic enumeration through HostTrie — trailing-'#', '+'-vs-literal per
  level, '$'-prefix exclusion, self-cover;
- vectorized `detect_covers` against exhaustive `covers_pair` pairwise
  sweeps over mixed populations;
- per-filter order keys reproduce the trie NFA's emission order;
- engine A/B twins (covering on vs off) bit-identical on delivery
  counts AND per-session delivery order across clean / shared-group /
  '$'-topic / dirty-overlay / churn traffic and both pairings covering
  engages on (trie-off vs shapes-root-on, trie-trie), plus the
  2/4/8-shard mesh;
- the engage rule (`covering_decision`, PR 25): a full set the
  shape-hash table holds whole builds cover-free and never runs
  detection; the engine cases therefore give their engines a
  `shape_cap` the full set overflows and the roots fit;
- the append path: a covered new subscription lands in the expansion
  CSR (no rebuild) and the match cache drops cached topics against the
  EXPANDED set — insert and delete;
- knob resolution (broker.subscription_covering beats
  EMQX_TPU_COVERING beats default-on) and the stats/ledger surfaces
  (cover_csr HBM category);
- the shared workload generator actually produces the cover ratio it
  promises (tools/workloads.py) and the legacy population stays
  cover-free.
"""

import numpy as np
import pytest

from emqx_tpu.broker import device_engine as DE
from emqx_tpu.broker.message import make
from emqx_tpu.broker.node import Node
from emqx_tpu.ops import cover as C
from emqx_tpu.ops.intern import PAD, InternTable
from emqx_tpu.ops.trie import HostTrie


class Sink:
    def __init__(self):
        self.got = []

    def deliver(self, topic_filter, msg):
        self.got.append((topic_filter, msg.topic, bytes(msg.payload)))
        return True


def mkmsg(topic, payload=b"x"):
    return make("pub", 0, topic, payload)


def _encode(intern, filters):
    L = max(len(f.split("/")) for f in filters)
    rows = np.zeros((len(filters), L), np.int32)
    lens = np.zeros(len(filters), np.int64)
    for i, f in enumerate(filters):
        ids = intern.encode_filter(f.split("/"))
        rows[i, :len(ids)] = ids
        lens[i] = len(ids)
    dollar = np.fromiter((f.startswith("$") for f in filters), bool,
                         len(filters))
    return rows, lens, dollar


# the covering edge cases named by the issue, all in one population:
# trailing '#' over exact/'+'/deeper-'#', '+' vs literal per level,
# root-'$' exclusion, '#' root, identical-shape distinct filters
EDGE_FILTERS = [
    "#", "a/#", "a/b", "a/+", "+/b", "a/b/#", "a/b/c", "a/+/c",
    "+/+", "a/+/+", "+/b/c", "s/#", "s/+/t", "s/u/t", "s/u/v",
    "$SYS/#", "$SYS/x", "$SYS/+", "b/#", "b/+/#",
]


def _enum_topics(words, depth):
    """Every topic over `words` up to `depth` levels."""
    out = [[w] for w in words]
    frontier = [[w] for w in words]
    for _ in range(depth - 1):
        frontier = [t + [w] for t in frontier for w in words]
        out.extend(frontier)
    return out


class TestCoversPairOracle:
    def test_against_topic_enumeration(self):
        """A covers B == topics(B) subset-of topics(A), brute-forced
        through HostTrie over an alphabet that exercises '$' roots."""
        intern = InternTable()
        # every literal appearing in EDGE_FILTERS, so no filter's
        # enumerated topic set is vacuously empty
        alphabet = ["a", "b", "c", "s", "t", "u", "v", "x", "$SYS"]
        topics = _enum_topics(alphabet, 4)
        enc = {}
        for i, f in enumerate(EDGE_FILTERS):
            t = HostTrie()
            t.insert(intern.encode_filter(f.split("/")), i)
            enc[f] = t

        def topic_set(f):
            t = enc[f]
            out = set()
            for tw in topics:
                ids = [intern.lookup(w) for w in tw]
                if t.match(ids, is_dollar=tw[0].startswith("$")):
                    out.add(tuple(tw))
            return out

        tsets = {f: topic_set(f) for f in EDGE_FILTERS}
        for fa in EDGE_FILTERS:
            wa = intern.encode_filter(fa.split("/"))
            for fb in EDGE_FILTERS:
                wb = intern.encode_filter(fb.split("/"))
                got = C.covers_pair(wa, wb,
                                    b_dollar=fb.startswith("$"))
                want = tsets[fb] <= tsets[fa]
                assert got == want, (fa, fb, got, want)

    def test_pointwise_cases(self):
        it = InternTable()

        def cp(a, b):
            return C.covers_pair(it.encode_filter(a.split("/")),
                                 it.encode_filter(b.split("/")),
                                 b_dollar=b.startswith("$"))

        assert cp("a/#", "a/b") and cp("a/#", "a/+") and cp("a/#", "a")
        assert cp("a/#", "a/b/#") and cp("#", "a/b/c")
        assert not cp("a/b/#", "a/#")        # deeper '#' covers less
        assert cp("a/+", "a/b") and not cp("a/b", "a/+")
        assert not cp("a/+", "a/b/c")        # '+' is exactly one level
        assert not cp("a/+", "a/#")          # '#' matches deeper
        assert not cp("#", "$SYS/x") and not cp("+/#", "$SYS/x")
        assert cp("$SYS/#", "$SYS/x")        # '$' literal root is fine
        assert cp("a/b", "a/b")              # self-cover: caller excludes


class TestDetection:
    def test_matches_exhaustive_pairwise(self):
        from tools.workloads import cover_heavy_filters
        intern = InternTable()
        filters = sorted(set(EDGE_FILTERS
                             + cover_heavy_filters(120, cover_ratio=0.5)))
        rows, lens, dollar = _encode(intern, filters)
        covers, inc = C.detect_covers(rows, lens, dollar)
        assert not inc.any()
        n = len(filters)
        for b in range(n):
            wb = [int(x) for x in rows[b, :lens[b]]]
            want = {a for a in range(n) if a != b and C.covers_pair(
                [int(x) for x in rows[a, :lens[a]]], wb,
                b_dollar=bool(dollar[b]))}
            assert set(int(x) for x in covers[b]) == want, filters[b]

    def test_assign_owners_roots_and_budget(self):
        intern = InternTable()
        filters = ["a/#", "a/1", "a/2", "a/3", "b/c"]
        rows, lens, dollar = _encode(intern, filters)
        covers, inc = C.detect_covers(rows, lens, dollar)
        assert C.fan_in(covers).tolist() == [3, 0, 0, 0, 0]
        owner = C.assign_owners(covers, inc)
        assert owner[0] == -1 and owner[4] == -1       # roots
        assert list(owner[1:4]) == [0, 0, 0]
        # budget: a cover owns the filters it covers where the budget
        # holds them all ...
        assert C.assign_owners(covers, inc, own_budget=3).tolist() \
            == owner.tolist()
        # ... and nothing where it does not (the parent let `a/#` own
        # the first two by fid and left the third a root): a wide root
        # stays in the match set alone, and so does all it covers
        owner2 = C.assign_owners(covers, inc, own_budget=2)
        assert (owner2[1:4] == 0).sum() == 0
        assert (owner2 == -1).sum() == 5               # wide -> all roots

    def test_order_keys_reproduce_trie_emission(self):
        import jax.numpy as jnp
        from emqx_tpu.ops.match import match_batch
        from emqx_tpu.ops.trie import build_tables
        intern = InternTable()
        filters = EDGE_FILTERS
        rows, lens, dollar = _encode(intern, filters)
        keys = C.trie_order_keys(rows, lens)
        tt = build_tables(rows, lens, node_capacity=256,
                          slot_capacity=1024)
        for topic in ("a/b", "a/b/c", "s/u/t", "s/u/v", "$SYS/x", "b"):
            tw = topic.split("/")
            ids = np.full((1, rows.shape[1]), PAD, np.int32)
            ids[0, :len(tw)] = [intern.lookup(w) for w in tw]
            mr = match_batch(tt, jnp.asarray(ids),
                             jnp.asarray([len(tw)], np.int32),
                             jnp.asarray([topic.startswith("$")]))
            row = [int(x) for x in np.asarray(mr.matches)[0]
                   if int(x) >= 0]
            assert row == sorted(row, key=lambda f: keys[f]), topic
            # keys are UNIQUE within one topic's match set — ties can
            # never co-occur, which is what makes the expansion sort
            # backend-independent
            assert len({int(keys[f]) for f in row}) == len(row)


# ---------------- engine A/B twins ----------------

POPULATIONS = {
    # few shapes (5 in the full set, 3 among the roots): with the
    # engine's shape_cap at SHAPE_CAPS["shapes"] the off twin runs the
    # trie and the on twin the roots under shapes
    "shapes": ["s/#", "s/+/t", "s/u/t", "s/u/v", "s/a/t",
               "q/1", "q/2", "w/+", "w/x"],
    # 10 shapes in the full set (deep '+' spread), 6 among the roots:
    # at shape_cap 8 the off twin runs the trie, the on twin
    # shapes-over-roots — the mixed-backend pairing
    "mixed": (["top/#"]
              + [f"top/{'+/' * (i % 4)}x{i}" for i in range(12)]
              + [f"d{i}/{'+/' * (i % 5)}m{i}/t{i}" for i in range(12)]
              + ["top/a/b", "top/+/c"]),
}


# the shape_cap at which a population's full set overflows the
# shape-hash table and its roots fit
SHAPE_CAPS = {"shapes": 4, "mixed": 8}


def _mk_twin_nodes(filters, conf=None, shape_cap=None):
    """(covering-on, covering-off) nodes with one sink+sid per filter.
    `shape_cap` (set before the first build) is how a small population
    overflows the shape-hash table, which is where covering engages."""
    nodes = []
    for covering in (True, False):
        cfg = {"broker": dict(conf or {},
                              subscription_covering=covering)}
        node = Node(cfg)
        if shape_cap is not None:
            node.device_engine.shape_cap = shape_cap
        sinks, sids = {}, {}
        for i, f in enumerate(filters):
            s = Sink()
            sid = node.broker.register(s, f"c{i}")
            node.broker.subscribe(sid, f, {"qos": 0})
            sinks[f], sids[f] = s, sid
        nodes.append((node, sinks, sids))
    return nodes


def _route_and_compare(on, off, topics, payload=b"x"):
    (n1, s1, _), (n2, s2, _) = on, off
    c1 = n1.device_engine.route_batch([mkmsg(t, payload)
                                       for t in topics])
    c2 = n2.device_engine.route_batch([mkmsg(t, payload)
                                       for t in topics])
    assert c1 is not None and c2 is not None
    assert c1 == c2, (c1, c2)
    # per-session delivery ORDER, not just counts
    for f in s1:
        assert s1[f].got == s2[f].got, f
    return c1


TRAFFIC = ["s/u/t", "s/u/v", "s/q", "s/a/t", "q/1", "w/x", "nomatch/z",
           "top/a/b", "top/zz", "top/x1", "d3/m3/t3", "$SYS/x"]


class TestEngineTwins:
    @pytest.mark.parametrize("pop", sorted(POPULATIONS))
    def test_clean_dirty_churn_twins(self, pop):
        filters = POPULATIONS[pop]
        on, off = _mk_twin_nodes(filters, shape_cap=SHAPE_CAPS[pop])
        # clean snapshot, repeated (cache-hit rounds included)
        for rnd in range(3):
            _route_and_compare(on, off, TRAFFIC, b"r%d" % rnd)
        st = on[0].device_engine.stats()
        assert st["cover"] and st["cover"]["covered"] > 0
        assert (st["backend"], st["cover_decision"]) == ("shapes",
                                                         "engaged")
        assert off[0].device_engine.stats()["backend"] == "trie"
        # dirty overlay: post-snapshot subscriptions — for the shapes
        # population "s/u/new" is covered by the built "s/#" (append
        # path on the on-twin); for mixed there is no covering root, so
        # it rides the overlay on both; "fresh/+" is uncovered always
        for node, sinks, _sids in (on, off):
            s = Sink()
            sid = node.broker.register(s, "dirty")
            node.broker.subscribe(sid, "s/u/new", {"qos": 0})
            node.broker.subscribe(sid, "fresh/+", {"qos": 0})
            sinks["s/u/new"] = sinks["fresh/+"] = s
        _route_and_compare(on, off, TRAFFIC + ["s/u/new", "fresh/go"],
                           b"dirty")
        # churn: unsubscribe a BUILT literal filter (covered on the
        # on-twin — its tombstone must drop it from the expanded rows)
        victim = [f for f in filters if "+" not in f and "#" not in f][0]
        for node, _sinks, sids in (on, off):
            node.broker.unsubscribe(sids[victim], victim)
        _route_and_compare(on, off, TRAFFIC, b"churn")

    def test_trie_both_twins(self):
        """shape_cap=0 forces BOTH twins onto the trie backend."""
        filters = POPULATIONS["shapes"]
        on, off = _mk_twin_nodes(filters)
        for node, _sinks, _sids in (on, off):
            node.device_engine.shape_cap = 0
        for rnd in range(2):
            _route_and_compare(on, off, TRAFFIC, b"t%d" % rnd)
        assert on[0].device_engine.stats()["backend"] == "trie"
        assert off[0].device_engine.stats()["backend"] == "trie"
        assert on[0].device_engine.stats()["cover"]["covered"] > 0

    def test_padded_window_over_a_covering_trie(self):
        """A fused window over a cover-carrying trie skips the NFA and
        `cover_expand` for a sub-batch of padding, and returns for it,
        at the cover's output width, what the walk returns: every plane
        bit-equal to sequential `route_step` calls on the same tables."""
        import jax

        from emqx_tpu.models import router_engine as RE
        from emqx_tpu.ops.match import encode_topics_str
        on, _off = _mk_twin_nodes(POPULATIONS["shapes"])
        eng = on[0].device_engine
        eng.shape_cap = 0
        eng.route_batch([mkmsg(t) for t in TRAFFIC])
        tables = eng._tables
        assert tables.trie.cover is not None
        W, B = 4, 16
        enc = np.zeros((W, B, eng.max_levels), np.int32)
        lens = np.zeros((W, B), np.int32)
        dol = np.zeros((W, B), bool)
        e, l, d, too_long = encode_topics_str(eng.intern, TRAFFIC,
                                              eng.max_levels)
        assert not too_long.any()
        n = len(TRAFFIC)
        for k in (0, 2):            # sub-batches 1 and 3 are padding
            enc[k, :n], lens[k, :n], dol[k, :n] = e, l, d
        mh = np.zeros((W, B), np.int32)
        kw = eng._caps_kw("trie")
        cur = np.asarray(eng._cursors)
        got = RE.route_window(tables, cur, enc, lens, dol, mh,
                              np.int32(0), **kw)
        assert got.matches.shape[-1] == \
            tables.trie.cover.out_pad.shape[0]
        assert (np.asarray(got.match_counts)[[1, 3]] == 0).all()
        assert np.asarray(got.match_counts)[0].sum() > 0
        for k in range(W):
            want = RE.route_step(tables, cur, enc[k], lens[k], dol[k],
                                 mh[k], np.int32(0), **kw)
            for a, b in zip(jax.tree.leaves(want),
                            jax.tree.leaves(RE.RouteResult(
                                *[x if x is None else x[k]
                                  for x in got]))):
                a, b = np.asarray(a), np.asarray(b)
                assert a.dtype == b.dtype and a.shape == b.shape
                assert (a == b).all()
            cur = want.new_cursors

    def test_shared_groups_post_expansion(self):
        """Shared-sub picks resolve on EXPANDED rows: a group on a
        covered filter must rotate identically across the twins."""
        filters = ["g/#", "g/+/t", "g/a/t"]
        on, off = _mk_twin_nodes(filters, shape_cap=2)
        for node, sinks, _sids in (on, off):
            a, bb = Sink(), Sink()
            node.broker.subscribe(node.broker.register(a, "m1"),
                                  "$share/grp/g/+/t")
            node.broker.subscribe(node.broker.register(bb, "m2"),
                                  "$share/grp/g/+/t")
            sinks["m1"], sinks["m2"] = a, bb
        for rnd in range(3):
            _route_and_compare(
                on, off, ["g/a/t", "g/b/t", "g/c", "g/a/t"],
                b"s%d" % rnd)
        assert on[0].device_engine.stats()["cover"]["covered"] == 2

    def test_unsubscribe_covered_filter(self):
        """Deleting a covered filter must stop its deliveries on both
        twins identically (tombstone against the expanded set)."""
        filters = ["s/#", "s/+/t", "s/u/t"]
        on, off = _mk_twin_nodes(filters, shape_cap=2)
        _route_and_compare(on, off, ["s/u/t"])
        assert on[0].device_engine.stats()["cover"]["covered"] == 2
        for node, _sinks, sids in (on, off):
            node.broker.unsubscribe(sids["s/+/t"], "s/+/t")
        _route_and_compare(on, off, ["s/u/t", "s/x/t"])


# ---------------- append path & cache invalidation ----------------

class TestAppendAndCache:
    def _node(self, shape_cap=2, **conf):
        """A covering node whose shape-hash table holds the roots of
        these cases' filters (1-2 shapes) and not the full set (2-3)."""
        node = Node({"broker": dict(conf, subscription_covering=True)})
        node.device_engine.shape_cap = shape_cap
        return node

    def test_covered_new_sub_is_csr_append_not_rebuild(self):
        node = self._node()
        s = Sink()
        sid = node.broker.register(s, "base")
        for f in ("s/#", "s/+/t", "other/x"):
            node.broker.subscribe(sid, f, {"qos": 0})
        eng = node.device_engine
        assert eng.route_batch([mkmsg("s/q")]) == [1]
        # new covered sub -> append, no overlay row, no rebuild
        s2 = Sink()
        node.broker.subscribe(node.broker.register(s2, "new"), "s/b")
        assert node.metrics.val("routing.cover.appends") == 1
        st = eng.stats()
        assert st["delta_filters"] == 0
        assert st["cover"]["appends"] == 1
        # s/b now matches s/# (base) and the appended s/b (new)
        assert eng.route_batch([mkmsg("s/b")]) == [2]
        assert [g[1] for g in s2.got] == ["s/b"]

    def test_cache_invalidation_walks_expanded_set(self):
        """The cached-topic drop must test the EXPANDED set: a cached
        topic whose row came from a covering root must be dropped when
        an appended filter matches it."""
        node = self._node(shape_cap=1)
        s = Sink()
        sid = node.broker.register(s, "base")
        for f in ("s/#", "s/+/t"):
            node.broker.subscribe(sid, f, {"qos": 0})
        eng = node.device_engine
        # seed the match cache for the topic the append will match
        # (batches must exceed the smallest class so analysis runs)
        assert eng.route_batch([mkmsg("s/b")] * 70
                               + [mkmsg("s/c")] * 70) == [1] * 140
        assert eng.route_batch([mkmsg("s/b")] * 70) == [1] * 70
        hits0 = eng.stats()["match_cache"]["hits"]
        assert hits0 >= 1
        s2 = Sink()
        node.broker.subscribe(node.broker.register(s2, "new"), "s/b")
        assert node.metrics.val("routing.cover.appends") == 1
        # cached row for s/b was dropped: the new subscriber delivers
        assert eng.route_batch([mkmsg("s/b")] * 70) == [2] * 70
        assert s2.got and all(g[1] == "s/b" for g in s2.got)
        # unrelated cached topics survive (drop is per expanded match,
        # not a flush)
        assert eng.route_batch([mkmsg("s/c")] * 70) == [1] * 70

    def test_overlay_delete_drops_cached_expanded_topic(self):
        node = self._node()
        s, s2 = Sink(), Sink()
        sid = node.broker.register(s, "base")
        for f in ("s/#", "s/+/t"):
            node.broker.subscribe(sid, f, {"qos": 0})
        sid2 = node.broker.register(s2, "victim")
        node.broker.subscribe(sid2, "s/u/t", {"qos": 0})
        eng = node.device_engine
        assert eng.route_batch([mkmsg("s/u/t")] * 70) == [3] * 70
        assert eng.route_batch([mkmsg("s/u/t")] * 70) == [3] * 70
        node.broker.unsubscribe(sid2, "s/u/t")
        # the drop walked the expanded set: covered filter's topic
        # re-resolves without the removed subscriber
        assert eng.route_batch([mkmsg("s/u/t")] * 70) == [2] * 70

    def test_new_covering_filter_counts_toward_compaction(self):
        node = self._node()
        s = Sink()
        sid = node.broker.register(s, "base")
        for f in ("s/#", "s/+/t", "q/x"):
            node.broker.subscribe(sid, f, {"qos": 0})
        eng = node.device_engine
        eng.rebuild()
        churn0 = eng._cover_churn
        # a new COVERING filter cannot append (it must own a segment):
        # it rides the overlay and marks cover churn for compaction
        node.broker.subscribe(sid, "q/#", {"qos": 0})
        assert eng._cover_churn > churn0
        assert node.metrics.val("routing.cover.append_rejects") >= 1
        assert eng._compaction_reason() in (None, "covering",
                                            "overflow", "churn",
                                            "tombstones")


    def test_a_new_filter_under_a_wide_root(self):
        """Subscribed after the build: a filter only the tenant's wide
        `t/#` covers is appended under it (nothing narrower can carry
        it), one under `t/#` AND an owning historian under the
        historian (the min-fid root is the wide one). Both are held to
        the off twin (counts, per-session order) and to the host's
        `router.match` message by message."""
        filters = _tenant(areas=2, per_area=100, alone=3)
        on, off = _mk_twin_nodes(filters, shape_cap=3)
        topics = ["t/a0/c0", "t/a1/q/c1", "t/solo/x0", "t/solo/new",
                  "t/a0/new", "t/a0/deeper/new", "t/zz", "u/v"]
        _route_and_compare(on, off, topics, b"built")
        eng = on[0].device_engine
        cs = eng._built.cover
        fid_of = eng._built.fid_of
        assert cs.wide == {fid_of["t/#"]} \
            and eng.stats()["cover"]["wide_roots"] == 1
        late = ["t/solo/new", "t/a0/new", "t/a0/+/new"]
        for node, sinks, _sids in (on, off):
            for i, f in enumerate(late):
                s = Sink()
                node.broker.subscribe(
                    node.broker.register(s, f"late{i}"), f, {"qos": 0})
                sinks[f] = s
        m = on[0].metrics
        assert m.val("routing.cover.appends") == 3 \
            and m.val("routing.cover.append_rejects") == 0
        assert eng.stats()["delta_filters"] == 0
        under = {eng._built.fid_filter[int(f)]:
                 eng._built.fid_filter[int(r)]
                 for f, r in zip(cs.ct.app_fid[:3], cs.ct.app_root[:3])}
        assert under == {"t/solo/new": "t/#", "t/a0/new": "t/a0/#",
                         "t/a0/+/new": "t/a0/#"}
        for rnd in range(2):
            _route_and_compare(on, off, topics, b"late%d" % rnd)
        # the host's own match, message by message: who got what
        node, sinks, _sids = on
        for t in topics:
            want = sorted(node.router.match(t))
            got = sorted(f for f, s in sinks.items()
                         if (f, t, b"late1") in s.got)
            assert got == want, t
        assert sorted(node.router.match("t/a0/deeper/new")) \
            == ["t/#", "t/a0/#", "t/a0/+/new"]
        assert m.val("routing.device.host_fallback") == 0


# ---------------- a root owns what it can hold, or nothing ----------

def _parent_assign_owners(covers, incomplete, own_budget=256):
    """`assign_owners` as it stood before PR 42: a covered filter goes
    to its smallest-fid covering root (a filter nothing covers) while
    that root has owned fewer than `own_budget`."""
    F = len(covers)
    owner = np.full(F, -1, np.int64)
    is_root = np.array([len(c) == 0 for c in covers]) | incomplete
    owned = np.zeros(F, np.int64)
    for fid in range(F):
        if is_root[fid]:
            continue
        for a in sorted(int(x) for x in covers[fid]):
            if is_root[a] and owned[a] < own_budget:
                owner[fid] = a
                owned[a] += 1
                break
    return owner


def _detected(filters):
    rows, lens, dollar = _encode(InternTable(), filters)
    covers, inc = C.detect_covers(rows, lens, dollar)
    return rows, lens, covers, inc


def _tenant(areas=3, per_area=4, alone=2):
    """`t/#` over `areas` historians `t/a{i}/#`, each over `per_area`
    filters, and `alone` exact filters only `t/#` covers."""
    filters = ["t/#"]
    for i in range(areas):
        filters.append(f"t/a{i}/#")
        filters += [f"t/a{i}/+/c{j}" if j % 2 else f"t/a{i}/c{j}"
                    for j in range(per_area)]
    filters += [f"t/solo/x{k}" for k in range(alone)]
    return filters + ["u/v"]


class TestOwnWhatYouCanHold:
    def test_a_wide_root_owns_nothing_and_frees_the_roots_below(self):
        filters = _tenant()
        _rows, _lens, covers, inc = _detected(filters)
        fan = C.fan_in(covers)
        assert fan[0] == 3 * 5 + 2 and fan[1] == 4 and fan[2] == 0
        owner = C.assign_owners(covers, inc, own_budget=4)
        name = {i: f for i, f in enumerate(filters)}
        owned_by = {name[i]: (name[o] if o >= 0 else None)
                    for i, o in enumerate(owner.tolist())}
        # the tenant's umbrella is wide: it owns nothing, stays a root
        assert owned_by["t/#"] is None
        assert "t/#" not in owned_by.values()
        for i in range(3):
            # a historian only the wide root covers is a root again ...
            assert owned_by[f"t/a{i}/#"] is None
            # ... and owns its own
            assert all(owned_by[f] == f"t/a{i}/#" for f in filters
                       if f.startswith(f"t/a{i}/") and f != f"t/a{i}/#")
        # what only the wide root covers stays in the match set
        assert owned_by["t/solo/x0"] is None \
            and owned_by["t/solo/x1"] is None and owned_by["u/v"] is None
        # the parent: `t/#` owned the first four by fid, no historian
        # owned anything, the other thirteen stayed roots
        old = _parent_assign_owners(covers, inc, own_budget=4)
        assert (old == 0).sum() == 4 and (old > 0).sum() == 0 \
            and (old == -1).sum() == len(filters) - 4

    def test_nested_wide_roots(self):
        """`#` over `t/#` over the historians: both wide, both own
        nothing, the historians own; a filter under both and nothing
        else is a root."""
        filters = ["#"] + _tenant()
        _rows, _lens, covers, inc = _detected(filters)
        owner = C.assign_owners(covers, inc, own_budget=4)
        wide = np.flatnonzero(C.fan_in(covers) > 4)
        assert [filters[i] for i in wide] == ["#", "t/#"]
        assert (owner[wide] == -1).all()
        assert not np.isin(owner, wide).any()
        hist = [i for i, f in enumerate(filters)
                if f.startswith("t/a") and f.endswith("/#")]
        assert (owner[hist] == -1).all()
        assert sorted(np.bincount(owner[owner >= 0]).nonzero()[0]) == hist
        assert owner[filters.index("u/v")] == -1     # only `#` covers it

    @pytest.mark.parametrize("covered,owns", [(6, True), (7, False)])
    def test_fan_in_at_the_budget_and_one_past_it(self, covered, owns):
        filters = ["k/#"] + [f"k/f{i}" for i in range(covered)]
        _rows, _lens, covers, inc = _detected(filters)
        owner = C.assign_owners(covers, inc, own_budget=6)
        assert owner[0] == -1
        assert (owner[1:] == (0 if owns else -1)).all()
        # whatever the fids' order, a root's segment holds 1 + budget
        # at most
        seg = 1 + np.bincount(owner[owner >= 0], minlength=1).max()
        assert seg <= 7

    def test_an_incomplete_filter_stays_a_root_and_may_own(self):
        filters = ["s/#", "s/+/t", "s/u/t", "s/u/v"]
        _rows, _lens, covers, inc = _detected(filters)
        # `s/+/t`'s own cover set overflowed at detection: it is kept
        # as a root (its list is empty) and still owns what it covers
        covers[1] = np.zeros(0, np.int64)
        inc = inc.copy()
        inc[1] = True
        owner = C.assign_owners(covers, inc)
        assert owner.tolist() == [-1, -1, 0, 0]
        # with `s/#` wide, `s/u/t` goes to the incomplete root, and
        # `s/u/v`, which only the wide root covers, stays
        assert C.assign_owners(covers, inc, own_budget=1).tolist() \
            == [-1, -1, 1, -1]

    @pytest.mark.parametrize("budget", [1, 4, 16, 256])
    def test_every_filter_is_in_exactly_one_segment(self, budget):
        filters = ["#"] + _tenant(areas=4, per_area=5, alone=3)
        rows, lens, covers, inc = _detected(filters)
        owner = C.assign_owners(covers, inc, own_budget=budget)
        ct = C.build_cover_tables(rows, lens, owner,
                                  C.trie_order_keys(rows, lens),
                                  fid_cap=64, out_width=16, cand_cap=64)
        F = len(filters)
        held = ct.exp_fid[:ct.exp_start[F]]
        assert sorted(held.tolist()) == list(range(F))
        seg = np.diff(ct.exp_start[:F + 1])
        # a covered filter's segment is empty, a root's is itself and
        # what it owns, and no segment passes 1 + budget
        assert (seg[owner >= 0] == 0).all() and (seg[owner < 0] >= 1).all()
        assert seg.max() <= 1 + budget
        for fid in np.flatnonzero(owner >= 0):
            o = int(owner[fid])
            assert owner[o] == -1 and o in covers[fid]

    def test_without_a_wide_filter_the_assignment_is_the_parents(self):
        from benchmark.populations import umbrella_cover
        pop = umbrella_cover.Population({"areas": 7}, 16)
        _rows, _lens, covers, inc = _detected(pop.filters())
        assert C.fan_in(covers).max() == 49 and not inc.any()
        for budget in (49, 192, 256):
            new = C.assign_owners(covers, inc, own_budget=budget)
            old = _parent_assign_owners(covers, inc, own_budget=budget)
            assert new.dtype == old.dtype and (new == old).all()
        assert (C.assign_owners(covers, inc) >= 0).sum() == 7 * 49
        # and on the generator's own cover-heavy set, nested umbrellas
        # and all, at a budget nothing passes
        from tools.workloads import cover_heavy_filters
        _r, _l, covers, inc = _detected(
            sorted(set(cover_heavy_filters(300, cover_ratio=0.5))))
        wide_at = int(C.fan_in(covers).max())
        assert (C.assign_owners(covers, inc, own_budget=wide_at)
                == _parent_assign_owners(covers, inc, wide_at)).all()

    def test_an_empty_set_and_a_cover_free_one(self):
        assert C.assign_owners([], np.zeros(0, bool)).shape == (0,)
        assert C.fan_in([]).shape == (0,)
        none = [np.zeros(0, np.int64)] * 3
        assert C.assign_owners(none, np.zeros(3, bool)).tolist() == [-1] * 3
        assert C.fan_in(none).tolist() == [0, 0, 0]


def _engine_with_budget(filters, cand_cap, shape_cap=2):
    """Covering twins whose engines give a root a budget of
    `cand_cap` - 64 (the NFA's match row), set before the first
    build."""
    on, off = _mk_twin_nodes(filters, shape_cap=shape_cap)
    on[0].device_engine.cover_cand_cap = cand_cap
    return on, off


class TestTheEnginesBudget:
    @pytest.mark.parametrize("covered", [64, 65])
    def test_no_owning_root_passes_the_candidate_plane(self, covered):
        """The engine's budget is its candidate ceiling less the other
        slots of the roots' match row: at 128 a root owns 64 filters
        (a segment of 65 in a plane of 128), and one that covers 65
        owns nothing. Either way no lane overflows the expansion."""
        filters = ["k/#"] + [f"k/f{i}" if i % 2 else f"k/+/g{i}"
                             for i in range(covered)] + ["z/y"]
        on, off = _engine_with_budget(filters, 128)
        topics = [f"k/f{i}" for i in range(1, covered, 2)] \
            + [f"k/q/g{i}" for i in range(0, covered, 2)] \
            + ["k/none", "z/y"]
        for rnd in range(2):
            _route_and_compare(on, off, topics, b"b%d" % rnd)
        st = on[0].device_engine.stats()
        m = on[0].metrics
        assert m.val("routing.device.cover_overflow") == 0 \
            and m.val("routing.device.host_fallback") == 0
        if covered == 64:
            assert st["cover_decision"] == "engaged"
            assert st["cover"]["wide_roots"] == 0 \
                and st["cover"]["largest_segment"] == 65 \
                and st["cover"]["cand_cap"] == 128 \
                and st["cover"]["covered"] == 64
            assert m.val("routing.cover.wide_roots") == 0
            # one root a topic: `k/#`, or `z/y` its own
            assert m.val("routing.device.cover_roots") \
                == m.val("routing.device.match_lanes") == len(topics)
        else:
            # the one cover relation's root is wide: nothing is covered
            assert st["cover_decision"] == "none_covered" \
                and st["cover"] is None
            assert m.val("routing.cover.wide_roots") == 1

    def test_a_wide_root_over_owning_historians_on_the_served_path(self):
        filters = _tenant(areas=3, per_area=70, alone=4)
        on, off = _engine_with_budget(filters, 256, shape_cap=3)
        topics = [f"t/a{i}/c{j}" for i in range(3) for j in (0, 2)] \
            + [f"t/a{i}/w/c{j}" for i in range(3) for j in (1, 3)] \
            + ["t/solo/x0", "t/other", "u/v", "none/x"]
        for rnd in range(2):
            _route_and_compare(on, off, topics, b"w%d" % rnd)
        st = on[0].device_engine.stats()
        assert st["cover_decision"] == "engaged"
        # `t/#` covers 3 * 71 + 4 = 217 > 192: wide; the historians own
        assert st["cover"]["wide_roots"] == 1 \
            and st["cover"]["largest_segment"] == 71 \
            and st["cover"]["covered"] == 3 * 70 \
            and st["cover"]["roots"] == 1 + 3 + 4 + 1
        m = on[0].metrics
        assert m.val("routing.cover.wide_roots") == 1
        assert m.val("routing.device.cover_overflow") == 0 \
            and m.val("routing.device.host_fallback") == 0
        # per topic: 12 under `t/#` and a historian, 2 under `t/#` and
        # nothing or one exact filter, `u/v` its own, one nothing
        # (two rounds: a batch this small takes no match-cache plan)
        assert m.val("routing.device.match_lanes") == 2 * len(topics)
        assert m.val("routing.device.cover_roots") \
            == 2 * (12 * 2 + 2 + 1 + 1 + 0) == st["cover_roots"]
        assert m.val("routing.device.cover_candidates") \
            == 2 * (12 * (1 + 71) + 2 + 1 + 1)


# ---------------- knob & surfaces ----------------

def _is_packed(rows) -> tuple:
    """([R] whether each row's valid ids are a prefix, no `-1` before a
    valid id; [R] how many it holds)."""
    rows = np.asarray(rows).reshape(-1, np.asarray(rows).shape[-1])
    n = (rows >= 0).sum(axis=1)
    return ((rows >= 0) == (np.arange(rows.shape[1])[None, :]
                            < n[:, None])).all(axis=1), n


def _packed(rows, counts) -> None:
    ok, n = _is_packed(rows)
    assert ok.all() and (n == np.asarray(counts).reshape(-1)).all()


class TestPrefixPackedRows:
    """A covering snapshot's match rows carry no interior hole, whatever
    matched the roots and wherever a row comes from, which is why the
    window's compact stage closes none over such a snapshot
    (`models/router_engine._match_holes`, ISSUE 39). The three origins a
    row has on the served path: fresh from `cover_expand`, a cached row
    filled from the CSR readback, a cached row filled from a dense
    one."""

    # the shape-hash probe leaves its holes among the ROOTS' slots:
    # `top/a/b` matches root shapes 0 (`top/#`) and, two slots on,
    # nothing in between; after the expansion the row must be packed
    TOPICS = TRAFFIC + ["top/a/b", "top/q/c", "top/x1", "d3/m3/t3"]

    def _window(self, compact: bool, shape_cap, rounds: int):
        on, _off = _mk_twin_nodes(
            POPULATIONS["mixed"],
            conf={"compact_readback": compact, "topic_dedup": rounds > 1},
            shape_cap=shape_cap)
        node = on[0]
        eng = node.device_engine
        # 70 lanes: past the smallest batch class, so the plan's
        # analysis runs and the readback seeds the cache
        msgs = [mkmsg(self.TOPICS[i % len(self.TOPICS)])
                for i in range(70)]
        for _ in range(rounds):
            h = eng.prepare(msgs, gate_cold=False)
            eng.dispatch(h)
            res = h.res
            eng.materialize(h)
            eng.finish(h)
        return node, eng, h, res

    @pytest.mark.parametrize("shape_cap", [SHAPE_CAPS["mixed"], 0],
                             ids=["roots_by_shapes", "roots_by_trie"])
    def test_fresh_from_the_expansion(self, shape_cap):
        node, eng, h, res = self._window(True, shape_cap, 1)
        assert eng.stats()["cover"]["covered"] > 0
        assert eng.stats()["backend"] == ("shapes" if shape_cap else "trie")
        assert h.plan is None
        counts = np.asarray(res.match_counts)
        assert counts.max() >= 2
        _packed(res.matches, counts)
        # and the roots' own probe does leave holes on this traffic,
        # where the shape-hash table matched them
        if shape_cap:
            from emqx_tpu.ops.shapes import shape_match
            enc, lens, dol = h.enc
            st = eng._tables.shapes._replace(cover=None)
            roots = shape_match(st, enc[0], lens[0], dol[0]).matches
            assert not _is_packed(roots)[0].all()

    @pytest.mark.parametrize("compact", [True, False],
                             ids=["filled_from_the_csr",
                                  "filled_from_a_dense_readback"])
    def test_a_cached_row_and_the_window_it_serves(self, compact):
        node, eng, h, res = self._window(compact, SHAPE_CAPS["mixed"], 2)
        assert node.metrics.val(
            "pipeline.readback.windows.compact" if compact
            else "pipeline.readback.windows.dense") == 2
        cache = eng._match_cache
        with cache._lock:
            rows = list(cache._rows.values())
        assert len(rows) >= 8   # topics of unknown words share a key
        assert max(r[1] for r in rows) >= 2
        _packed(np.stack([r[0] for r in rows]), [r[1] for r in rows])
        # the second window was served under a plan from those rows:
        # what it hands the compact stage is packed too
        assert h.plan is not None and h.plan.n_hit > 0
        _packed(res.matches, res.match_counts)


class TestKnobAndSurfaces:
    def test_config_beats_env_beats_default(self, monkeypatch):
        assert DE.resolve_subscription_covering() is True
        monkeypatch.setenv("EMQX_TPU_COVERING", "0")
        assert DE.resolve_subscription_covering() is False
        assert DE.resolve_subscription_covering(True) is True
        monkeypatch.setenv("EMQX_TPU_COVERING", "off")
        assert DE.resolve_subscription_covering() is False
        monkeypatch.delenv("EMQX_TPU_COVERING")
        assert DE.resolve_subscription_covering(False) is False

    def test_env_routes_engine_and_mesh(self, monkeypatch):
        monkeypatch.setattr(DE, "_ENV_COVERING", False)
        node = Node({})
        assert node.device_engine.subscription_covering is False
        node2 = Node({"broker": {"subscription_covering": True}})
        assert node2.device_engine.subscription_covering is True

    def test_stats_and_ledger_category(self):
        node = Node({"broker": {"subscription_covering": True}})
        s = Sink()
        sid = node.broker.register(s, "c")
        for f in ("s/#", "s/+/t", "s/u/t"):
            node.broker.subscribe(sid, f, {"qos": 0})
        eng = node.device_engine
        eng.shape_cap = 2       # 3 shapes in the full set, 1 root
        eng.rebuild()
        st = eng.stats()
        assert st["subscription_covering"] is True
        assert st["cover_decision"] == "engaged"
        assert node.metrics.val("routing.cover.skipped_builds") == 0
        cov = st["cover"]
        assert cov["roots"] >= 1 and cov["covered"] == 2
        assert cov["reduction"] == pytest.approx(3.0)
        # expansion-CSR buffers ride their own HBM category
        led = node.hbm_ledger
        assert led is not None
        cats = led.section()["categories"]
        assert "cover_csr" in cats
        assert cats["cover_csr"]["live_bytes"] > 0

    def test_off_twin_has_no_cover_state(self):
        node = Node({"broker": {"subscription_covering": False}})
        s = Sink()
        sid = node.broker.register(s, "c")
        for f in ("s/#", "s/+/t"):
            node.broker.subscribe(sid, f, {"qos": 0})
        node.device_engine.rebuild()
        st = node.device_engine.stats()
        assert st["subscription_covering"] is False
        assert st["cover"] is None
        assert st["cover_decision"] == "off"


# ---------------- the engage rule (PR 25) ----------------

class TestEngageRule:
    """Covering engages only where the full set does not fit the
    shape-hash backend (`ops/cover.covering_decision`)."""

    @pytest.mark.parametrize("ns_full,shape_cap,L,want", [
        (3, 32, 6, (False, "fits_shapes")),
        (32, 32, 20, (False, "fits_shapes")),    # at the cap: fits
        (33, 32, 6, (True, "engaged")),          # over the cap: trie
        (1, 0, 2, (True, "engaged")),            # shape_cap 0: trie
        (3, 32, 21, (True, "engaged")),          # too deep for shapes
        (3, 32, 24, (True, "engaged")),
        (3, 32, 25, (False, "too_deep")),        # too deep for the key
    ])
    def test_decision_function(self, ns_full, shape_cap, L, want):
        assert C.covering_decision(ns_full, shape_cap, L) == want

    # one engine per outcome the build reports: (filters, knob,
    # shape_cap) -> cover_decision, cover state?, skipped_builds
    OUTCOMES = {
        "off": (["s/#", "s/+/t", "s/u/t"], False, 2, False, 0),
        "fits_shapes": (["s/#", "s/+/t", "s/u/t"], True, None, False, 1),
        "none_covered": (["a/+/x", "b/y", "c/#"], True, 2, False, 0),
        "engaged": (["s/#", "s/+/t", "s/u/t"], True, 2, True, 0),
    }

    @pytest.mark.parametrize("want", sorted(OUTCOMES))
    def test_build_reports_its_decision(self, want, monkeypatch):
        filters, knob, cap, has_cover, skipped = self.OUTCOMES[want]
        node = Node({"broker": {"subscription_covering": knob}})
        s = Sink()
        sid = node.broker.register(s, "c")
        for f in filters:
            node.broker.subscribe(sid, f, {"qos": 0})
        eng = node.device_engine
        if cap is not None:
            eng.shape_cap = cap
        if want in ("off", "fits_shapes"):
            # decided BEFORE detection: it must not even run
            def boom(*a, **k):
                raise AssertionError("detect_covers ran")
            monkeypatch.setattr(C, "detect_covers", boom)
        eng.rebuild()
        st = eng.stats()
        assert st["cover_decision"] == want
        assert (st["cover"] is not None) is has_cover
        assert node.metrics.val("routing.cover.skipped_builds") == skipped
        # the consume companions are padded only where covering engaged
        assert (len(eng._built.seg_np) > len(filters)) is has_cover

    def test_site_plus_builds_cover_free(self, monkeypatch):
        """The benchmark cell's population at rehearse size: family C
        covers family B, but 3 shapes fit the shape-hash table — the
        snapshot is cover-free, no window runs the expansion, and the
        deliveries equal the covering-off twin's."""
        from benchmark.populations.site_plus import Population
        pop = Population({"sites": 4, "lines": 3, "devs": 4, "meas": 5,
                          "b_meas": 2}, conns=4)
        filters = pop.filters()
        intern = InternTable()
        rows, lens, dollar = _encode(intern, filters)
        covers, inc = C.detect_covers(rows, lens, dollar)
        assert (C.assign_owners(covers, inc) >= 0).sum() == 4 * 3 * 2

        def boom(*a, **k):
            raise AssertionError("detect_covers ran")
        monkeypatch.setattr(C, "detect_covers", boom)
        on, off = _mk_twin_nodes(filters)
        topics = [pop.topic(k) for k in range(4 * 3 * 4 * 5)]
        for rnd in range(2):
            counts = _route_and_compare(on, off, topics, b"p%d" % rnd)
        # fan-out 2, 3 where family B matches too (m < b_meas)
        assert sorted(set(counts)) == [2, 3]
        node = on[0]
        st = node.device_engine.stats()
        assert st["backend"] == "shapes"
        assert st["cover"] is None
        assert st["cover_decision"] == "fits_shapes"
        assert node.metrics.val("routing.device.windows") >= 2
        assert node.metrics.val("pipeline.cover.windows") == 0
        assert node.metrics.val("routing.cover.skipped_builds") >= 1
        # a post-snapshot subscription under a would-be cover rides the
        # delta overlay, as on any cover-free snapshot
        for n2, sinks, _sids in (on, off):
            s = Sink()
            n2.broker.subscribe(n2.broker.register(s, "late"),
                                "site/s0/line/l0/+/m4", {"qos": 0})
            sinks["late"] = s
        _route_and_compare(on, off, topics, b"late")
        assert node.metrics.val("routing.cover.appends") == 0
        assert node.device_engine.stats()["delta_filters"] == 1


# ---------------- workloads generator ----------------

class TestWorkloads:
    def test_cover_ratio_is_detected(self):
        from tools.workloads import cover_heavy_filters
        filters = sorted(set(cover_heavy_filters(400, cover_ratio=0.5)))
        intern = InternTable()
        rows, lens, dollar = _encode(intern, filters)
        covers, inc = C.detect_covers(rows, lens, dollar)
        owner = C.assign_owners(covers, inc)
        frac = (owner >= 0).sum() / len(filters)
        assert frac >= 0.4, frac

    def test_legacy_population_is_cover_free(self):
        from tools.workloads import shape_spread_filters
        filters = shape_spread_filters(300, tail_hash=True)
        intern = InternTable()
        rows, lens, dollar = _encode(intern, filters)
        covers, _inc = C.detect_covers(rows, lens, dollar)
        assert all(len(c) == 0 for c in covers)

    def test_concretize_matches_its_filter(self):
        from tools.workloads import (concretize, cover_heavy_filters,
                                     shape_spread_filters)
        intern = InternTable()
        for f in (cover_heavy_filters(60, cover_ratio=0.5)
                  + shape_spread_filters(20)):
            t = concretize(f)
            trie = HostTrie()
            trie.insert(intern.encode_filter(f.split("/")), 0)
            ids = [intern.lookup(w) for w in t.split("/")]
            assert trie.match(ids, is_dollar=t.startswith("$")) == [0], \
                (f, t)


# ---------------- mesh twins ----------------

@pytest.mark.parametrize("route", [2, 4, 8])
def test_mesh_twin_bit_identical(route):
    filters = (["m/#", "m/+/t"] + [f"m/{i}/t" for i in range(6)]
               + [f"n{i}/+/w" for i in range(4)] + ["$SYS/#", "deep/#"])
    topics = ([f"m/{i}/t" for i in range(6)]
              + ["m/zz/t", "m/q", "n1/a/w", "$SYS/x", "none/x"])
    results = []
    for covering in (True, False):
        node = Node({"broker": {
            "multichip": {"enable": True, "devices": route, "dp": 1,
                          "max_batch": 32},
            "device_min_batch": 1,
            "subscription_covering": covering}})
        sinks = {}
        for i, f in enumerate(filters):
            s = Sink()
            node.broker.subscribe(node.broker.register(s, f"c{i}"), f)
            sinks[f] = s
        eng = node.device_engine
        eng.rebuild()
        counts = []
        for rnd in range(2):
            counts.append(eng.route_batch(
                [mkmsg(t, b"r%d" % rnd) for t in topics], wait=True))
        # churn: covered new sub + removal, served via per-shard rebuild
        s = Sink()
        node.broker.subscribe(node.broker.register(s, "late"), "m/late/t")
        sinks["m/late/t"] = s
        counts.append(eng.route_batch(
            [mkmsg(t, b"c") for t in topics + ["m/late/t"]], wait=True))
        st = eng.stats()
        assert st["subscription_covering"] is covering
        if covering:
            assert st["cover"]["covered"] > 0
        results.append((counts, {f: sinks[f].got for f in sinks}))
    (c_on, got_on), (c_off, got_off) = results
    assert c_on == c_off
    assert got_on == got_off


def test_mesh_serves_a_tenant_wide_umbrella():
    """The mesh build inherits `assign_owners`' rule: the benchmark's
    `tenant_umbrella` population at its rehearsal size over two shards
    (each `org{k}/#` covers ~300 of its shard's filters, past the
    budget the shard's fixed candidate lane leaves: 256 less the match
    row's 64), delivery sets held to the population's closed form and
    to the host's `router.match`, with no lane sent to the host; and
    no shard's owning root can pass that lane by its own segment."""
    from benchmark.populations import tenant_umbrella
    from emqx_tpu.parallel import serving
    pop = tenant_umbrella.Population({"areas": 12, "orgs": 2}, 16)
    node = Node({"broker": {
        "multichip": {"enable": True, "devices": 2, "dp": 1,
                      "max_batch": 64},
        "device_min_batch": 1, "subscription_covering": True}})
    sinks = [Sink() for _ in range(pop.conns)]
    for c, s in enumerate(sinks):
        sid = node.broker.register(s, f"c{c}")
        for f, q in pop.subscriptions(c):
            node.broker.subscribe(sid, f, {"qos": q})
    eng = node.device_engine
    eng.rebuild()
    budget = serving._COVER_CAND_CAP - eng.match_cap
    assert budget == 192
    segs = []
    for b in eng._builts:
        # a shard's cover state as its build left it: detect again and
        # hold the owners to the rule at the budget the build passes
        rows, lens, dollar = _encode(InternTable(), b.fid_filter)
        covers, inc = C.detect_covers(rows, lens, dollar)
        owner = C.assign_owners(covers, inc, own_budget=budget)
        assert b.cover_covered == (owner >= 0).sum() > 0
        assert b.cover_roots == (owner < 0).sum()
        wide = np.flatnonzero(C.fan_in(covers) > budget)
        assert len(wide) >= 1 and not np.isin(owner, wide).any()
        assert all(b.fid_filter[w].count("/") == 1 for w in wide)
        segs.append(1 + int(np.bincount(owner[owner >= 0]).max()))
    # an owning root's segment and a root in every other slot of the
    # match row fit the fixed lane
    assert max(segs) + eng.match_cap - 1 <= serving._COVER_CAND_CAP
    # (a filter's historian may live on the other shard: fewer than
    # the 12 * 49 a single table covers)
    assert 0 < eng.stats()["cover"]["covered"] \
        == sum(b.cover_covered for b in eng._builts) <= 12 * 49
    rng = np.random.default_rng(42)
    keys = rng.integers(0, int(np.prod(pop.dims)), 120)
    msgs = [mkmsg(pop.topic(int(k)), b"%d" % i) for i, k in enumerate(keys)]
    counts = []
    for lo in range(0, len(msgs), 60):
        counts += eng.route_batch(msgs[lo:lo + 60], wait=True)
    want = pop.expect(keys)
    assert counts == (want >= 0).sum(axis=1).tolist()
    owner_of = {f: c for c in range(pop.conns)
                for f, _q in pop.subscriptions(c)}
    for i, m in enumerate(msgs):
        got = sorted((c, f) for c, s in enumerate(sinks)
                     for f, t, p in s.got if p == b"%d" % i)
        assert got == sorted((owner_of[f], f)
                             for f in node.router.match(m.topic)), m.topic
        assert sorted(c for c, _f in got) == sorted(
            int(x) for x in want[i] if x >= 0)
    assert node.metrics.val("routing.device.host_fallback") == 0
