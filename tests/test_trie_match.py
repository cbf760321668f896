"""Trie/NFA equivalence tests.

Oracle chain (mirrors reference emqx_trie tests, where emqx_topic:match/2 is
the oracle for emqx_trie:match/1): brute-force `topic.match` over all filters
== HostTrie.match == device match_batch, over randomized filter/topic sets.
"""

import random

import numpy as np
import pytest

from emqx_tpu.ops import intern as I
from emqx_tpu.ops.match import encode_topics, match_batch
from emqx_tpu.ops.trie import HostTrie, build_tables
from emqx_tpu.utils import topic as T

WORDS = ["a", "b", "c", "dev", "x1", "$sys", ""]


def rand_filter(rng, max_levels=6):
    n = rng.randint(1, max_levels)
    ws = []
    for i in range(n):
        r = rng.random()
        if r < 0.2:
            ws.append("+")
        elif r < 0.3 and i == n - 1:
            ws.append("#")
        else:
            ws.append(rng.choice(WORDS))
    return "/".join(ws)


def rand_topic(rng, max_levels=6):
    n = rng.randint(1, max_levels)
    return "/".join(rng.choice(WORDS) for _ in range(n))


def brute_force(topic, filters):
    return sorted(fid for fid, f in enumerate(filters) if T.match(topic, f))


class Fixture:
    """Interns a filter list, builds HostTrie + TrieTables."""

    def __init__(self, filters, max_levels=8):
        self.filters = filters
        self.intern = I.InternTable()
        self.host = HostTrie()
        self.max_levels = max_levels
        rows = np.zeros((len(filters), max_levels), np.int32)
        lens = np.zeros(len(filters), np.int64)
        for fid, f in enumerate(filters):
            wids = self.intern.encode_filter(T.words(f))
            assert len(wids) <= max_levels
            self.host.insert(wids, fid)
            rows[fid, :len(wids)] = wids
            lens[fid] = len(wids)
        self.tables = build_tables(rows, lens)

    def host_match(self, topic):
        ws = T.words(topic)
        return sorted(self.host.match(
            self.intern.encode_topic(ws), is_dollar=ws[0].startswith("$")))

    def device_match(self, topics, **caps):
        tw = [T.words(t) for t in topics]
        enc, lens, dollar, too_long = encode_topics(self.intern, tw, self.max_levels)
        assert not too_long.any()
        res = match_batch(self.tables, enc, lens, dollar, **caps)
        out = []
        for i in range(len(topics)):
            assert not bool(res.overflow[i]), f"overflow on {topics[i]}"
            out.append(sorted(int(x) for x in res.matches[i][:int(res.counts[i])]))
        return out


BASIC_FILTERS = [
    "a/b/c",        # 0 exact
    "a/+/c",        # 1
    "a/#",          # 2
    "#",            # 3
    "+/+/+",        # 4
    "+",            # 5
    "a",            # 6
    "$sys/#",       # 7
    "$sys/+",       # 8
    "a/b/#",        # 9
    "+/b/c",        # 10
    "a/b",          # 11
    "/+",           # 12
    "+/a",          # 13
]


class TestHostTrie:
    @pytest.fixture(scope="class")
    def fx(self):
        return Fixture(BASIC_FILTERS)

    @pytest.mark.parametrize("topic", [
        "a/b/c", "a", "a/b", "x", "/a", "/x", "$sys", "$sys/a", "$sys/a/b",
        "a/x/c", "a/b/c/d", "", "x/y/z", "x/a",
    ])
    def test_matches_brute_force(self, fx, topic):
        assert fx.host_match(topic) == brute_force(topic, BASIC_FILTERS)

    def test_delete(self):
        fx = Fixture(["a/+", "a/b"])
        wids = fx.intern.encode_filter(["a", "+"])
        fx.host.delete(wids)
        assert fx.host_match("a/b") == [1]
        fx.host.delete(fx.intern.encode_filter(["a", "b"]))
        assert fx.host_match("a/b") == []
        assert fx.host.is_empty()

    def test_delete_keeps_shared_prefix(self):
        fx = Fixture(["a/b/c", "a/b"])
        fx.host.delete(fx.intern.encode_filter(["a", "b"]))
        assert fx.host_match("a/b/c") == [0]
        assert fx.host_match("a/b") == []


class TestDeviceMatch:
    @pytest.fixture(scope="class")
    def fx(self):
        return Fixture(BASIC_FILTERS)

    @pytest.mark.parametrize("topic", [
        "a/b/c", "a", "a/b", "x", "/a", "/x", "$sys", "$sys/a", "$sys/a/b",
        "a/x/c", "a/b/c/d", "", "x/y/z", "x/a", "unseen/words/here",
    ])
    def test_matches_brute_force(self, fx, topic):
        got = fx.device_match([topic])[0]
        assert got == brute_force(topic, BASIC_FILTERS), topic

    def test_batch(self, fx):
        topics = ["a/b/c", "x", "$sys/a", "a", "/a"]
        got = fx.device_match(topics)
        assert got == [brute_force(t, BASIC_FILTERS) for t in topics]

    def test_batch_padding_rows(self, fx):
        # lens == 0 rows must produce nothing (not even '#')
        enc = np.zeros((3, fx.max_levels), np.int32)
        lens = np.zeros(3, np.int32)
        dollar = np.zeros(3, bool)
        res = match_batch(fx.tables, enc, lens, dollar)
        assert int(res.counts.sum()) == 0
        assert not bool(res.overflow.any())

    def test_empty_trie(self):
        fx = Fixture([])
        assert fx.device_match(["a/b"]) == [[]]

    def test_match_cap_overflow_flag(self):
        filters = [f"a/{i}/#"[:-2] + "#" for i in range(8)]  # a/i/#
        filters += ["a/+/+", "#", "a/#"]
        fx = Fixture(filters)
        tw = [T.words("a/3/z")]
        enc, lens, dollar, _ = encode_topics(fx.intern, tw, fx.max_levels)
        res = match_batch(fx.tables, enc, lens, dollar, match_cap=2)
        assert bool(res.overflow[0])


class TestRandomizedEquivalence:
    @pytest.mark.parametrize("seed", [7, 21, 42, 1001])
    def test_random_sets(self, seed):
        rng = random.Random(seed)
        filters = sorted({rand_filter(rng) for _ in range(rng.randint(5, 120))})
        fx = Fixture(filters)
        topics = [rand_topic(rng) for _ in range(64)]
        want = [brute_force(t, filters) for t in topics]
        assert [fx.host_match(t) for t in topics] == want
        got = fx.device_match(topics, frontier_cap=32, match_cap=128)
        assert got == want

    def test_deep_topics(self):
        rng = random.Random(5)
        filters = ["+/+/+/+/+/+/+/+", "a/#", "a/a/a/a/a/a/a/a", "#",
                   "a/+/a/+/a/+/a/+"]
        fx = Fixture(filters)
        topics = ["/".join(rng.choice(["a", "b"]) for _ in range(8))
                  for _ in range(32)]
        got = fx.device_match(topics, frontier_cap=32)
        assert got == [brute_force(t, filters) for t in topics]

    def test_bench_shape_filters(self):
        # the reference bench shape: device/{{id}}/+/{{num}}/# (broker_bench.erl:25-34)
        filters = [f"device/{i}/+/{n}/#" for i in range(8) for n in range(16)]
        fx = Fixture(filters)
        topics = [f"device/{i}/x/{n}/tail" for i in range(8) for n in range(16)]
        got = fx.device_match(topics)
        assert got == [brute_force(t, filters) for t in topics]


# ---- ISSUE 29: a level step as wide as the live frontier ----------------

def _plus_family(root, depth):
    """Every filter `root/x1/../xdepth` with x_i a literal or '+': a
    topic under `root` has 2**i live paths after i more levels."""
    lits = "abcdefgh"
    return ["/".join([root] + [lits[i] if (m >> i) & 1 else "+"
                               for i in range(depth)])
            for m in range(1 << depth)]


# `w/a/b/c/x/y/z`: 1, 1, 2, 4 live paths into levels 0-3 (narrow), 8 into
# level 4 (the one wide step), then the one filter with a tail (narrow)
PLUS_HEAVY = _plus_family("w", 3) + ["w/a/b/c/x/y/z", "w/+/b/c/x/#"]


def _walk_case(name):
    """(filters, topics, caps) of one case of the adaptive walk."""
    if name.startswith("random"):
        rng = random.Random(int(name[6:]))
        filters = sorted({rand_filter(rng)
                          for _ in range(rng.randint(5, 120))})
        return filters, [rand_topic(rng) for _ in range(64)], {}
    if name == "deep":
        rng = random.Random(5)
        return (["+/+/+/+/+/+/+/+", "a/#", "a/a/a/a/a/a/a/a", "#",
                 "a/+/a/+/a/+/a/+"],
                ["/".join(rng.choice(["a", "b"]) for _ in range(8))
                 for _ in range(32)], {"frontier_cap": 32})
    if name == "bench_shape":
        return ([f"device/{i}/+/{n}/#" for i in range(8)
                 for n in range(16)],
                [f"device/{i}/x/{n}/tail" for i in range(8)
                 for n in range(16)], {})
    topics = ["w/a/b/c/x/y/z", "w/a/b", "w/q/b/c/x/y", "a/b/c", "$sys/w"]
    caps = {"plus_heavy": {},
            "plus_heavy_cover_caps": {"frontier_cap": 32, "match_cap": 128},
            "frontier_cap4": {"frontier_cap": 4},
            "frontier_cap1_match_cap1": {"frontier_cap": 1, "match_cap": 1},
            "frontier_cap8_match_cap2": {"frontier_cap": 8, "match_cap": 2},
            }[name]
    return PLUS_HEAVY + ["a/b/c", "a/#", "$sys/#"], topics, caps


@pytest.mark.parametrize("name", [
    "random7", "random21", "random42", "random1001", "deep", "bench_shape",
    "plus_heavy", "plus_heavy_cover_caps", "frontier_cap4",
    "frontier_cap1_match_cap1", "frontier_cap8_match_cap2"])
def test_adaptive_walk_is_the_wide_walk_bit_for_bit(name):
    """The walk that narrows its step to the live frontier returns the
    walk at `frontier_cap`'s `matches` (order included), `counts` and
    `overflow`, with two padding rows in the batch; and it counts the
    steps it ran wide."""
    filters, topics, caps = _walk_case(name)
    fx = Fixture(filters)
    L = fx.max_levels
    enc, lens, dollar, too_long = encode_topics(
        fx.intern, [T.words(t) for t in topics], L)
    assert not too_long.any()
    enc = np.concatenate([enc, np.full((2, L), I.PAD, np.int32)])
    lens = np.concatenate([lens, np.zeros(2, np.int32)])
    dollar = np.concatenate([dollar, np.zeros(2, bool)])
    got = match_batch(fx.tables, enc, lens, dollar, **caps)
    want = match_batch(fx.tables, enc, lens, dollar, _rungs=(), **caps)
    for a, b in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(want.wide_steps) == L + 1
    assert int(got.counts[-2:].sum()) == 0 and not got.overflow[-2:].any()
    F = caps.get("frontier_cap", 16)
    wide = int(got.wide_steps)
    if F < 4:                       # no rung below the cap: all wide
        assert wide == L + 1
    elif name == "bench_shape":     # two live paths at most
        assert wide == 0
    elif name.startswith("plus_heavy"):
        assert wide == 1            # 8 live paths into level 4 alone
    if name.startswith(("random", "plus_heavy")) and not got.overflow.any():
        for i, t in enumerate(topics):
            assert sorted(int(x) for x in got.matches[i][:int(got.counts[i])]) \
                == brute_force(t, filters)


def test_detect_covers_is_what_the_wide_walk_detects(monkeypatch):
    import functools

    from emqx_tpu.ops import cover as C
    from emqx_tpu.ops import match as M
    from tools.workloads import cover_heavy_filters
    filters = sorted(set(cover_heavy_filters(300, cover_ratio=0.5)))
    intern = I.InternTable()
    rows = np.zeros((len(filters), 16), np.int32)
    lens = np.zeros(len(filters), np.int64)
    for fid, f in enumerate(filters):
        w = intern.encode_filter(T.words(f))
        rows[fid, :len(w)] = w
        lens[fid] = len(w)
    dollar = np.array([f.startswith("$") for f in filters])
    got, got_inc = C.detect_covers(rows, lens, dollar, batch=512)
    monkeypatch.setattr(M, "match_batch",
                        functools.partial(M.match_batch, _rungs=()))
    want, want_inc = C.detect_covers(rows, lens, dollar, batch=512)
    assert (got_inc == want_inc).all() and sum(map(len, want)) > 100
    for a, b in zip(got, want):
        assert list(a) == list(b)
