"""The `umbrella-cover` population through the engine (ISSUE 38): the
first deployment whose served snapshot has covering engaged.

The benchmark's `umbrella_cover` population at a small size, installed
through `Node` and driven through `device_engine`'s own stages
(`prepare_window` .. `finish_sub`, as the batcher makes them) on seeded
topics, one case a window kind: a single batch, a fused window (W > 1,
one sub-batch of padding), a match-cache plan, the CSR readback. Each
is held bit-equal, in delivery sets and per-session order, to

  (a) its `subscription_covering: false` twin, which matches the same
      61 shapes by the trie NFA, and
  (b) the host's `router.match` (the `HostTrie`) message by message;

and the expansion's counters (`routing.device.match_lanes`,
`.cover_candidates`, `.cover_overflow`) are held to what the window
did. One case puts a second owning root across an umbrella, so that
the topics under both pass the candidate plane: the overflow counter
moves and the host serves the lane. A cover-free snapshot's program reports what the parent's did.
"""

import numpy as np
import pytest

from benchmark.populations import umbrella_cover
from emqx_tpu.broker.message import make
from emqx_tpu.broker.node import Node

AREAS, CONNS = 7, 16        # every prefix depth once and one twice
MODES = {
    # broker settings, sub-batches, distinct keys a sub-batch
    "plain": ({"topic_dedup": False, "compact_readback": False}, 1, 96),
    "fused": ({"topic_dedup": False, "compact_readback": False}, 3, 96),
    "plan": ({"topic_dedup": True, "compact_readback": False}, 1, 24),
    "compact": ({"topic_dedup": False, "compact_readback": True}, 2, 96),
    "all": ({}, 3, 24),
}


class Sink:
    def __init__(self):
        self.got = []

    def deliver(self, topic_filter, msg):
        self.got.append((topic_filter, msg.topic, bytes(msg.payload)))
        return True


def _node(covering, pop, **broker):
    node = Node({"broker": dict(broker, subscription_covering=covering)})
    sinks = [Sink() for _ in range(pop.conns)]
    for c, sink in enumerate(sinks):
        sid = node.broker.register(sink, f"c{c}")
        for f, qos in pop.subscriptions(c):
            node.broker.subscribe(sid, f, {"qos": qos})
    return node, sinks


def _window(pop, seed, subs, distinct, per_sub=96, first=0):
    """`subs` sub-batches of `per_sub` PUBLISHes over `distinct` seeded
    keys each, and the keys sent; the payload numbers the message, from
    `first`."""
    rng = np.random.default_rng(seed)
    lives, sent, n = [], [], first
    for _k in range(subs):
        keys = rng.choice(rng.integers(0, int(np.prod(pop.dims)), distinct),
                          per_sub)
        lives.append([make("pub", 0, pop.topic(int(k)), b"%d" % (n + i))
                      for i, k in enumerate(keys)])
        sent.append(keys)
        n += per_sub
    return lives, np.concatenate(sent)


def _serve(node, lives):
    """One window through the engine's stages; (handle, counts)."""
    eng = node.device_engine
    h = eng.prepare_window(lives, gate_cold=False)
    assert h is not None
    eng.dispatch(h)
    eng.materialize(h)
    counts = []
    for k in range(len(lives)):
        counts += eng.finish_sub(h, k, defer=False)
    return h, counts


def _by_the_host(node, pop, lives):
    """What `router.match` says each connection gets, in message order:
    [conn] -> [(topic, payload)], and per message the (conn, filter)
    pairs."""
    owner = {f: c for c in range(pop.conns)
             for f, _q in pop.subscriptions(c)}
    seqs = [[] for _ in range(pop.conns)]
    sets = []
    for msgs in lives:
        for m in msgs:
            hit = node.router.match(m.topic)
            sets.append(sorted((owner[f], f) for f in hit))
            for c, _f in sets[-1]:
                seqs[c].append((m.topic, bytes(m.payload)))
    return seqs, sets


@pytest.mark.parametrize("mode", list(MODES))
def test_the_window_kinds_equal_the_off_twin_and_the_host(mode):
    conf, subs, distinct = MODES[mode]
    pop = umbrella_cover.Population({"areas": AREAS}, CONNS)
    on, on_sinks = _node(True, pop, **conf)
    off, off_sinks = _node(False, pop, **conf)
    rounds = [_window(pop, 36 + r, subs, distinct, first=1000 * r)
              for r in range(2)]
    for r, (lives, keys) in enumerate(rounds):
        moved = {k: on.metrics.val(f"routing.device.{k}") for k in (
            "match_lanes", "cover_candidates")}
        h_on, n_on = _serve(on, lives)
        h_off, n_off = _serve(off, lives)
        if r == 0:
            st = on.device_engine.stats()
            assert (st["cover_decision"], st["backend"]) \
                == ("engaged", "shapes")
            assert st["cover"]["roots"] == AREAS * 51 \
                and st["cover"]["covered"] == AREAS * 49 \
                and st["cover"]["incomplete"] == 0
            assert off.device_engine.stats()["backend"] == "trie" \
                and off.device_engine.stats()["cover"] is None
            # room for an umbrella's 50 beside a root of its own in the
            # other 12 of the roots' 13 shapes: 62 -> 64, not the 256
            # the engine allows
            ct = on.device_engine._tables.shapes.cover
            assert ct.cand_pad.shape[0] == 64 \
                < on.device_engine.cover_cand_cap == 256
            assert ct.out_pad.shape[0] == 64 and ct.app_root.shape[0] == 64
        assert n_on == n_off
        assert n_on == (pop.expect(keys) >= 0).sum(axis=1).tolist()
        # the window kind the case is about, on both twins
        real = sum(map(len, lives))
        for h in (h_on, h_off):
            # a fused window is padded to its class's W
            assert (np.asarray(h.res.overflow).shape[0] > subs) \
                == (subs > 1)
            if "topic_dedup" not in conf or conf["topic_dedup"]:
                assert h.plan is not None and h.plan.n_miss < real * 0.6
            else:
                assert h.plan is None
            assert (h.cres is not None) \
                == conf.get("compact_readback", True)
        # the expansion's counters: the lanes the match stage took and
        # the candidates it verified for them (a root's own entry, and
        # an umbrella's 49 filters: 1 to 50 a lane)
        lanes = on.metrics.val("routing.device.match_lanes") \
            - moved["match_lanes"]
        cands = on.metrics.val("routing.device.cover_candidates") \
            - moved["cover_candidates"]
        assert lanes == (h_on.plan.n_miss if h_on.plan is not None
                         else real)
        assert lanes <= cands <= 50 * lanes and cands > 10 * lanes
        assert h_off.res.cover_candidates is None \
            and h_off.res.cover_overflow is None
        assert np.asarray(h_on.res.cover_candidates).shape \
            == np.asarray(h_on.res.overflow).shape[:1]
    for node in (on, off):
        assert node.metrics.val("routing.device.host_fallback") == 0
        assert node.metrics.val("routing.device.cover_overflow") == 0
    assert off.metrics.val("routing.device.match_lanes") \
        == off.metrics.val("routing.device.nfa_lanes") > 0
    assert on.metrics.val("routing.device.nfa_lanes") == 0
    assert on.metrics.val("pipeline.cover.windows") == len(rounds)
    # (a) the off twin, delivery by delivery and in order
    for a, b in zip(on_sinks, off_sinks):
        assert a.got == b.got
    # (b) the host trie: the sets message by message, the order a session
    lives = [msgs for w, _keys in rounds for msgs in w]
    seqs, sets = _by_the_host(on, pop, lives)
    for c, sink in enumerate(on_sinks):
        assert [(t, p) for _f, t, p in sink.got] == seqs[c]
    got = {}
    for c, sink in enumerate(on_sinks):
        for f, _t, p in sink.got:
            got.setdefault(p, []).append((c, f))
    for m, want in zip((m for msgs in lives for m in msgs), sets):
        assert sorted(got.pop(bytes(m.payload), [])) == want, m.topic
    assert not got


def test_candidates_past_cand_cap_go_to_the_host_and_are_counted():
    """An umbrella's segment holds 50 filters and the build gives the
    plane 64 lanes. A second owning root across it (`+/area0/v5/#`
    over 40 filters of other tenants: neither covers the other) puts
    91 candidates on the topics under both: those lanes flag the
    expansion's own overflow, the host route serves them (same
    deliveries as the twin), and `routing.device.cover_overflow`
    counts exactly them. One root's own segment cannot do that any
    more: a root whose covered set the plane cannot hold owns nothing
    (`ops/cover.assign_owners`, tests/test_cover.py)."""
    pop = umbrella_cover.Population({"areas": AREAS}, CONNS)
    conf = {"topic_dedup": False}
    across = ["+/area0/v5/#"] + [f"w{i}/area0/v5/k{i}" for i in range(40)]
    twins = []
    for covering in (True, False):
        node, sinks = _node(covering, pop, **conf)
        sinks.append(Sink())
        sid = node.broker.register(sinks[-1], "across")
        for f in across:
            node.broker.subscribe(sid, f, {"qos": 0})
        twins.append((node, sinks))
    (on, on_sinks), (off, off_sinks) = twins
    lives, _keys = _window(pop, 41, 2, 96)
    both = pop.topic(5)
    assert both == "org0/area0/v5/c5"
    lives[0] += [make("pub", 0, both, b"x%d" % i) for i in range(5)]
    h_on, n_on = _serve(on, lives)
    _h, n_off = _serve(off, lives)
    assert int(h_on.res.matches.shape[-1]) == 64
    st = on.device_engine.stats()
    assert st["cover"]["cand_cap"] == 64 < on.device_engine.cover_cand_cap
    assert st["cover"]["largest_segment"] == 50 \
        and st["cover"]["wide_roots"] == 0 \
        and st["cover"]["covered"] == AREAS * 49 + 40
    under = sum(m.topic.startswith("org0/area0/v5/")
                for msgs in lives for m in msgs)
    assert 5 <= under < 20
    m = on.metrics
    assert m.val("routing.device.cover_overflow") == under \
        == m.val("routing.device.host_fallback")
    assert m.val("routing.device.match_overflow") == 0 \
        and m.val("routing.device.fanout_overflow") == 0
    assert st["cover_overflow"] == under
    assert off.metrics.val("routing.device.host_fallback") == 0
    assert n_on == n_off
    for a, b in zip(on_sinks, off_sinks):
        assert sorted(a.got) == sorted(b.got)
        # per topic the order is the publisher's, host-served or not
        for topic in {t for _f, t, _p in a.got}:
            assert [p for _f, t, p in a.got if t == topic] \
                == [p for _f, t, p in b.got if t == topic]
    assert len(on_sinks[-1].got) == under


@pytest.mark.parametrize("backend", ["shapes", "trie"])
def test_a_cover_free_program_reports_what_the_parent_did(backend):
    """Covering adds two fields to `RouteResult`; a snapshot without
    cover state leaves them None, so its programs' outputs are the
    parent's: the planes below and nothing else."""
    pop = umbrella_cover.Population({"areas": AREAS}, CONNS)
    if backend == "trie":
        node, _sinks = _node(False, pop, topic_dedup=False)
    else:
        node = Node({"broker": {"topic_dedup": False}})
        sid = node.broker.register(Sink(), "c0")
        for f in ("a/+/c", "a/b/#", "a/b/c"):   # 3 shapes: the table's
            node.broker.subscribe(sid, f, {"qos": 0})
    h, _n = _serve(node, [[make("pub", 0, "a/b/c", b"0"),
                           make("pub", 0, pop.topic(5), b"1")]])
    st = node.device_engine.stats()
    assert st["backend"] == backend and st["cover"] is None
    reported = {f for f, v in h.res._asdict().items() if v is not None}
    parent = {"matches", "match_counts", "rows", "opts", "fan_counts",
              "shared_sids", "shared_rows", "shared_opts", "overflow",
              "new_cursors", "occur", "match_overflow", "fanout_overflow",
              "compact"}
    assert reported == parent | ({"nfa_wide_steps"}
                                 if backend == "trie" else set())
    assert node.metrics.val("routing.device.match_lanes") == 2
    assert node.metrics.val("routing.device.cover_candidates") == 0
    assert node.metrics.val("pipeline.cover.windows") == 0
