"""The `tenant-umbrella` population through the engine (ISSUE 42): a
tenant-wide `#` over more filters than a root can own.

The benchmark's `tenant_umbrella` population at its rehearsal size (12
areas in 2 orgs: each `org{k}/#` covers 600 filters, past the engine's
budget of 192), installed through `Node` and driven through
`device_engine`'s own stages as `tests/test_umbrella_engine.py` drives
`umbrella_cover`, one case a window kind: a single batch, a fused
window with a padding sub-batch, a match-cache plan, the CSR readback,
all of them. Each is held bit-equal, in delivery sets and per-session
order, to

  (a) its `subscription_covering: false` twin (62 shapes: the trie NFA),
  (b) the host's `router.match` (the `HostTrie`) message by message;

and what the build did with the wide roots (`stats()["cover"]`) and
what the window did (`routing.device.cover_roots`: two roots a topic,
the org's and the area's or the standalone filter's;
`.cover_candidates`, `.cover_overflow`, `.host_fallback`) are held to
the population's closed form.
"""

import numpy as np
import pytest

from benchmark.populations import tenant_umbrella
from tests.test_umbrella_engine import (MODES, _by_the_host, _node,
                                        _serve, _window)

AREAS, ORGS, CONNS = 12, 2, 16


def _pop():
    return tenant_umbrella.Population({"areas": AREAS, "orgs": ORGS},
                                      CONNS)


@pytest.mark.parametrize("mode", list(MODES))
def test_the_window_kinds_equal_the_off_twin_and_the_host(mode):
    conf, subs, distinct = MODES[mode]
    pop = _pop()
    on, on_sinks = _node(True, pop, **conf)
    off, off_sinks = _node(False, pop, **conf)
    rounds = [_window(pop, 42 + r, subs, distinct, first=1000 * r)
              for r in range(2)]
    counted = ("match_lanes", "cover_roots", "cover_candidates")
    for r, (lives, keys) in enumerate(rounds):
        before = {k: on.metrics.val(f"routing.device.{k}") for k in counted}
        h_on, n_on = _serve(on, lives)
        h_off, n_off = _serve(off, lives)
        if r == 0:
            st = on.device_engine.stats()
            assert (st["cover_decision"], st["backend"]) \
                == ("engaged", "shapes")
            # the two org umbrellas are wide and own nothing; every
            # historian below them is a root again and owns its 49; the
            # standalone filters, which only an org's umbrella covers,
            # are roots
            assert st["cover"] == {
                "roots": ORGS + AREAS * 51, "covered": AREAS * 49,
                "appends": 0, "incomplete": 0, "wide_roots": ORGS,
                "largest_segment": 50, "cand_cap": 64,
                "reduction": round((ORGS + AREAS * 100)
                                   / (ORGS + AREAS * 51), 2)}
            assert on.metrics.val("routing.cover.wide_roots") == ORGS
            assert off.device_engine.stats()["backend"] == "trie" \
                and off.device_engine.stats()["cover"] is None
            ct = on.device_engine._tables.shapes.cover
            # the org root's segment is itself alone, a historian's 50
            fid_of = on.device_engine._built.fid_of
            seg = np.diff(np.asarray(ct.exp_start))
            assert all(seg[fid_of[f"org{k}/#"]] == 1 for k in range(ORGS))
            assert sorted(set(seg[:len(pop.filters())].tolist())) \
                == [0, 1, 50]
        assert n_on == n_off
        assert n_on == (pop.expect(keys) >= 0).sum(axis=1).tolist()
        assert set(n_on) == {2, 3}
        real = sum(map(len, lives))
        for h in (h_on, h_off):
            assert (np.asarray(h.res.overflow).shape[0] > subs) \
                == (subs > 1)
            if "topic_dedup" not in conf or conf["topic_dedup"]:
                assert h.plan is not None and h.plan.n_miss < real * 0.6
            else:
                assert h.plan is None
            assert (h.cres is not None) \
                == conf.get("compact_readback", True)
        moved = {k: on.metrics.val(f"routing.device.{k}") - before[k]
                 for k in counted}
        assert moved["match_lanes"] == (
            h_on.plan.n_miss if h_on.plan is not None else real)
        # two roots a topic whatever its slot: the org's umbrella, and
        # the area's historian or one standalone filter
        assert moved["cover_roots"] == 2 * moved["match_lanes"]
        if h_on.plan is None:
            # the candidates: the org root's own entry, and a
            # historian's 50 (slots 0-5) or a standalone filter's 1
            slot = np.unravel_index(keys, pop.dims)[1]
            assert moved["cover_candidates"] \
                == int(np.where(slot < 6, 51, 2).sum())
        else:
            assert 2 * moved["match_lanes"] <= moved["cover_candidates"] \
                <= 51 * moved["match_lanes"]
        assert h_off.res.cover_roots is None
        assert np.asarray(h_on.res.cover_roots).shape \
            == np.asarray(h_on.res.cover_candidates).shape \
            == np.asarray(h_on.res.overflow).shape[:1]
    for node in (on, off):
        assert node.metrics.val("routing.device.host_fallback") == 0
        assert node.metrics.val("routing.device.cover_overflow") == 0
    assert on.metrics.val("routing.device.nfa_lanes") == 0
    assert on.metrics.val("pipeline.cover.windows") == len(rounds)
    assert off.metrics.val("routing.device.cover_roots") == 0
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
        assert any(f.count("/") == 1 and f.endswith("/#")
                   for _c, f in want)            # its org's umbrella
    assert not got


def test_the_parents_rule_sent_every_topic_to_the_host():
    """What `assign_owners` did with this set before PR 42, held here
    so that the reason for the rule stays checkable: each `org{k}/#`
    owned the first 256 filters of its tenant by fid, no historian
    owned anything, and its segment of 257 passed the candidate
    plane's ceiling of 256 on every topic."""
    from emqx_tpu.ops import cover as C
    from tests.test_cover import _detected, _parent_assign_owners
    pop = _pop()
    filters = pop.filters()
    _rows, _lens, covers, inc = _detected(filters)
    old = _parent_assign_owners(covers, inc, own_budget=256)
    orgs = [len(filters) - ORGS + k for k in range(ORGS)]
    assert sorted(np.unique(old[old >= 0]).tolist()) == orgs
    assert all((old == o).sum() == 256 for o in orgs)
    assert 1 + np.bincount(old[old >= 0]).max() == 257 > 256
    new = C.assign_owners(covers, inc, own_budget=192)
    assert not np.isin(new, orgs).any()
    assert (np.bincount(new[new >= 0], minlength=1).max(), (new >= 0).sum()) \
        == (49, AREAS * 49)
