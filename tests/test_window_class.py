"""One window program, one window class (ISSUE 30): a class the engine
warmed through its zero-filled-argument path serves its first live
window from the jit cache.

The jit cache keeps numpy and device arguments apart, so the dummies of
`DeviceRouteEngine._window_call` have to be what a live dispatch hands
`route_window`, argument by argument: the property `inpath_executables`
measures on the chip, held here for every stage the program can run.
"""

import asyncio
import functools

import pytest

from emqx_tpu.broker.message import make
from emqx_tpu.broker.node import Node

# caps no other test builds an engine with: every class below is first
# compiled by this file's own warm pass, whatever ran in the process
# before it
FANOUT_CAP, SLOT_CAP = 24, 6
STAGES = {"plain": (), "plan": ("Bm",), "delta": ("dC",),
          "compact": ("P",), "plan_delta_compact": ("Bm", "dC", "P")}


class Sink:
    def deliver(self, topic_filter, msg):
        return True


def _node(on) -> Node:
    node = Node({"broker": {
        "device_fanout_cap": FANOUT_CAP, "device_slot_cap": SLOT_CAP,
        "topic_dedup": "Bm" in on, "delta_overlay": "dC" in on,
        "compact_readback": "P" in on}})
    b = node.broker
    for i in range(6):
        b.subscribe(b.register(Sink(), f"c{i}"), f"wc/{i}/+", {"qos": 0})
    for m in range(2):
        b.subscribe(b.register(Sink(), f"g{m}"), "$share/g/wc/0/+",
                    {"qos": 1})
    return node


@pytest.mark.parametrize("stages", list(STAGES))
def test_a_warmed_class_serves_its_first_live_window_from_the_cache(stages):
    from emqx_tpu.models import router_engine as RE
    on = STAGES[stages]
    node = _node(on)
    eng = node.device_engine
    eng.rebuild()
    if "dC" in on:
        # a filter younger than the snapshot: the overlay's one row
        node.broker.subscribe(node.broker.register(Sink(), "late"),
                              "wc/late/#", {"qos": 0})
    # 100 PUBLISHes of 7 topics: batch class 256, miss class 64
    msgs = [make("p", 0, f"wc/{i % 6}/x" if i % 7 else "wc/late/x", b"")
            for i in range(100)]

    async def go():
        """The serving path's own sequence: a gated window registers the
        classes its stages want, the background warm brings them online,
        the next window takes the stage; until one has them all."""
        for _round in range(8):
            h = eng.prepare(msgs, gate_cold=True)
            c = eng._class_of(1, 256, h.plan, h.delta, h.pcap)
            if all(getattr(c, f) is not None for f in on) \
                    and c in eng._warm_classes:
                return h, c
            eng.abandon(h)
            eng._kick_class_warm()
            assert eng._fuse_warm_task is not None
            await eng._fuse_warm_task
        raise AssertionError(f"class never came warm: {c}")

    loop = asyncio.new_event_loop()
    try:
        h, c = loop.run_until_complete(asyncio.wait_for(go(), 300))
    finally:
        loop.close()
    assert c[3:] == (64 if "Bm" in on else None, 16 if "dC" in on else None,
                     32 * 256 if "P" in on else None)
    by_shape = node.pipeline_telemetry.snapshot()["compiles"]["by_shape"]
    label = {"plain": "warm W1xB256", "plan": "warm W1xB256mB64",
             "delta": "warm W1xB256d16", "compact": "warm W1xB256c8192",
             "plan_delta_compact": "warm W1xB256mB64d16c8192"}[stages]
    assert by_shape[label]["executables"] >= 1, sorted(by_shape)
    assert node.metrics.val("routing.device.warm_failed") == 0
    cold = {s: node.metrics.val(f"routing.device.cold_{s}_class")
            for s in ("cached", "delta", "compact")}
    assert {s for s, n in cold.items() if n} == {
        {"Bm": "cached", "dC": "delta", "P": "compact"}[f] for f in on}

    before = RE.route_window._cache_size()
    eng.dispatch(h)
    assert RE.route_window._cache_size() == before, \
        f"the first live window of {c.label} compiled in the dispatch path"
    assert (h.plan is not None, h.dres is not None, h.cres is not None) \
        == ("Bm" in on, "dC" in on, "P" in on)
    assert (h.dcres is not None) == ("dC" in on and "P" in on)
    eng.materialize(h)
    counts = eng.finish(h)
    assert counts == [("dC" in on) if m.topic == "wc/late/x"
                      else 1 + (m.topic == "wc/0/x") for m in msgs]
    assert eng._outstanding == 0
    assert not [k for k in node.pipeline_telemetry.snapshot()[
        "compiles"]["by_shape"] if k.startswith("dispatch")]


# ---------- ISSUE 39: a window costs what it holds ----------
#
# A fused window is padded to its class's W, and since ISSUE 39 the
# scan step of a padding sub-batch is skipped under one `lax.cond`
# (`router_engine._window_scan`) and hole-closing runs only over a
# shape-hash snapshot without cover state (`_match_holes`). Neither may
# show in what a window returns: held here plane for plane against the
# step programs, on the three kinds of snapshot the served path builds.

W8, B16 = 8, 16
ORACLE_FILTERS = ["s/#", "s/+/t", "s/u/t", "s/u/v", "s/a/t", "q/1", "q/2",
                  "w/+", "w/x"]
ORACLE_TOPICS = ["s/u/t", "s/u/v", "s/q", "s/a/t", "q/1", "w/x",
                 "nomatch/z", "late/x", "s/u/t", "q/2", "w/y"]
# how each kind of snapshot comes about (tests/test_cover.py): the full
# set's 5 shapes fit the default table; a `shape_cap` of 0 sends it to
# the trie; at 4 the full set overflows and the 3 root shapes fit, so
# covering engages over a shape-hash table
ORACLE_BACKENDS = {"shapes": (None, True), "trie": (0, False),
                   "cover": (4, True)}


@functools.lru_cache(maxsize=None)
def _oracle_fixture(backend):
    """The tables the engine builds for `backend`, a delta overlay of
    one younger filter, and a W8 x B16 window of topics, every
    sub-batch full."""
    import numpy as np

    from emqx_tpu.ops.delta import build_delta_tables
    from emqx_tpu.ops.match import encode_topics_str
    shape_cap, covering = ORACLE_BACKENDS[backend]
    node = Node({"broker": {"subscription_covering": covering,
                            "device_fanout_cap": FANOUT_CAP,
                            "device_slot_cap": SLOT_CAP}})
    eng = node.device_engine
    if shape_cap is not None:
        eng.shape_cap = shape_cap
    b = node.broker
    for i, f in enumerate(ORACLE_FILTERS):
        b.subscribe(b.register(Sink(), f"o{i}"), f, {"qos": 0})
    for m in range(3):      # a group of three: the cursors must thread
        b.subscribe(b.register(Sink(), f"og{m}"), "$share/g/s/u/t",
                    {"qos": 1})
    eng.rebuild()
    st = eng.stats()
    assert (st["backend"], st["cover"] is not None) == {
        "shapes": ("shapes", False), "trie": ("trie", False),
        "cover": ("shapes", True)}[backend]
    L = eng.max_levels
    rng = np.random.RandomState(39)
    names = [ORACLE_TOPICS[i] for i in rng.randint(
        len(ORACLE_TOPICS), size=W8 * B16)]
    enc, lens, dol, too_long = encode_topics_str(eng.intern, names, L)
    assert not too_long.any()
    delta = build_delta_tables(
        [(eng.intern.encode_filter(["late", "+"]), 900, [(7, 1), (8, 0)])],
        row_cap=8, level_cap=L)
    return {
        "tables": eng._tables, "cur": np.asarray(eng._cursors),
        "kw": eng._caps_kw(st["backend"]), "trie": st["backend"] == "trie",
        "cover": st["cover"] is not None, "delta": delta,
        "enc": enc.reshape(W8, B16, L), "lens": lens.reshape(W8, B16),
        "dol": dol.reshape(W8, B16),
        "hash": rng.randint(0, 1 << 30, size=(W8, B16)).astype(np.int32),
    }


def _held(fx, held):
    """The fixture's window holding `held` sub-batches, the last one
    partial; the rest is the class's padding as `_prepare_window` lays
    it (PAD words, length 0)."""
    from emqx_tpu.ops import intern as I
    enc, lens, dol = fx["enc"].copy(), fx["lens"].copy(), fx["dol"].copy()
    for k in range(W8):
        n = 0 if k >= held else 5 if k == held - 1 else B16
        enc[k, n:], lens[k, n:], dol[k, n:] = I.PAD, 0, False
    return enc, lens, dol


def _steps(fx, lanes):
    """W sequential step programs threading the cursors, every plane
    stacked [W, ...]: the reference. A padding sub-batch runs the full
    step like any other."""
    import numpy as np

    from emqx_tpu.models import router_engine as RE
    step = RE.route_step if fx["trie"] else RE.route_step_shapes
    cur, out = fx["cur"], []
    for k in range(W8):
        r = step(fx["tables"], cur, lanes[0][k], lanes[1][k], lanes[2][k],
                 fx["hash"][k], np.int32(0), **fx["kw"])
        out.append(r)
        cur = r.new_cursors
    return RE.RouteResult(*[
        None if out[0][i] is None
        else np.stack([np.asarray(r[i]) for r in out])
        for i in range(len(out[0]))])


def _plan_of(fx, lanes, with_delta):
    """A match-cache plan over the window as `_plan_window` makes one:
    the lanes collapse to their unique topics, the padding lanes to one
    sentinel row that is neither hit nor miss; every other real unique
    topic is a hit whose base row is the matcher's own, the rest are
    the miss lanes. Returns (WindowPlan, overlay base rows or None, the
    step program's result on the miss lanes)."""
    import jax
    import numpy as np

    from emqx_tpu.models import router_engine as RE
    from emqx_tpu.ops.delta import delta_match
    flat = tuple(a.reshape((W8 * B16,) + a.shape[2:]) for a in lanes)
    keys = np.concatenate([flat[0], flat[1][:, None],
                           flat[2][:, None].astype(np.int32)], axis=1)
    _u, first, inv = np.unique(keys, axis=0, return_index=True,
                               return_inverse=True)
    Bu = len(first)
    assert Bu <= B16
    uniq = tuple(a[first] for a in flat)
    real = uniq[1] > 0
    hit = real & (np.cumsum(real) % 2 == 0)
    miss_u = np.flatnonzero(real & ~hit)
    assert hit.any() and len(miss_u)

    def padded(src, rows, fill):
        out = np.full((B16,) + src.shape[1:], fill, src.dtype)
        out[:len(rows)] = src[rows]
        return out
    miss = (padded(uniq[0], miss_u, 0), padded(uniq[1], miss_u, 0),
            padded(uniq[2], miss_u, False))
    pos = np.full(B16, B16, np.int32)               # pad = B: dropped
    pos[:len(miss_u)] = miss_u
    step = RE.route_step if fx["trie"] else RE.route_step_shapes

    def routed(e, l, d):
        return step(fx["tables"], fx["cur"], e, l, d,
                    np.zeros(B16, np.int32), np.int32(0), **fx["kw"])

    def base_rows(matches, counts, overflow):
        m = np.full((B16,) + matches.shape[1:], -1, np.int32)
        c, o = np.zeros(B16, np.int32), np.zeros(B16, bool)
        m[:Bu][hit] = np.asarray(matches)[:Bu][hit]
        c[:Bu][hit] = np.asarray(counts)[:Bu][hit]
        o[:Bu][hit] = np.asarray(overflow)[:Bu][hit]
        return m, c, o
    all_u = tuple(padded(a, np.arange(Bu), 0) for a in uniq)
    ru = routed(*all_u)
    dbase = None
    if with_delta:
        dm = delta_match(jax.device_put(fx["delta"]), *all_u, match_cap=4)
        dbase = base_rows(dm.matches, dm.counts, dm.overflow)
    plan = RE.WindowPlan(*miss, *base_rows(ru.matches, ru.match_counts,
                                           ru.match_overflow),
                         pos, inv.reshape(W8, B16).astype(np.int32))
    return plan, dbase, routed(*miss)


@pytest.mark.parametrize("held", [1, 2, 5, 8])
@pytest.mark.parametrize("stages", ["", "delta_compact"])
@pytest.mark.parametrize("plan", ["plain", "plan"])
@pytest.mark.parametrize("backend", list(ORACLE_BACKENDS))
def test_a_padded_window_equals_the_step_programs(backend, plan, stages,
                                                  held):
    """A W = 8 window holding 1, 2, 5 or 8 sub-batches (the last one
    partial) returns, on every kind of snapshot, plain and under a
    plan, with and without the delta and compact stages, what W
    sequential step programs return on every plane, cursors and `occur`
    included: a skipped padding row is bit for bit the full step's
    output on an empty sub-batch, and a covering snapshot's CSR is what
    hole-closing would have made of it."""
    import jax
    import numpy as np

    from emqx_tpu.models import router_engine as RE
    from emqx_tpu.ops.compact import compact_result
    from emqx_tpu.ops.delta import delta_overlay
    fx = _oracle_fixture(backend)
    lanes = _held(fx, held)
    want = _steps(fx, lanes)
    assert (np.asarray(want.match_counts)[held:] == 0).all()
    assert np.asarray(want.match_counts)[:held].any(axis=1).all()
    if held > 1:        # the group's cursor moved, and not in the padding
        assert np.asarray(want.occur)[:held].sum() > 0
        assert (np.asarray(want.new_cursors)[held - 1:]
                == np.asarray(want.new_cursors)[-1]).all()
    kw, args, wplan, delta = dict(fx["kw"]), lanes, None, None
    if plan == "plan":
        wplan, dbase, probe = _plan_of(fx, lanes, "delta" in stages)
        args = (None, None, None)
        in_row_0 = {f: None if getattr(probe, f) is None else np.array(
            [getattr(probe, f)] + [0] * (W8 - 1), np.int32)
            for f in ("nfa_wide_steps", "cover_candidates", "cover_roots")}
        want = want._replace(**in_row_0)
    if "delta" in stages:
        delta = RE.WindowDelta(fx["delta"], dbase if wplan else None)
        kw.update(delta_match_cap=4, delta_fanout_cap=8)
        flat = tuple(a.reshape((W8 * B16,) + a.shape[2:]) for a in lanes)
        dp = delta_overlay(fx["delta"], *flat, match_cap=4, fanout_cap=8)
        want = want._replace(delta=type(dp)(*[
            np.asarray(x).reshape((W8, B16) + x.shape[1:]) for x in dp]))
    if "compact" in stages:
        kw.update(payload_cap=512, d_payload_cap=64)
        r, dp = want, want.delta
        # the reference closes holes whatever the snapshot
        want = want._replace(
            compact=compact_result(
                r.matches, r.rows, r.opts, r.fan_counts, r.shared_sids,
                r.shared_rows, r.shared_opts, payload_cap=512,
                match_holes=True),
            d_compact=compact_result(
                dp.fids, dp.rows, dp.opts, dp.fan_counts,
                np.full((W8, B16, 1), -1, np.int32),
                np.zeros((W8, B16, 1), np.int32),
                np.zeros((W8, B16, 1), np.int8),
                payload_cap=64, match_holes=True))
    got = RE.route_window(fx["tables"], fx["cur"], *args, fx["hash"],
                          np.int32(0), wplan, delta, **kw)
    for name, g, w in zip(got._fields, got, want):
        assert (g is None) == (w is None), name
        if g is None:
            continue
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == b.dtype and a.shape == b.shape, name
            assert (a == b).all(), name
    assert (got.nfa_wide_steps is not None) == fx["trie"]
    assert (got.cover_candidates is not None) == fx["cover"] \
        == (got.cover_roots is not None)
    if "compact" in stages:
        assert not np.asarray(got.compact.row_overflow).any()


def _eqns_under_jits(jaxpr):
    """The equations of `jaxpr` and of the jits it calls, without
    entering a loop or a conditional."""
    for e in jaxpr.eqns:
        if e.primitive.name in ("pjit", "jit", "closed_call",
                                "custom_jvp_call"):
            inner = e.params.get("jaxpr") or e.params.get("call_jaxpr")
            yield from _eqns_under_jits(getattr(inner, "jaxpr", inner))
        else:
            yield e


def _all_eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for j in v if isinstance(v, (tuple, list)) else (v,):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield from _all_eqns(j)


@pytest.mark.parametrize("plan", ["plain", "plan"])
@pytest.mark.parametrize("backend", list(ORACLE_BACKENDS))
def test_one_skip_in_the_scan_body_and_none_at_w_1(backend, plan):
    """The W = 8 program's scan body holds exactly one conditional, the
    step skip (the trie NFA has none of its own any more), and the
    W = 1 program none: a class without a padding sub-batch runs the
    program it ran before, its predicate decided from the input's
    shape."""
    import jax
    import numpy as np

    from emqx_tpu.models import router_engine as RE
    fx = _oracle_fixture(backend)

    def body_of(W):
        lanes = tuple(a[:W] for a in _held(fx, W))
        wplan = None
        if plan == "plan":
            wplan = _plan_of(fx, _held(fx, W8), False)[0]
            wplan = wplan._replace(inv=wplan.inv[:W])
            lanes = (None, None, None)
        jaxpr = jax.make_jaxpr(functools.partial(RE.route_window,
                                                 **fx["kw"]))(
            fx["tables"], fx["cur"], *lanes, fx["hash"][:W], np.int32(0),
            wplan)
        scans = [e for e in _eqns_under_jits(jaxpr.jaxpr)
                 if e.primitive.name == "scan"
                 and e.params["length"] == W]
        assert len(scans) == 1, [e.primitive.name for e in scans]
        return jaxpr, scans[0].params["jaxpr"].jaxpr

    def conds(eqns):
        return [e for e in eqns if e.primitive.name == "cond"]
    whole, body = body_of(W8)
    skips = conds(_eqns_under_jits(body))
    assert len(skips) == 1
    # both branches return a whole step: the routed one holds the
    # match, the fan-out and the shared pick, the skipped one nothing
    # but constants
    sizes = sorted(sum(1 for _ in _all_eqns(br.jaxpr))
                   for br in skips[0].params["branches"])
    assert sizes[0] < 40 < sizes[1], sizes
    whole, body = body_of(1)
    assert not conds(_eqns_under_jits(body))
    if not fx["trie"]:      # the NFA's level step switches its width
        assert not conds(_all_eqns(whole.jaxpr))


def test_stats_count_the_slots_of_a_window_beside_what_it_holds():
    """`routing.device.window_slots` adds the class's W where
    `window_subs` adds the sub-batches held: 1 - subs / slots is the
    share of scan steps a run's windows skipped."""
    node = _node(())
    eng = node.device_engine
    eng.rebuild()
    msgs = [make("p", 0, f"wc/{i % 6}/x", b"") for i in range(40)]

    def counted():
        st = eng.stats()
        assert st["window_subs"] == node.metrics.val(
            "routing.device.window_subs")
        return st["window_subs"], st["window_slots"]
    assert counted() == (0, 0)
    h = eng.prepare_window([msgs], gate_cold=False)     # unfused: W = 1
    eng.dispatch(h), eng.materialize(h), eng.finish(h)
    assert counted() == (1, 1)
    h = eng.prepare_window([msgs, msgs, msgs[:7]], gate_cold=False)
    assert h.enc[1].shape == (8, 1024)
    eng.dispatch(h), eng.materialize(h)
    counts = [n for k in range(3) for n in eng.finish_sub(h, k, defer=False)]
    assert counts == [1 + (m.topic == "wc/0/x")
                      for m in msgs + msgs + msgs[:7]]
    assert eng._outstanding == 0
    assert counted() == (4, 9)
    decisions = node.pipeline_telemetry.snapshot()["decisions"]
    assert (decisions["routing.device.window_subs"],
            decisions["routing.device.window_slots"]) == (4, 9)
