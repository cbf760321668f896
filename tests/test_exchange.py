"""Device-to-device sharded exchange stage (ISSUE 15).

Acceptance criteria, as tests:

- **Bit-identity A/B twin**: exchange on vs broker.device_exchange=0
  (host gather/merge) must produce identical delivery COUNTS and
  identical PER-SESSION delivery order, across mesh sizes 2/4/8 ×
  {clean traffic, shared groups, dirty shards at consume, churn
  mid-window, segment-capacity overflow} — every fallback rung must be
  invisible to subscribers.
- **Chaos**: an injected `mesh_exchange` fault mid-window replays
  through the host rung with zero QoS>=1 loss and the breaker
  re-closes; a dead ring (the exchange program itself raising)
  degrades THAT window to host gather without losing it.
- **Ring rotation**: parallel.sharded.ring_rotate (a collective-permute
  on every backend) equals np.roll over the stacked blocks.
- **Knob**: EMQX_TPU_EXCHANGE / broker.device_exchange=0 leaves no
  exchange aux, no exchange program, no pipeline.exchange.* traffic.
"""

import asyncio

import numpy as np
import pytest

import jax

from emqx_tpu.broker import supervise as S
from emqx_tpu.broker.message import make
from emqx_tpu.broker.node import Node

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


def run(coro, timeout=180):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()


def mkmsg(topic, payload=b"x", qos=0):
    return make("pub", qos, topic, payload)


class Rec:
    """One subscriber session: records its delivery sequence."""

    def __init__(self):
        self.got = []

    def deliver(self, topic_filter, msg):
        self.got.append((topic_filter, msg.topic, bytes(msg.payload)))
        return True


def _mk_node(devices, dp, *, exchange, max_batch=16, lanes=0,
             extra=None):
    conf = {"broker": {"multichip": {"enable": True, "devices": devices,
                                     "dp": dp, "max_batch": max_batch},
                       "device_min_batch": 1, "deliver_lanes": lanes,
                       "device_exchange": 1 if exchange else 0}}
    if extra:
        conf["broker"].update(extra)
    return Node(conf)


def _subscribe(node, spec):
    """spec: [(client, filter, opts)] — one Rec per distinct client,
    subscribed (possibly to several filters, spread over shards)."""
    recs = {}
    broker = node.broker
    for client, f, opts in spec:
        if client not in recs:
            recs[client] = (Rec(), None)
            sid = broker.register(recs[client][0], client)
            recs[client] = (recs[client][0], sid)
        broker.subscribe(recs[client][1], f, dict(opts) if opts else None)
    return {c: r for c, (r, _sid) in recs.items()}


# one client on several filters (different shards) + fan-out filters
# with many clients: the per-session interleaving actually has content
_SPEC = ([("multi", "ab/+", 0), ("multi", "ab/x", 0),
          ("multi", "ab/#", 0), ("multi", "+/x", 0)]
         + [(f"fan{i}", "hot/+", 0) for i in range(6)]
         + [(f"solo{i}", f"solo/t{i}", 0) for i in range(6)])

_TOPICS = (["ab/x", "hot/1", "solo/t3", "ab/y", "nomatch/z", "hot/2"]
           + [f"solo/t{i}" for i in range(6)] + ["ab/x", "q/x"])


def _route(node, topics, wait=True):
    eng = node.device_engine
    msgs = [mkmsg(t, ("p%d" % i).encode()) for i, t in enumerate(topics)]
    counts = eng.route_batch(msgs, wait=wait)
    assert counts is not None
    return counts


class TestBitIdentityAB:
    """Exchange on vs off: identical counts AND per-session order."""

    @pytest.mark.parametrize("devices,dp", [(2, 1), (4, 2), (8, 2)])
    def test_clean_traffic(self, devices, dp):
        results = {}
        for mode in (True, False):
            node = _mk_node(devices, dp, exchange=mode)
            recs = _subscribe(node, _SPEC)
            eng = node.device_engine
            eng.rebuild()
            if mode:
                assert eng.warm_exchange(len(_TOPICS)), \
                    (eng._exch_warm, eng._wanted_ecap)
            # warm-up + segment-class adaptation (a small ring can
            # overflow the cold class — the EWMA then grows it); both
            # modes see the SAME warm-up traffic, captures cleared
            for _ in range(3):
                _route(node, _TOPICS)
                if not mode or \
                        node.metrics.val("pipeline.exchange.windows"):
                    break
                eng.warm_exchange(len(_TOPICS))
            for r in recs.values():
                r.got.clear()
            before = node.metrics.val("pipeline.exchange.windows")
            counts = _route(node, _TOPICS)
            counts2 = _route(node, list(reversed(_TOPICS)))
            if mode:
                assert node.metrics.val("pipeline.exchange.windows") \
                    >= before + 2, node.metrics.all()
            else:
                assert node.metrics.val("pipeline.exchange.windows") == 0
                assert eng.aux is None
            results[mode] = (counts, counts2,
                             {c: list(r.got) for c, r in recs.items()})
        on, off = results[True], results[False]
        assert on[0] == off[0] and on[1] == off[1]
        assert on[2] == off[2], (on[2], off[2])

    def test_shared_groups_fall_back_identically(self):
        results = {}
        spec = _SPEC + [(f"sh{i}", "$share/g/ab/+", 0) for i in range(3)]
        for mode in (True, False):
            node = _mk_node(8, 2, exchange=mode)
            recs = _subscribe(node, spec)
            eng = node.device_engine
            eng.rebuild()
            if mode:
                eng.warm_exchange(len(_TOPICS))
            counts = _route(node, _TOPICS)
            if mode:
                # a shared-slot hit is device-flagged unclean: the
                # window gathers, subscribers can't tell
                assert node.metrics.val(
                    "pipeline.exchange.fallback.unclean") >= 1
            results[mode] = (counts,
                             {c: list(r.got) for c, r in recs.items()})
        assert results[True] == results[False]

    def test_dirty_shards_at_consume_fall_back_identically(self):
        """Churn marks landing between dispatch and consume: the
        exchange-landed window must re-land dense (late fallback) and
        deliver exactly like the gather twin under the same churn."""
        results = {}
        for mode in (True, False):
            node = _mk_node(8, 2, exchange=mode)
            recs = _subscribe(node, _SPEC)
            eng = node.device_engine
            eng.rebuild()
            if mode:
                eng.warm_exchange(4)
                eng.warm_exchange(len(_TOPICS))
            _route(node, _TOPICS[:4])       # same warm-up both modes
            if mode:
                assert node.metrics.val("pipeline.exchange.windows") >= 1
            msgs = [mkmsg(t, b"late") for t in _TOPICS]
            h = eng.prepare(msgs)
            assert h is not None
            eng.dispatch(h)
            eng.materialize(h)
            # churn lands after materialize: consume must not trust the
            # snapshot's clean masks
            late = Rec()
            node.broker.subscribe(node.broker.register(late, "late"),
                                  "ab/+")
            assert eng.dirty_shards
            counts = eng.finish(h)
            if mode:
                assert node.metrics.val(
                    "pipeline.exchange.fallback.late") >= 1
            # drain the dirty marks for a deterministic end state
            assert eng.poll_rebuild()
            results[mode] = (counts, list(late.got),
                             {c: list(r.got) for c, r in recs.items()})
        assert results[True] == results[False]

    def test_churn_mid_stream_identical(self):
        """Subscribe bursts between batches (the per-shard update path)
        with exchange on vs off: same counts, same sequences."""
        results = {}
        for mode in (True, False):
            node = _mk_node(8, 2, exchange=mode)
            recs = _subscribe(node, _SPEC)
            eng = node.device_engine
            eng.rebuild()
            if mode:
                eng.warm_exchange(len(_TOPICS))
            seq = []
            added = {}
            for rnd in range(3):
                seq.append(_route(node, _TOPICS))
                r = Rec()
                added[f"ch{rnd}"] = r
                node.broker.subscribe(
                    node.broker.register(r, f"ch{rnd}"),
                    f"solo/t{rnd}")
            seq.append(_route(node, _TOPICS))
            results[mode] = (seq,
                             {c: list(r.got) for c, r in recs.items()},
                             {c: list(r.got) for c, r in added.items()})
        assert results[True] == results[False]

    def test_segment_overflow_falls_back_identically(self):
        """More rows to one delivery shard than the capacity class
        holds: the window must gather (counted) and deliver
        identically; the EWMA then grows the class."""
        spec = [(f"big{i}", "hot/+", 0) for i in range(80)]
        topics = ["hot/a"] * 4
        results = {}
        for mode in (True, False):
            node = _mk_node(8, 2, exchange=mode)
            recs = _subscribe(node, spec)
            eng = node.device_engine
            eng.rebuild()
            if mode:
                eng.warm_exchange(len(topics))
                assert eng._choose_ecap(eng._batch_class(
                    len(topics))) == 16   # 80 rows over 4 dests won't fit
            counts = _route(node, topics)
            if mode:
                assert node.metrics.val("pipeline.exchange.overflow") \
                    >= 1
                assert node.metrics.val("pipeline.exchange.windows") == 0
                # the miss taught the ladder: next class fits
                assert eng._choose_ecap(eng._batch_class(
                    len(topics))) > 16
            results[mode] = (counts,
                             {c: list(r.got) for c, r in recs.items()})
        assert results[True] == results[False]

    def test_lanes_preserve_per_session_order(self):
        """The delivery-lane path (plan.add_rows chunks): per-session
        sequences identical between exchange and gather."""
        results = {}
        for mode in (True, False):
            node = _mk_node(8, 2, exchange=mode, lanes=2,
                            extra={"batch_window_us": 1000})
            recs = _subscribe(node, _SPEC)
            eng = node.device_engine
            eng.rebuild()
            # warm the base batch classes in BOTH modes (a cold class
            # host-routes the window — a different order source than
            # the device path, and not what this test compares)
            eng._warm_one(2)
            eng._warm_one(4)
            if mode:
                eng.warm_exchange(2)
                eng.warm_exchange(4)

            # every wave takes the path under test: the chooser's
            # verdicts follow what each run happened to compile (a
            # window compiled under is no cost sample), and a
            # host-routed wave is a different order source
            node.publish_batcher._device_worth_it = lambda n: True

            async def go():
                for w in range(4):
                    await asyncio.gather(*[
                        node.publish_async(mkmsg(
                            t, b"w%d" % w, qos=1))
                        for t in ("ab/x", "hot/1", "solo/t0",
                                  "ab/y")])
                pool = node.deliver_lanes
                if pool is not None:
                    await pool.drain()
            run(go())
            if mode:
                assert node.metrics.val("pipeline.exchange.windows") \
                    >= 1
            results[mode] = {c: list(r.got) for c, r in recs.items()}
        assert results[True] == results[False]


class TestExchangeChaos:
    @pytest.mark.chaos
    def test_mid_ring_fault_replays_through_host_rung(self):
        """Injected mesh_exchange fault while exchange serves: the
        window replays through the host rung — zero QoS>=1 loss — and
        after the half-open probe the breaker re-closes and exchange
        windows resume."""
        node = _mk_node(8, 2, exchange=True, lanes=0,
                        extra={"supervise": True,
                               "supervise_threshold": 1,
                               "batch_window_us": 1000})
        sup = node.supervisor
        for br in sup.breakers.values():
            br.base_cooldown_s = br.cooldown_s = 0.05
        recs = _subscribe(node, _SPEC)
        eng = node.device_engine
        eng.rebuild()
        assert eng.warm_exchange(8)
        _route(node, ["ab/x"] * 8)
        assert node.metrics.val("pipeline.exchange.windows") >= 1
        sup.injector = S.FaultInjector(S.parse_faults(
            "mesh_exchange:exception:count=1"))

        async def go():
            outs = []
            import time as _t
            deadline = _t.monotonic() + 60
            while _t.monotonic() < deadline:
                outs.extend(await asyncio.gather(*[
                    node.publish_async(mkmsg("ab/x", b"c%d" % i,
                                             qos=1))
                    for i in range(8)]))
                await asyncio.sleep(0.05)
                if sup.breakers["mesh_exchange"].state == "closed" \
                        and sup.injector.faults[0].fired:
                    break
            return outs
        outs = run(go())
        assert sup.injector.faults[0].fired
        assert all(c >= 1 for c in outs)       # zero QoS1 loss
        assert node.metrics.val("messages.dropped") == 0
        assert sup.breakers["mesh_exchange"].state == "closed"
        # exchange serves again after recovery
        before = node.metrics.val("pipeline.exchange.windows")
        _route(node, ["ab/x"] * 8)
        assert node.metrics.val("pipeline.exchange.windows") > before

    def test_dead_ring_degrades_to_host_gather(self):
        """The exchange program itself dying (a dead ring, not an
        injected control fault) must cost only the exchange: the window
        lands via host gather, nothing is lost, the fault is counted
        against the mesh_exchange breaker."""
        node = _mk_node(8, 2, exchange=True, lanes=0,
                        extra={"supervise": True,
                               "supervise_threshold": 3})
        recs = _subscribe(node, _SPEC)
        eng = node.device_engine
        eng.rebuild()
        assert eng.warm_exchange(len(_TOPICS))
        baseline = _route(node, _TOPICS)

        Bp = eng._batch_class(len(_TOPICS))
        E = eng._choose_ecap(Bp)

        def dead_ring(*a, **k):
            raise RuntimeError("ring down")

        eng._exch_steps[E] = dead_ring
        counts = _route(node, _TOPICS)
        assert counts == baseline       # nothing lost to the dead ring
        m = node.metrics
        assert m.val("pipeline.exchange.fallback.error") >= 1
        assert m.val("supervise.faults.mesh_exchange") >= 1
        # consecutive ring faults ACCUMULATE (the step's success must
        # not reset the domain's count) — at threshold 3 the breaker
        # trips, shedding the mesh to the host rung with zero loss
        sup = node.supervisor
        counts2 = _route(node, _TOPICS)
        counts3 = _route(node, _TOPICS)
        assert counts2 == baseline and counts3 == baseline
        assert m.val("supervise.faults.mesh_exchange") >= 3
        assert sup.breakers["mesh_exchange"].state == "open"


class TestRingRotate:
    def test_ring_rotate_matches_roll_oracle(self):
        """ring_rotate over the 'route' ring == np.roll on the stacked
        blocks, for every hop count."""
        from emqx_tpu.parallel.mesh import make_mesh
        from emqx_tpu.parallel.sharded import _shard_map, ring_rotate
        from jax.sharding import PartitionSpec as P
        mesh = make_mesh(8, dp=2, route=4)
        x = np.arange(2 * 4 * 6, dtype=np.int32).reshape(2, 4, 6)
        for k in range(1, 4):
            def local(xs, k=k):
                return ring_rotate(xs[0, 0], k, "route", 4)[None, None]

            fn = jax.jit(_shard_map(local, mesh, (P("dp", "route"),),
                                    P("dp", "route")))
            # device (dp, r) ends up holding source (r-k)%4's block
            np.testing.assert_array_equal(np.asarray(fn(x)),
                                          np.roll(x, k, axis=1))

    def test_exchange_program_registered_in_compile_stats(self):
        import gc

        from emqx_tpu.models.router_engine import compile_stats
        from emqx_tpu.parallel.mesh import make_mesh
        from emqx_tpu.parallel.sharded import make_exchange_step

        def n_steps():
            return sum(k.startswith("exchange_step")
                       for k in compile_stats())

        base = n_steps()
        fn = make_exchange_step(make_mesh(8, dp=2, route=4), seg_cap=16)
        assert n_steps() == base + 1
        # the registry holds programs weakly: dropping the fn must not
        # pin its compiled executables for the life of the process
        # (gc may also reap entries of earlier tests' dead servers, so
        # only the upper bound is meaningful)
        del fn
        gc.collect()
        assert n_steps() <= base


class TestKnobResolution:
    def test_resolver_config_beats_env(self, monkeypatch):
        from emqx_tpu.parallel.serving import resolve_device_exchange
        monkeypatch.setenv("EMQX_TPU_EXCHANGE", "0")
        assert resolve_device_exchange(1) is True
        assert resolve_device_exchange(None) is False
        monkeypatch.setenv("EMQX_TPU_EXCHANGE", "1")
        assert resolve_device_exchange(None) is True
        assert resolve_device_exchange(0) is False
        monkeypatch.delenv("EMQX_TPU_EXCHANGE")
        assert resolve_device_exchange(None) is True   # default-on
        # the sibling resolvers' spellings disable too (overload,
        # compact_readback precedent) — they must not crash boot
        for off in ("false", "off", "0"):
            monkeypatch.setenv("EMQX_TPU_EXCHANGE", off)
            assert resolve_device_exchange(None) is False
