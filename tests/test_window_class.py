"""One window program, one window class (ISSUE 30): a class the engine
warmed through its zero-filled-argument path serves its first live
window from the jit cache.

The jit cache keeps numpy and device arguments apart, so the dummies of
`DeviceRouteEngine._window_call` have to be what a live dispatch hands
`route_window`, argument by argument: the property `inpath_executables`
measures on the chip, held here for every stage the program can run.
"""

import asyncio

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
