"""The route programs' share of their roofline, in %: the least time
the messages the chip routed while the trace ran could have taken by
bytes (`route_bytes.py`, at the device's HBM peak from `peaks.json`)
over the device time the route programs took. Bytes bind; 0 when the
chip routed nothing in the traced span."""

from __future__ import annotations

from benchmark import populations
from benchmark.readers import route_bytes, xplane


def read(ctx, match):
    trace = ctx.get("trace")
    if not trace or not ctx.get("peaks"):
        return None
    seconds, _n = xplane.program_seconds(trace, match)
    # the counter counts deliveries, not PUBLISHes
    delivered = ctx["trace_m1"].get("messages.routed.device", 0) \
        - ctx["trace_m0"].get("messages.routed.device", 0)
    if not seconds or not delivered:
        return 0.0
    pop = ctx["pop"]
    w = ctx["window"]
    pub = ctx["pub"]
    keys = pub["key"][(pub["send_ns"] >= w["t0_ns"])
                      & (pub["send_ns"] < w["t1_ns"])]
    per_msg = populations.expected_count(pop, keys) / max(1, len(keys))
    if not per_msg:
        return 0.0
    need = delivered / per_msg * route_bytes.message_bytes(
        len(pop.topic(0).split("/")), route_bytes.shapes_of(pop.filters()),
        per_msg)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
