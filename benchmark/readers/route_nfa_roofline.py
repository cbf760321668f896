"""The trie NFA's share of its roofline, in %: the least time the
topics it matched while the trace ran could have taken by bytes
(`nfa_bytes.py`, at the device's HBM peak from `peaks.json`) over the
device self time of the route programs' operations under scope `match`
(`trace_scope.scope_seconds`).

The topics it matched are the program's own count
(`routing.device.nfa_lanes`: every real lane of a plain window of a
trie-backed snapshot, the misses alone of a window that took the
match-cache plan), so cache hits and duplicates are out of the
numerator as they are out of the NFA's work; the scope's time still
holds the plan's merge and gather. A topic's bytes are the mean over
the keys sent in the measured window: depth and matches do not depend
on the gateway the Zipf draw skews, so the misses' mean is the
window's. None where the program has no such counter; 0 when the NFA
matched nothing in the traced span.
"""

from __future__ import annotations

import numpy as np

from benchmark.readers import nfa_bytes, trace_scope, xplane

LANES = "routing.device.nfa_lanes"


def read(ctx, match):
    trace = trace_scope.loaded(ctx)
    if not trace or not ctx.get("peaks") \
            or LANES not in ctx.get("trace_m1", {}):
        return None
    lanes = ctx["trace_m1"][LANES] - ctx["trace_m0"].get(LANES, 0)
    key = "_scope_seconds:" + ",".join(match)   # trace_scope's own memo
    by_scope = ctx.get(key)
    if by_scope is None:
        by_scope = ctx[key] = trace_scope.scope_seconds(trace, match) \
            if xplane.device_planes(trace) else {}
    seconds = by_scope.get("match", 0.0)
    if not lanes or not seconds:
        return 0.0
    pop, w, pub = ctx["pop"], ctx["window"], ctx["pub"]
    keys, count = np.unique(
        pub["key"][(pub["send_ns"] >= w["t0_ns"])
                   & (pub["send_ns"] < w["t1_ns"])], return_counts=True)
    if not len(keys):
        return 0.0
    per_topic = np.array([nfa_bytes.topic_bytes(
        len(pop.topic(int(k)).split("/")), int((row >= 0).sum()))
        for k, row in zip(keys, pop.expect(keys))])
    need = lanes * float((per_topic * count).sum() / count.sum())
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
