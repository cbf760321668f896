"""The match stage's share of its roofline, in %, whatever matches: the
least time the topics it matched while the trace ran could have taken
by bytes (`match_floor_bytes.py`: by the work, not by the
implementation; at the device's HBM peak from `peaks.json`) over the
device self time of the route programs' operations under scope `match`
(`trace_scope.scope_seconds`; a covering snapshot's expansion is traced
under `match/cover` and stays in `match`, the innermost scope that
reader knows).

The topics it matched are the program's own count
(`routing.device.match_lanes`: every real lane of a plain window, the
misses alone of a window that took the match-cache plan), so cache hits
and duplicates are out of the numerator as they are out of the
matcher's work. A topic's bytes are the mean over the keys sent in the
measured window, weighed by how often each was sent; of more than
`SAMPLE` distinct keys every n-th (in key order) stands for the rest,
since the walk is a Python loop a key. None where the program has no
such counter; 0 when nothing was matched in the traced span or the
trace has no device plane.
"""

from __future__ import annotations

import numpy as np

from benchmark.readers import match_floor_bytes, trace_scope, xplane

LANES = "routing.device.match_lanes"
SAMPLE = 16384


def mean_topic_bytes(pop, keys: np.ndarray) -> float:
    """Mean of `match_floor_bytes.topic_bytes` over the keys as sent."""
    distinct, count = np.unique(keys, return_counts=True)
    if not len(distinct):
        return 0.0
    step = -(-len(distinct) // SAMPLE)
    distinct, count = distinct[::step], count[::step]
    index = match_floor_bytes.FilterLevels(pop.filters())
    per_topic = np.empty(len(distinct))
    for i, k in enumerate(distinct):
        topic = pop.topic(int(k))
        per_topic[i] = match_floor_bytes.topic_bytes(
            topic.count("/") + 1, index.matched(topic))
    return float((per_topic * count).sum() / count.sum())


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
    w, pub = ctx["window"], ctx["pub"]
    need = lanes * mean_topic_bytes(ctx["pop"], pub["key"][
        (pub["send_ns"] >= w["t0_ns"]) & (pub["send_ns"] < w["t1_ns"])])
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
