"""Device-idle time named by the program's own spans.

The broker puts every stage on the profiler's host timeline itself
(`emqx_tpu/broker/trace.py`: `emqx:ingress`, `emqx:batch_form`,
`emqx:host_route`, `emqx:prepare_window`, `emqx:dispatch`,
`emqx:materialize`, `emqx:finish_sub`, `emqx:lane`, `emqx:settle`,
`emqx:gc`). Idle is what `xplane.reduce` calls idle: the complement,
inside the traced window, of the union of the "XLA Ops" intervals of a
device plane. Every idle nanosecond goes to one span: of the `emqx:`
spans that cover it, on whatever thread, the innermost (shortest), as
`xplane.reduce` does it for the harness's own; or to no span at all.

mode "idle": milliseconds of device idle under the spans in `names`
per traced second (mean over the device planes); with `unnamed` true,
the idle under no `emqx:` span. The named groups, the idle under
`emqx:dispatch` and `emqx:materialize`, and the unnamed rest add up to
the traced idle time. A program without the spans (an older commit)
reads 0 under every name and all idle as unnamed.
"""

from __future__ import annotations

import heapq

from benchmark.readers import xplane

PREFIX = "emqx:"


def spans(trace: dict) -> list:
    """The program's spans, [(name, start, end)], any host thread."""
    out = []
    for p in trace["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            out += [(ev[0], ev[1], ev[1] + ev[2]) for ev in ln["events"]
                    if ev[0].startswith(PREFIX)]
    return out


def idle_intervals(plane: dict, t0: float, t1: float) -> list:
    """[(start, end)] inside [t0, t1] in which no operation ran."""
    busy = xplane.union([(s, e) for _n, s, e in xplane._clip(
        xplane._line(plane, xplane.OPS_LINE), t0, t1)])
    edges = [t0] + [x for se in busy for x in se] + [t1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def innermost(cover: list) -> list:
    """[(start, end, name)], sorted and disjoint: at every instant that
    any of `cover` [(name, start, end)] holds, the shortest that does."""
    cover = sorted(cover, key=lambda x: x[1])
    points = sorted({x for _n, s, e in cover for x in (s, e)})
    out, live, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(cover) and cover[i][1] <= a:
            n, s, e = cover[i]
            heapq.heappush(live, (e - s, e, n))
            i += 1
        while live and live[0][1] <= a:
            heapq.heappop(live)
        if live:
            out.append((a, b, live[0][2]))
    return out


def idle_by_span(trace: dict) -> dict:
    """{"window_ns", "idle_ns", "by_span": {name: ns}, "unnamed_ns"},
    each the mean over the device planes."""
    t0, t1 = xplane.window(trace)
    planes = xplane.device_planes(trace) or xplane._rehearsal_plane(trace)
    segs = innermost([(n, max(s, t0), min(e, t1)) for n, s, e in
                      spans(trace) if e > t0 and s < t1])
    by_span: dict = {}
    idle = named = 0.0
    for p in planes:
        k = 0
        for g0, g1 in idle_intervals(p, t0, t1):
            idle += g1 - g0
            while k < len(segs) and segs[k][1] <= g0:
                k += 1
            j = k
            while j < len(segs) and segs[j][0] < g1:
                a, b, n = segs[j]
                part = min(b, g1) - max(a, g0)
                by_span[n] = by_span.get(n, 0.0) + part
                named += part
                j += 1
    k = len(planes)
    return {"window_ns": t1 - t0, "idle_ns": idle / k,
            "by_span": {n: v / k for n, v in by_span.items()},
            "unnamed_ns": (idle - named) / k}


def read(ctx, names=(), unnamed=False, mode="idle"):
    trace = ctx.get("trace")
    if not trace:
        return None
    if mode != "idle":
        raise ValueError(f"trace_spans: no mode {mode!r}")
    r = ctx.get("_idle_by_span")
    if r is None:
        r = ctx["_idle_by_span"] = idle_by_span(trace)
    ns = r["unnamed_ns"] if unnamed else \
        sum(r["by_span"].get(PREFIX + n, 0.0) for n in names)
    return 1e3 * ns / r["window_ns"] if r["window_ns"] else 0.0
