"""A second of the loop's own thread, named by the program's spans.

`trace_spans.py` splits the DEVICE's idle time by the innermost `emqx:`
span of any thread, and so is blind while the chip works. This reader
takes the one host line that holds `emqx:loop_wait` (the broker's
`trace.LoopWatch` puts that span around every `select()` of the asyncio
loop that may block, so the line is the loop's thread) and gives every
nanosecond of the WHOLE traced window to the innermost `emqx:` span of
that line, to `emqx:loop_wait`, or to none. Spans of other threads
(`emqx:dispatch`, `emqx:materialize` on their executors) are on other
lines and count nowhere here.

`names`: milliseconds per traced second under those spans; `unnamed`:
the line's time under no `emqx:` span at all (work the program does not
name: asyncio's own transports, task stepping, timers, zero-timeout
polls; the wait is not in it); `other`: under an `emqx:` span that
`NAMED` does not list. `NAMED` is what the metric files name between
them, so wait + the named groups + other + unnamed is the window: 1,000
ms a second. A trace without `emqx:loop_wait` (an older program, or a
loop without a selector) reads None and the metric is left out.
"""

from __future__ import annotations

from benchmark.readers import trace_spans, xplane

PREFIX = trace_spans.PREFIX
WAIT = PREFIX + "loop_wait"
NAMED = ("loop_wait", "ingress", "control", "batch_form", "host_route",
         "prepare_window", "finish_sub", "lane", "settle", "gc")


def loop_line(trace: dict, t0: float, t1: float):
    """The events [(name, start, end)] of the host line with the most
    `emqx:loop_wait` time inside [t0, t1], or None where no line has
    any."""
    best, best_ns = None, 0.0
    for p in trace["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            evs = [(ev[0], max(ev[1], t0), min(ev[1] + ev[2], t1))
                   for ev in ln["events"] if ev[0].startswith(PREFIX)
                   and ev[1] + ev[2] > t0 and ev[1] < t1]
            ns = sum(e - s for n, s, e in evs if n == WAIT)
            if ns > best_ns:
                best, best_ns = evs, ns
    return best


def second_by_span(trace: dict):
    """{"window_ns", "by_span": {name: ns}, "unnamed_ns"} of the loop's
    line, or None where the trace has no such line."""
    t0, t1 = xplane.window(trace)
    line = loop_line(trace, t0, t1)
    if line is None:
        return None
    by_span: dict = {}
    for a, b, n in trace_spans.innermost(line):
        by_span[n] = by_span.get(n, 0.0) + (b - a)
    return {"window_ns": t1 - t0, "by_span": by_span,
            "unnamed_ns": (t1 - t0) - sum(by_span.values())}


def read(ctx, names=(), unnamed=False, other=False):
    trace = ctx.get("trace")
    if not trace:
        return None
    if "_loop_second" not in ctx:
        ctx["_loop_second"] = second_by_span(trace)
    r = ctx["_loop_second"]
    if r is None or not r["window_ns"]:
        return None
    if unnamed:
        ns = r["unnamed_ns"]
    elif other:
        known = {PREFIX + n for n in NAMED}
        ns = sum(v for n, v in r["by_span"].items() if n not in known)
    else:
        ns = sum(r["by_span"].get(PREFIX + n, 0.0) for n in names)
    return 1e3 * ns / r["window_ns"]
