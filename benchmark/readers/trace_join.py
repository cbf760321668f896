"""The device's clock per window: each route program's execution on
the chip joined to the host spans of the window that launched it.

Every dispatch runs under `emqx:dispatch` on the one dispatch thread
(stat `trace_id`: the window's), and its readback under
`emqx:materialize` with the same id (`emqx_tpu/broker/trace.py`). The
device plane's "XLA Modules" line holds one event per execution of a
program; a v5e's trace has no "Steps" line to carry the step number,
so executions of the programs whose name holds any of `match` are
paired with the `emqx:dispatch` spans **by order**: the chip runs what
the one dispatch thread launches in the order it launches it. Inside
the traced window the first executions were launched before the trace
began (the chip's queue was seen three windows deep), and the last
spans' executions fall past its end. So the pairing drops 0 to
`MAX_LEAD` leading executions, and of the alignments that are possible
(every execution starts after the start of the span that launched it,
and every window's `emqx:materialize` ends after its execution does)
takes the one in which the readbacks end soonest after their
executions: one execution too early and every readback would have
waited a whole program longer than it did. The pairing is **refused**,
and every value reads 0, when no alignment is possible, when no paired
window has an `emqx:materialize` span to hold it by, when executions
and spans differ in number by more than `MAX_LEAD`, or when the trace
has no device plane.

what = "queue": mean over the pairs of (device start - end of
`emqx:dispatch`), in ms, a negative difference (the program started
while the span was still open) counted as 0: how long a launched
window waited behind earlier ones on the chip.
what = "tail": mean over the pairs whose window has an
`emqx:materialize` span of (end of that span - device end, or - the
span's own start where the readback was asked for only after the
device had finished), in ms: transfer plus the read thread waking up.
Together with the execution itself they split `materialize`'s span.
"""

from __future__ import annotations

from benchmark.readers import trace_scope, xplane

DISPATCH, MATERIALIZE = "emqx:dispatch", "emqx:materialize"
MAX_LEAD = 8      # the batcher's pipeline holds at most 8 windows


def host_events(trace: dict, name: str) -> list:
    """[(start, end, stats)] of the host events called `name`, by start."""
    return sorted(((ev[1], ev[1] + ev[2], ev[3])
                   for p in trace["planes"]
                   if not p["name"].startswith("/device:")
                   for ln in p["lines"] for ev in ln["events"]
                   if ev[0] == name), key=lambda x: x[:2])


def readbacks(trace: dict) -> dict:
    """{trace id: (start, end)} of the `emqx:materialize` spans."""
    return {m[2]["trace_id"]: m[:2]
            for m in host_events(trace, MATERIALIZE) if "trace_id" in m[2]}


def pairs(trace: dict, match: list):
    """[(dispatch (start, end, stats), execution (start, end))] on the
    first device plane, or None where the pairing is refused."""
    planes = xplane.device_planes(trace)
    if not planes:
        return None
    t0, t1 = trace_scope.window(trace)
    runs = sorted((ev[1], ev[1] + ev[2])
                  for ev in xplane._line(planes[0], xplane.MODULES_LINE)
                  if any(x in ev[0] for x in match)
                  and ev[1] >= t0 and ev[1] + ev[2] <= t1)
    spans = [d for d in host_events(trace, DISPATCH)
             if d[0] >= t0 and d[1] <= t1]
    if abs(len(runs) - len(spans)) > MAX_LEAD:
        return None
    done = readbacks(trace)
    best = None
    for lead in range(MAX_LEAD + 1):
        joined = list(zip(spans, runs[lead:]))
        tails = [done[span[2]["trace_id"]][1] - run[1]
                 for span, run in joined
                 if span[2].get("trace_id") in done]
        if not tails or min(tails) < 0 \
                or any(run[0] < span[0] for span, run in joined):
            continue
        if best is None or sum(tails) / len(tails) < best[0]:
            best = (sum(tails) / len(tails), joined)
    return best[1] if best else None


def read(ctx, what, match):
    trace = trace_scope.loaded(ctx)
    if not trace:
        return None
    key = "_trace_join:" + ",".join(match)
    if key not in ctx:
        ctx[key] = pairs(trace, match)
    joined = ctx[key]
    if not joined:
        return 0.0
    if what == "queue":
        waits = [max(0.0, run[0] - span[1]) for span, run in joined]
    elif what == "tail":
        done = readbacks(trace)
        waits = []
        for span, run in joined:
            m = done.get(span[2].get("trace_id"))
            if m is not None:
                waits.append(m[1] - max(run[1], m[0]))
    else:
        raise ValueError(f"trace_join: no {what!r}")
    return sum(waits) / len(waits) / 1e6 if waits else 0.0
