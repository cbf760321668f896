"""Ratio of counter deltas over the measured window.

`num` and `den` are lists of names of the node's counters
(`node.metrics`), or of `window.publishes` / `window.seconds` /
`window.deliveries`, which the harness counts itself from the
generators' logs. The value is scale * sum(delta num) / sum(delta den);
without `den` it is the summed delta. A denominator that did not move
(no burst, no window of that kind) reads as 0: the metric is still
reported, since a cell's line has to carry each of its metrics.

A share is not capped. Where its numerator and denominator move at
different moments of one window's life (`routing.device.windows` at
prepare and `routing.device.cached_windows` at dispatch;
`routing.device.nfa_steps` before a device read and
`routing.device.nfa_narrow_steps` after it), a snapshot can fall
between the two, so the share is off by the windows in flight at either
edge and can read a little over 100 (100.38 seen, PR 37). Anything
beyond that is a miscount, and a cap would hide it.
"""

from __future__ import annotations


def _sum(ctx, names) -> float:
    total = 0.0
    for n in names:
        if n.startswith("window."):
            total += ctx["window"][n[7:]]
        elif n.endswith(".*"):
            total += sum(v - ctx["m0"].get(k, 0)
                         for k, v in ctx["m1"].items()
                         if k.startswith(n[:-1]))
        else:
            total += ctx["m1"].get(n, 0) - ctx["m0"].get(n, 0)
    return total


def read(ctx, num, den=None, scale=1.0):
    top = _sum(ctx, num)
    if den is None:
        return scale * top
    bottom = _sum(ctx, den)
    return scale * top / bottom if bottom else 0.0
