"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window, in %, mean over chips."""

from __future__ import annotations


def read(ctx):
    r = ctx.get("trace_reduced")
    if not r:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
