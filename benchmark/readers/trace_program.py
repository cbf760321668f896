"""Device milliseconds of the programs whose name holds any of `match`
per device window formed while the trace ran (`routing.device.batches`
between the trace's two ends); 0 when none was formed."""

from __future__ import annotations

from benchmark.readers import xplane


def read(ctx, match):
    trace = ctx.get("trace")
    if not trace:
        return None
    seconds, _n = xplane.program_seconds(trace, match)
    windows = ctx["trace_m1"].get("routing.device.batches", 0) \
        - ctx["trace_m0"].get("routing.device.batches", 0)
    return 1000.0 * seconds / windows if windows else 0.0
