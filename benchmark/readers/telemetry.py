"""A number from `PipelineTelemetry.snapshot()`, by key path (or the sum
over a list of key paths).

how = "delta": scale * (delta of `num`) / (delta of `den`) between the
snapshots at the window's two ends (e.g. a stage's `sum_ms` over its
`count`). how = "last": the same on the closing snapshot alone, with
`mul` multiplied in (e.g. a rebuild stage's `mean_ms` times `count`).
A path that is absent from the closing snapshot (a stage that never
ran), or a denominator that did not move, reads as 0: the metric is
still reported, since a cell's line has to carry each of its metrics.
"""

from __future__ import annotations


def _get(snap: dict, path: str):
    cur = snap
    for part in path.split("/"):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def read(ctx, num, den=None, mul=None, scale=1.0, how="delta"):
    def val(paths):
        """Sum over a path or a list of paths; absent ones add nothing."""
        total = None
        for path in [paths] if isinstance(paths, str) else paths:
            last = _get(ctx["tele1"], path)
            if last is None:
                continue
            if how == "delta":
                last -= _get(ctx["tele0"], path) or 0
            total = (total or 0.0) + last
        return total

    top = val(num) or 0.0
    if mul is not None:
        top *= val(mul) or 0.0
    if den is None:
        return scale * top
    bottom = val(den)
    return scale * top / bottom if bottom else 0.0
