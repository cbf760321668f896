"""End-to-end metric arithmetic, from the generators' logs alone.

Times are CLOCK_MONOTONIC ns stamped by the generator processes: a
delivery counts where the subscriber process read it.
"""

from __future__ import annotations


def delivered_per_s(pub, sub, t0: int, t1: int) -> float:
    live = ~sub["dup"]
    r = sub["recv_ns"][live]
    return float(((r >= t0) & (r < t1)).sum()) / ((t1 - t0) / 1e9)


def publishes_in(pub, t0: int, t1: int) -> int:
    return int(((pub["send_ns"] >= t0) & (pub["send_ns"] < t1)).sum())


METRICS = {f.__name__: f for f in (delivered_per_s,)}
