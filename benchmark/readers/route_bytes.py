"""Least bytes the route step has to move for a window, from shapes.

Counted from what the algorithm needs, not from what the compiler
emitted (`program_costs` is XLA's estimate of its own code): the route
step matches each topic against a shape-partitioned, two-choice
bucketed hash table (`ops/shapes.py`: BK = 8 entries a bucket, a row is
h1 | h2 | fid = 3 * BK int32), looks up each matched filter's
subscribers, and hands the deliveries back.

Per routed message:
  in       levels * 4 (interned words) + 8 (length, '$' flag)
  probe    shapes * 2 home buckets * 3 * BK * 4
  fan-out  8 per delivery read (subscriber id, options)
  out      8 per delivery (subscriber id, filter id) + 4 (count)

No floating-point work to speak of (a few integer hash folds a level),
so bytes bind and the roofline is bytes / HBM bytes per second.
"""

from __future__ import annotations

BK = 8


def shapes_of(filters) -> int:
    """Distinct wildcard shapes: which levels are `+`, whether the
    filter ends in `#`, and how many levels it has."""
    return len({tuple(w if w in "+#" else "w" for w in f.split("/"))
                for f in filters})


def message_bytes(levels: int, shapes: int, deliveries: float) -> float:
    return (levels * 4 + 8) + shapes * 2 * 3 * BK * 4 \
        + 8 * deliveries + (8 * deliveries + 4)
