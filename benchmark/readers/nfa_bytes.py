"""Least bytes the trie NFA has to move to match one topic.

Counted from what a level-stepped walk over `ops/trie.TrieTables`
needs, not from what the compiler emitted: the matcher
(`ops/match.match_batch`) carries a frontier of live trie nodes per
topic and advances it one topic level a step. The floor is a topic with
a single live path, which is what a topic of a set without covering
filters has outside the levels where a `+` branches off:

  in     levels * 4 (interned words) + 8 (length, '$' flag)
  node   (levels + 1) * NODE_ROW: at every depth 0 .. levels the live
         node's row (plus_child | hash_child | node_filter, int32 each):
         its '#' child and its own filter are what a step emits, its
         '+' child where the walk branches
  edge   levels * SLOT_ROW: one slot of the edge hash table
         (slot_parent | slot_word | slot_child) a level consumed: the
         first probe hits (the builder allows 8)
  out    4 per matched filter id + 4 (count)

The frontier's second live path behind a `+`, the other seven probes,
the '#' child's own node row and the [B, match_cap] output plane are
what an implementation may add; none is in the floor. No floating-point
work, so bytes bind.
"""

from __future__ import annotations

NODE_ROW = 3 * 4
SLOT_ROW = 3 * 4


def topic_bytes(levels: int, matches: float) -> float:
    return (levels * 4 + 8) + (levels + 1) * NODE_ROW \
        + levels * SLOT_ROW + (4 * matches + 4)

