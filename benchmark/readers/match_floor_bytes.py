"""Least bytes to match one topic against a filter set, by the work.

Counted from what ANY matcher has to move, not from what this one does
(`nfa_bytes.py` counts a level-stepped trie walk, `route_bytes.py` a
shape-hash probe; a covering snapshot is neither):

  in    levels * 4 (interned words) + 4 (length)
  tell  for each filter the topic matches, that filter's levels * 4:
        the words (or wildcard marks) that tell it from its neighbours
        have to be compared with the topic's at least once
  out   4 per matched filter id

A filter that does not match costs nothing in the floor; hash probes
that miss, a candidate that fails its verification, padding lanes and
sorting are what an implementation may add. No floating-point work, so
bytes bind.

Which filters a topic matches, and how many levels each has, comes from
a plain walk written here from the MQTT specification (section 4.7, as
`benchmark/plain.py`: `+` one level, a trailing `#` the parent and
anything below, no root wildcard for a `$` topic) over a dict trie of
the population's filters: nothing of the program is imported.
"""

from __future__ import annotations


class FilterLevels:
    """The population's filters as a trie of dicts; `matched(topic)`
    gives the number of levels of every filter the topic matches."""

    END, HASH = 0, 1        # keys no topic level can be (levels are str)

    def __init__(self, filters):
        self.root: dict = {}
        for f in filters:
            node = self.root
            levels = f.split("/")
            for w in levels[:-1] if levels[-1] == "#" else levels:
                node = node.setdefault(w, {})
            kind = self.HASH if levels[-1] == "#" else self.END
            node[kind] = node.get(kind, 0) + 1

    def matched(self, topic: str) -> list:
        t = topic.split("/")
        out: list = []
        live = [self.root]
        for depth, w in enumerate(t):
            wild_ok = not (depth == 0 and w.startswith("$"))
            nxt = []
            for node in live:
                if wild_ok and self.HASH in node:
                    out += [depth + 1] * node[self.HASH]
                child = node.get(w)
                if child is not None:
                    nxt.append(child)
                if wild_ok and "+" in node:
                    nxt.append(node["+"])
            live = nxt
        for node in live:
            out += [len(t)] * node.get(self.END, 0)
            out += [len(t) + 1] * node.get(self.HASH, 0)   # a/# matches a
        return out


def topic_bytes(levels: int, filter_levels) -> float:
    """`levels` of the topic, `filter_levels` of each filter it
    matches."""
    return (levels * 4 + 4) + sum(4 * fl + 4 for fl in filter_levels)
