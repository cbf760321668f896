"""Plain MQTT topic matching, written from the specification.

MQTT 3.1.1 section 4.7: a filter and a topic name are split on `/`; they
match level by level, `+` stands for exactly one level, a trailing `#`
for the parent and any number of further levels, and a filter that
starts with a wildcard does not match a topic whose first level starts
with `$`. Imports nothing of the program: this is the reference the
populations' closed-form oracles are cross-checked against, by brute
force over every filter.
"""

from __future__ import annotations


def match_levels(topic: list, filt: list) -> bool:
    if topic[0].startswith("$") and filt[0] in ("+", "#"):
        return False
    for i, f in enumerate(filt):
        if f == "#":
            return i == len(filt) - 1
        if i >= len(topic):
            return False
        if f != "+" and f != topic[i]:
            return False
    return len(topic) == len(filt)


def match(topic: str, filt: str) -> bool:
    return match_levels(topic.split("/"), filt.split("/"))


def matching(topic: str, split_filters: list) -> list:
    """Indices of the pre-split filters that match `topic`."""
    t = topic.split("/")
    return [k for k, f in enumerate(split_filters) if match_levels(t, f)]
