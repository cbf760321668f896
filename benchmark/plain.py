"""Plain MQTT topic matching, written from the specification.

MQTT 3.1.1 section 4.7: a filter and a topic name are split on `/`; they
match level by level, `+` stands for exactly one level, a trailing `#`
for the parent and any number of further levels, and a filter that
starts with a wildcard does not match a topic whose first level starts
with `$`. MQTT 5 section 4.8.2: a shared subscription's filter is
`$share/{group}/{filter}`; the sessions that subscribe to one under the
same group name form a group, and each message that matches the filter
goes to one of them. Imports nothing of the program: this is the
reference the populations' closed-form oracles are cross-checked
against, by brute force over every filter.
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


def split_share(filt: str) -> tuple:
    """(group, filter) of `$share/{group}/{filter}`; (None, filt) for a
    filter that is not shared."""
    if not filt.startswith("$share/"):
        return None, filt
    group, _, rest = filt[7:].partition("/")
    if not group or not rest or "+" in group or "#" in group:
        raise ValueError(f"malformed shared subscription {filt!r}")
    return group, rest


def match(topic: str, filt: str) -> bool:
    return match_levels(topic.split("/"), filt.split("/"))


def matching(topic: str, split_filters: list) -> list:
    """Indices of the pre-split filters that match `topic`."""
    t = topic.split("/")
    return [k for k, f in enumerate(split_filters) if match_levels(t, f)]
