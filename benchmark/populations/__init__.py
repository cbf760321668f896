"""Subscription populations, found by the name a configuration gives.

A population module has a `Population(params, conns)` with `dims`,
`filters()` (the topic filters, without any `$share/<group>/` prefix),
`subscriptions(conn)`, `topic(key)` and `expect(keys)`: an int array
[keys, most matches a key has] of the connection that owns each
matching plain subscription, -1 where there is none.

The group half, for a population with `$share` subscriptions (absent =
no groups): `expect_shared(keys)` -> int array [keys, G, M], for each
key the member connections of each matching group, -1 padded: exactly
one of a group's members gets the message. `group_ids(keys)` -> int
array [keys, G], one number per matching group (-1 where there is
none), equal for two keys exactly when they match the same group: the
round-robin balance is counted per group over the run. `sub_qos` =
{"plain": q, "shared": q}, the QoS its subscriptions ask for. No two
groups matching one key may share a member connection.
"""

from __future__ import annotations

import importlib

import numpy as np


def load(config: dict):
    """The population a configuration file names, at its own size."""
    mod = importlib.import_module(
        f"benchmark.populations.{config['population']['name']}")
    return mod.Population(config["population"]["params"],
                          int(config["connections"]["subscribers"]))


def members(pop, keys):
    """`expect_shared(keys)`, or None for a population without groups."""
    fn = getattr(pop, "expect_shared", None)
    return None if fn is None else np.asarray(fn(keys))


def expected_count(pop, keys) -> int:
    """Deliveries the guarantees promise for `keys`: one per matching
    plain subscription and one per matching group."""
    n = int((pop.expect(keys) >= 0).sum())
    shared = members(pop, keys)
    if shared is not None:
        n += int((shared >= 0).any(axis=2).sum())
    return n
