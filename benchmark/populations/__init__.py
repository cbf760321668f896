"""Subscription populations, found by the name a configuration gives.

A population module has a `Population(params, conns)` with `dims`,
`filters()`, `subscriptions(conn)`, `topic(key)` and `expect(keys)`: an
int array [keys, most matches a key has] of the connection that owns
each matching subscription, -1 where there is none.
"""

from __future__ import annotations

import importlib


def load(config: dict):
    """The population a configuration file names, at its own size."""
    mod = importlib.import_module(
        f"benchmark.populations.{config['population']['name']}")
    return mod.Population(config["population"]["params"],
                          int(config["connections"]["subscribers"]))
