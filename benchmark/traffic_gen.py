"""The one general traffic generator's draws: keys from data.

A configuration names how its publishers choose topics (`publish.keys`)
and a traffic mix names the loop and the connections; nothing here
knows a cell by name. Everything is drawn from the run's seed.
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, *stream])


def draw_keys(rng: np.random.Generator, n: int, dims: tuple,
              spec: dict) -> np.ndarray:
    """n key indices over the population's key space `dims`.

    {"dist": "uniform"}: every dimension uniform."""
    if spec["dist"] != "uniform":
        raise ValueError(f"unknown key distribution {spec['dist']!r}")
    cols = [rng.integers(0, d, n) for d in dims]
    return np.ravel_multi_index(cols, dims).astype(np.int64)
