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

    {"dist": "uniform"}: every dimension uniform.
    {"dist": "zipf", "s": 1.3, "dim": 0}: dimension `dim` is
    min(zipf(s) - 1, d - 1), rank 0 the hottest and the tail beyond the
    dimension folded onto its last index, as `chip_smoke.Traffic` and
    `bench.py` draw a device id; the others uniform."""
    if spec["dist"] not in ("uniform", "zipf"):
        raise ValueError(f"unknown key distribution {spec['dist']!r}")
    skewed = int(spec.get("dim", 0)) if spec["dist"] == "zipf" else None
    cols = [np.minimum(rng.zipf(float(spec["s"]), n) - 1, d - 1)
            if k == skewed else rng.integers(0, d, n)
            for k, d in enumerate(dims)]
    return np.ravel_multi_index(cols, dims).astype(np.int64)
