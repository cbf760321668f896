#!/usr/bin/env python
"""Shared bench topic populations (ISSUE 18 satellite).

The microbenches (skew/churn/cover) used to hand-roll their filter
generators inline — all uniform populations with zero filter-over-filter
cover relations, which silently hides what subscription covering buys.
This module is the one place bench populations come from:

  shape_spread_filters   the legacy generator the skew/churn benches
                         inlined (byte-identical output, so historical
                         rates stay comparable): depth 3..10, one '+'
                         at a rotating level, shared d%97 vocabulary up
                         front. NO cover relations by construction
                         (every filter carries its own s{i} literals).
  cover_heavy_filters    what real broker populations look like per
                         arXiv:1811.07088: umbrella filters (`fleet/#`)
                         cover a configurable fraction of narrower
                         subscriptions under their prefix; depths drawn
                         from a Zipf so shallow umbrellas dominate.
  concretize             filter -> one concrete matching topic (wildcard
                         levels materialized; a trailing '#' gains one
                         concrete level so the topic exercises the
                         multi-level tail).

Populations are deterministic per (n, knobs, seed): benches stay
reproducible and resume signatures can key on the knobs alone.
"""

from __future__ import annotations

import numpy as np


def shape_spread_filters(n: int, *, tail_hash: bool = False) -> list:
    """The legacy inline generator, extracted verbatim: `n` wildcard
    filters spread over many SHAPES (depth and '+' position vary) with
    zero cover relations. tail_hash alternates '#' tails (the skew
    bench's variant); off, every filter ends in its own t{i} literal
    (the churn bench's variant)."""
    filters = []
    for i in range(n):
        depth = 3 + (i % 8)            # 8 depths x 2 tails = 16 shapes
        mid = i % depth
        levels = [f"s{i}" if li != mid else "+" for li in range(depth)]
        levels[0] = f"d{i % 97}"       # shared vocabulary up front
        tail = ("#" if i % 2 else f"t{i}") if tail_hash else f"t{i}"
        filters.append("/".join(levels) + "/" + tail)
    return filters


def cover_heavy_filters(n: int, *, cover_ratio: float = 0.5,
                        zipf_a: float = 1.4, max_depth: int = 8,
                        vocab: int = 97, seed: int = 7) -> list:
    """Cover-heavy population: ~`cover_ratio` of the `n` filters are
    covered by a broader umbrella filter already in the set.

    Roots (the covering set) split into umbrellas — trailing-'#'
    filters at a Zipf-drawn depth (shallow dominates, like real fleet/
    building/sensor hierarchies) — and standalone exact/'+' filters
    that cover nothing. Covered filters extend an umbrella's prefix by
    1-2 levels, every third one through a '+' (covered-with-wildcard is
    the case naive prefix tricks get wrong; the device detection must
    still fold it). At the sizes the tests draw, an umbrella's fan-in
    stays under what the engine lets a root own (its candidate ceiling
    less the other slots of the roots' match row, 192 by default), so
    the requested ratio is what the snapshot detects. Past that (a
    depth-1 umbrella `d{k}/#` covers every filter under its word: n /
    97 of them) the build finds the umbrella wide: it owns nothing and
    stays a root alone, and the narrower umbrellas under it are roots
    again and own their filters (`ops/cover.assign_owners`; the
    benchmark's `tenant-umbrella` is that deployment), so the detected
    ratio is that of the filters some narrower umbrella covers."""
    if not 0 <= cover_ratio < 1:
        raise ValueError(f"cover_ratio {cover_ratio} outside [0, 1)")
    rng = np.random.RandomState(seed)
    n_cov = int(round(n * cover_ratio))
    n_roots = max(1, n - n_cov)
    filters = []
    umbrellas = []
    depths = 1 + (rng.zipf(zipf_a, size=n_roots) - 1) % max_depth
    for i in range(n_roots):
        depth = int(depths[i])
        levels = [f"d{i % vocab}"] + [f"u{i}l{li}"
                                      for li in range(1, depth)]
        if i % 3 == 0:                 # every third root is an umbrella
            umbrellas.append(levels)
            filters.append("/".join(levels) + "/#")
        else:
            filters.append("/".join(levels) + f"/t{i}")
    if not umbrellas:                  # tiny n: keep the ratio honest
        umbrellas.append(["d0"])
        filters[0] = "d0/#"
    for j in range(n_cov):
        base = umbrellas[j % len(umbrellas)]
        ext = 1 + j % 3                # 1-3 levels past the umbrella
        tail = []
        for e in range(ext - 1):
            # '+' per a bitmask of j: covered-with-wildcard plus
            # depth x plus-mask diversity — the full set's SHAPE count
            # far exceeds the covering set's, which is the whole
            # covering bet
            tail.append("+" if (j >> e) & 1 else f"m{j}e{e}")
        tail.append(f"c{j}")
        filters.append("/".join(base + tail))
    return filters


def concretize(f: str, salt: str = "x") -> str:
    """One concrete topic matching `f`: '+' levels materialize to a
    positional literal; a trailing '#' becomes one extra concrete level
    (so `a/#` yields `a/x1`, exercising the hash tail)."""
    parts = f.split("/")
    out = [p if p not in ("+", "#") else f"{salt}{i}"
           for i, p in enumerate(parts)]
    return "/".join(out)
