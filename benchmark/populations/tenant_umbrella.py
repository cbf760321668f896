"""A tenant-wide `#` over the tenant's whole tree, the area historians
nested below it.

`umbrella_cover`'s areas exactly as they are, under `orgs` tenants
(area a is tenant `org{a % orgs}`'s), plus one `org{k}/#` a tenant:
the data-lake, billing or audit bridge that a multi-tenant platform
holds over everything the tenant publishes. This is arXiv:1811.07088's
cover-heavy set with the depth-1 umbrellas that
`tools/workloads.cover_heavy_filters` draws (`d{k}/#`, a third of its
umbrellas) kept, where `umbrella_cover` left them out: cover chains
are two deep, `org{k}/#` over `org{k}/area{a}/...#` over the 49 filters
an area's historian covers, and an org's umbrella also covers the 50
exact filters under each of its areas' sibling prefixes, which nothing
else covers.

Filters, in this order (the connection that owns a filter is its
number % conns), all QoS 0, none `$share`:

  areas * 100   `umbrella_cover`'s, numbered a * 100 + place, unchanged
  orgs          `org{k}/#`, number areas * 100 + k

At the configuration's size (2,500 areas, 50 orgs) an org's umbrella
covers 5,000 filters: 50 historians, the 2,450 filters those cover and
2,500 exact ones; 62 filter shapes (`umbrella_cover`'s 61 and the
one-level `#`). The rehearsal keeps an org's umbrella wide (12 areas in
2 orgs: 600 filters under each), which is why `orgs` is a parameter.

Keys and topics are `umbrella_cover`'s: `dims` = (areas, 8, 49). Every
topic lies under its org's umbrella as well, so fan-out is 3 for slots
r 0-3 (org, area, C_pick), 2 for r 4-5 (org, area) and 2 for r 6-7
(org, S_pick): 2.5 over the key space. `expect(keys)` is [keys, 3]: the
connection that owns the org's umbrella, then `umbrella_cover`'s two
columns.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

from benchmark.manifest import ManifestError
from benchmark.populations import umbrella_cover
from benchmark.populations.umbrella_cover import PER_AREA

ROOTS_METRIC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "layer_metrics", "cover_roots_per_topic.flood.json")


def refuse_program_without_the_roots_counter() -> None:
    """Fail at once on a program that serves this deployment from the
    host, instead of measuring its host route.

    Before PR 42 a cover's owned count was capped in fid order
    (`assign_owners(own_budget=256)`): `org{k}/#` took the first 256
    filters of its tenant, nothing below it was a root any more, so no
    area historian owned anything, the build sized the candidate plane
    at its ceiling of 256 for a largest segment of 257, and every
    topic of every tenant flagged the expansion's own overflow and went
    to the host route (what that program read on the chip is in
    `PERF.md`, section 6, PR 42). It runs the cell to `correct` and
    never hangs; what it would measure is the host's trie, on a cell
    about the chip's covering path, and a traced run of it may hold no
    device operation. So the question is put to the program that is
    loaded, as `umbrella_cover` and `fleet_broadcast` put theirs: does
    its source name the counter that this cell's own
    `cover_roots_per_topic.flood` reads (the metric's file says
    which)? A program that counts the roots entering its expansion
    came with the rule that lets a wide root own nothing. A generator
    process (`loadgen.py`) loads no program and has nothing to ask."""
    program = sys.modules.get("emqx_tpu")
    if program is None:
        return
    with open(ROOTS_METRIC) as f:
        counter = json.load(f)["args"]["num"][0]
    for where, _dirs, files in os.walk(os.path.dirname(program.__file__)):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(where, name),
                          encoding="utf-8") as f:
                    if counter in f.read():
                        return
    raise ManifestError(
        f"population tenant_umbrella has a tenant-wide '#' over "
        f"thousands of filters, and this program has no {counter}: "
        f"its build lets that umbrella own the first filters it meets, "
        f"every topic overflows the candidate plane, and the cell "
        f"would measure the host route")


class Population(umbrella_cover.Population):
    def __init__(self, params: dict, conns: int):
        refuse_program_without_the_roots_counter()
        super().__init__({"areas": params["areas"]}, conns)
        self.orgs = int(params["orgs"])
        if not 0 < self.orgs <= self.areas:
            raise ManifestError(
                f"tenant_umbrella: {self.orgs} orgs over {self.areas} "
                f"areas: every org's umbrella has to cover an area")
        self.n += self.orgs

    def _prefix(self, a: int, solo: bool = False) -> list:
        return [f"org{a % self.orgs}"] \
            + umbrella_cover.Population._prefix(a, solo)[1:]

    def filters(self) -> list:
        if self._filters is None:
            self._filters = super().filters() \
                + [f"org{k}/#" for k in range(self.orgs)]
        return self._filters

    def expect(self, keys) -> np.ndarray:
        """Per key, the connection owning the org's umbrella, then
        `umbrella_cover`'s two: the area's umbrella or the standalone
        filter, and the covered filter."""
        keys = np.asarray(keys, np.int64)
        a = keys // (self.dims[1] * self.dims[2])
        org = (self.areas * PER_AREA + a % self.orgs) % self.conns
        return np.concatenate([org[:, None], super().expect(keys)], axis=1)
