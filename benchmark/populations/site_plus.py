"""100,000 single-level-wildcard filters over a 6-level hierarchy.

Topics are `site/s{s}/line/l{l}/d{d}/m{m}` (s < sites, l < lines,
d < devs, m < meas). Three filter families, none with `#` or `$share`,
all QoS 0:

  A  site/s{s}/line/l{l}/d{d}/+     one per (s, l, d)
  B  site/s{s}/line/l{l}/+/m{m}     one per (s, l, m < b_meas)
  C  site/s{s}/line/+/+/m{m}        one per (s, m)

so a topic matches its A and its C filter, and its B filter when
m < b_meas. Filter k of a family is owned by connection k % conns; a
connection that owns two of a topic's filters gets two copies, as EMQ X
delivers once per matching subscription.
"""

from __future__ import annotations

import numpy as np


class Population:
    def __init__(self, params: dict, conns: int):
        self.sites = int(params["sites"])
        self.lines = int(params["lines"])
        self.devs = int(params["devs"])
        self.meas = int(params["meas"])
        self.b_meas = int(params["b_meas"])
        self.conns = conns
        self.dims = (self.sites, self.lines, self.devs, self.meas)

    def _families(self):
        a = [f"site/s{s}/line/l{l}/d{d}/+" for s in range(self.sites)
             for l in range(self.lines) for d in range(self.devs)]
        b = [f"site/s{s}/line/l{l}/+/m{m}" for s in range(self.sites)
             for l in range(self.lines) for m in range(self.b_meas)]
        c = [f"site/s{s}/line/+/+/m{m}" for s in range(self.sites)
             for m in range(self.meas)]
        return a, b, c

    def filters(self) -> list:
        a, b, c = self._families()
        return a + b + c

    def subscriptions(self, conn: int) -> list:
        return [(f, 0) for fam in self._families()
                for f in fam[conn::self.conns]]

    def topic(self, key: int) -> str:
        s, l, d, m = np.unravel_index(int(key), self.dims)
        return f"site/s{s}/line/l{l}/d{d}/m{m}"

    def expect(self, keys: np.ndarray) -> np.ndarray:
        """Per key, the connection owning each matching filter (-1 pads
        a filter the key does not match)."""
        keys = np.asarray(keys, np.int64)
        s, l, d, m = np.unravel_index(keys, self.dims)
        ka = (s * self.lines + l) * self.devs + d
        kb = (s * self.lines + l) * self.b_meas + m
        kc = s * self.meas + m
        return np.stack([ka % self.conns, kc % self.conns,
                         np.where(m < self.b_meas, kb % self.conns, -1)],
                        axis=1)
