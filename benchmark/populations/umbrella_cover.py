"""Per-area `#` historians over the filters they cover.

arXiv:1811.07088's cover-heavy subscription set as the repo's own
generator draws it (`tools/workloads.cover_heavy_filters(cover_ratio=
0.5)`: umbrella `#` filters at several depths, covered filters a few
levels below them with `+` by a bitmask, standalone exact filters),
stated in closed form and with every umbrella on an AREA, not on the
top level (the tool's depth-1 umbrellas `d{i % 97}/#` would own the
whole set between 97 of them).

Area a < `areas` has a prefix of D = 2 + a % 6 levels:
`org{a % 50}/area{a}` and the path levels l = 2 .. D-1,
`p{l}w{(a + l) % 97}` (`mixed_depth`'s 97-word vocabulary); its sibling
prefix is the same with `solo{a}` for `area{a}`. Its 100 filters, in
this order (filter number a * 100 + its place here; the connection that
owns a filter is its number % conns), all QoS 0, none `$share`:

  U    1   `<prefix>/#`: the area's historian or dashboard
  C_j  49  j < 49: `<prefix>` and 1 + j % 4 more levels: the last is
           `c{j}`, each earlier one e is `+` where bit e of j // 4 is
           set, else `m{j}e{e}`. Covered by U and by nothing else: its
           last level is its own
  S_k  50  k < 50: `<sibling>/t{k}` for even k, `<sibling>/x{k}/t{k}`
           for odd k: exact, covered by nothing

so half the filters are covered, 1 % are umbrellas that own 49 each,
and by (levels, `+` places, `#` tail) the roots have 13 shapes (6
umbrella depths, exact filters of 3-9 levels), the covered filters 55,
the full set 61: more than the engine's 32-shape table holds, so the
full set is a trie's and the roots fit the table.

Key space `dims` = (areas, 8, 49), key (a, r, pick):

  r 0-3  the topic of C_pick, a `+` level filled with
         `v{(pick + r) % 16}`: matches U and C_pick, fan-out 2
  r 4    `<prefix>/z{pick}`, r 5 `<prefix>/z{pick}/y`: U alone, 1
  r 6-7  S_pick's own topic: fan-out 1 (S_49 gets no traffic)

Mean fan-out 1.5 over the key space; three quarters of the keys match
an umbrella, whose expansion segment holds 50 filters. 8, the 4 / 2 / 2
split, 49 / 50 / 1 and the 16 fill words are constants of the
population, not parameters.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

from benchmark.manifest import ManifestError

VOCAB = 97
ORGS = 50
SLOTS = 8            # key slots r: 4 covered, 2 umbrella-only, 2 standalone
COVERED = 49
STANDALONE = 50
PER_AREA = 1 + COVERED + STANDALONE
FILL = 16            # words a '+' level is filled with
CANDIDATES_METRIC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "layer_metrics", "cover_candidates_per_topic.flood.json")


def refuse_program_without_the_cover_counters() -> None:
    """Fail at once on a program that does not serve this deployment
    from the chip, instead of measuring its host route by the draw.

    Before PR 38 the expansion ran at 256 candidate lanes a topic
    whatever the build knew, a device sub-batch cost about what the
    host trie's does, and the chooser took the cell off the chip or
    did not, run by run: 7,883 deliveries/s with `device_routed_share`
    23.6 %, 12,709 with 86.3 %, and a traced run at 13.3 % whose 3 s
    trace holds no device operation at all (my chip runs, PR 38);
    7,653-9,201 in three runs (PR 36's builder); a median of 12,015
    with the middle half of six runs 2,461 apart, 20 % (the driver's
    first check of PR 36). That program runs the cell to `correct` and
    never hangs. What it reads there is which side of its chooser's
    coin a run drew, on a path this cell is not about; a new cell's
    runs are held to half the bound on both sides, so the parent's
    draw alone refuses the cell whatever the change does (it did, in
    that check), and a traced run in which nothing ran on the device
    is refused too. Every later check has this PR's program or a later
    one as its parent, so nothing is compared with that reading again.
    The question is put to the program that is loaded, in the
    yardstick's own terms, as `fleet_broadcast` puts it: does its
    source name the counter that this cell's
    `cover_candidates_per_topic.flood` reads (the metric's file says
    which)? A program that counts what its expansion verifies can
    report this cell's own metrics; one that lacks the counter would
    leave all three out. A generator process (`loadgen.py`) loads no
    program and has nothing to ask."""
    program = sys.modules.get("emqx_tpu")
    if program is None:
        return
    with open(CANDIDATES_METRIC) as f:
        counter = json.load(f)["args"]["num"][0]
    for where, _dirs, files in os.walk(os.path.dirname(program.__file__)):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(where, name),
                          encoding="utf-8") as f:
                    if counter in f.read():
                        return
    raise ManifestError(
        f"population umbrella_cover has 2,500 umbrellas that own 49 "
        f"filters each, and this program has no {counter}: its "
        f"expansion loses to the host trie, its chooser leaves the "
        f"chip, and the cell would measure the host route by the draw")


class Population:
    def __init__(self, params: dict, conns: int):
        refuse_program_without_the_cover_counters()
        self.areas = int(params["areas"])
        self.conns = conns
        self.dims = (self.areas, SLOTS, COVERED)
        self.n = self.areas * PER_AREA
        self._filters = None

    @staticmethod
    def _prefix(a: int, solo: bool = False) -> list:
        depth = 2 + a % 6
        return [f"org{a % ORGS}", f"solo{a}" if solo else f"area{a}"] \
            + [f"p{l}w{(a + l) % VOCAB}" for l in range(2, depth)]

    @staticmethod
    def _below(j: int, fill=None) -> list:
        """The levels of C_j under its area's prefix; with `fill`, the
        word that stands where the filter has a `+`."""
        more = 1 + j % 4
        return [(fill or "+") if (j // 4 >> e) & 1 else f"m{j}e{e}"
                for e in range(more - 1)] + [f"c{j}"]

    @staticmethod
    def _alone(k: int) -> list:
        return [f"x{k}", f"t{k}"] if k % 2 else [f"t{k}"]

    def _area(self, a: int) -> list:
        prefix, solo = self._prefix(a), self._prefix(a, solo=True)
        return ["/".join(prefix + ["#"])] \
            + ["/".join(prefix + self._below(j)) for j in range(COVERED)] \
            + ["/".join(solo + self._alone(k)) for k in range(STANDALONE)]

    def filters(self) -> list:
        if self._filters is None:
            self._filters = [f for a in range(self.areas)
                             for f in self._area(a)]
        return self._filters

    def subscriptions(self, conn: int) -> list:
        return [(f, 0) for f in self.filters()[conn::self.conns]]

    def topic(self, key: int) -> str:
        a, rest = divmod(int(key), SLOTS * COVERED)
        r, pick = divmod(rest, COVERED)
        if r < 4:
            return "/".join(self._prefix(a) + self._below(
                pick, fill=f"v{(pick + r) % FILL}"))
        if r < 6:
            return "/".join(self._prefix(a) + [f"z{pick}"]
                            + (["y"] if r == 5 else []))
        return "/".join(self._prefix(a, solo=True) + self._alone(pick))

    def expect(self, keys) -> np.ndarray:
        """Per key, the connection owning the area's umbrella (where
        the topic lies under it) and the one owning C_pick or S_pick."""
        a, r, pick = np.unravel_index(np.asarray(keys, np.int64), self.dims)
        first = a * PER_AREA
        umbrella = first % self.conns
        covered = (first + 1 + pick) % self.conns
        alone = (first + 1 + COVERED + pick) % self.conns
        return np.stack([np.where(r < 6, umbrella, alone),
                         np.where(r < 4, covered, -1)], axis=1)
