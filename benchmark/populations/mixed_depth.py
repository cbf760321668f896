"""Mixed `+` / `#` filters over topic trees of 4 to 12 levels.

BASELINE config 3's filter population as the repo's own micro-benchmark
draws it (`tools/workloads.shape_spread_filters(tail_hash=True)`, as
`tools/skew_bench.py` installs it: depth 3 to 10 before the tail, one
`+` at a rotating level, half the filters `#`-tailed, no filter covering
another), given a closed-form oracle and a second matching filter for a
quarter of the topics.

Stream i = g * streams + m (g < gateways, m < streams) has D = 3 + i % 8
levels before its tail: `gw{g}/n{m}` and the path levels l = 2 .. D-1,
`p{l}w{(i + l) % 97}` (a 97-word vocabulary a level, shared across
streams: only `gw{g}/n{m}` is unique to a stream).

  A_i  its own filter: the path with `+` at path index (i // 16) % (D - 2),
       then `#` when (i // 8) % 2 == 1, else `t{i % 13}`; owned by
       connection i % conns
  B_i  for i % 4 == 1 only (D is 4 or 8): the path with `+` at the next
       path index, then `#`; owned by connection (i // 4) % conns

all QoS 0, none `$share`. The topic of key i is the path with
`t{i % 13}` after it, and one more level `e` when A_i is `#`-tailed. It
matches A_i, B_i where there is one, and nothing else: the two differ in
where their `+` stands, so neither covers the other, and every other
stream's filters start with another `gw{g}/n{m}`. 8 depths x their
D - 2 places for the `+` x 2 tails = 72 filter shapes.
"""

from __future__ import annotations

import inspect
import sys

import numpy as np

from benchmark.manifest import ManifestError

VOCAB = 97
TAILS = 13


def refuse_program_without_trie_windows() -> None:
    """Fail at once on a program that can never finish this cell's
    set-up, instead of hanging until the run is killed.

    72 shapes overflow the engine's 32-shape table, so the snapshot is
    a trie. Set-up (`run.py`) waits up to 900 s, longer than a run may
    take, for the engine to report its fused (8, 1024) class warm, and
    before PR 28 a trie snapshot never fused: the window programs could
    not scan the trie NFA. The driver tries a new cell on the parent
    commit with these files laid over it, and there a run that is
    killed refuses the PR where one that exits does not. So the
    question is put to the program that is loaded: do its window
    programs take the NFA's `frontier_cap`, the keyword the engine
    passes them for a trie? A generator process (`loadgen.py`) loads no
    program and has nothing to ask. Bounding set-up's wait is a
    `benchmark` PR's edit to `run.py`; this check goes with it."""
    if "emqx_tpu" not in sys.modules:
        return
    from emqx_tpu.models import router_engine
    if "frontier_cap" not in inspect.signature(
            router_engine.route_window_full).parameters:
        raise ManifestError(
            "population mixed_depth builds a trie snapshot, and this "
            "program's route_window_full cannot scan the trie NFA: its "
            "fused class would never come warm")


class Population:
    def __init__(self, params: dict, conns: int):
        refuse_program_without_trie_windows()
        self.gateways = int(params["gateways"])
        self.streams = int(params["streams"])
        self.conns = conns
        self.dims = (self.gateways, self.streams)
        self.n = self.gateways * self.streams

    def _levels(self, i: int) -> list:
        """`gw{g}/n{m}` and the path levels of stream i."""
        g, m = divmod(i, self.streams)
        depth = 3 + i % 8
        return [f"gw{g}", f"n{m}"] + [f"p{l}w{(i + l) % VOCAB}"
                                      for l in range(2, depth)]

    @staticmethod
    def _hash_tailed(i: int) -> bool:
        return (i // 8) % 2 == 1

    def _filter(self, i: int, shift: int, tail: str) -> str:
        """Stream i's path with `+` `shift` places on from its own
        filter's, then `tail`."""
        lv = self._levels(i)
        places = len(lv) - 2
        lv[2 + ((i // 16) % places + shift) % places] = "+"
        return "/".join(lv + [tail])

    def _own(self, i: int) -> str:
        return self._filter(
            i, 0, "#" if self._hash_tailed(i) else f"t{i % TAILS}")

    def _cross(self, i: int) -> str:
        return self._filter(i, 1, "#")

    def filters(self) -> list:
        return [self._own(i) for i in range(self.n)] \
            + [self._cross(i) for i in range(1, self.n, 4)]

    def subscriptions(self, conn: int) -> list:
        own = [(self._own(i), 0) for i in range(conn, self.n, self.conns)]
        cross = [(self._cross(i), 0) for i in range(1, self.n, 4)
                 if (i // 4) % self.conns == conn]
        return own + cross

    def topic(self, key: int) -> str:
        i = int(key)
        lv = self._levels(i) + [f"t{i % TAILS}"]
        if self._hash_tailed(i):
            lv.append("e")
        return "/".join(lv)

    def expect(self, keys) -> np.ndarray:
        """Per key, the connection owning A_i and the one owning B_i
        (-1 where the stream has no B)."""
        i = np.asarray(keys, np.int64)
        return np.stack([i % self.conns,
                         np.where(i % 4 == 1, (i // 4) % self.conns, -1)],
                        axis=1)
