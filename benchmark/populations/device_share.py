"""`device/d{i}/+/n{n}/#` filters, a stated share of them `$share` groups.

The reference's own bench shape (`emqx_broker_bench.erl:25-34`,
`device/{{id}}/+/{{num}}/#`), populated as `chip_smoke.py`'s
`Population` is. Filter (i, n), i < ids, n < nums, is owned by
connection i % conns. It is shared when (i * nums + n) % 100 <
shared_pct: then it is subscribed as `$share/<group>/<filter>` at QoS 1
by `members` connections in a row, the owner first; a plain filter is
subscribed at QoS 0 by its owner alone. The topic of key (i, n) is
`device/d{i}/x/n{n}/t`: it matches filter (i, n) and nothing else, so
every message has one delivery.
"""

from __future__ import annotations

import numpy as np


class Population:
    sub_qos = {"plain": 0, "shared": 1}

    def __init__(self, params: dict, conns: int):
        self.ids = int(params["ids"])
        self.nums = int(params["nums"])
        self.shared_pct = int(params["shared_pct"])
        self.group = str(params["group"])
        self.members = int(params["members"])
        self.conns = conns
        self.dims = (self.ids, self.nums)
        if not 1 <= self.members <= conns:
            raise ValueError(f"{self.members} members over {conns} "
                             f"connections")

    def _shared(self, i, n):
        return (i * self.nums + n) % 100 < self.shared_pct

    @staticmethod
    def _filter(i: int, n: int) -> str:
        return f"device/d{i}/+/n{n}/#"

    def filters(self) -> list:
        return [self._filter(i, n) for i in range(self.ids)
                for n in range(self.nums)]

    def subscriptions(self, conn: int) -> list:
        """Its own filters, then its memberships of the groups owned by
        the `members - 1` connections before it."""
        head = f"$share/{self.group}/"
        out = []
        for back in range(self.members):
            owner = (conn - back) % self.conns
            for i in range(owner, self.ids, self.conns):
                for n in range(self.nums):
                    if self._shared(i, n):
                        out.append((head + self._filter(i, n),
                                    self.sub_qos["shared"]))
                    elif not back:
                        out.append((self._filter(i, n),
                                    self.sub_qos["plain"]))
        return out

    def topic(self, key: int) -> str:
        i, n = divmod(int(key), self.nums)
        return f"device/d{i}/x/n{n}/t"

    def _split(self, keys):
        keys = np.asarray(keys, np.int64)
        i, n = np.divmod(keys, self.nums)
        return keys, i % self.conns, self._shared(i, n)

    def expect(self, keys) -> np.ndarray:
        _keys, owner, shared = self._split(keys)
        return np.where(shared, -1, owner)[:, None]

    def expect_shared(self, keys) -> np.ndarray:
        _keys, owner, shared = self._split(keys)
        who = (owner[:, None] + np.arange(self.members)) % self.conns
        return np.where(shared[:, None], who, -1)[:, None, :]

    def group_ids(self, keys) -> np.ndarray:
        keys, _owner, shared = self._split(keys)
        return np.where(shared, keys, -1)[:, None]
