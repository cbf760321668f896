"""A device fleet's uplink into `$share` worker pools, commands back down.

`devices` devices behind `gateways` gateway connections, and three
back-end services that each consume the fleet's uplink through one
shared subscription, so that a service's workers split the stream
(`emqx_shared_sub.erl`, `$share/<group>/<filter>`). Connections, in
this order:

  0 .. gateways - 1     gateway c owns device i where i % gateways == c
  then `store` workers  members of  $share/store/up/#
  then `rules` workers  members of  $share/rules/up/+/state/#
  then `alert` workers  members of  $share/alert/up/+/event/+

Every subscription is QoS 1. Filters (without the `$share/<group>/`
prefix), in this order: `down/d{i}/cmd/+` for each device i (plain, its
gateway's), then `up/#`, `up/+/state/#`, `up/+/event/+`: devices + 3
filters in 4 shapes, devices + store + rules + alert subscriptions.
`up/#` covers the two narrower group filters, but they are three
groups: a message that matches two of them has two deliveries.

Key space `dims` = (devices, 20), key (i, slot):

  slot 0 .. 1    down/d{i}/cmd/c{slot}    a command: gateway i % gateways
  slot 2 .. 14   up/d{i}/metric/n{slot}   telemetry: one `store` worker
  slot 15 .. 18  up/d{i}/state/n{slot}    state: `store` + `rules`
  slot 19        up/d{i}/event/n{slot}    an event: `store` + `alert`

With the slot drawn uniformly: 10 % commands, 65 % metrics, 20 % state,
5 % events; 1.25 deliveries and 1.15 group picks a PUBLISH, at most two
groups a message, and no two groups share a member.

`expect(keys)` is [keys, 1] (the gateway, or -1), `expect_shared(keys)`
[keys, 2, largest group]: `store`'s members in row 0, `rules`' or
`alert`'s in row 1, -1 padded; `group_ids(keys)` [keys, 2]: 0 for
`store`, 1 for `rules`, 2 for `alert`, -1 for none.
"""

from __future__ import annotations

import numpy as np

SLOTS = 20
CMD_SLOTS = 2           # slots 0 .. 1 a command
STATE_FROM = 15         # slots 15 .. 18 state
EVENT_SLOT = 19         # slot 19 an event
GROUPS = (("store", "up/#"), ("rules", "up/+/state/#"),
          ("alert", "up/+/event/+"))


class Population:
    sub_qos = {"plain": 1, "shared": 1}

    def __init__(self, params: dict, conns: int):
        self.devices = int(params["devices"])
        self.gateways = int(params["gateways"])
        self.sizes = [int(params[name]) for name, _f in GROUPS]
        if self.gateways + sum(self.sizes) != conns or min(self.sizes) < 1:
            raise ValueError(
                f"fanin_workers: {self.gateways} gateways and pools of "
                f"{self.sizes} are not {conns} connections")
        self.conns = conns
        self.dims = (self.devices, SLOTS)
        # first connection of each pool
        self.first = [self.gateways + sum(self.sizes[:g])
                      for g in range(len(GROUPS))]
        # a pool's connections in a row, -1 past its size; the last row,
        # which group id -1 reads, is nobody
        seat = np.arange(max(self.sizes))
        self._members = np.array(
            [np.where(seat < size, first + seat, -1)
             for first, size in zip(self.first, self.sizes)]
            + [np.full(len(seat), -1)])

    def filters(self) -> list:
        return [f"down/d{i}/cmd/+" for i in range(self.devices)] \
            + [f for _name, f in GROUPS]

    def subscriptions(self, conn: int) -> list:
        if conn < self.gateways:
            return [(f"down/d{i}/cmd/+", 1)
                    for i in range(conn, self.devices, self.gateways)]
        for (name, f), first, size in zip(GROUPS, self.first, self.sizes):
            if first <= conn < first + size:
                return [(f"$share/{name}/{f}", 1)]
        raise ValueError(f"fanin_workers: no connection {conn}")

    def topic(self, key: int) -> str:
        i, slot = divmod(int(key), SLOTS)
        if slot < CMD_SLOTS:
            return f"down/d{i}/cmd/c{slot}"
        kind = "metric" if slot < STATE_FROM else \
            "state" if slot < EVENT_SLOT else "event"
        return f"up/d{i}/{kind}/n{slot}"

    def _split(self, keys):
        i, slot = np.divmod(np.asarray(keys, np.int64), SLOTS)
        return i, slot

    def expect(self, keys) -> np.ndarray:
        i, slot = self._split(keys)
        return np.where(slot < CMD_SLOTS, i % self.gateways, -1)[:, None]

    def group_ids(self, keys) -> np.ndarray:
        _i, slot = self._split(keys)
        up = slot >= CMD_SLOTS
        second = np.where(slot == EVENT_SLOT, 2,
                          np.where(slot >= STATE_FROM, 1, -1))
        return np.stack([np.where(up, 0, -1), second], axis=1)

    def expect_shared(self, keys) -> np.ndarray:
        return self._members[self.group_ids(keys)]
