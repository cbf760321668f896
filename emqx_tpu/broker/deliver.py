"""Parallel fan-out delivery lanes: the session-affine egress stage.

ISSUE 5 tentpole. PR 2-4 made match, readback and churn device-fast,
but every delivery still funneled through one serial Python loop on the
consume side (`DeviceRouteEngine._fast_deliver` row-by-row into
`Broker._deliver`), with a `msg.copy()` + headers-dict mutation + hook
dispatch per subscriber — at the north-star fan-out (deliveries/s >>
matches/s) egress was the hard ceiling, and it blocked the next
window's finish. This module turns deliver into its own overlapped
pipeline stage:

- **DeliveryPlan**: the vectorized delivery plan of one consumed
  sub-batch. The engine's row-attribution gather already produces
  `(row_msg, sid, opt, fid)` arrays; the plan buckets them by
  `sid % n_lanes` with ONE stable argsort pass (secondary key `sid`, so
  same-session deliveries are contiguous for coalescing) and hands each
  lane a contiguous slice. A session always hashes to the same lane,
  so per-session FIFO — the MQTT ordering invariant — holds by
  construction. A `$share` member the device picked is such a row too
  (ISSUE 35), behind its message's plain rows and with `share=<group>`
  in its subopts (`GroupPicks`); a pick that cannot be honoured at
  delivery (the member left) goes once through the host's dispatch of
  the group (`_repick`). Slow-path messages (rich subopts,
  delta-matched, dirty filters, host fallbacks; of the shared ones
  those whose group changed since the snapshot, was created after it
  or has a member on another node, and every message under a cluster)
  ride the SAME plan as ordered closures behind an all-lanes barrier:
  every lane finishes its fast slices first, exactly one worker runs
  the slow closures in batch order, and no lane proceeds past the
  barrier meanwhile (fast rows first, then slow messages, per window).

- **DeliveryLanePool**: a small pool of asyncio lane workers (config
  `broker.deliver_lanes` / env `EMQX_TPU_DELIVER_LANES`, default
  `min(4, cpus)`; `=0` restores the inline loop exactly — the A/B
  baseline) consuming per-lane queues. The batcher's consume stage
  submits the plan and returns, so delivery overlaps the next window's
  dispatch/materialize (which run on executor threads and release the
  GIL in XLA); `admit()` bounds outstanding
  plans and propagates backpressure to the batcher's `_inflight` queue,
  and `drain()` serializes host-routed batches behind in-flight lane
  work so device/host interleaving cannot reorder a session's stream.

- **DeliveryView**: the copy-on-write per-delivery message. Replaces
  the per-subscriber `msg.copy()` + `headers["subopts"]` mutation with
  one small object sharing the frozen payload/topic/headers of the
  routed message and overlaying `subopts`; the first write (set_header
  / set_flag / update_expiry) materializes private dicts, and `copy()`
  yields a real, independent `Message` — so downstream enrichment
  (session._enrich) is untouched. Metric/hook bookkeeping
  (`messages.delivered`, `message.delivered`) is batched per lane
  slice instead of per row; same-session runs within a slice coalesce
  into one `deliver_batch()` call (one session accept + one socket
  drain) when the subscriber supports it.

- **The plan's table** (ISSUE 41): `submit` cuts the session runs and
  finds the distinct (message, subopts word) keys by numpy; a key has
  ONE view and one QoS 0 frame a protocol version for the whole plan,
  built on first touch by whichever lane, chunk or retry asks
  (`DeliveryPlan.view` / `.joined`). A run whose subscriber has
  `deliver_frames` (`Channel`) and whose rows all share their frame
  goes out as one gather, one `join` and one write, no Python a row;
  any other run is walked row by row as before, its views from the
  same table.

Ordering contract (what the property tests pin): for every session,
the delivered sequence under `deliver_lanes=N` is identical to the
inline `deliver_lanes=0` sequence. Within a window the inline order is
"all fast rows, then slow messages in batch order"; lanes reproduce it
with the slice-then-barrier queueing above, and windows serialize
per-lane because plans enqueue in consume (FIFO) order. The one place
the two differ: the inline loop has no shared rows (a shared message
is a slow one there), so a session that holds plain and `$share`
subscriptions sees a window's clean shared messages among its plain
ones, in message order, where the inline loop delivers them after;
a topic's messages keep their order either way, which is MQTT's.
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
from bisect import bisect_left
from typing import Callable, Optional

import numpy as np

from emqx_tpu.mqtt import packet as P
from emqx_tpu.mqtt.frame import serialize

log = logging.getLogger("emqx.deliver")

# a fast-path message whose deliveries were handed to the lanes: the
# consume loop must not treat it as "needs the slow path" (None) nor as
# a settled count (int) — the plan's finalize writes the real count
DEFERRED = object()


def _unpack_opts(b: int) -> dict:
    return {"qos": b & 0x3, "nl": (b >> 2) & 1, "rap": (b >> 3) & 1,
            "rh": (b >> 4) & 0x3}


# The packed subopts word is 6 bits (qos:2 | nl:1 | rap:1 | rh:2), so
# there are exactly 64 distinct unpacked dicts — precompute them all
# once instead of re-unpacking (and re-dict-copying) per delivery.
# CONTRACT: these dicts are FROZEN — every consumer treats delivered
# subopts as read-only (session._enrich only reads; dispatch paths that
# need to extend them build a new dict, e.g. dict(opts, share=g)).
OPT_TABLE = tuple(_unpack_opts(b) for b in range(64))


def resolve_deliver_lanes(configured=None) -> int:
    """The one deliver-lanes resolution: config beats
    EMQX_TPU_DELIVER_LANES beats the built-in min(4, cpus). 0 disables
    the lanes (the inline-loop A/B baseline); negatives are a
    deployment error worth failing loudly on."""
    if configured is not None:
        val = int(configured)
    else:
        env = os.environ.get("EMQX_TPU_DELIVER_LANES")
        if env is None:
            return min(4, os.cpu_count() or 1)
        try:
            val = int(env)
        except ValueError:
            raise ValueError(
                f"EMQX_TPU_DELIVER_LANES={env!r} is not an integer")
    if val < 0:
        raise ValueError(f"deliver_lanes must be >= 0, got {val}")
    return val


class GroupPicks:
    """What the lanes need of a snapshot to deliver a `$share` member
    the device picked as a row of a plan. Such a row's `opt` word holds,
    above the 6 bits of packed subopts, 1 + the slot of its group (a
    plain row holds 0 there): `keys` is slot -> (filter, group name),
    `subopts` the frozen dict a word stands for, the packed options
    with `share=<group>` beside them as the host's dispatch gives them
    (one dict a (packed opts, group), not one a delivery), `redispatch`
    the host's own dispatch of one group, (filter, group, message) ->
    delivered, for a pick that cannot be honoured."""

    __slots__ = ("keys", "redispatch", "_subopts")

    def __init__(self, keys: list, redispatch: Callable):
        self.keys = keys
        self.redispatch = redispatch
        self._subopts: dict[tuple, dict] = {}

    @staticmethod
    def words(opt: np.ndarray, slot: np.ndarray) -> np.ndarray:
        return (opt & 0x3F) | ((slot + 1) << 6)

    def key(self, word: int) -> tuple:
        return self.keys[(word >> 6) - 1]

    def subopts(self, word: int) -> dict:
        key = (word & 0x3F, self.key(word)[1])
        so = self._subopts.get(key)
        if so is None:
            so = self._subopts[key] = dict(OPT_TABLE[key[0]], share=key[1])
        return so


class _ViewHeaders:
    """Read-through headers mapping of a DeliveryView: the base
    message's headers with `subopts` overlaid, no dict built. Writing
    through it materializes the view's private headers dict first
    (copy-on-write)."""

    __slots__ = ("_v",)

    def __init__(self, view: "DeliveryView"):
        self._v = view

    def get(self, key, default=None):
        h = self._v._headers
        if h is not None:
            return h.get(key, default)
        if key == "subopts":
            return self._v._subopts
        return self._v._base_headers.get(key, default)

    def __getitem__(self, key):
        h = self._v._headers
        if h is not None:
            return h[key]
        if key == "subopts":
            return self._v._subopts
        return self._v._base_headers[key]

    def __contains__(self, key):
        h = self._v._headers
        if h is not None:
            return key in h
        return key == "subopts" or key in self._v._base_headers

    def __setitem__(self, key, val):
        self._v._materialize_headers()[key] = val

    def pop(self, key, *a):
        return self._v._materialize_headers().pop(key, *a)

    def setdefault(self, key, default=None):
        return self._v._materialize_headers().setdefault(key, default)

    def update(self, *a, **kw):
        self._v._materialize_headers().update(*a, **kw)

    def __delitem__(self, key):
        del self._v._materialize_headers()[key]

    def popitem(self):
        return self._v._materialize_headers().popitem()

    def clear(self):
        self._v._materialize_headers().clear()

    def _as_dict(self) -> dict:
        h = self._v._headers
        if h is not None:
            return dict(h)
        d = dict(self._v._base_headers)
        d["subopts"] = self._v._subopts
        return d

    def items(self):
        return self._as_dict().items()

    def keys(self):
        return self._as_dict().keys()

    def values(self):
        return self._as_dict().values()

    def copy(self) -> dict:
        return self._as_dict()

    def __iter__(self):
        return iter(self._as_dict())

    def __len__(self):
        return len(self._as_dict())

    def __eq__(self, other):
        if isinstance(other, _ViewHeaders):
            other = other._as_dict()
        return self._as_dict() == other

    def __repr__(self):
        return repr(self._as_dict())


class DeliveryView:
    """Copy-on-write per-delivery message: shares the routed message's
    payload/topic/flags/headers and overlays `subopts` — the lightweight
    replacement for `msg.copy()` + `headers["subopts"] = subopts` on
    the lane fast path. Message-API compatible: reads delegate, the
    first write materializes a private dict, `copy()` returns a real
    independent Message (so session._enrich keeps working unchanged).

    Copy-on-write boundary: mutations through the Message API
    (set_flag / set_header / headers[...] / update_expiry) are
    isolated; the `flags` and `extra` dicts read through to the BASE
    message until a set_flag materializes — a consumer that mutates
    `msg.flags`/`msg.extra` by direct dict access would write the
    routed message every subscriber shares. No in-repo consumer does
    (session enrichment copies first; hooks read), and delivered
    messages are read-only by the Subscriber protocol contract
    (pubsub.py) — `copy()` first if you must mutate beyond the API."""

    __slots__ = ("topic", "payload", "qos", "from_", "id", "ts", "extra",
                 "_base_flags", "_base_headers", "_subopts", "_flags",
                 "_headers", "_wire", "_tally")

    def __init__(self, msg, subopts: dict):
        self.topic = msg.topic
        self.payload = msg.payload
        self.qos = msg.qos
        self.from_ = msg.from_
        self.id = msg.id
        self.ts = msg.ts
        self.extra = msg.extra
        self._base_flags = msg.flags
        self._base_headers = msg.headers
        self._subopts = subopts
        self._flags = None
        self._headers = None
        self._wire = None       # protocol version -> shared frame | None
        # [frames serialized] of the plan whose view this is, if any
        # (`pipeline.deliver.frames_built`)
        self._tally: Optional[list] = None

    # -- the frame every subscriber gets (ROADMAP Speed 1) --
    def wire_qos0(self, ver: int, clientid: str) -> Optional[bytes]:
        """This delivery's PUBLISH frame at protocol `ver` where it is
        the same bytes for every subscriber the view is shared by
        (`frame_qos0`) and `clientid` is not the publisher under a
        no-local subscription. None where a subscriber's own copy has
        to be built: the caller then takes the path every delivery took
        before (`Session.deliver` + `_to_publish`)."""
        if self._subopts.get("nl") and self.from_ == clientid:
            return None
        return self.frame_qos0(ver)

    def shares_frame(self) -> bool:
        """What `frame_qos0` asks of the message and the subscription,
        without serializing: QoS 0 after the subscription's cap (no
        packet id), no `subid`, no expiry to recompute at send time,
        nothing written through the view."""
        so = self._subopts
        props = self._base_headers.get("properties")
        return min(self.qos, so.get("qos", 0)) == 0 \
            and self._headers is None and self._flags is None \
            and "subid" not in so \
            and not (props and "message_expiry_interval" in props)

    def frame_qos0(self, ver: int) -> Optional[bytes]:
        """The frame whoever the subscriber is, serialized once a
        (view, version); None where `shares_frame` says no."""
        w = self._wire
        if w is None:
            w = self._wire = {}
        elif ver in w:
            return w[ver]
        data = None
        if self.shares_frame():
            so = self._subopts
            flags = self._base_flags
            props = self._base_headers.get("properties")
            # Session._enrich: the retain bit survives only with
            # retain-as-published or on a retained-store replay
            retain = bool(flags.get("retain")) and bool(
                so.get("rap") or flags.get("retained"))
            data = serialize(P.Publish(
                topic=self.topic, payload=self.payload, qos=0,
                retain=retain, dup=bool(flags.get("dup")), packet_id=0,
                properties=dict(props or {}) if ver == 5 else None), ver)
            if self._tally is not None:
                self._tally[0] += 1
        w[ver] = data
        return data

    # -- copy-on-write materialization --
    def _materialize_headers(self) -> dict:
        if self._headers is None:
            h = dict(self._base_headers)
            h["subopts"] = self._subopts
            self._headers = h
        return self._headers

    def _materialize_flags(self) -> dict:
        if self._flags is None:
            self._flags = dict(self._base_flags)
        return self._flags

    @property
    def headers(self):
        if self._headers is not None:
            return self._headers
        return _ViewHeaders(self)

    @property
    def flags(self):
        return self._flags if self._flags is not None else self._base_flags

    # -- Message API parity (emqx_tpu.broker.message.Message) --
    def get_flag(self, name: str, default: bool = False) -> bool:
        return bool(self.flags.get(name, default))

    def set_flag(self, name: str, val: bool = True) -> "DeliveryView":
        self._materialize_flags()[name] = val
        return self

    @property
    def retain(self) -> bool:
        return self.get_flag("retain")

    @property
    def dup(self) -> bool:
        return self.get_flag("dup")

    @property
    def is_sys(self) -> bool:
        return self.get_flag("sys") or self.topic.startswith("$SYS/")

    def get_header(self, name: str, default=None):
        if self._headers is not None:
            return self._headers.get(name, default)
        if name == "subopts":
            return self._subopts
        return self._base_headers.get(name, default)

    def set_header(self, name: str, val) -> "DeliveryView":
        self._materialize_headers()[name] = val
        return self

    def expiry_interval(self) -> Optional[int]:
        props = self.get_header("properties") or {}
        return props.get("message_expiry_interval")

    def is_expired(self) -> bool:
        from emqx_tpu.broker.message import now_ms
        exp = self.expiry_interval()
        if exp is None:
            return False
        return now_ms() > self.ts + exp * 1000

    def update_expiry(self) -> "DeliveryView":
        from emqx_tpu.broker.message import now_ms
        exp = self.expiry_interval()
        if exp is not None:
            remaining = max(1, exp - (now_ms() - self.ts) // 1000)
            props = dict(self.get_header("properties") or {})
            props["message_expiry_interval"] = int(remaining)
            self.set_header("properties", props)
        return self

    def copy(self):
        from emqx_tpu.broker.message import Message
        if self._headers is not None:
            headers = dict(self._headers)
        else:
            headers = dict(self._base_headers)
            headers["subopts"] = self._subopts
        return Message(topic=self.topic, payload=self.payload,
                       qos=self.qos, from_=self.from_,
                       flags=dict(self.flags), headers=headers,
                       id=self.id, ts=self.ts, extra=dict(self.extra))

    def to_map(self) -> dict:
        from emqx_tpu.broker.message import base62_encode
        return {
            "id": base62_encode(self.id), "topic": self.topic,
            "qos": self.qos, "from": self.from_,
            "payload": self.payload, "flags": dict(self.flags),
            "timestamp": self.ts, "retain": self.retain,
        }

    def to_wire(self) -> dict:
        return self.copy().to_wire()

    def __repr__(self):
        return (f"DeliveryView(topic={self.topic!r}, qos={self.qos}, "
                f"from_={self.from_!r})")


class DeliveryPlan:
    """One consumed sub-batch's delivery work: fast rows destined for
    the lanes plus slow-path closures behind the barrier. `counts[i]`
    accumulates message i's successful deliveries; `target` (the
    LaneCounts list the engine returned to the batcher) is back-filled
    at finalize, and done-callbacks fire last (publisher futures,
    handle release)."""

    __slots__ = ("pool", "msgs", "counts", "fast_idx", "slow_items",
                 "filters", "_chunks", "routed_device", "pending",
                 "done", "target", "_cbs", "s_midx", "_s_opt", "_s_fid",
                 "_lists", "run_lo", "run_sid", "keys", "inv", "views",
                 "frames", "tally", "_nl", "_barrier_left",
                 "_barrier_evt", "trace", "n_rows", "picks")

    def __init__(self, pool: "DeliveryLanePool", msgs: list):
        self.pool = pool
        self.msgs = msgs
        self.counts = np.zeros(len(msgs), np.int64)
        self.fast_idx: list[int] = []
        self.slow_items: list[tuple[int, Callable[[], int]]] = []
        self.filters = None         # fid -> topic-filter string
        self.picks: Optional[GroupPicks] = None   # of its `$share` rows
        self._chunks: list[tuple] = []
        self.n_rows = 0             # fast rows handed to the lanes
        self.routed_device = False
        self.pending = 0            # outstanding lane parts
        self.done = False
        self.target = None          # LaneCounts to back-fill
        self._cbs: list[Callable[[], None]] = []
        # the fast rows as `_sort_rows` leaves them: columns sorted so
        # that a session's rows are one run, the runs' bounds, and the
        # plan-wide table of views and frames a (message, subopts word)
        self.s_midx = self._s_opt = self._s_fid = self._lists = None
        self.run_lo: list[int] = [0]
        self.run_sid: list[int] = []
        self.keys: list[int] = []
        self.inv: list[int] = []
        self.views: list[Optional[DeliveryView]] = []
        self.frames: dict[int, list] = {}
        # [frames its views serialized, of them counted already]
        self.tally = [0, 0]
        self._nl: Optional[tuple[dict[int, str], set]] = None
        self._barrier_left = 0
        self._barrier_evt: Optional[asyncio.Event] = None
        # flight-recorder trace id (ISSUE 7): set by the engine from
        # its window handle; lane work records against it, and it
        # SURVIVES a lane-worker restart because the queue items carry
        # the plan (the causal context is data, not task state)
        self.trace = 0

    # -- building (engine consume stage, event loop) --
    def register_fast(self, indices) -> None:
        """Mark message indices whose deliveries the lanes own (their
        no-subscriber drop bookkeeping moves to finalize)."""
        self.fast_idx.extend(np.asarray(indices, np.int64).tolist())

    def add_rows(self, midx, sid, opt, fid, filters, slot=None,
                 picks: Optional[GroupPicks] = None) -> None:
        """One vectorized chunk of fast deliveries: parallel arrays of
        (message index, session id, packed opts, filter id) plus the
        fid -> filter-string table they index (the pinned snapshot's
        `fid_filter` for the single-chip engine; a plan-local list for
        the mesh). `slot`, where the chunk has rows of device-picked
        `$share` members: each row's group as a slot of `picks.keys`,
        -1 on a plain row."""
        if self.filters is None:
            self.filters = filters
        elif self.filters is not filters:
            # shouldn't happen (one snapshot per plan) — remap defensively
            base = len(self.filters)
            self.filters = list(self.filters) + list(filters)
            fid = np.asarray(fid) + base
        opt = np.asarray(opt, np.int64)
        if slot is None:
            opt = opt & 0x3F
        else:
            self.picks = picks
            opt = GroupPicks.words(opt, slot)
        self._chunks.append((np.asarray(midx, np.int64),
                             np.asarray(sid, np.int64), opt,
                             np.asarray(fid, np.int64)))

    def add_rows_py(self, msg_idx: int, rows: list[tuple]) -> None:
        """Python-built fast rows for one message (mesh consume):
        `rows` is [(sid, packed_opt, filter_string)]. Appends to a
        plan-local filter table."""
        if not rows:
            return
        if self.filters is None:
            self.filters = []
        base = len(self.filters)
        n = len(rows)
        midx = np.full(n, msg_idx, np.int64)
        sid = np.fromiter((r[0] for r in rows), np.int64, n)
        opt = np.fromiter((r[1] & 0x3F for r in rows), np.int64, n)
        fidx = np.arange(base, base + n, dtype=np.int64)
        self.filters.extend(r[2] for r in rows)
        self._chunks.append((midx, sid, opt, fidx))

    def add_slow(self, msg_idx: int, fn: Callable[[], int]) -> None:
        """A message the fast path cannot prove clean: `fn` runs the
        ordering-safe inline consume for it (behind the barrier) and
        returns its delivery count."""
        self.slow_items.append((msg_idx, fn))

    def add_done_callback(self, cb: Callable[[], None]) -> None:
        if self.done:
            cb()
        else:
            self._cbs.append(cb)

    # -- the sorted rows and their table (submit / deliver_now) --
    def _sort_rows(self, order, midx, sid, opt, fid) -> np.ndarray:
        """Take the fast rows in `order` (stable in the session id, so
        a session's rows are contiguous and in the order given) and cut
        them by numpy: a run is one session's rows, `run_lo[r]` to
        `run_lo[r + 1]`; `keys` are the distinct (message << 32 | word)
        of the plan and `inv` a row's index into them, so that a view
        and a frame are made once a key for the whole plan, whichever
        lane, chunk or retry asks first (`view`, `joined`). Returns the
        runs' session ids."""
        sid = sid[order]
        midx = self.s_midx = midx[order]
        opt = self._s_opt = opt[order]
        self._s_fid = fid[order]
        n = self.n_rows = len(sid)
        if not n:
            return sid
        starts = np.concatenate(([0], np.flatnonzero(np.diff(sid)) + 1))
        run_sid = sid[starts]
        self.run_sid = run_sid.tolist()
        self.run_lo = starts.tolist()
        self.run_lo.append(n)
        keys, inv = np.unique((midx << 32) | opt, return_inverse=True)
        self.keys = keys.tolist()
        self.inv = inv.tolist()
        self.views = [None] * len(keys)
        # no-local rows (bit 2 of the word): key -> the publisher whose
        # own session must not get the frame, and those publishers
        nl = np.flatnonzero(keys & 4).tolist()
        if nl:
            by_key = {k: self.msgs[self.keys[k] >> 32].from_ for k in nl}
            self._nl = (by_key, set(by_key.values()))
        return run_sid

    def row_lists(self) -> tuple:
        """(message index, word, filter id) a sorted row as plain lists,
        for a run that is walked row by row: per-row numpy scalar
        indexing costs ~3x a list index. Made on first use: a plan
        whose runs all go out as joined frames never asks."""
        if self._lists is None:
            self._lists = (self.s_midx.tolist(), self._s_opt.tolist(),
                           self._s_fid.tolist())
        return self._lists

    def view(self, k: int) -> DeliveryView:
        """The plan's one DeliveryView of key `k`, shared across its
        fan-out and across lanes. The share is safe by the
        copy-on-write contract: every mutation path on the view
        (set_header / set_flag / update_expiry / copy) materializes
        private state, and delivered messages are read-only by
        protocol (Subscriber docstring in pubsub.py). A `$share` row's
        word names its group (GroupPicks): a session a message reaches
        by a plain filter and by a group gets two views, the second
        with `share=<group>` in its subopts."""
        v = self.views[k]
        if v is None:
            key = self.keys[k]
            word = key & 0xFFFFFFFF
            v = self.views[k] = DeliveryView(
                self.msgs[key >> 32], OPT_TABLE[word] if word < 64
                else self.picks.subopts(word))
            v._tally = self.tally
        return v

    def joined(self, i: int, j: int, ver: int,
               clientid: str) -> Optional[bytes]:
        """Rows i..j (one session's run) as the frames every subscriber
        gets at protocol `ver`, joined for one write: a gather through
        `inv` and a `join`, no Python a row. None where a row of the
        run needs a copy of its subscriber's own
        (`DeliveryView.shares_frame`, or no-local with `clientid` the
        publisher): the run then goes by `deliver_batch` / `deliver`
        whole."""
        fr = self.frames.get(ver)
        if fr is None:
            fr = self.frames[ver] = [None] * len(self.keys)
        own = self._nl
        if own is not None and clientid not in own[1]:
            own = None      # no publisher of this plan: no row to spare
        if j - i == 1:
            k = self.inv[i]
            if own is not None and own[0].get(k) == clientid:
                return None
            data = fr[k]
            if data is None:
                data = fr[k] = self.view(k).frame_qos0(ver) or False
            return data or None
        idx = self.inv[i:j]
        if own is not None and any(own[0].get(k) == clientid for k in idx):
            return None
        try:
            return b"".join(map(fr.__getitem__, idx))
        except TypeError:
            # a key no run has asked for at this version yet (None), or
            # one that shares no frame (False): `join` found it at C
            # speed, and the first touch pays for the look
            views, view = self.views, self.view
            for k in idx:
                data = fr[k]
                if data is None:
                    data = fr[k] = \
                        (views[k] or view(k)).frame_qos0(ver) or False
                if data is False:
                    # in row order, so what was serialized before this
                    # row is what `_send_shared` would have
                    return None
            return b"".join(map(fr.__getitem__, idx))

    # -- completion (lane workers, event loop) --
    def _finish_part(self) -> None:
        self.pending -= 1
        if self.pending <= 0 and not self.done:
            self._finalize()

    def _finalize(self) -> None:
        self.done = True
        pool = self.pool
        counts = self.counts
        if self.target is not None:
            self.target[:] = counts.tolist()
            # the LaneCounts points back at this plan: let go, or every
            # delivered window's messages wait for a full collection
            self.target = None
        # no-subscriber bookkeeping for lane-owned messages (the slow
        # closures did their own inside the inline consume)
        if self.fast_idx:
            metrics = pool.metrics
            hooks = pool.hooks
            fast = np.asarray(self.fast_idx, np.int64)
            for i in fast[counts[fast] == 0].tolist():
                if self.msgs[i].is_sys:
                    continue
                metrics.inc("messages.dropped")
                metrics.inc("messages.dropped.no_subscribers")
                if hooks is not None:
                    hooks.run("message.dropped",
                              (self.msgs[i], "no_subscribers"))
        for cb in self._cbs:
            try:
                cb()
            except Exception:  # noqa: BLE001 — one waiter must not
                log.exception("delivery-plan callback failed")  # stall
        self._cbs = []
        pool._plan_done(self)


class LaneCounts(list):
    """finish_sub's return value when the lanes own the deliveries: a
    plain list of per-message counts (placeholders until the plan
    completes) carrying the plan so the batcher can defer publisher
    futures with `plan.add_done_callback`."""

    plan: DeliveryPlan


_PARK = ("park",)


class DeliveryLanePool:
    """N session-affine delivery lanes on the event loop.

    Why asyncio tasks and not threads: every subscriber callback
    (channel -> session -> asyncio transport write) is loop-affine, so
    thread workers would need a lock per session; loop tasks keep the
    single-writer discipline for free, and the OVERLAP the stage buys
    is with the device dispatch/materialize stages, which run on
    executor threads and release the GIL inside XLA. The lanes also
    amortize per-row Python: one view object
    instead of a Message copy, coalesced same-session drains, and
    per-slice (not per-row) metric/hook bookkeeping.
    """

    def __init__(self, broker, metrics, *, hooks=None, telemetry=None,
                 n_lanes: int = 4, depth: int = 8, supervisor=None,
                 spans=None):
        self.broker = broker
        self.metrics = metrics
        self.hooks = hooks
        # the one span call: deliver_lane{i} histogram, lane{i} ring
        # span, emqx:lane on the profiler timeline
        if spans is None:
            from emqx_tpu.broker.trace import Spans
            spans = Spans(telemetry, getattr(telemetry, "recorder", None))
        self.spans = spans
        # fault-domain supervision (ISSUE 6): the lane_deliver breaker
        # gates active() (open → the engines deliver inline, the rung
        # below the lanes), slice faults are contained + retried, dead
        # workers are restarted by the drain/admit watchdogs. None
        # restores the pre-ISSUE-6 behavior exactly.
        self.sup = supervisor
        self.n_lanes = n_lanes
        # max outstanding PLANS (consumed sub-batches) before admit()
        # blocks the batcher's consumer — the backpressure bound
        self.depth = max(1, depth)
        self._loop = None
        self._queues: list[asyncio.Queue] = []
        self._workers: list[Optional[asyncio.Task]] = []
        self._wake: Optional[asyncio.Event] = None
        self._gate: Optional[asyncio.Event] = None
        self._paused = False
        self._live_plans = 0
        # fast rows of the plans in flight: the batcher bounds what it
        # forms by them (`_rows_in_flight`), since a plan is 1,000 rows
        # or 100,000 by the fan-out of what was published
        self.live_rows = 0
        self._plans: list[DeliveryPlan] = []     # in-flight, FIFO
        self._lane_items: list[int] = [0] * n_lanes  # real work per lane
        # same-sid coalescing yields one drain per run; chunk big slices
        # so one huge fan-out cannot monopolize the loop between yields.
        # What 2048 rows hold the loop for, on the host of a TPU v5e
        # (PERF.md section 5, PR 41's chip runs): 3.6 ms where every run
        # is joined frames at a fan-out of 110 (1.74 us a row), 16-26 ms
        # where four rows in five or one in two serialize their frame
        # (8.0-12.6 us a row at fan-outs of 1.25-2), 52-57 ms where
        # every run holds a QoS 1 row and is walked (25-28 us); at a
        # fan-out under 8 a lane's slice of a 1,024-message window is
        # 250-500 rows, so only a wide fan-out meets a full chunk.
        # Finer chunks measurably thrash (sweep on a 2-cpu box, before
        # the frame path: 512→310k, 2048→556k, 8192→378k deliveries/s
        # at lanes=4: too-fine interleaving rotates lanes' working sets
        # through cache per yield)
        self._chunk = 2048

    # ---- lifecycle ------------------------------------------------------
    def active(self) -> bool:
        if self.n_lanes <= 0:
            return False
        if self.sup is None or self.sup.lanes_enabled():
            return True
        # lane_deliver breaker open: stop taking NEW plans only once the
        # in-flight lane work has drained — an immediate inline fallback
        # could deliver a session's newer message while its older rows
        # are still queued on a lane (per-session FIFO violation). Plans
        # admitted here still ride the ordered lane queues; the
        # consumer's windows are sequential, so once busy() goes false
        # the lanes are empty and the inline fallback is order-safe.
        return self.busy()

    def ensure_loop(self) -> bool:
        """(Re)start the workers on the CURRENT running loop. Tests run
        several event loops against one Node; workers from a dead loop
        are discarded and fresh queues built — plans never span loops
        (drain() runs before a loop winds down in every serving path)."""
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return False
        if loop is not self._loop:
            orphans = [p for p in self._plans if not p.done]
            self._plans = []
            self._loop = loop
            self._queues = [asyncio.Queue() for _ in range(self.n_lanes)]
            self._workers = [None] * self.n_lanes
            self._wake = asyncio.Event()
            self._gate = asyncio.Event()
            if not self._paused:
                self._gate.set()
            self._lane_items = [0] * self.n_lanes
            # plans stranded by a torn-down loop (tests run several
            # loops against one node) must still finalize: their
            # callbacks release pinned snapshot handles — leaking one
            # would block every future swap on this engine
            self._live_plans = len(orphans)
            self.live_rows = sum(p.n_rows for p in orphans)
            for p in orphans:
                p.pending = 0
                p._finalize()
        for i in range(self.n_lanes):
            w = self._workers[i]
            if w is None or w.done():
                from emqx_tpu.broker.supervise import guard_task
                self._workers[i] = guard_task(
                    loop.create_task(self._worker(i)),
                    f"deliver-lane{i}", self.metrics)
        return True

    def pause(self) -> None:
        """Quiesce the lanes (tests, shutdown drains): queued plans stay
        queued; resume() releases them."""
        self._paused = True
        if self._gate is not None:
            self._gate.clear()

    def resume(self) -> None:
        self._paused = False
        if self._gate is not None:
            self._gate.set()

    # ---- plan intake (engine consume stage) -----------------------------
    def new_plan(self, msgs: list) -> Optional[DeliveryPlan]:
        if not self.active() or not self.ensure_loop():
            return None
        return DeliveryPlan(self, msgs)

    def submit(self, plan: DeliveryPlan) -> None:
        """Bucket the plan's fast rows into session-affine lane slices
        (one stable argsort: primary sid % n_lanes, secondary sid — so
        a session's rows stay in arrival order AND contiguous for the
        coalesced drain) and enqueue; slow closures ride behind an
        all-lanes barrier. Returns immediately — this is the overlap."""
        # workers may have parked since new_plan() — the barrier needs
        # every lane live, so re-arm them before enqueuing anything
        self.ensure_loop()
        parts = 0
        slices = []
        if plan._chunks:
            if len(plan._chunks) == 1:
                midx, sid, opt, fid = plan._chunks[0]
            else:
                midx = np.concatenate([c[0] for c in plan._chunks])
                sid = np.concatenate([c[1] for c in plan._chunks])
                opt = np.concatenate([c[2] for c in plan._chunks])
                fid = np.concatenate([c[3] for c in plan._chunks])
            plan._chunks = []
            lane = sid % self.n_lanes
            # stable single-key argsort: lane-major, sid-minor, original
            # order within a sid (sids are < 2^31 — broker sid counter)
            order = np.argsort((lane << np.int64(31)) | sid,
                               kind="stable")
            run_sid = plan._sort_rows(order, midx, sid, opt, fid)
            # a lane's slice in RUNS: lanes change only where sessions do
            bounds = np.searchsorted(
                run_sid % self.n_lanes,
                np.arange(self.n_lanes + 1)).tolist()
            for ln in range(self.n_lanes):
                lo, hi = bounds[ln], bounds[ln + 1]
                if lo == hi:
                    continue
                parts += 1
                slices.append((ln, lo, hi))
            self.metrics.inc("pipeline.deliver.rows", plan.n_rows)
        if plan.slow_items:
            parts += 1
            plan._barrier_left = self.n_lanes
            plan._barrier_evt = asyncio.Event()
            self.metrics.inc("pipeline.deliver.barriers")
            self.metrics.inc("pipeline.deliver.slow_msgs",
                             len(plan.slow_items))
        # all fallible work is done: go live, then enqueue (put_nowait
        # on unbounded queues cannot raise — a half-enqueued plan would
        # wedge drain()/admit() forever)
        plan.pending = parts
        self._live_plans += 1
        self.live_rows += plan.n_rows
        for ln, lo, hi in slices:
            self._lane_items[ln] += 1
            self._queues[ln].put_nowait(("slice", plan, lo, hi))
        if plan.slow_items:
            # the barrier holds EVERY lane: the slow closures run with
            # all prior fast deliveries done and nothing overtaking —
            # the ordering-safe serialization the inline loop had
            for ln, q in enumerate(self._queues):
                self._lane_items[ln] += 1
                q.put_nowait(("barrier", plan))
        self.metrics.inc("pipeline.deliver.plans")
        if parts == 0:
            plan._finalize()
        else:
            self._plans.append(plan)

    def deliver_now(self, msgs: list, midx, sid, opt, fid,
                    filters) -> np.ndarray:
        """The synchronous form, for a caller that needs its counts on
        return (`finish_sub(defer=False)`): the rows of one sub-batch,
        delivered session by session on the caller's stack exactly as
        a lane delivers its slice (one table of views and frames, one
        write a session). Stable in the session id, so every session
        sees its rows in the order given. Returns the deliveries a
        message."""
        plan = DeliveryPlan(self, msgs)
        plan.routed_device = True
        plan.filters = filters
        sid = np.asarray(sid, np.int64)
        plan._sort_rows(np.argsort(sid, kind="stable"),
                        np.asarray(midx, np.int64), sid,
                        np.asarray(opt, np.int64) & 0x3F,
                        np.asarray(fid, np.int64))
        self._deliver_rows(plan, 0, len(plan.run_sid))
        return plan.counts

    def _plan_done(self, plan: DeliveryPlan) -> None:
        try:
            self._plans.remove(plan)
        except ValueError:
            pass    # zero-part plans finalize before tracking
        self._live_plans -= 1
        self.live_rows -= plan.n_rows
        if self._wake is not None:
            self._wake.set()
        if self._live_plans == 0:
            # park the workers: idle tasks pending at loop teardown
            # would otherwise warn "task was destroyed" on every test
            for q in self._queues:
                q.put_nowait(_PARK)

    # ---- flow control (batcher consume stage) ---------------------------
    async def admit(self) -> None:
        """Backpressure: block while more than `depth` plans are
        outstanding. Called by the batcher after enqueuing a plan — the
        stall propagates to its `_inflight` queue and from there to
        submit()/enqueue(), instead of dropping or buffering unboundedly."""
        if self._wake is None or self._live_plans <= self.depth:
            return
        self.metrics.inc("pipeline.deliver.backpressure_waits")
        while self._live_plans > self.depth:
            self._wake.clear()
            await self._wait_wake()

    async def drain(self) -> None:
        """Wait for every outstanding plan to finish delivering. Host-
        routed batches call this before delivering inline, so a host
        batch can never overtake lane-queued deliveries for a session
        (the device/host FIFO contract the batcher's consumer enforces
        extends through the lanes)."""
        if self._wake is None:
            return
        if self._loop is not asyncio.get_running_loop():
            # drain on a NEW loop (tests tear loops down under a live
            # node): rebind first — ensure_loop force-finalizes plans
            # stranded on the dead loop, releasing their pinned
            # snapshot handles, so this drain returns instead of
            # waiting forever on a wake event nobody can set
            self.ensure_loop()
        while self._live_plans > 0:
            self._wake.clear()
            await self._wait_wake()

    async def progress(self) -> None:
        """One wait for the lanes to move: a plan done, or a `nudge`.
        For a caller that holds work back by `live_rows` (the batcher's
        `_ROWS_IN_FLIGHT`) and looks again after each."""
        if self._wake is None:
            await asyncio.sleep(0)
            return
        self._wake.clear()
        await self._wait_wake()

    def nudge(self) -> None:
        """Something ahead of the lanes moved (the batcher's consumer
        took a window up): whoever waits in `progress` looks again."""
        if self._wake is not None:
            self._wake.set()

    async def _wait_wake(self) -> None:
        """One bounded wait on lane progress. With a supervisor
        (ISSUE 6) the wait is a lane-queue watchdog: a deadline expiry
        counts a stall, RESTARTS any dead lane workers (their queues
        are intact, so a revived worker drains in order — the
        crashed-lane recovery contract) and advances the lane_deliver
        breaker, instead of wedging the caller forever on a queue
        nobody is consuming."""
        sup = self.sup
        if sup is None:
            await self._wake.wait()
            return
        try:
            await asyncio.wait_for(self._wake.wait(),
                                   sup.deadline("lane_deliver"))
        except asyncio.TimeoutError:
            sup.note_stall("lane_deliver")
            if self._revive_workers():
                sup.note_restart("lane_worker")

    def _revive_workers(self) -> int:
        """Restart dead lane workers on the current loop (the stall
        watchdog's recovery arm; ensure_loop does the same lazily at
        the next plan intake). Queues are untouched — a restarted
        worker picks up exactly where the dead one stopped, in order."""
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return 0
        if loop is not self._loop:
            return 0
        revived = 0
        from emqx_tpu.broker.supervise import guard_task
        for i, w in enumerate(self._workers):
            if w is None or w.done():
                self._workers[i] = guard_task(
                    loop.create_task(self._worker(i)),
                    f"deliver-lane{i}", self.metrics)
                revived += 1
        return revived

    def busy(self) -> bool:
        return self._live_plans > 0

    def queued_items(self) -> int:
        return sum(self._lane_items)

    def lane_depth(self) -> int:
        """Deepest lane (pending work items) right now — the exported
        gauge (park sentinels are housekeeping, not work: excluded)."""
        return max(self._lane_items, default=0)

    # ---- telemetry ------------------------------------------------------
    def state(self) -> dict:
        return {
            "lanes": self.n_lanes,
            "depth_limit": self.depth,
            "live_plans": self._live_plans,
            "live_rows": self.live_rows,
            "queued_items": self.queued_items(),
            "lane_depth": self.lane_depth(),
            "paused": self._paused,
        }

    def stats_fun(self, stats) -> None:
        """Registered on Node.stats: the point-in-time lane-depth gauge
        every exporter carries (Prometheus gauge family, StatsD |g,
        $SYS stats/)."""
        stats.setstat("pipeline.deliver.lane_depth", self.lane_depth())
        stats.setstat("pipeline.deliver.live_plans", self._live_plans)

    # ---- lane workers ---------------------------------------------------
    async def _worker(self, lane: int) -> None:
        q = self._queues[lane]
        spans = self.spans
        while True:
            item = await q.get()
            if item[0] == "park":
                if self._live_plans == 0 and q.empty():
                    return
                continue
            worked = True
            sp = None
            try:
                if not self._gate.is_set():
                    try:
                        await self._gate.wait()
                    except asyncio.CancelledError:
                        # dying while HOLDING a popped item: surrender
                        # it (lost-but-accounted) or its plan's part
                        # leaks and every future drain wedges on work
                        # nobody owns — the gap the ISSUE-6 lane
                        # watchdog test exposed
                        self._surrender(item)
                        raise
                # one span per lane item, opened after the gate wait
                # (not lane work). item is ("slice", plan, lo, hi) or
                # ("barrier", plan): the plan rides at [1] and carries
                # its window's trace. The profiler annotation covers
                # the synchronous walk of runs only: every await below
                # releases it
                sp = spans.span(
                    "lane", getattr(item[1], "trace", 0),
                    stage=f"deliver_lane{lane}", ring=f"lane{lane}",
                    track=f"lane{lane}", meta={"lane": lane}).__enter__()
                if item[0] == "slice":
                    _k, plan, lo, hi = item
                    try:
                        try:
                            if self.sup is not None:
                                # ISSUE 6 injection point: a lane
                                # worker failing mid-slice must be
                                # contained, not a silent task death
                                self.sup.fire("lane_deliver")
                            await self._run_slice(plan, lane, lo, hi,
                                                  sp)
                            if self.sup is not None:
                                self.sup.note_ok("lane_deliver")
                        except Exception as e:  # noqa: BLE001
                            if self.sup is None:
                                raise   # pre-ISSUE-6: the task dies
                            # real delivery faults are contained PER
                            # CHUNK inside _run_slice; reaching here
                            # means the slice failed BEFORE any
                            # delivery (the injection point, chunk-
                            # boundary code), so a whole-slice retry
                            # cannot duplicate
                            self.sup.note_fault("lane_deliver", e)
                            try:
                                # re-run CHUNKED (cooperative yields) —
                                # one flat _deliver_rows over a huge
                                # slice would monopolize the loop, the
                                # exact stall the chunking prevents
                                await self._run_slice(plan, lane,
                                                      lo, hi, sp)
                            except Exception:  # noqa: BLE001
                                log.exception(
                                    "lane %d slice %d..%d lost after "
                                    "retry", lane, lo, hi)
                                self.metrics.inc(
                                    "pipeline.deliver.deliver_errors")
                    finally:
                        plan._finish_part()
                else:  # barrier
                    _k, plan = item
                    plan._barrier_left -= 1
                    if plan._barrier_left == 0:
                        try:
                            await self._run_slow(plan, sp)
                        finally:
                            plan._barrier_evt.set()
                            plan._finish_part()
                    else:
                        # waiting out another lane's slow tail is not
                        # THIS lane's work: recording it would read as
                        # uniform slowness and mask real per-lane
                        # hashing skew in the deliver_lane{i}
                        # histograms
                        worked = False
                        with sp.released():
                            await plan._barrier_evt.wait()
            finally:
                # gauge accounting must survive cancellation anywhere
                # in the item's processing (mid-slice, barrier wait) or
                # lane_depth overreports a stuck-deep lane forever
                self._lane_items[lane] -= 1
                if sp is not None:
                    if worked:
                        sp.__exit__(None, None, None)
                        # what emqx:lane covered: the item less the
                        # stretches it was released for
                        self.metrics.inc(
                            "pipeline.deliver.lane_us",
                            round((sp.dur - sp.away) * 1e6))
                    else:
                        sp.drop()

    def _surrender(self, item) -> None:
        """Account a popped-but-unprocessed queue item when its worker
        dies: the plan part is finished so drains can complete (the
        worker's finally owns the lane-depth gauge decrement). A
        surrendered slice loses its deliveries (counted as
        deliver_errors; finalize then books the no-subscriber drops);
        a surrendered barrier passes this lane through, and the LAST
        lane's surrender runs the slow closures synchronously (they
        are plain callables) so their deliveries survive."""
        if item[0] == "slice":
            self.metrics.inc("pipeline.deliver.deliver_errors")
            item[1]._finish_part()
        elif item[0] == "barrier":
            plan = item[1]
            plan._barrier_left -= 1
            if plan._barrier_left == 0:
                for idx, fn in plan.slow_items:
                    try:
                        plan.counts[idx] = fn()
                    except Exception:  # noqa: BLE001 — death path
                        self.metrics.inc("pipeline.deliver.slow_errors")
                if plan._barrier_evt is not None:
                    plan._barrier_evt.set()
                plan._finish_part()

    async def _run_slice(self, plan: DeliveryPlan, lane: int,
                         lo: int, hi: int, sp) -> None:
        """Deliver one lane's slice, coalescing same-session runs, with
        a cooperative yield between chunks so a huge fan-out cannot
        monopolize the loop (other lanes and the producer keep running;
        later plans queue behind this one per-lane, so order holds).

        Fault containment is PER CHUNK (ISSUE 6): a raising chunk is
        retried once, and only that chunk — retrying the whole slice
        would re-deliver (and double-count) the chunks that already
        succeeded. Counts apply only on a chunk's successful return, so
        a retried chunk is at-least-once for its subscribers but never
        double-counted toward the publisher."""
        run_lo = plan.run_lo
        sup = self.sup
        pos = lo
        while pos < hi:
            # `lo`, `hi`, `pos` count RUNS (a session's contiguous rows:
            # the coalesced drain and its all-or-none accept are per
            # run), so a chunk never splits one: it ends at the first
            # run boundary `_chunk` rows on
            nxt = bisect_left(run_lo, run_lo[pos] + self._chunk,
                              pos + 1, hi)
            if sup is None:
                self._deliver_rows(plan, pos, nxt)
            else:
                try:
                    self._deliver_rows(plan, pos, nxt)
                except Exception as e:  # noqa: BLE001 — contained
                    sup.note_fault("lane_deliver", e)
                    try:
                        self._deliver_rows(plan, pos, nxt)
                    except Exception:  # noqa: BLE001
                        log.exception("lane %d chunk %d..%d lost "
                                      "after retry", lane, pos, nxt)
                        self.metrics.inc(
                            "pipeline.deliver.deliver_errors")
            pos = nxt
            if pos < hi:
                with sp.released():
                    await asyncio.sleep(0)

    def _deliver_rows(self, plan: DeliveryPlan, lo: int, hi: int) -> None:
        """Deliver runs lo..hi of the plan, a run a session. A run whose
        subscriber takes joined frames (`Channel.deliver_frames`) and
        whose rows all share theirs costs no Python a row: the
        subscriber gathers and joins them from the plan's table
        (`DeliveryPlan.joined`) and writes once. Every other run is
        walked row by row into `deliver_batch` / `deliver`, its views
        from the same table."""
        broker = self.broker
        registry = broker._subscribers
        meta = broker._sub_meta
        hooks = self.hooks
        delivered_cbs = hooks.lookup("message.delivered") \
            if hooks is not None else ()
        picks = plan.picks
        run_lo, run_sid = plan.run_lo, plan.run_sid
        joined = plan.joined
        delivered = 0
        frame_rows = 0
        drains = 0
        accept_ns = 0   # inside the subscribers' deliver calls
        clock = time.perf_counter_ns
        # what was accepted: whole runs as [first row, end) with
        # neighbours merged (a chunk in which every run is accepted is
        # ONE pair: its counts are one bincount of a slice), and the
        # message index of a row accepted by itself, or delivered by
        # the host's dispatch of its group (which counts its own
        # `messages.delivered`)
        taken: list[list[int]] = []
        singles: list[int] = []
        repicked: list[int] = []
        for r in range(lo, hi):
            sid = run_sid[r]
            i, j = run_lo[r], run_lo[r + 1]
            sub = registry.get(sid)
            if sub is None:
                if picks is not None:
                    repicked.extend(self._repick(plan, sid, range(i, j)))
                continue
            # Deliberate divergence from the inline loop: a raising
            # subscriber/hook here is contained to ITS deliveries
            # (logged + counted) instead of failing the whole batch's
            # publish futures — one bad session must not poison every
            # publisher sharing the window. deliver_errors/slow_errors
            # make the containment observable.
            send = getattr(sub, "deliver_frames", None)
            if delivered_cbs:
                send = None     # a callback wants (meta, view) a row
            sent = False
            if send is not None:
                t0 = clock()
                try:
                    sent = send(joined, i, j)
                except Exception:  # noqa: BLE001 — one bad subscriber
                    log.exception("deliver_frames failed sid=%s", sid)
                    self.metrics.inc("pipeline.deliver.deliver_errors")
                    sent = None
                accept_ns += clock() - t0
            whole = False       # the run was accepted, all of it
            nacked = ()
            if sent is not False:
                # the frame entry took the run, or raised under it (as
                # a raising deliver_batch: not taken, not tried again)
                drains += 1
                if sent:
                    whole = True
                    frame_rows += j - i
                else:
                    nacked = range(i, j)
            else:
                midx, _opts, fids = plan.row_lists()
                filters, views, view = plan.filters, plan.views, plan.view
                items = [(filters[f], views[k] or view(k))
                         for f, k in zip(fids[i:j], plan.inv[i:j])]
                batch_fn = getattr(sub, "deliver_batch", None) \
                    if j - i > 1 else None
                if batch_fn is not None:
                    # coalesced drain: one session accept + one socket
                    # write for the whole run (all-or-none by contract)
                    t0 = clock()
                    try:
                        whole = bool(batch_fn(items))
                    except Exception:  # noqa: BLE001 — one bad subscriber
                        log.exception("deliver_batch failed sid=%s", sid)
                        self.metrics.inc(
                            "pipeline.deliver.deliver_errors")
                    accept_ns += clock() - t0
                    drains += 1
                    if not whole:
                        nacked = range(i, j)
                    elif delivered_cbs:
                        for _f, v in items:
                            hooks.run("message.delivered",
                                      (meta.get(sid), v))
                else:
                    drains += j - i
                    t0 = clock()
                    for k, (f, v) in zip(range(i, j), items):
                        try:
                            ok = sub.deliver(f, v)
                        except Exception:  # noqa: BLE001
                            log.exception("deliver failed sid=%s", sid)
                            self.metrics.inc(
                                "pipeline.deliver.deliver_errors")
                            ok = False
                        if ok:
                            singles.append(midx[k])
                            if delivered_cbs:
                                hooks.run("message.delivered",
                                          (meta.get(sid), v))
                        else:
                            nacked += (k,)
                    accept_ns += clock() - t0
            if whole:
                if taken and taken[-1][1] == i:
                    taken[-1][1] = j
                else:
                    taken.append([i, j])
            if nacked and picks is not None:
                repicked.extend(self._repick(plan, sid, nacked))
        counts = plan.counts
        if taken:
            s_midx = plan.s_midx
            rows = s_midx[taken[0][0]:taken[0][1]] if len(taken) == 1 \
                else np.concatenate([s_midx[a:b] for a, b in taken])
            counts += np.bincount(rows, minlength=len(counts))
            delivered = len(rows)
        if singles or repicked:
            np.add.at(counts, singles + repicked, 1)
            delivered += len(singles)
        # per-slice (not per-row) bookkeeping: the batching win the
        # coalesce.ratio histogram quantifies
        metrics = self.metrics
        if delivered:
            metrics.inc("messages.delivered", delivered)
            if plan.routed_device:
                metrics.inc("messages.routed.device", delivered)
        n_rows = run_lo[hi] - run_lo[lo]
        metrics.inc("pipeline.deliver.deliveries", n_rows)
        metrics.inc("pipeline.deliver.drains", drains)
        metrics.inc("pipeline.deliver.accept_us", round(accept_ns / 1000))
        # rows that went out as a run of joined frames, and the frames
        # the plan's views serialized since it last counted them (by
        # either path; a chunk that raised left its own for its retry,
        # which finds them in the table): against `deliveries`, how
        # often the frame path engages and how far a frame is shared
        if frame_rows:
            metrics.inc("pipeline.deliver.frame_rows", frame_rows)
        tally = plan.tally
        if tally[0] != tally[1]:
            metrics.inc("pipeline.deliver.frames_built",
                        tally[0] - tally[1])
            tally[1] = tally[0]
        if n_rows:
            metrics.hist("pipeline.deliver.coalesce.ratio",
                         lo=1.0 / 256, n_buckets=9,
                         unit="ratio").observe(1.0 - drains / n_rows)

    def _repick(self, plan: DeliveryPlan, sid: int, rows) -> list[int]:
        """The `$share` rows among `rows` (one session's run, in message
        order) that their picked member did not take: each goes once
        more through the host's dispatch of its group where the host's
        own pick would (`DeviceRouteEngine._consume_one`): the member
        has left the group, or the ack protocol is on; a nack from a
        live member without it is final. Returns the message indices
        delivered so. The host's dispatch counts its own
        `messages.delivered` and runs its own hooks."""
        picks, broker = plan.picks, self.broker
        midx, opts, _fids = plan.row_lists()
        out = []
        for k in rows:
            if opts[k] < 64:
                continue
            f, gname = picks.key(opts[k])
            grp = broker.shared.get(f, {}).get(gname)
            if not (grp is None or sid not in grp.members
                    or broker.shared_dispatch_ack):
                continue
            self.metrics.inc("routing.device.shared_repick")
            try:
                if picks.redispatch(f, gname, plan.msgs[midx[k]]):
                    out.append(midx[k])
            except Exception:  # noqa: BLE001 — as a raising delivery
                log.exception("shared re-dispatch failed %s/%s", gname, f)
                self.metrics.inc("pipeline.deliver.deliver_errors")
        return out

    async def _run_slow(self, plan: DeliveryPlan, sp) -> None:
        """The ordering-safe serialized tail: slow-path messages in
        batch order, all lanes held at the barrier."""
        for n, (idx, fn) in enumerate(plan.slow_items):
            try:
                plan.counts[idx] = fn()
            except Exception:  # noqa: BLE001 — a failing hook/deliver
                log.exception("slow-path consume failed")  # != lost lane
                self.metrics.inc("pipeline.deliver.slow_errors")
            if n % 64 == 63:
                with sp.released():
                    await asyncio.sleep(0)
