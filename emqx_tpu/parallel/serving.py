"""Multichip serving: a live node routing through the dp×route mesh.

`ShardedRouteServer` is the multi-device sibling of
broker.device_engine.DeviceRouteEngine: it compiles the node's live
routing state into PER-SHARD RouterTables (filters partitioned by
crc32(filter) % route — the device-mesh analog of the reference's
`broker_pool` topic-hash serialization, emqx_broker.erl:427-428), serves
publish batches through parallel.sharded.make_sharded_route_step, and
consumes the [B, route, ...] RouteResult into real session deliveries.
It implements the PublishBatcher engine protocol, so a node boots with it
exactly like the single-chip engine and channels publish through the
same micro-batch window.

Churn model (simpler than the single-chip engine's dirty-filter +
delta-trie scheme): a subscription/route change dirties its filter's
SHARD; the next batch's `poll_rebuild` rebuilds the dirty shards
host-side with the snapshot's capacity classes and writes only their
slices into the stacked device arrays (parallel.sharded.update_shard —
one XLA dynamic_update_index_in_dim per shard, nothing else moves;
non-donating, so pipelined in-flight batches keep their pinned arrays).
Per-shard updates are synchronous-before-serve. A shard OUTGROWING its
capacity class kicks a BACKGROUND full rebuild (capture on the event
loop, compile+upload on a thread): while it runs, poll_rebuild returns
False and every batch routes host-side — correct, never stale, just
slower — until the swap; churn landing after the capture stays dirty
and follows as per-shard updates.

Cluster interplay: normal-route forwarding works exactly as the
single-chip consume (cluster.forward on the matched set). Shared groups
ride device slots in BOTH modes: standalone slots hold the local
members; under a cluster each shard's slots hold the CLUSTER-WIDE
membership (device_engine.capture_shared), remote members as
reserved-range sids (>= _REMOTE_SID_BASE) that consume turns into
directed `shared.deliver_fwd` RPCs — the reference's cross-node shared
dispatch (emqx_shared_sub.erl:239-268) with the pick already made on
the mesh. Membership replication dirties the filter's shard
(cluster.py:232 → note_member_change), so the synchronous per-shard
update keeps the slots cluster-fresh before every served batch.

Reference parity anchors: emqx_broker.erl:199-308 (the per-message path
this replaces), emqx_router.erl:77-86 (full replication this shards),
SURVEY.md §2.4 P2/P4/P6.
"""

from __future__ import annotations

import os
import threading
import time
import zlib
from typing import Optional

import numpy as np

from emqx_tpu.broker.device_engine import (_REMOTE_SID_BASE,
                                           DeviceRouteEngine, _is_rich,
                                           _next_pow2, _pack_opts,
                                           _unpack_opts, capture_shared)
from emqx_tpu.broker.deliver import DEFERRED, OPT_TABLE, LaneCounts
from emqx_tpu.broker.message import Message
from emqx_tpu.ops import intern as I
from emqx_tpu.ops.compact import csr_slices
from emqx_tpu.utils import topic as T

# gfid | packed_opt << 24 is the exchange wire word: global filter ids
# above this no longer fit next to the 6 subopt bits, so the stage
# stands down (counted) rather than corrupting rows
_EXCHANGE_MAX_GFID = 1 << 24

# a covering shard's candidate lane (`CoverTables.cand_pad`): uniform
# across shards and rebuilds, so that the stacked pytree keeps its shape
_COVER_CAND_CAP = 256


def resolve_device_exchange(configured=None) -> bool:
    """The one device-exchange resolution (ISSUE 15): config
    broker.device_exchange beats EMQX_TPU_EXCHANGE beats the built-in
    default-on. =0 restores the host gather/merge readback exactly —
    no exchange aux tables, no exchange program, no pipeline.exchange.*
    traffic — the A/B twin baseline the bit-identity tests pin."""
    if configured is not None:
        return bool(configured)
    return os.environ.get("EMQX_TPU_EXCHANGE", "1") \
        not in ("0", "false", "off")


class _ShardBuilt:
    """Host index of one shard's compiled tables."""

    __slots__ = ("fid_of", "fid_filter", "seg_len", "slot_key", "rich",
                 "host_extra", "remote_members", "seg_np", "fid_slow",
                 "cover_roots", "cover_covered")

    def __init__(self):
        # subscription covering (ISSUE 18): per-shard detection counters
        # for stats(); roots == len(fid_filter) when covering found
        # nothing (identity expansion)
        self.cover_roots = 0
        self.cover_covered = 0
        self.fid_of: dict[str, int] = {}
        self.fid_filter: list[str] = []
        self.seg_len: list[int] = []
        self.slot_key: list[tuple] = []      # local slot -> (filter, group)
        self.rich: set[str] = set()          # host-dict dispatch filters
        self.host_extra: list[tuple] = []    # too-deep: (filter, words)
        # device sid _REMOTE_SID_BASE+i -> (origin, remote_sid): consume
        # forwards picks for these over RPC (per shard, like _Built's)
        self.remote_members: list[tuple] = []
        # vectorized-consume companions (ISSUE 9 satellite; set once at
        # build, mirroring the single-chip _Built):
        self.seg_np = np.zeros(0, np.int64)   # seg_len as an array
        self.fid_slow = np.zeros(0, bool)     # rich OR snapshot slots


class _Handle:
    """One dispatched batch (PublishBatcher handle protocol).

    Pins the FULL snapshot it was prepared against — host index AND
    device tables/cursors — so a shard update applied while this batch
    is in the pipeline can neither re-index its decode nor swap the
    arrays under its dispatch (the batch serves the snapshot it saw,
    exactly like the single-chip engine's in-flight batches)."""

    __slots__ = ("subs", "built", "tables", "cursors", "enc", "res",
                 "np_res", "t0", "host_idx", "trace", "sub_traces",
                 "aux", "exch", "exch_bytes", "exch_fits")

    def __init__(self, subs, built, tables, cursors, enc, host_idx,
                 aux=None, exch_fits=True):
        self.subs = subs          # [[Message, ...]] — W=1: one sub-batch
        self.built = built        # list[_ShardBuilt] snapshot
        self.tables = tables      # stacked device pytree at prepare time
        self.cursors = cursors
        self.enc = enc
        self.host_idx = host_idx  # msg indexes forced host-side (too_long)
        self.res = None
        self.np_res = None
        self.trace = 0            # flight-recorder window trace (ISSUE 7)
        self.sub_traces = None    # per-sub trace ids (W=1 on the mesh)
        self.t0: Optional[float] = None
        self.aux = aux            # ExchangeAux snapshot (ISSUE 15)
        self.exch = None          # ExchangeResult once the stage ran
        self.exch_bytes = 0       # bytes the exchange landing cost
        self.exch_fits = exch_fits  # snapshot's gfid-space verdict


class ShardedRouteServer:
    """Serve a node's publishes through an n-device (dp×route) mesh."""

    def __init__(self, node, *, n_devices: Optional[int] = None,
                 dp: Optional[int] = None, mesh=None,
                 frontier_cap: int = 16, match_cap: int = 64,
                 fanout_cap: int = 128, slot_cap: int = 16,
                 level_cap: int = 16, max_batch: int = 256,
                 compact_readback: Optional[bool] = None,
                 delta_overlay: Optional[bool] = None,
                 supervisor=None, ledger=None,
                 dispatch_depth: Optional[int] = None,
                 device_exchange: Optional[bool] = None,
                 subscription_covering: Optional[bool] = None):
        from emqx_tpu.parallel.mesh import make_mesh
        self.node = node
        self.broker = node.broker
        self.router = node.broker.router
        from emqx_tpu.broker.trace import spans_of
        self.spans = spans_of(node)
        if mesh is None:
            import jax
            n_devices = n_devices or len(jax.devices())
            mesh = make_mesh(n_devices, dp=dp)
        self.mesh = mesh
        self.n_route = mesh.shape["route"]
        self.n_dp = mesh.shape["dp"]
        self.frontier_cap = frontier_cap
        self.match_cap = match_cap
        self.fanout_cap = fanout_cap
        self.slot_cap = slot_cap
        self.level_cap = level_cap
        # pow2: _batch_class quantizes onto the doubling warm ladder — a
        # non-pow2 cap would name a class the ladder never compiles
        self.max_batch = _next_pow2(max_batch)
        self._STD_CLASSES = ((1, self.max_batch),)

        from emqx_tpu.parallel.sharded import make_sharded_route_step
        self.step = make_sharded_route_step(
            mesh, backend="trie", frontier_cap=frontier_cap,
            match_cap=match_cap, fanout_cap=fanout_cap, slot_cap=slot_cap)

        self.intern = I.InternTable()
        self.tables = None            # stacked device pytree [R, ...]
        self.cursors = None           # device [R, G_cap]
        self._builts: Optional[list[_ShardBuilt]] = None
        self._caps: Optional[dict] = None
        self.dirty_shards: set[int] = set()
        self._warm_classes: set[int] = set()
        self._warm_thread: Optional[threading.Thread] = None
        self._rebuild_thread: Optional[threading.Thread] = None
        self._capture_task = None     # pending chunked capture (loop ctx)
        # build generations: every capture start bumps _build_gen; a
        # build result adopts only if its gen is newer than the adopted
        # one, and a pending capture whose gen is no longer current is
        # SUPERSEDED (a sync rebuild() raced past it) — its result is
        # dropped rather than regressing the snapshot
        self._build_gen = 0
        self._adopted_gen = 0
        self._capture_gen = 0
        self._rebuild_backoff_until = 0.0
        self._lock = threading.Lock()   # dispatch thread vs loop rebuilds

        # CSR readback compaction (ISSUE 3), mesh edition: unlike the
        # single-chip engine the compaction is a SECOND small jitted
        # call in materialize, run over the stacked
        # [B, R, ...] planes reshaped to one [1, B*R] pseudo-window.
        # Payload classes are (Bp, P) keyed — independent of the
        # capacity classes, so they survive rebuilds — warmed by the
        # same background thread as the batch classes.
        if compact_readback is None:
            from emqx_tpu.broker.device_engine import _ENV_COMPACT
            compact_readback = _ENV_COMPACT
        self.compact_readback = bool(compact_readback)

        # delta overlay knob (ISSUE 4): accepted for config parity with
        # the single-chip engine, but the mesh's churn path is ALREADY
        # incremental — a subscription change dirties only its filter's
        # shard and poll_rebuild recompiles that shard host-side into
        # the stacked arrays (update_shard) before the next served
        # batch, i.e. a per-shard compaction with no world recapture.
        # The fused per-shard overlay (delta rows merged inside
        # make_sharded_route_step) is the designed next step; until
        # then stats() reports the mode so bench rows can't mistake the
        # per-shard rebuild for the single-chip overlay. The PR-2/3
        # handled-set sweep and per-slot staleness guard in _consume_one
        # are the churn-correctness invariants either path must keep.
        if delta_overlay is None:
            from emqx_tpu.broker.device_engine import _ENV_DELTA
            delta_overlay = _ENV_DELTA
        self.delta_overlay = bool(delta_overlay)
        # subscription covering (ISSUE 18), mesh edition: each shard's
        # trie holds only its local COVERING set and the per-shard
        # expansion CSR re-expands after the match stage — INSIDE
        # match_batch, so the exchange ships already-expanded rows and
        # the aggregation per filter-hash shard needs no new step. When
        # on, EVERY shard carries cover tables (empty ones where the
        # shard has no covered filters) so the stacked pytree stays
        # uniform; cover-set churn rides the existing per-shard
        # incremental rebuild (which re-detects covers for that shard).
        if subscription_covering is None:
            from emqx_tpu.broker.device_engine import _ENV_COVERING
            subscription_covering = _ENV_COVERING
        self.subscription_covering = bool(subscription_covering)
        # double-buffered window pipeline (ISSUE 9): the mesh gains the
        # same prepare/materialize split as the single-chip engine — at
        # dispatch_depth >= 2 the batcher runs up to that many windows'
        # stages concurrently (each pinning its own snapshot by
        # reference; the copy-on-write _builts discipline already
        # supports N in-flight handles), and dispatch() starts the
        # device→host readback transfers at return so materialize is
        # consume-on-arrival.
        from emqx_tpu.broker.batcher import resolve_dispatch_depth
        self.dispatch_depth = resolve_dispatch_depth(dispatch_depth)
        self._payload_mults = (8, 32, 128)
        self._pay_ewma: Optional[float] = None
        # device-to-device exchange stage (ISSUE 15): after the sharded
        # match, compact each shard's delivery rows to CSR segments
        # keyed by owning delivery shard (sid % route — the PR 5
        # session-affinity discipline) and ring-exchange them
        # device-to-device (parallel.sharded.ring_rotate, a
        # collective-permute), so materialize lands ONLY the
        # per-dest final delivery plans instead of the gathered result
        # set. broker.device_exchange / EMQX_TPU_EXCHANGE =0 restores
        # host gather/merge exactly. Segment capacity classes (E) ride
        # an EWMA ladder like the CSR payload classes; a window whose
        # rows outgrow its class falls back to host gather (counted),
        # as does any window the clean-proof rejects (shared hit, rich
        # fid, overflow, cluster, too-deep host_extra).
        self.device_exchange = resolve_device_exchange(device_exchange)
        self.aux = None                   # device ExchangeAux [R, ...]
        self._exch_steps: dict = {}       # E -> jitted exchange program
        self._exch_warm: set[tuple] = set()      # {(Bp, E)}
        self._wanted_ecap: set[tuple] = set()
        self._exch_ewma: Optional[float] = None
        self._exch_fits = True            # global fid space < 2^24
        # combined fid->filter table across shards, memoized per
        # snapshot identity (the copy-on-write _builts list) — the
        # vectorized consume's plan hand-off indexes it
        self._flat_memo: Optional[tuple] = None
        self._compact_warm: set[tuple] = set()    # {(Bp, P)}
        self._wanted_pcap: set[tuple] = set()

        # fault-domain supervision (ISSUE 6): the mesh_exchange breaker
        # gates the whole sharded path (open → prepare_window returns
        # None → host route, the mesh's rung-2); the injection point
        # rides dispatch. A mesh fault also advances the batcher's
        # generic dispatch-stage breaker — both gates fall back to the
        # same host rung, so double accounting is harmless.
        self.sup = supervisor if supervisor is not None \
            else getattr(node, "supervisor", None)
        if self.sup is not None:
            self.sup.register_probe("mesh_exchange", self._probe_mesh)

        # HBM ledger (ISSUE 8): the stacked mesh shard tables + cursors
        # register under mesh_tables / mesh_cursors; dispatch handles
        # ride the pin sentinel like the single-chip engine's
        self.ledger = ledger if ledger is not None \
            else getattr(node, "hbm_ledger", None)

        # engine wiring (same hooks DeviceRouteEngine claims)
        self.broker.device_engine = self
        node.device_engine = self
        self.router.on_route_change = self.note_route_change

    # ---- churn tracking -------------------------------------------------
    def shard_of(self, topic_filter: str) -> int:
        return zlib.crc32(topic_filter.encode()) % self.n_route

    def note_route_change(self, topic_filter: str, added: bool) -> None:
        self.dirty_shards.add(self.shard_of(topic_filter))

    def note_member_change(self, real: str, group) -> None:
        self.dirty_shards.add(self.shard_of(real))

    # ---- build ----------------------------------------------------------
    def _bucket_filters(self) -> list[list[str]]:
        """One pass over the filter universe → per-shard lists (crc32
        once per filter, not once per filter per shard)."""
        buckets: list[list[str]] = [[] for _ in range(self.n_route)]
        for f in list(self.router.exact) + list(self.router.wildcards):
            buckets[self.shard_of(f)].append(f)
        return buckets

    def _capture_filters(self, fs, subs: dict, shared: dict) -> None:
        """Capture a sub-list of filters into subs/shared dicts — ONE
        body shared by the sync shard capture and the chunked async
        capture, so the two snapshots can never desynchronize. Shared
        groups capture cluster-wide membership with remote members as
        ((origin, sid), None) refs (device_engine.capture_shared — same
        scheme as the single-chip snapshot)."""
        broker = self.broker
        for f in fs:
            s = broker.subs.get(f)
            if s:
                subs[f] = list(s.items())
            cap = capture_shared(broker, f)
            if cap:
                shared[f] = cap

    def _capture_shard(self, mine: list[str]):
        """(filters, subs, shared) for one shard's bucketed filter list."""
        subs: dict = {}
        shared: dict = {}
        self._capture_filters(mine, subs, shared)
        return mine, subs, shared

    def _shard_dims(self, capture) -> dict:
        """Raw (un-padded) dims one shard's capture needs."""
        mine, subs, shared = capture
        n_slots = sum(len(g) for g in shared.values())
        return {
            "filters": len(mine),
            "nodes": sum(len(T.tokens(f)) for f in mine) + 1,
            "subs": sum(len(v) for v in subs.values()),
            "slots": n_slots,
            "members": sum(len(m[0]) for g in shared.values()
                           for m in g.values()),
        }

    @staticmethod
    def _caps_of(dims: dict) -> dict:
        return {k: _next_pow2(max(2, v)) for k, v in dims.items()}

    @staticmethod
    def _fits(dims: dict, caps: dict) -> bool:
        return all(dims[k] <= caps[k] for k in dims)

    def _build_shard(self, capture, caps: dict):
        """Compile one shard's capture into (built, RouterTables host,
        cursors row) with the given capacity classes."""
        from emqx_tpu.models.router_engine import RouterTables
        from emqx_tpu.ops.fanout import build_subtable
        from emqx_tpu.ops.trie import build_tables

        mine, subs_cap, shared_cap = capture
        b = _ShardBuilt()
        L = self.level_cap
        # filters deeper than the level cap can't ride the device trie:
        # they match host-side per message (rare; SURVEY §5.7's too-deep
        # fallback)
        deep = [f for f in mine if len(T.tokens(f)) > L]
        for f in deep:
            b.host_extra.append((f, T.tokens(f)))
        mine = [f for f in mine if len(T.tokens(f)) <= L]
        rows = np.full((len(mine), L), I.PAD, np.int32)
        lens = np.zeros(len(mine), np.int32)
        normal: dict[int, list] = {}
        filter_slots: dict[int, list] = {}
        shared_members: dict[int, list] = {}
        seg_len = [0] * len(mine)
        cursors = []
        for fid, f in enumerate(sorted(mine)):
            ws = T.tokens(f)
            ids = self.intern.encode_filter(ws)
            rows[fid, :len(ids)] = ids
            lens[fid] = len(ids)
            b.fid_of[f] = fid
            b.fid_filter.append(f)
            entries = []
            for sid, opts in subs_cap.get(f, ()):
                # rich subopts (v5 subids etc.) don't survive the packed
                # byte: keep the device rows for alignment but deliver
                # through the host dict (same split as the single-chip
                # engine's rich_filters)
                if _is_rich(opts):
                    b.rich.add(f)
                entries.append((sid, _pack_opts(opts)))
            if entries:
                normal[fid] = entries
                seg_len[fid] = len(entries)
            for gname in sorted(shared_cap.get(f, {})):
                members_raw, cursor = shared_cap[f][gname]
                slot = len(b.slot_key)
                b.slot_key.append((f, gname))
                members = []
                for sid, o in members_raw:
                    if isinstance(sid, tuple):
                        # remote member ref -> reserved-range device sid
                        dev_sid = _REMOTE_SID_BASE + len(b.remote_members)
                        b.remote_members.append(sid)
                        members.append((dev_sid, 0))
                    else:
                        members.append((sid, _pack_opts(o)))
                shared_members[slot] = members
                filter_slots.setdefault(fid, []).append(slot)
                cursors.append(cursor)
        b.seg_len = seg_len
        # vectorized-consume masks (ISSUE 9 satellite): a matched fid
        # flagged here sends its message down the ordering-safe slow
        # path — rich subopts (host-dict delivery) or snapshot shared
        # slots (pick/ack/cluster semantics). Groups created AFTER this
        # snapshot dirty their shard, and the fast path stands down
        # whenever dirty_shards is non-empty, so the live-state check
        # the per-message walk performed is preserved.
        nf = len(b.fid_filter)
        b.seg_np = np.asarray(seg_len, np.int64)
        b.fid_slow = np.zeros(max(1, nf), bool)
        for f in b.rich:
            b.fid_slow[b.fid_of[f]] = True
        for fid in filter_slots:
            b.fid_slow[fid] = True

        # subscription covering (ISSUE 18): detect cover relations among
        # this shard's filters, compile the trie over the COVERING set
        # only, and attach the expansion CSR so match_batch re-expands
        # matched covers into the exact full-set row BEFORE the exchange
        # ships it. The stacked mesh pytree must be structurally uniform
        # across shards and across incremental rebuilds, so the knob
        # alone decides attachment: when on, every shard carries cover
        # tables — an identity CSR (every filter its own root) where the
        # shard has no cover relations. Uniform constants (match_cap out
        # width, `_COVER_CAND_CAP`-candidate verify lane,
        # caps["filters"] verify rows, 1-row append region — mesh churn rides the per-shard rebuild,
        # not the append path) keep shard slices stack/update-compatible.
        cover_np = None
        roots = None
        if self.subscription_covering:
            from emqx_tpu.ops import cover as cover_mod
            if L <= cover_mod.MAX_KEY_LEVELS:
                n = len(mine)
                dollar = np.fromiter(
                    (f.startswith("$") for f in b.fid_filter), bool, n)
                if n >= 2:
                    covers, inc = cover_mod.detect_covers(
                        rows[:n], lens, dollar)
                    # a root owns what the fixed candidate lane holds
                    # beside a root in every other slot of the NFA's
                    # match row, or nothing (`assign_owners`): no
                    # owning root overflows the lane by its own segment
                    owner = cover_mod.assign_owners(
                        covers, inc, own_budget=max(
                            0, _COVER_CAND_CAP - self.match_cap))
                else:
                    owner = np.full(n, -1, np.int64)
                keys = cover_mod.trie_order_keys(rows[:n], lens)
                cover_np = cover_mod.build_cover_tables(
                    rows[:n], lens, owner, keys,
                    fid_cap=caps["filters"], out_width=self.match_cap,
                    cand_cap=_COVER_CAND_CAP, verify_cap=caps["filters"],
                    append_cap=1)
                roots = np.flatnonzero(owner < 0).astype(np.int64)
                b.cover_roots = int(roots.size)
                b.cover_covered = n - int(roots.size)

        if cover_np is not None:
            trie = build_tables(rows[roots], lens[roots],
                                filter_ids=roots,
                                node_capacity=caps["nodes"],
                                slot_capacity=4 * caps["nodes"])
            trie = trie._replace(cover=cover_np)
        else:
            trie = build_tables(rows[:len(mine)], lens,
                                node_capacity=caps["nodes"],
                                slot_capacity=4 * caps["nodes"])
        subs_tbl = build_subtable(
            caps["filters"], {k: v for k, v in normal.items()},
            filter_slots, shared_members,
            slot_cap=caps["slots"], sub_rows_cap=caps["subs"],
            fs_rows_cap=caps["slots"], member_rows_cap=caps["members"])
        cur = np.zeros(caps["slots"], np.int32)
        cur[:len(cursors)] = cursors
        return b, RouterTables(trie=trie, subs=subs_tbl), cur

    def _next_gen(self) -> int:
        self._build_gen += 1
        return self._build_gen

    def rebuild(self) -> None:
        """Full build, synchronously: capture every shard, compute shared
        capacity classes, compile, stack, place on the mesh. Direct
        callers (tests, boot warm-up) use this; the SERVING path never
        does — poll_rebuild hands full rebuilds to a background thread
        and serves host-side meanwhile. Bumps the build generation, so
        any in-flight background capture/build is superseded (its result
        would be staler than this one and is dropped at adopt)."""
        gen = self._next_gen()
        seen = set(self.dirty_shards)
        self.dirty_shards.clear()   # the capture below covers everything
        try:
            self._adopt_full_build(self._full_build(
                [self._capture_shard(mine)
                 for mine in self._bucket_filters()]), gen)
        except Exception:
            # a failed build must not eat the churn marks: the old
            # snapshot keeps serving and those shards still need repair
            # analysis: ok(cross-thread-state) — set |= set is ONE
            # C-level update under the GIL; idempotent re-mark (same
            # mark-restore discipline as the async capture path)
            self.dirty_shards |= seen
            raise

    def _full_build(self, captures):
        """Compile every shard from its capture (loop-free: thread-safe
        off the event loop)."""
        from emqx_tpu.parallel.sharded import put_sharded, stack_tables
        dims = [self._shard_dims(c) for c in captures]
        caps = self._caps_of({k: max(d[k] for d in dims)
                              for k in dims[0]})
        builts, tables, cursors = [], [], []
        for c in captures:
            b, t, cur = self._build_shard(c, caps)
            builts.append(b)
            tables.append(t)
            cursors.append(cur)
        stacked = stack_tables(tables)
        dev_tables, dev_cursors = put_sharded(
            self.mesh, stacked, np.stack(cursors), ledger=self.ledger)
        aux, fits = self._build_aux(builts, caps) \
            if self.device_exchange else (None, True)
        return caps, builts, dev_tables, dev_cursors, aux, fits

    # ---- exchange aux (ISSUE 15) ----------------------------------------
    def _aux_host_rows(self, b: _ShardBuilt, f_cap: int):
        """One shard's exchange companions, padded to the capacity
        class: per-fid fan-out segment lengths + the slow mask."""
        seg = np.zeros(f_cap, np.int32)
        slow = np.zeros(f_cap, bool)
        nf = len(b.fid_filter)
        seg[:nf] = b.seg_np
        slow[:nf] = b.fid_slow[:nf]
        return seg, slow

    @staticmethod
    def _fid_offsets(builts) -> "tuple[np.ndarray, bool]":
        """Global-fid base per shard — the device mirror of
        _flat_filters' offsets (both are the cumsum of per-shard filter
        counts in shard order, so device-packed gfids index the same
        flat table the host consume builds). Pure: returns (offsets,
        fits-in-packed-gfid-space); the caller adopts the verdict —
        writing live state from here would let a superseded background
        build override the adopted snapshot's verdict."""
        offs = np.zeros(len(builts), np.int32)
        total = 0
        for r, b in enumerate(builts):
            offs[r] = total
            total += len(b.fid_filter)
        return offs, total < _EXCHANGE_MAX_GFID

    def _build_aux(self, builts, caps):
        """Stack + place the exchange aux tables with the 'route'
        sharding next to the shard tables."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from emqx_tpu.models.router_engine import ExchangeAux
        rows = [self._aux_host_rows(b, caps["filters"]) for b in builts]
        offs, fits = self._fid_offsets(builts)
        spec = NamedSharding(self.mesh, P("route"))
        # hbm: held by the adopter/caller under exchange_aux
        aux = ExchangeAux(
            seg_len=jax.device_put(np.stack([r[0] for r in rows]), spec),
            fid_slow=jax.device_put(np.stack([r[1] for r in rows]), spec),
            fid_off=jax.device_put(offs, spec))
        return aux, fits

    def _update_aux_shard(self, s: int, b: _ShardBuilt, builts):
        """Per-shard churn twin of _build_aux: slice-update the seg/slow
        planes (non-donating, like the tables) and re-place the tiny
        fid_off vector, which can shift for every shard after `s` when
        the shard's filter count changed."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from emqx_tpu.models.router_engine import ExchangeAux
        from emqx_tpu.parallel.sharded import _apply_shard_update_keep
        seg, slow = self._aux_host_rows(b, self._caps["filters"])
        seg2, slow2 = _apply_shard_update_keep(
            (self.aux.seg_len, self.aux.fid_slow), (seg, slow),
            np.int32(s))
        offs, fits = self._fid_offsets(builts)
        # analysis: ok(cross-thread-state) — poll_rebuild calls this
        # inside `with self._lock:`; the live-snapshot verdict adopts
        # under the same lock _adopt_full_build takes (a background
        # build's verdict instead travels in its result tuple)
        self._exch_fits = fits
        # hbm: held by the caller under exchange_aux
        off_dev = jax.device_put(offs,
                                 NamedSharding(self.mesh, P("route")))
        return ExchangeAux(seg_len=seg2, fid_slow=slow2, fid_off=off_dev)

    def _hold(self, category: str, tree, owner=None):
        """Register a persistent device allocation with the HBM ledger
        (ISSUE 8); identity passthrough when the ledger is off."""
        if self.ledger is not None:
            return self.ledger.hold(category, tree, owner=owner)
        return tree

    def _adopt_full_build(self, result, gen: int) -> bool:
        caps, builts, dev_tables, dev_cursors, aux, fits = result
        with self._lock:
            if gen <= self._adopted_gen:
                return False    # a newer build already adopted: drop
            self._adopted_gen = gen
            self.tables = dev_tables
            self.cursors = dev_cursors
            self._builts = builts
            if caps != self._caps:
                # capacity classes are the jit signature: only a class
                # change invalidates compiled batch classes — clearing
                # on every rebuild kept the device permanently cold
                # under subscribe churn. The exchange programs trace
                # the aux planes' filter capacity, so their warm set
                # rides the same clock.
                self._warm_classes.clear()
                self._exch_warm.clear()
            self._caps = caps
            self.aux = self._hold("exchange_aux", aux) \
                if aux is not None else None
            self._exch_fits = fits
        return True

    def _kick_full_rebuild(self) -> None:
        """Background full rebuild: CAPTURE on the event-loop side in
        yielding chunks (a large routing state must not stall every
        connection for the whole capture — round-4 advisor finding;
        mirrors DeviceRouteEngine._capture_state_async), COMPILE +
        UPLOAD on a thread. Serving stays host-side until the swap
        (prepare_window returns None while this runs) — the single-chip
        engine's double-buffered rebuild, mesh edition.

        Dirty marks clear BEFORE the capture starts: churn landing
        mid-capture or mid-compile re-dirties its shard and follows as a
        per-shard update after the swap, which also self-heals any
        filter the chunked capture saw half-mutated. A failed build
        restores the marks and backs off before the next attempt — a
        persistent compile error must not become a tight respawn
        loop."""
        import asyncio
        if self._rebuild_thread is not None \
                and self._rebuild_thread.is_alive():
            return
        if self._capture_task is not None \
                and not self._capture_task.done():
            return
        if time.monotonic() < self._rebuild_backoff_until:
            return
        gen = self._next_gen()
        seen = set(self.dirty_shards)
        # analysis: ok(cross-thread-state) — set -= set is ONE C-level
        # difference_update under the GIL; removing exactly `seen`
        # keeps any mark the build thread adds concurrently (the
        # mark-restore discipline the gen checks below complete)
        self.dirty_shards -= seen
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            # no loop (tests / boot warm-up thread): sync capture is fine
            self._start_build_thread(
                [self._capture_shard(mine)
                 for mine in self._bucket_filters()], seen, gen)
            return
        self._capture_gen = gen
        from emqx_tpu.broker.supervise import guard_task
        self._capture_task = guard_task(
            loop.create_task(self._capture_then_build(seen, gen)),
            "mesh-capture", self.node.metrics)

    async def _capture_then_build(self, seen, gen: int) -> None:
        import asyncio
        chunk = 2048
        try:
            captures = []
            for mine in self._bucket_filters():
                subs: dict = {}
                shared: dict = {}
                for i in range(0, len(mine), chunk):
                    self._capture_filters(mine[i:i + chunk], subs, shared)
                    await asyncio.sleep(0)
                captures.append((mine, subs, shared))
        except Exception:   # noqa: BLE001 — surfaced + retried
            import logging
            logging.getLogger("emqx_tpu.serving").exception(
                "chunked mesh capture failed; backing off")
            # analysis: ok(cross-thread-state) — set |= set is ONE
            # C-level update under the GIL; re-marking is idempotent
            # against the build thread's concurrent |=
            self.dirty_shards |= seen
            self._rebuild_backoff_until = time.monotonic() + 5.0
            return
        if gen != self._build_gen:
            # superseded by a newer capture/rebuild: drop the captures,
            # but RESTORE the marks — if the superseding build failed,
            # these shards' churn would otherwise be permanently lost
            # analysis: ok(cross-thread-state) — set |= set is ONE
            # C-level update under the GIL; idempotent re-mark
            self.dirty_shards |= seen
            return
        self._start_build_thread(captures, seen, gen)

    def _start_build_thread(self, captures, seen, gen: int) -> None:
        def work():
            try:
                result = self._full_build(captures)
            except Exception:   # noqa: BLE001 — surfaced + retried
                import logging
                logging.getLogger("emqx_tpu.serving").exception(
                    "background mesh rebuild failed; backing off")
                self.node.metrics.inc("routing.mesh.rebuild_failed")
                # analysis: ok(cross-thread-state) — set |= set is ONE
                # C-level update under the GIL; the loop side's -= of
                # its own snapshot can't lose this re-mark
                self.dirty_shards |= seen
                self._rebuild_backoff_until = time.monotonic() + 5.0
                return
            if not self._adopt_full_build(result, gen):
                # a newer build won the race; its capture covered this
                # one's state, but conservatively re-mark the shards
                # analysis: ok(cross-thread-state) — set |= set is ONE
                # C-level update under the GIL; idempotent re-mark
                self.dirty_shards |= seen

        self._rebuild_thread = threading.Thread(target=work, daemon=True)
        self._rebuild_thread.start()

    def poll_rebuild(self) -> bool:
        """Apply pending churn BEFORE serving. Dirty shards rebuild
        host-side with the snapshot's capacities and only their device
        slices update (non-donating: in-flight handles still read the
        previous arrays); outgrowing a class kicks a BACKGROUND full
        rebuild. Returns False while the mesh cannot serve (no snapshot
        yet / full rebuild in progress) — callers route host-side."""
        if self._rebuild_thread is not None \
                and self._rebuild_thread.is_alive():
            return False
        if self._capture_task is not None \
                and not self._capture_task.done() \
                and self._capture_gen == self._build_gen:
            return False    # authoritative capture in progress
        if self._builts is None:
            self._kick_full_rebuild()
            return False
        if not self.dirty_shards:
            return True
        from emqx_tpu.parallel.sharded import update_shard
        buckets = self._bucket_filters()
        pending = sorted(self.dirty_shards)
        for s in pending:
            capture = self._capture_shard(buckets[s])
            if not self._fits(self._shard_dims(capture), self._caps):
                self._kick_full_rebuild()
                return False
            b, t, cur = self._build_shard(capture, self._caps)
            with self._lock:
                # update_shard emits all-new stacked arrays (donate=
                # False): re-register them so the ledger tracks the
                # live generation (the superseded arrays release on GC)
                self.tables = self._hold(
                    "mesh_tables", update_shard(self.tables, s, t,
                                                donate=False))
                cur_np = np.array(self.cursors)     # copy: jax buffers
                cur_np[s] = cur                     # are read-only
                import jax
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P
                self.cursors = self._hold("mesh_cursors", jax.device_put(
                    cur_np, NamedSharding(self.mesh, P("route"))))
                # copy-on-write: in-flight handles keep decoding with the
                # list they captured (their tables snapshot predates this
                # update), and the dispatch-side `_builts is h.built`
                # cursor guard must FIRE for them now
                builts = list(self._builts)
                builts[s] = b
                self._builts = builts
                if self.aux is not None:
                    # exchange aux rides the same per-shard update so
                    # a handle's (tables, aux) snapshot stays coherent
                    self.aux = self._hold(
                        "exchange_aux",
                        self._update_aux_shard(s, b, builts))
                self.dirty_shards.discard(s)
        return True

    # ---- PublishBatcher engine protocol ---------------------------------
    def _batch_class(self, n: int) -> int:
        return min(self.max_batch,
                   max(self.n_dp, _next_pow2(max(2, n))))

    def batch_class_warm(self, n_msgs: int) -> bool:
        return self._builts is not None and \
            self._batch_class(n_msgs) in self._warm_classes

    def _kick_class_warm(self) -> None:
        """Compile the standard batch classes off the serving path."""
        if self._warm_thread is not None and self._warm_thread.is_alive():
            return
        if self._builts is None:
            return

        def warm_all():
            # loop until every class is warm for the CURRENT capacity
            # signature: a caps-changing rebuild mid-loop clears earlier
            # classes, and a single ascending pass would never revisit
            # them (observed: only the last class warm, device cold)
            classes = []
            Bp = self.n_dp
            while Bp <= self.max_batch:
                classes.append(Bp)
                Bp *= 2
            for _ in range(8 * (len(classes) + 4)):   # bounded self-heal
                if self._builts is None:
                    return
                missing = [c for c in classes
                           if c not in self._warm_classes]
                # demand-registered compact readback classes re-run the
                # (cached) step for their Bp and compact ITS result, so
                # the compaction compiles against the step outputs'
                # actual shardings/dtypes (a numpy dummy would warm the
                # wrong program variant). list() first: materialize on
                # the executor thread .add()s concurrently, and a set
                # comprehension over the live set is a bytecode-level
                # iteration that would raise changed-size-during-iter
                # and kill the warm pass (list(set) is one atomic C call)
                want_c = sorted({bq for bq, P in list(self._wanted_pcap)
                                 if (bq, P) not in self._compact_warm})
                # demand-registered exchange classes (ISSUE 15) warm the
                # same way: re-run the step for their Bp and exchange
                # ITS result (right shardings); same atomic list()
                # snapshot discipline against concurrent .add()s
                want_e = sorted({bq for bq, E in list(self._wanted_ecap)
                                 if (bq, E) not in self._exch_warm})
                if not missing and not want_c and not want_e:
                    return
                self._warm_one((missing + want_c + want_e)[0])

        def warm():
            try:
                warm_all()
            except Exception:  # noqa: BLE001 — classes stay cold, retry
                import logging
                logging.getLogger("emqx.device").exception(
                    "mesh class warm-compile failed; affected classes "
                    "stay host-routed until the next attempt")
                self.node.metrics.inc("routing.device.warm_failed")

        self._warm_thread = threading.Thread(target=warm, daemon=True)
        self._warm_thread.start()

    def _warm_one(self, Bp: int) -> None:
        import contextlib

        import jax
        from emqx_tpu.ops.shared import STRATEGY_ROUND_ROBIN
        tele = getattr(self.node, "pipeline_telemetry", None)
        enc = (np.full((Bp, self.level_cap), I.PAD, np.int32),
               np.zeros(Bp, np.int32), np.zeros(Bp, bool),
               np.zeros(Bp, np.int32))
        with self._lock:
            tables, cursors, caps = self.tables, self.cursors, self._caps
            aux = self.aux
        ctx = tele.compile_context(f"warm mesh B{Bp}") \
            if tele is not None else contextlib.nullcontext()
        with ctx:
            res = self.step(tables, cursors, *enc,
                            np.int32(STRATEGY_ROUND_ROBIN))
            jax.block_until_ready(res)
        with self._lock:
            if self._caps == caps:      # signature still current
                self._warm_classes.add(Bp)
        # wanted compact classes for this Bp compile against the step's
        # own outputs (right shardings); keyed (Bp, P) only — payload
        # classes are capacity-signature independent
        from emqx_tpu.ops.compact import compact_planes_jit
        # sorted() snapshots the set in one atomic C call — safe against
        # concurrent materialize-side .add()s
        for bq, P in sorted(self._wanted_pcap):
            if bq != Bp or (Bp, P) in self._compact_warm:
                continue
            cw = tele.compile_context(f"warm mesh B{Bp}c{P}") \
                if tele is not None else contextlib.nullcontext()
            with cw:
                cp = compact_planes_jit(
                    res.matches, res.rows, res.opts, res.fan_counts,
                    res.shared_sids, res.shared_rows, res.shared_opts,
                    payload_cap=P, match_holes=False)
                jax.block_until_ready(cp.offsets)
            self._compact_warm.add((Bp, P))
        # wanted exchange classes for this Bp (ISSUE 15): the exchange
        # program compiles against the warm step's own outputs plus the
        # live aux snapshot; keyed (Bp, E) and cleared with the caps
        # signature (the aux planes' filter capacity is traced)
        if aux is not None:
            from emqx_tpu.parallel.sharded import make_exchange_step
            for bq, E in sorted(self._wanted_ecap):
                if bq != Bp or (Bp, E) in self._exch_warm:
                    continue
                fn = self._exch_steps.get(E)
                if fn is None:
                    fn = make_exchange_step(self.mesh, seg_cap=E)
                    self._exch_steps[E] = fn
                ce = tele.compile_context(f"warm mesh B{Bp}x{E}") \
                    if tele is not None else contextlib.nullcontext()
                with ce:
                    ex = fn(res.matches, res.rows, res.opts,
                            res.shared_sids, res.overflow, *aux)
                    jax.block_until_ready(ex.plan)
                with self._lock:
                    if self._caps == caps:
                        self._exch_warm.add((Bp, E))

    def _probe_mesh(self) -> None:
        """mesh_exchange half-open probe (ISSUE 6): run the sharded
        step warm-shaped over an all-pad batch, off the serving path —
        the same call _warm_one already makes from background threads.
        With the exchange stage on, the probe also registers (and so
        runs) the exchange program at the probe's batch class: the
        domain covers the ring, and a breaker opened by a dead ring
        must not be re-closed by a probe that never touches it.
        Raising keeps the breaker open."""
        if self._builts is None:
            return      # nothing to probe: vacuous health
        if self.device_exchange and self.aux is not None \
                and self._exch_fits:
            key = (self.n_dp, self._choose_ecap(self.n_dp))
            self._wanted_ecap.add(key)
            # discard so _warm_one RE-RUNS the program even if the
            # class is warm — a dead ring behind a warm class would
            # otherwise pass the probe untraversed (the serving thread
            # at most gathers one window as cold_class meanwhile)
            self._exch_warm.discard(key)
        self._warm_one(self.n_dp)

    def max_fuse(self) -> int:
        return 1        # no window fusion on the mesh path (yet)

    def abandon(self, h: _Handle) -> None:
        h.res = None
        h.np_res = None
        h.exch = None
        if self.ledger is not None:
            self.ledger.unpin(id(h))

    def prepare(self, msgs: list[Message]) -> Optional[_Handle]:
        return self.prepare_window([msgs])

    def prepare_window(self, lives, gate_cold: bool = True) -> \
            Optional[_Handle]:
        """Stage 1 (event loop): encode one micro-batch (W=1).

        The single-chip engine's match cache / dedup layer is explicitly
        BYPASSED here: the mesh step matches against R per-shard table
        stacks whose slices are updated independently (update_shard), so
        there is no single snapshot id a cached row could be keyed to —
        a per-shard (shard, generation) key space is the prerequisite
        before the mesh can consult the same cache. Until then every
        mesh batch pays the full sharded match, and stats() reports the
        bypass so bench rows can't mistake it for a cold cache."""
        if self.sup is not None:
            self.sup.poll()     # supervision tick (probe launcher)
            if not self.sup.mesh_enabled():
                # mesh_exchange breaker open (ISSUE 6): the mesh's
                # rung-2 — every batch host-routes until the half-open
                # probe (a warm-shaped step off the serving path)
                # proves the mesh healthy again
                return None
        if not self.poll_rebuild() or self._builts is None or not lives:
            return None
        from emqx_tpu.ops.match import encode_topics_str
        msgs = lives[0]
        Bp = self._batch_class(len(msgs))
        if len(msgs) > Bp:
            return None
        enc, lens, dollar, too_long = encode_topics_str(
            self.intern, [m.topic for m in msgs], self.level_cap)
        host_idx = set(np.flatnonzero(too_long).tolist())
        pad = Bp - len(msgs)
        if pad:
            enc = np.vstack([enc, np.full((pad, self.level_cap), I.PAD,
                                          np.int32)])
            lens = np.concatenate([lens, np.zeros(pad, np.int32)])
            dollar = np.concatenate([dollar, np.zeros(pad, bool)])
        msg_hash = np.array(
            [zlib.crc32(m.topic.encode()) & 0x7FFFFFFF for m in msgs]
            + [0] * pad, np.int32)
        tele = getattr(self.node, "pipeline_telemetry", None)
        if tele is not None:
            tele.record_occupancy(f"b{Bp}", len(msgs) / Bp)
        with self._lock:
            h = _Handle(subs=[msgs], built=self._builts,
                        tables=self.tables, cursors=self.cursors,
                        enc=(enc, lens, dollar, msg_hash),
                        host_idx=host_idx, aux=self.aux,
                        exch_fits=self._exch_fits)
        if self.ledger is not None:
            # pin sentinel (ISSUE 8): mesh handles pin the whole
            # stacked snapshot by reference — a leaked one holds every
            # shard's HBM, so it rides the same stale-pin clock
            self.ledger.note_window()
            self.ledger.pin(id(h), h)
        return h

    def dispatch(self, h: _Handle) -> None:
        """Stage 2 (executor thread): run the mesh step on the handle's
        pinned snapshot; adopt cursors unless an update raced (then the
        freshly written cursor row wins — a one-batch fairness blip, not
        a correctness input). The batcher serializes dispatches on one
        thread, so cursor threading across batches is ordered."""
        with self.spans.span("dispatch", h.trace, track="dispatch",
                             meta={"B": h.enc[0].shape[0]}):
            self._dispatch(h)

    def _dispatch(self, h: _Handle) -> None:
        import contextlib

        from emqx_tpu.ops.shared import STRATEGIES
        strategy = STRATEGIES.get(self.broker.shared_strategy, 0)
        tele = getattr(self.node, "pipeline_telemetry", None)
        with self._lock:
            # live cursors when no update raced (pipelined batches chain
            # round-robin state); the pinned ones otherwise — they are
            # the only set consistent with h.tables' slot layout
            cursors = self.cursors if self._builts is h.built \
                else h.cursors
        ctx = tele.compile_context(f"mesh B{h.enc[0].shape[0]}") \
            if tele is not None else contextlib.nullcontext()
        try:
            with ctx:
                if self.sup is not None:
                    # ISSUE 6 injection point (executor thread): the
                    # cross-shard exchange — exceptions propagate to
                    # the batcher's consumer (host replay) with the
                    # mesh domain noted here; hangs are caught by the
                    # consumer's watchdog deadline
                    self.sup.fire("mesh_exchange")
                h.res = self.step(h.tables, cursors, *h.enc,
                                  np.int32(strategy))
        except Exception as e:
            if self.sup is not None:
                self.sup.note_fault("mesh_exchange", e)
            raise
        with self._lock:
            if self._builts is h.built:    # no rebuild raced us
                self.cursors = self._hold("mesh_cursors",
                                          h.res.new_cursors)
        # the mesh_exchange domain covers the step AND the ring: the
        # domain's ok is recorded only once both succeeded — a note_ok
        # for the step alone would reset the breaker's consecutive-
        # fault count right before a persistently dead ring's
        # note_fault, and the breaker could never trip
        exchange_faulted = self._run_exchange(h)
        if self.sup is not None and not exchange_faulted:
            self.sup.note_ok("mesh_exchange")
        if self.dispatch_depth > 1:
            # ISSUE 9: start the readback transfers while this thread
            # still owns the dispatch slot — materialize(W) then hides
            # under dispatch(W+1)
            self._start_readback(h)

    def _start_readback(self, h: _Handle) -> None:
        """Async-start the device→host transfer of the planes
        materialize will read (ISSUE 9): the small overflow/occur
        planes always; the dense result planes only when the CSR
        compaction will not supersede them (a compact materialize runs
        its own jitted pass first — prefetching the dense planes would
        waste exactly the bytes ISSUE 3 removed). The in-flight result
        registers with the HBM ledger under `pipeline_buffers`.
        Best-effort: a backend without async copies keeps the
        synchronous transfer in materialize."""
        r = h.res
        if r is None:
            return
        if self.ledger is not None:
            self._hold("pipeline_buffers", r)
        planes = [r.overflow, r.occur]
        if h.exch is not None:
            # exchange windows land only the occupied plan prefix —
            # prefetch the small control planes (ok probe, counts) ON
            # TOP of the base overflow/occur, which the gather rung
            # still needs if the clean-proof rejects this window; the
            # plan slice itself is cut after the counts arrive
            planes += [h.exch.ok, h.exch.plan_cnt, h.exch.src_cnt]
        else:
            Bp = int(r.matches.shape[0])
            P = self._choose_pcap(Bp)
            if P is None or (Bp, P) not in self._compact_warm:
                planes += [r.matches, r.rows, r.opts, r.shared_sids,
                           r.shared_rows, r.shared_opts]
        for a in planes:
            try:
                a.copy_to_host_async()
            except AttributeError:
                return
            except Exception:  # noqa: BLE001 — best-effort prefetch
                return

    def _choose_pcap(self, Bp: int) -> Optional[int]:
        """Payload class for a Bp-wide mesh readback, or None for dense.
        Same peak-biased-EWMA + pow2-multiple-ladder scheme as the
        single-chip engine (device_engine._choose_payload_cap); entry
        totals sum over shards, so the ladder multiplies Bp, not Bp*R."""
        if not self.compact_readback:
            return None
        dense = self.match_cap + 2 * self.fanout_cap + 3 * self.slot_cap
        mults = [m for m in self._payload_mults if m < dense]
        if not mults:
            return None
        ew = self._pay_ewma
        if ew is None:
            return mults[min(1, len(mults) - 1)] * Bp
        for m in mults:
            if m * Bp >= 2.0 * ew:
                return m * Bp
        return None

    def _note_payload(self, total: float) -> None:
        ew = self._pay_ewma
        self._pay_ewma = total if (ew is None or total > ew) \
            else 0.8 * ew + 0.2 * total

    # ---- exchange stage (ISSUE 15) --------------------------------------
    def _choose_ecap(self, Bp: int) -> int:
        """Per-dest exchange segment capacity class for a Bp-wide
        window: the smallest rung of a {pow2, 1.5*pow2} ladder holding
        1.25x the peak-biased EWMA of observed per-dest row counts —
        finer steps than the pow2-only payload ladder because every
        padded slot here is a byte the host lands. Bounded above by the
        everything-to-one-dest worst case: a dest's merged plan can
        hold every source shard's full fan-out plane for its dp
        block."""
        b_local = max(1, Bp // self.n_dp)
        cap_max = _next_pow2(b_local * self.fanout_cap
                             * max(1, self.n_route))
        ew = self._exch_ewma
        if ew is None:
            need = max(16, b_local // max(1, self.n_route))
        else:
            # class headroom over the peak-biased EWMA absorbs window-
            # to-window variance (an undersized class overflows whole
            # windows to gather); the padding it buys never crosses to
            # the host — materialize lands only the occupied prefix
            need = max(16, int(1.25 * ew) + 1)
        E = 16
        while E < need and E < cap_max:
            # 16, 24, 32, 48, 64, 96, 128, ...
            E = E * 3 // 2 if (E & (E - 1)) == 0 else E * 4 // 3
        return min(E, cap_max)

    def _note_exch(self, mx: float) -> None:
        ew = self._exch_ewma
        self._exch_ewma = mx if (ew is None or mx > ew) \
            else 0.8 * ew + 0.2 * mx

    def warm_exchange(self, n_msgs: int) -> bool:
        """Blocking warm of the exchange class serving `n_msgs`-wide
        batches (tests / bench warm-up — never the serving path, which
        demand-registers and warms in the background)."""
        if not self.device_exchange or self._builts is None \
                or self.aux is None:
            return False
        Bp = self._batch_class(n_msgs)
        key = (Bp, self._choose_ecap(Bp))
        self._wanted_ecap.add(key)
        self._warm_one(Bp)
        return key in self._exch_warm

    def _run_exchange(self, h: _Handle) -> bool:
        """Stage 2b (executor thread, right after the route step): run
        the device-to-device exchange program on the handle's pinned
        (result, aux) snapshot. Every stand-down is counted, never
        silent; a raising program degrades THIS window to host gather
        and advances the mesh_exchange breaker — a dead ring sheds to
        the gather rung instead of losing windows. Returns True iff
        the program FAULTED (the caller then withholds the domain's
        note_ok so the breaker's fault count actually accumulates);
        stand-downs are not faults."""
        if not self.device_exchange or h.aux is None or h.res is None:
            return False
        metrics = self.node.metrics
        if not h.exch_fits:
            # the handle's PINNED snapshot verdict, not the live one —
            # a rebuild adopted between prepare and dispatch must not
            # run this aux's gfids against the new verdict. Counted per
            # stood-down WINDOW (the every-stand-down-is-counted
            # invariant), not once per table build.
            metrics.inc("pipeline.exchange.fallback.gfid_space")
            return False
        if self.broker.cluster is not None \
                or self.broker.shared_strategy not in \
                self._dev_strategies() \
                or any(b.host_extra for b in h.built):
            metrics.inc("pipeline.exchange.fallback.precluded")
            return False
        if h.host_idx:
            # too-long topics route host-side per message: the device
            # plan can't represent them, so the window gathers
            metrics.inc("pipeline.exchange.fallback.host_idx")
            return False
        Bp = int(h.res.matches.shape[0])
        E = self._choose_ecap(Bp)
        if (Bp, E) not in self._exch_warm:
            # target class cold: background-warm it, and meanwhile keep
            # serving with the largest warm class that still holds the
            # observed peak (overflow falls back per window anyway) —
            # without this, every EWMA-driven resize would flap the
            # whole stage back to host gather until the compile landed
            self._wanted_ecap.add((Bp, E))
            self._kick_class_warm()
            ew = self._exch_ewma
            # sorted() snapshots the set in one atomic C call — safe
            # against the warm thread's concurrent .add()s
            cand = [e for bq, e in sorted(self._exch_warm)
                    if bq == Bp and (ew is None or e >= ew)]
            if not cand:
                metrics.inc("pipeline.exchange.cold_class")
                return False
            E = max(cand)
        fn = self._exch_steps.get(E)
        if fn is None:      # warm set says yes but builder raced: punt
            metrics.inc("pipeline.exchange.cold_class")
            return False
        t0 = time.perf_counter()
        r = h.res
        try:
            h.exch = fn(r.matches, r.rows, r.opts, r.shared_sids,
                        r.overflow, *h.aux)
        except Exception as e:  # noqa: BLE001 — degrade, don't lose
            if self.sup is not None:
                self.sup.note_fault("mesh_exchange", e)
            metrics.inc("pipeline.exchange.fallback.error")
            h.exch = None
            return True
        if self.ledger is not None:
            self._hold("exchange_buffers", h.exch)
        # bytes moved device-to-device: every device sends R-1 blocks
        # of [E, 3] int32 around the ring (counts ride one tiny
        # all_gather: R*4 bytes per device, included)
        R = self.n_route
        n_dev = self.n_dp * R
        metrics.inc("pipeline.exchange.rounds", R - 1)
        metrics.inc("pipeline.exchange.bytes_exchanged",
                    n_dev * ((R - 1) * E * 12 + R * 4))
        self.spans.record("exchange", h.trace, t0, stage="exchange",
                          track="dispatch")
        return False

    def materialize(self, h: _Handle) -> None:
        """Stage 3 (executor thread): device → host readbacks.

        With compaction on (ISSUE 3) the [B, R, ...] result planes are
        compacted by a second small jitted call into one [1, B*R] CSR
        payload (lane = i*R + r) and only offsets + actual entries cross
        to the host; the small overflow/occur planes ride along either
        way. A window outgrowing its payload class reads the dense
        planes instead (row_overflow) — correctness never depends on the
        class fitting. Bytes transferred land in pipeline.readback.*."""
        with self.spans.span("materialize", h.trace,
                             track="materialize"):
            self._materialize(h)

    def _materialize(self, h: _Handle) -> None:
        metrics = self.node.metrics
        r = h.res
        if h.exch is not None and self._materialize_exchange(h, metrics):
            return
        Bp = int(r.matches.shape[0])
        P = self._choose_pcap(Bp)
        if P is not None and (Bp, P) not in self._compact_warm:
            # cold compact class: dense this batch, background-warm it
            # (materialize runs off-loop, but an in-path XLA compile
            # would still stall this batch's pipeline slot for seconds)
            self._wanted_pcap.add((Bp, P))
            self._kick_class_warm()
            metrics.inc("routing.device.cold_compact_class")
            P = None
        csr_probe_bytes = 0
        if P is not None:
            from emqx_tpu.ops.compact import compact_planes_jit
            # match_holes=False: the mesh step is trie-backed (its NFA
            # emissions are densely packed, never hole-y like shapes)
            cp = compact_planes_jit(
                r.matches, r.rows, r.opts, r.fan_counts, r.shared_sids,
                r.shared_rows, r.shared_opts, payload_cap=P,
                match_holes=False)
            off = np.asarray(cp.offsets)[0]
            c3 = np.asarray(cp.counts3)[0]
            rovf = np.asarray(cp.row_overflow)
            self._note_payload(float(off[-1]))
            if rovf.any():
                metrics.inc("routing.device.compact_overflow")
                # the CSR probe planes already crossed: bill them to the
                # dense window below so the exported reduction stays
                # honest on overflowing workloads
                csr_probe_bytes = off.nbytes + c3.nbytes + rovf.nbytes
            else:
                pay = np.asarray(cp.payload)[0]
                overflow = np.asarray(r.overflow)
                occur = np.asarray(r.occur)
                h.np_res = {"csr": (off, c3, pay), "overflow": overflow,
                            "occur": occur}
                metrics.inc("pipeline.readback.bytes.compact",
                            off.nbytes + c3.nbytes + pay.nbytes
                            + overflow.nbytes + occur.nbytes)
                metrics.inc("pipeline.readback.windows.compact")
                return
        h.np_res = self._dense_np_res(r)
        metrics.inc("pipeline.readback.bytes.dense",
                    sum(a.nbytes for a in h.np_res.values())
                    + csr_probe_bytes)
        metrics.inc("pipeline.readback.windows.dense")

    @staticmethod
    def _dense_np_res(r) -> dict:
        return {
            "matches": np.asarray(r.matches),
            "rows": np.asarray(r.rows), "opts": np.asarray(r.opts),
            "shared_sids": np.asarray(r.shared_sids),
            "shared_rows": np.asarray(r.shared_rows),
            "shared_opts": np.asarray(r.shared_opts),
            "overflow": np.asarray(r.overflow),
            "occur": np.asarray(r.occur),      # [R, G]
        }

    def _fast_lane_live_ok(self, builts) -> bool:
        """THE post-dispatch live-state guard, shared by every fast
        lane (_consume_fast, the exchange materialize/consume): a
        cluster, churn marks, a raced snapshot swap, a rebuild in
        flight, a non-device strategy or too-deep filters mean the
        snapshot-proven clean masks can no longer be trusted. One
        predicate on purpose — a disqualifier added to one lane but
        not the other would silently diverge the fast paths from the
        per-message oracle. Note dirty_shards alone is NOT sufficient:
        a rebuild clears the marks at capture while the old snapshot
        keeps serving, and a per-shard sync update swaps the LIVE
        builts under an in-flight handle still pinned to the old list
        — either way the pinned fid_slow masks can miss a shared group
        subscribed after this handle's snapshot, and those messages
        must ride the per-message path, whose handled-set sweep checks
        live broker.shared."""
        broker = self.broker
        return not (broker.cluster is not None or self.dirty_shards
                    or builts is not self._builts
                    or (self._rebuild_thread is not None
                        and self._rebuild_thread.is_alive())
                    or (self._capture_task is not None
                        and not self._capture_task.done())
                    or broker.shared_strategy
                    not in self._dev_strategies()
                    or any(b.host_extra for b in builts))

    def _materialize_exchange(self, h: _Handle, metrics) -> bool:
        """Land the exchange result if every device reported clean +
        in-capacity; else count the reason and let the gather path land
        this window (the dense/CSR planes are outputs of the same step
        — transferring them is the fallback, computing them was free).
        Returns True when the exchange plans were landed."""
        if not self._fast_lane_live_ok(h.built):
            # disqualified already: land dense HERE, on the executor
            # thread, where the gather path always transfers — leaving
            # it for finish would block the event loop on a cold
            # multi-MB readback (the finish-time re-check below only
            # catches the rare churn that lands after this point)
            metrics.inc("pipeline.exchange.fallback.late")
            return False
        ex = h.exch
        ok = np.asarray(ex.ok)
        if not ok.size or int(ok.min()) != 3:
            if ok.size and not (ok & 2).all():
                # a segment/plan outgrew its capacity class: count it
                # and push the EWMA past the class so the next window
                # registers the bigger program
                metrics.inc("pipeline.exchange.overflow")
                cnt = np.asarray(ex.plan_cnt)
                if cnt.size:
                    # the true count is clamped at the class cap: bump
                    # one ladder rung past it and let the next landed
                    # windows' real maxima settle the EWMA
                    self._note_exch(float(cnt.max()) * 1.25)
            else:
                metrics.inc("pipeline.exchange.fallback.unclean")
            metrics.inc("pipeline.exchange.probe_bytes", ok.nbytes)
            return False
        cnt = np.asarray(ex.plan_cnt)
        scnt = np.asarray(ex.src_cnt)
        hi = int(cnt.max()) if cnt.size else 0
        self._note_exch(float(hi))
        # land only the occupied prefix of the plans: the class slack
        # (E - max cnt) never crosses the device→host link. Quantized
        # to 8 rows so the slice program set stays bounded (≤ E/8
        # cached variants per class).
        E = int(ex.plan.shape[2])
        hq = min(E, max(8, -(-hi // 8) * 8))
        plan = np.asarray(ex.plan[:, :, :hq])
        h.np_res = {"exchange": (plan, cnt, scnt)}
        # windows/host_landed_bytes are counted at CONSUME, once the
        # plans actually served — a finish-time disqualifier re-lands
        # dense, and billing this window on both paths would deflate
        # every bytes-per-window rate built on the counters
        h.exch_bytes = ok.nbytes + plan.nbytes + cnt.nbytes + scnt.nbytes
        return True

    def _land_dense(self, h: _Handle) -> dict:
        """Late gather fallback (finish-time disqualifier: churn or a
        cluster landed between dispatch and consume): transfer the
        dense planes from the still-held device result and bill them
        honestly as a dense readback window."""
        np_res = self._dense_np_res(h.res)
        self.node.metrics.inc("pipeline.exchange.fallback.late")
        self.node.metrics.inc("pipeline.readback.bytes.dense",
                              sum(a.nbytes for a in np_res.values()))
        self.node.metrics.inc("pipeline.readback.windows.dense")
        return np_res

    def finish_sub(self, h: _Handle, k: int,
                   defer: bool = True) -> list[int]:
        """Stage 4 (event loop): consume into deliveries (W=1: k==0).

        Reuses the ISSUE-5 delivery-lane pool when the node carries one
        (`defer=True`, the pipelined path): messages whose every
        delivery is a plain local fan-out row are collected into the
        session-affine plan (_collect_clean), everything else —
        host-forced, overflow, shared groups, rich filters, too-deep
        host_extra, clustered — rides the plan's ordered barrier
        closures, so the per-session interleaving matches the inline
        loop exactly. `defer=False` (route_batch) stays inline."""
        with self.spans.span("finish_sub", h.trace, stage="deliver",
                             track="consume"):
            return self._finish_sub(h, k, defer)

    def _finish_sub(self, h: _Handle, k: int, defer: bool):
        msgs = h.subs[k]
        np_res = h.np_res
        plan = None
        pool = None
        if defer:
            pool = getattr(self.node, "deliver_lanes", None)
            if pool is not None and pool.active():
                plan = pool.new_plan(msgs)  # None without a loop
                if plan is not None:
                    plan.routed_device = True
                    # causal context → lanes; per-sub when the batcher
                    # attributed one (fused windows — max_fuse() is 1
                    # on the mesh today, so this is the W=1 lead trace)
                    plan.trace = h.sub_traces[k] \
                        if h.sub_traces and k < len(h.sub_traces) \
                        else h.trace
        # exchange windows (ISSUE 15): the landed per-dest plans ARE
        # the delivery work — consume them directly. A finish-time
        # disqualifier (churn/cluster landed after dispatch) re-lands
        # the dense planes from the still-held device result instead:
        # correctness first, the bytes billed honestly.
        fast = None
        if np_res is not None and "exchange" in np_res:
            fast = self._consume_exchange(msgs, np_res["exchange"],
                                          h.built, plan)
            if fast is None:
                # the landed-but-unconsumed plan bytes bill as probe
                # traffic; the window itself bills as the dense window
                # it becomes
                self.node.metrics.inc("pipeline.exchange.probe_bytes",
                                      h.exch_bytes)
                np_res = self._land_dense(h)
                h.np_res = np_res
            else:
                self.node.metrics.inc("pipeline.exchange.windows")
                self.node.metrics.inc(
                    "pipeline.exchange.host_landed_bytes", h.exch_bytes)
        if fast is None:
            # vectorized pre-pass (ISSUE 9 satellite): one numpy sweep
            # over the [B, route] planes serves every provably-clean
            # message; None (global disqualifier: cluster / dirty
            # shard / host_extra) keeps the pre-vectorized per-message
            # path below bit-exact
            fast = self._consume_fast(msgs, np_res, h.built, plan,
                                      h.host_idx)
        counts: list[int] = []
        for i, msg in enumerate(msgs):
            if fast is not None and fast[i] is not None:
                counts.append(0 if fast[i] is DEFERRED
                              else int(fast[i]))
                continue
            if i in h.host_idx or bool(np_res["overflow"][i].any()):
                if plan is not None:
                    counts.append(0)
                    plan.add_slow(i, lambda m=msg: self._host_route(m))
                else:
                    counts.append(self._host_route(msg))
                continue
            if plan is not None:
                rows = self._collect_clean(msg, i, np_res, h.built) \
                    if fast is None else None
                counts.append(0)
                if rows is not None:
                    plan.register_fast([i])
                    plan.add_rows_py(i, rows)
                else:
                    plan.add_slow(
                        i, lambda m=msg, j=i: self._consume_one(
                            m, j, np_res, h.built))
                continue
            counts.append(self._consume_one(msg, i, np_res, h.built))
        if "occur" in np_res:
            # exchange windows skip the occur plane: clean-proof means
            # no shared-slot occurrences, so there is nothing to mirror
            self._writeback_cursors(np_res["occur"], h.built)
        # one consumed device batch — the same counter the single-chip
        # engine's finish_sub keeps, so "did the device serve" reads the
        # same on both engines
        self.node.metrics.inc("routing.device.batches")
        if plan is not None:
            out = LaneCounts(counts)
            out.plan = plan
            plan.target = out
            pool.submit(plan)
            counts = out
        if self.ledger is not None:
            # consumed (lane plans keep the arrays alive by reference;
            # the pin tracks swap-blocking in-flight handles only)
            self.ledger.unpin(id(h))
        return counts

    def _flat_filters(self, builts):
        """(flat fid->filter list, per-shard offsets) across the
        snapshot's shards: global fid = offs[r] + local fid. Memoized on
        the copy-on-write _builts identity, so a shard update refreshes
        it and in-flight handles pinned to the old snapshot still
        resolve through their own builts list."""
        memo = self._flat_memo
        if memo is not None and memo[0] is builts:
            return memo[1], memo[2]
        flat: list[str] = []
        offs = np.zeros(self.n_route, np.int64)
        for r, b in enumerate(builts):
            offs[r] = len(flat)
            flat.extend(b.fid_filter)
        self._flat_memo = (builts, flat, offs)
        return flat, offs

    def _consume_fast(self, msgs, np_res, builts, plan, host_idx):
        """Vectorized mesh consume (ISSUE 9 satellite — the port of the
        single-chip commit-19f9192 design to the [B, route] planes):
        ONE numpy pass proves which messages are clean — no cluster, no
        dirty shard pending, no too-deep host_extra, no overflow, no
        shared-slot hit, no rich/slotted matched fid — then gathers
        every clean fan-out row grouped per shard. Python runs only at
        session hand-off (the _deliver calls, or zero per-row work at
        all when the delivery lanes take the rows). Returns a [B] list:
        per-message counts (DEFERRED under lanes), None entries for
        slow messages, or None WHOLE when a global disqualifier stands
        (callers then run the pre-vectorized per-message path
        unchanged). SHARDED_r05 measured the per-message Python walk at
        530 msg/s wall — this pass is what removes it."""
        broker = self.broker
        if not self._fast_lane_live_ok(builts):
            return None
        B = len(msgs)
        if B == 0:
            return []
        R = self.n_route
        slow = np.asarray(np_res["overflow"])[:B].reshape(B, -1) \
            .any(axis=1)
        if host_idx:
            slow[sorted(host_idx)] = True
        csr = np_res.get("csr")
        shard_rows = []
        if csr is not None:
            off, c3, pay = csr
            lanes = np.arange(B)[:, None] * R + np.arange(R)[None, :]
            slow |= (c3[:, 2][lanes] > 0).any(axis=1)
            for r in range(R):
                idx = np.arange(B) * R + r
                cm = c3[idx, 0].astype(np.int64)
                base = off[idx].astype(np.int64)
                total_m = int(cm.sum())
                mi = np.repeat(np.arange(B), cm)
                if total_m:
                    mcum = np.cumsum(cm) - cm
                    fids = pay[np.arange(total_m)
                               - np.repeat(mcum, cm)
                               + np.repeat(base, cm)].astype(np.int64)
                else:
                    fids = np.zeros(0, np.int64)
                cf = c3[idx, 1].astype(np.int64)
                fbase = base + cm
                obase = base + cm + cf

                def fetch(row_msg, col, fbase=fbase, obase=obase):
                    return (pay[fbase[row_msg] + col],
                            pay[obase[row_msg] + col])

                shard_rows.append((mi, fids, fetch))
        else:
            slow |= (np.asarray(np_res["shared_sids"])[:B] >= 0) \
                .any(axis=(1, 2))
            matches = np.asarray(np_res["matches"])
            for r in range(R):
                m = matches[:B, r]
                valid = m >= 0
                mi, _cols = np.nonzero(valid)
                fids = m[valid].astype(np.int64)
                rows_p = np_res["rows"]
                opts_p = np_res["opts"]

                def fetch(row_msg, col, r=r, rows_p=rows_p,
                          opts_p=opts_p):
                    return (rows_p[row_msg, r, col],
                            opts_p[row_msg, r, col])

                shard_rows.append((mi, fids, fetch))
        for r in range(R):
            mi, fids, _f = shard_rows[r]
            if fids.size:
                np.logical_or.at(slow, mi, builts[r].fid_slow[fids])
        out: list = [None] * B
        fast_ok = ~slow
        if not fast_ok.any():
            return out
        counts = np.zeros(B, np.int64)
        delivered = 0
        metrics = self.node.metrics
        deliver = broker._deliver
        if plan is not None:
            flat, offs = self._flat_filters(builts)
            plan.register_fast(np.flatnonzero(fast_ok))
        for r in range(R):
            b = builts[r]
            mi, fids, fetch = shard_rows[r]
            if not fids.size:
                continue
            keep = fast_ok[mi]
            mi_f, fids_f = mi[keep], fids[keep]
            if not mi_f.size:
                continue
            seg = b.seg_np[fids_f]
            total = int(seg.sum())
            if not total:
                continue
            row_msg, col, row_fid, _ = DeviceRouteEngine._attribute_rows(
                mi_f, fids_f, seg, total)
            sid, opt = fetch(row_msg, col)
            valid = sid >= 0
            if plan is not None:
                # lane hand-off: one gather chunk per shard, global fid
                # space so every chunk shares ONE plan filter table
                plan.add_rows(row_msg[valid], sid[valid], opt[valid],
                              row_fid[valid] + offs[r], flat)
                continue
            fid_filter = b.fid_filter
            for bi, s, ob, fd in zip(row_msg[valid].tolist(),
                                     sid[valid].tolist(),
                                     opt[valid].tolist(),
                                     row_fid[valid].tolist()):
                if deliver(s, fid_filter[fd], msgs[bi],
                           dict(OPT_TABLE[ob & 0x3F])):
                    counts[bi] += 1
                    delivered += 1
        if plan is not None:
            for i in np.flatnonzero(fast_ok).tolist():
                out[i] = DEFERRED
            return out
        if delivered:
            metrics.inc("messages.routed.device", delivered)
        hooks = broker.hooks
        for i in np.flatnonzero(fast_ok).tolist():
            n = int(counts[i])
            if n == 0 and not msgs[i].is_sys:
                metrics.inc("messages.dropped")
                metrics.inc("messages.dropped.no_subscribers")
                hooks.run("message.dropped", (msgs[i],
                                              "no_subscribers"))
            out[i] = n
        return out

    def _consume_exchange(self, msgs, exch_pl, builts, plan):
        """Consume the exchanged per-dest delivery plans (ISSUE 15).

        Every message in an exchange-landed window is device-proven
        clean, so this is the _consume_fast fast lane fed from the
        plans instead of the gathered planes. Chunks hand to the
        delivery lanes per SOURCE shard in ascending order — and within
        a chunk, per dest, dp blocks ascending = global msg ascending —
        so a session's delivery sequence is bit-identical to the
        gather/merge walk: (src shard asc, msg asc, row asc).

        Returns the per-message counts list (DEFERRED under lanes), or
        None when a finish-time disqualifier stands (the rare churn
        that raced in AFTER materialize's own live-state check — the
        caller then pays one loop-side dense transfer, counted)."""
        broker = self.broker
        if not self._fast_lane_live_ok(builts):
            return None
        plan_p, _cnt_p, scnt = exch_pl
        B = len(msgs)
        if B == 0:
            return []
        R = self.n_route
        dpn = plan_p.shape[0]
        flat, _offs = self._flat_filters(builts)
        starts = np.cumsum(scnt, axis=2) - scnt       # [dp, dst, src]
        counts = np.zeros(B, np.int64)
        delivered = 0
        metrics = self.node.metrics
        deliver = broker._deliver
        if plan is not None:
            plan.register_fast(range(B))
        for r in range(R):
            pieces = []
            for d in range(R):
                for dp in range(dpn):
                    c = int(scnt[dp, d, r])
                    if c:
                        s0 = int(starts[dp, d, r])
                        pieces.append(plan_p[dp, d, s0:s0 + c])
            if not pieces:
                continue
            arr = np.concatenate(pieces) if len(pieces) > 1 \
                else pieces[0]
            msg_i = arr[:, 0]
            sid = arr[:, 1]
            w2 = arr[:, 2]
            gfid = w2 & (_EXCHANGE_MAX_GFID - 1)
            opt = (w2 >> 24) & 0x3F
            if plan is not None:
                plan.add_rows(msg_i, sid, opt, gfid, flat)
                continue
            for bi, s, ob, fd in zip(msg_i.tolist(), sid.tolist(),
                                     opt.tolist(), gfid.tolist()):
                if deliver(s, flat[fd], msgs[bi],
                           dict(OPT_TABLE[ob & 0x3F])):
                    counts[bi] += 1
                    delivered += 1
        if plan is not None:
            return [DEFERRED] * B
        if delivered:
            metrics.inc("messages.routed.device", delivered)
        hooks = broker.hooks
        out = []
        for i in range(B):
            n = int(counts[i])
            if n == 0 and not msgs[i].is_sys:
                metrics.inc("messages.dropped")
                metrics.inc("messages.dropped.no_subscribers")
                hooks.run("message.dropped", (msgs[i],
                                              "no_subscribers"))
            out.append(n)
        return out

    def _collect_clean(self, msg, i: int, np_res, builts):
        """Clean-proof + row collection for the delivery lanes: returns
        [(sid, packed_opt, filter)] when EVERY delivery of this message
        is a plain local fan-out row — standalone node, no shared group
        on any matched filter, no rich filter, no device shared-slot
        hit, no too-deep host_extra on any shard — else None (the
        ordering-safe _consume_one closure serves it)."""
        broker = self.broker
        if broker.cluster is not None:
            return None
        csr = np_res.get("csr")
        # pass 1 — fid-level disqualifier scan ONLY (no per-row work):
        # a slow message's deferred _consume_one repeats the full walk,
        # so collecting rows before the verdict would double the
        # per-row Python cost for exactly the messages that gain
        # nothing from it
        decoded = []
        for r in range(self.n_route):
            b = builts[r]
            if b.host_extra:
                return None
            if csr is not None:
                (row_m, rows, opts, srow, _prow, _orow) = csr_slices(
                    csr[0], csr[1], csr[2], i * self.n_route + r)
            else:
                row_m = np_res["matches"][i, r]
                rows = np_res["rows"][i, r]
                opts = np_res["opts"][i, r]
                srow = np_res["shared_sids"][i, r]
            for slot in srow:
                if slot >= 0:
                    return None
            for fid in row_m:
                if fid < 0:
                    continue
                f = b.fid_filter[fid]
                if f in b.rich or broker.shared.get(f):
                    return None
            decoded.append((b, row_m, rows, opts))
        # pass 2 — proven clean: collect the fan-out rows
        out: list[tuple] = []
        for b, row_m, rows, opts in decoded:
            off = 0
            for fid in row_m:
                if fid < 0:
                    continue
                f = b.fid_filter[fid]
                seg = b.seg_len[fid]
                for j in range(off, off + seg):
                    sid = int(rows[j])
                    if sid >= 0:
                        out.append((sid, int(opts[j]), f))
                off += seg
        return out

    def _writeback_cursors(self, occur, builts) -> None:
        """Mirror device round-robin advances onto the host
        SharedGroup.cursor — the next shard capture re-seeds the device
        row from it, so without this every churn event would reset the
        group's rotation (the single-chip engine's _sync_cursors)."""
        if self.broker.shared_strategy != "round_robin":
            return
        for r in range(self.n_route):
            b = builts[r]
            occ = occur[r]
            for slot in np.flatnonzero(occ[:len(b.slot_key)]):
                f, gname = b.slot_key[slot]
                g = self.broker.shared.get(f, {}).get(gname)
                if g is not None and g.members:
                    g.cursor = (g.cursor + int(occ[slot])) \
                        % len(g.members)

    def finish(self, h: _Handle) -> list[int]:
        # sync callers need final counts: inline consume, no lanes
        return self.finish_sub(h, 0, defer=False)

    # ---- consume --------------------------------------------------------
    def _host_route(self, msg: Message) -> int:
        broker = self.broker
        return broker._route(msg, broker.router.match(msg.topic))

    def _host_shared_dispatch(self, f: str, gname: str, msg) -> bool:
        """One group's host-side dispatch: cluster-wide pick under a
        cluster, local strategy pick standalone (single-chip engine's
        helper, mesh edition)."""
        broker = self.broker
        if broker.cluster is not None:
            return broker.cluster._dispatch_one_group(broker, f, gname,
                                                      msg)
        g = broker.shared.get(f, {}).get(gname)
        return bool(g and g.members
                    and broker._shared_pick_deliver(gname, f, g, msg))

    def _consume_one(self, msg, i: int, np_res, builts) -> int:
        broker = self.broker
        metrics = self.node.metrics
        cluster = broker.cluster
        dev_shared = self.broker.shared_strategy in self._dev_strategies()
        n = 0
        matched: list[str] = []
        handled: set[tuple] = set()   # (filter, group) the mesh served
        csr = np_res.get("csr")
        for r in range(self.n_route):
            b = builts[r]
            off = 0
            if csr is not None:
                # CSR lane (i, r) → i*R + r (ops.compact pseudo-window
                # layout): the valid entries of every plane in order,
                # no pad — the walks below are layout-agnostic
                (row_m, rows, opts, srow, prow, orow) = csr_slices(
                    csr[0], csr[1], csr[2], i * self.n_route + r)
            else:
                row_m = np_res["matches"][i, r]
                rows = np_res["rows"][i, r]
                opts = np_res["opts"][i, r]
                srow = np_res["shared_sids"][i, r]
                prow = np_res["shared_rows"][i, r]
                orow = np_res["shared_opts"][i, r]
            # fan-out rows are the concatenation of per-filter segments
            # in LOCAL fid order of the matched set
            for fid in row_m:
                if fid < 0:
                    continue
                f = b.fid_filter[fid]
                matched.append(f)
                seg = b.seg_len[fid]
                if f in b.rich:      # rich-subopts filter: host dict
                    n += broker.dispatch(f, msg)
                else:
                    for j in range(off, off + seg):
                        sid = int(rows[j])
                        if sid >= 0 and broker._deliver(
                                sid, f, msg, _unpack_opts(int(opts[j]))):
                            n += 1
                            metrics.inc("messages.routed.device")
                off += seg
            # too-deep filters: host match per message (rare); string
            # form so the $-topic exclusion rule applies
            for f, _fws in b.host_extra:
                if T.match(msg.topic, f):
                    matched.append(f)
                    n += broker.dispatch(f, msg)
            if dev_shared:
                for k, slot in enumerate(srow):
                    if slot < 0 or slot >= len(b.slot_key):
                        continue
                    f, gname = b.slot_key[slot]
                    handled.add((f, gname))
                    sid = int(prow[k])
                    if sid >= _REMOTE_SID_BASE:
                        # device picked a remote member: directed
                        # forward, the pick already made on the mesh
                        if cluster is not None:
                            origin, rsid = \
                                b.remote_members[sid - _REMOTE_SID_BASE]
                            cluster._spawn_fwd(
                                origin, "shared.deliver_fwd",
                                [f, gname, rsid, msg.to_wire()],
                                key=msg.topic)
                            n += 1
                            metrics.inc("messages.routed.device")
                            metrics.inc(
                                "messages.routed.device.remote_shared")
                        elif self._host_shared_dispatch(f, gname, msg):
                            n += 1   # cluster torn down since the build
                    elif sid >= 0:
                        # per-slot staleness guard (ADVICE r5): the pick
                        # was made against this handle's PINNED shard
                        # snapshot — if the member left the group
                        # mid-batch (session may still be alive, so
                        # _deliver would succeed wrongly) or the shard
                        # was re-dirtied since, re-pick host-side
                        # against live membership, mirroring the
                        # single-chip consume's dirty_slots check
                        grp = broker.shared.get(f, {}).get(gname)
                        stale = (grp is None or sid not in grp.members
                                 or self.shard_of(f) in self.dirty_shards)
                        if stale:
                            if self._host_shared_dispatch(f, gname, msg):
                                n += 1
                        elif broker._deliver(
                                sid, f, msg,
                                dict(_unpack_opts(int(orow[k])),
                                     share=gname)):
                            n += 1
                            metrics.inc("messages.routed.device")
                        elif broker.shared_dispatch_ack \
                                and self._host_shared_dispatch(
                                    f, gname, msg):
                            # nack with the ack protocol on: host
                            # re-pick (a nack from a live member with
                            # dispatch_ack off stays final, matching
                            # the host pick's semantics)
                            n += 1
        if not dev_shared:
            n += broker._dispatch_shared(msg, matched)
        else:
            # handled-set sweep (single-chip engine parity, round-5
            # advisor finding): any (filter, group) LIVE on a matched
            # filter but absent from this handle's pinned shard snapshot
            # dispatches host-side. That covers groups subscribed
            # between prepare and finish (the per-shard update landed
            # AFTER this batch's snapshot was pinned — they previously
            # got ZERO deliveries), and too-deep filters' groups, which
            # never get device slots (host_extra above, round-4 advisor
            # finding).
            for f in matched:
                names = set(broker.shared.get(f, ()))
                if cluster is not None:
                    names |= cluster._groups_by_real.get(f, set())
                for gname in names:
                    if (f, gname) in handled:
                        continue
                    handled.add((f, gname))
                    if self._host_shared_dispatch(f, gname, msg):
                        n += 1
        if cluster:
            n += cluster.forward(msg, matched)
        if n == 0 and not msg.is_sys:
            metrics.inc("messages.dropped")
            metrics.inc("messages.dropped.no_subscribers")
            broker.hooks.run("message.dropped", (msg, "no_subscribers"))
        return n

    @staticmethod
    def _dev_strategies():
        from emqx_tpu.ops.shared import STRATEGIES
        return STRATEGIES

    # ---- synchronous composition (publish_batch / tests / bench) --------
    def route_batch(self, msgs: list[Message],
                    wait: bool = False) -> Optional[list[int]]:
        """Route one batch synchronously. Returns None when the mesh
        cannot serve right now (first build / background rebuild in
        flight) — callers fall back to the host path. wait=True blocks
        until the mesh CAN serve (tests, dryrun, boot warm-up: never the
        event loop)."""
        if wait:
            t = self._rebuild_thread
            if t is not None and t.is_alive():
                t.join()
            if self._builts is None:
                self.rebuild()
            if not self.poll_rebuild():     # churn kicked a bg rebuild
                ct = self._capture_task
                if ct is not None and not ct.done():
                    # a loop-side chunked capture is pending and a
                    # wait=True caller (thread, can't pump the loop)
                    # needs a snapshot NOW: build synchronously — the
                    # generation bump supersedes the pending capture
                    self.rebuild()
                else:
                    t = self._rebuild_thread
                    if t is not None:
                        t.join()
                self.poll_rebuild()
        h = self.prepare(msgs)
        if h is None:
            return None
        h.t0 = time.perf_counter()
        self.dispatch(h)
        self.materialize(h)
        return self.finish(h)

    def stats(self) -> dict:
        return {
            **(getattr(self.node, "device_info", None) or {}),
            "built": self._builts is not None,
            "mesh": {"dp": self.n_dp, "route": self.n_route},
            "filters": sum(len(b.fid_filter) for b in self._builts or ()),
            "shared_slots": sum(len(b.slot_key)
                                for b in self._builts or ()),
            "dirty_shards": sorted(self.dirty_shards),
            "caps": dict(self._caps or {}),
            "warm_classes": sorted(self._warm_classes),
            # the single-chip engine's snapshot-keyed match cache needs a
            # per-shard key space on the mesh — explicitly bypassed here
            # (see prepare_window), not merely cold
            "match_cache": "bypassed",
            "compact_readback": self.compact_readback,
            "dispatch_depth": self.dispatch_depth,
            # churn handling on the mesh: per-shard incremental rebuild
            # (see __init__) — not the single-chip fused overlay
            "delta_overlay": "per-shard-rebuild" if self.delta_overlay
            else False,
            "payload_ewma": round(self._pay_ewma, 1)
            if self._pay_ewma is not None else None,
            # device-to-device exchange stage (ISSUE 15): off restores
            # host gather/merge exactly; warm classes are (Bp, E)
            "device_exchange": bool(self.device_exchange
                                    and self._exch_fits),
            "exchange_warm": sorted(self._exch_warm),
            "exchange_ewma": round(self._exch_ewma, 1)
            if self._exch_ewma is not None else None,
            # subscription covering (ISSUE 18): per-shard detection,
            # aggregated; reduction = full set / covering set
            "subscription_covering": self.subscription_covering,
            "cover": {
                "roots": (nr := sum(b.cover_roots
                                    for b in self._builts or ())),
                "covered": (nc := sum(b.cover_covered
                                      for b in self._builts or ())),
                "reduction": round((nr + nc) / max(1, nr), 2),
            } if self.subscription_covering else None,
        }
