"""Device route engine: the fused route step wired into the serving path.

This is the piece that makes the TPU program THE broker hot path instead of
a side-car demo: it compiles the live routing state (Router filter universe +
Broker subscriber/shared-group membership) into the fused device tables
(models.router_engine), runs its one window program, `route_window`,
with the optional stages a window's class asks for, over publish
micro-batches, and consumes the `RouteResult` into actual session deliveries
— replacing the reference's per-message publish path
(emqx_broker.erl:199-308: match_routes → dispatch fold → shared pick).

Serving is staged so the asyncio event loop never blocks on the device
(round-2 weak #3): `prepare()` (loop: tokenize+encode), `dispatch()`
(executor thread: the jitted step), `materialize()` (executor thread:
device→host readbacks),
`finish()` (loop: consume RouteResult rows into session deliveries).
`route_batch()` remains the synchronous composition for callers without a
pipeline (publish_batch, tests, warmup).

Snapshot/consistency model (SURVEY.md §7 hard-part 1, "mutable trie on
immutable arrays"):

- The compiled tables are an immutable snapshot; mutations keep flowing into
  the authoritative host dicts and are *tracked* relative to the snapshot:
  - a filter whose subscriber membership changed since the build is DIRTY —
    its fan-out segment on device is stale, so its deliveries come from the
    live host dict instead (correct for adds, removes and opts changes);
  - a filter added since the build lands in the DEVICE-RESIDENT DELTA
    OVERLAY (ISSUE 4, ops/delta.py): a small linear-matcher table fused
    into the route programs, so it is matched AND delivered on device in
    the same dispatch. The host delta trie remains the fallback for
    filters the overlay cannot hold (overlay program class still
    warming, row overflow past the top class, deeper than max_levels) —
    those match host-side as before, counted by
    `routing.device.host_delta`. With `EMQX_TPU_DELTA_OVERLAY=0` /
    `broker.delta_overlay=false` EVERY delta filter takes that host
    path (the pre-overlay behavior, the A/B baseline);
  - a (filter, group) shared slot that changed is dirty likewise; a group
    added to a built filter is dispatched host-side until the next rebuild.
- The full rebuild is demoted to a rare **compaction** (overlay row
  overflow / delete-tombstone ratio / built-filter membership churn past
  `rebuild_threshold` — see _compaction_reason), recompiled **in the
  background, double-buffered** (round-2 weak #7): the router/broker
  state is captured in cooperative chunks on the loop — incrementally,
  from the previous build's capture plus the touched-filter journal,
  instead of re-walking the world — compiled + uploaded + warm-jitted
  off the loop, and swapped in atomically once no dispatched batch is
  outstanding. Mutations during the build are journaled and replayed
  against the new snapshot at swap, so no churn is lost and serving
  never stalls on a rebuild.

Delivery attribution: device fan-out rows for one message are the
concatenation of per-filter CSR segments in match order, so the host walks
`matches[i]` and slices `rows[i]` by the *built* segment lengths — clean
filters deliver straight from device rows (packed opts unpacked on the fly),
no host dict walk. Messages flagged overflow/too-deep fall back to the full
host path (emqx_router.erl:136-141 short-circuit analog).

Shared subscriptions: device picks (ops.shared cursors) drive delivery for
EVERY strategy (round_robin / random / hash_* / sticky), clustered or
not. Under a cluster the snapshot's member list is the CLUSTER-WIDE
membership (emqx_shared_sub:pick semantics over all nodes' members,
emqx_shared_sub.erl:239-268): local members carry their subopts, remote
members ride as reserved-range sids (>= _REMOTE_SID_BASE) that index a
host-side (origin, remote_sid) list — a remote pick is forwarded with the
same directed shared.deliver_fwd RPC the host path uses
(emqx_shared_sub.erl dispatch's cross-node SubPid ! send). Sticky rides
the cursor state reinterpreted as an affinity pointer (seeded by
capture_shared, never advanced on device); only RE-picking after a
member death is feedback-dependent and runs host-side via the consume
fallback (emqx_shared_sub.erl:269-283). A remote join/leave dirties the
slot (store watcher → note_member_change) so the group serves host-side
until the next rebuild.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import time
import zlib
from typing import NamedTuple, Optional

import jax
import numpy as np

from emqx_tpu.broker.deliver import (DEFERRED, OPT_TABLE, GroupPicks,
                                     LaneCounts)
from emqx_tpu.broker.match_cache import DEFAULT_CAPACITY, MatchCache
from emqx_tpu.broker.message import Message
from emqx_tpu.ops.compact import csr_slices
from emqx_tpu.ops import intern as I
from emqx_tpu.utils import topic as T

_PACKED_KEYS = {"qos", "nl", "rap", "rh"}

# reuse layers in front of the device match (both host-tunable without a
# restart of anything but the node):
#   EMQX_TPU_DEDUP=0        disables in-window unique-topic dedup AND the
#                           cached dispatch variant that rides on it (the
#                           cross-batch cache has no vehicle without it)
#   EMQX_TPU_MATCH_CACHE=N  cross-batch match-cache capacity in unique
#                           topics; 0 disables the cache layer only
#                           (in-window dedup still engages)
def resolve_dedup(configured=None) -> bool:
    """The one dedup-knob resolution: config (``broker.topic_dedup``)
    beats ``EMQX_TPU_DEDUP`` beats default-on. ``=0`` disables
    in-window unique-topic dedup AND the cached dispatch variant that
    rides on it — the ISSUE-2 A/B baseline."""
    if configured is not None:
        return bool(configured)
    return os.environ.get("EMQX_TPU_DEDUP", "1") \
        not in ("0", "false", "off")


def resolve_match_cache_size(configured=None) -> int:
    """The one match-cache-capacity resolution: config
    (``broker.match_cache_size``) beats ``EMQX_TPU_MATCH_CACHE`` beats
    the built-in ``DEFAULT_CAPACITY``. 0 disables the cache layer only
    (in-window dedup still engages)."""
    if configured is not None:
        return int(configured)
    env = os.environ.get("EMQX_TPU_MATCH_CACHE")
    return int(env) if env is not None else DEFAULT_CAPACITY


def resolve_compact_readback(configured=None) -> bool:
    """The one compact-readback resolution: config
    (``broker.compact_readback``) beats ``EMQX_TPU_COMPACT_READBACK``
    beats default-on. ``=0`` restores dense-plane readback exactly —
    the ISSUE-3 A/B baseline the acceptance criteria compare."""
    if configured is not None:
        return bool(configured)
    return os.environ.get("EMQX_TPU_COMPACT_READBACK", "1") \
        not in ("0", "false", "off")


def resolve_delta_overlay(configured=None) -> bool:
    """The one delta-overlay resolution: config
    (``broker.delta_overlay``) beats ``EMQX_TPU_DELTA_OVERLAY`` beats
    default-on. ``=0`` restores host-trie fallback + full O(N)
    recaptures at the rebuild threshold exactly — the ISSUE-4 churn
    A/B baseline."""
    if configured is not None:
        return bool(configured)
    return os.environ.get("EMQX_TPU_DELTA_OVERLAY", "1") \
        not in ("0", "false", "off")


def resolve_subscription_covering(configured=None) -> bool:
    """The one subscription-covering resolution: config
    (``broker.subscription_covering``) beats ``EMQX_TPU_COVERING``
    beats default-on. On means the engine MAY use covering: a snapshot
    build engages it only where the full set does not fit the
    shape-hash backend (``ops/cover.covering_decision``). ``=0`` means
    never: the full-set match exactly — the ISSUE-18 A/B baseline
    (twin-tested bit-identical on delivery counts and per-session
    order)."""
    if configured is not None:
        return bool(configured)
    return os.environ.get("EMQX_TPU_COVERING", "1") \
        not in ("0", "false", "off")


# module-level one-shot resolutions: engines read these when their
# config leaves a knob unset (tests monkeypatch them directly, and
# parallel/serving.py imports the compact/delta/covering set for the
# mesh)
_ENV_DEDUP = resolve_dedup()
_ENV_COMPACT = resolve_compact_readback()
_ENV_DELTA = resolve_delta_overlay()
_ENV_COVERING = resolve_subscription_covering()


def resolve_rebuild_threshold(configured=None) -> int:
    """The one rebuild-threshold resolution: config beats
    EMQX_TPU_REBUILD_THRESHOLD beats the built-in 256. The env knob lets
    deployments tune churn tolerance without a config edit (mirroring
    the EMQX_TPU_* family above); it must be a positive integer —
    anything else is a deployment error worth failing loudly on."""
    if configured is not None:
        return int(configured)
    env = os.environ.get("EMQX_TPU_REBUILD_THRESHOLD")
    if env is None:
        return 256
    try:
        val = int(env)
    except ValueError:
        raise ValueError(
            f"EMQX_TPU_REBUILD_THRESHOLD={env!r} is not an integer")
    if val <= 0:
        raise ValueError(
            f"EMQX_TPU_REBUILD_THRESHOLD must be > 0, got {val}")
    return val


_snapshot_ids = itertools.count(1)

# delta-overlay capacity ladder (ISSUE 4): pow2 row classes so the jit
# signature of the fused delta programs stays stable while the overlay
# grows; beyond the top class the oldest _OVERLAY_MAX delta filters
# keep their device rows and the rest serve host-side until the
# compaction the overflow triggers completes. Fan-out is a fixed
# per-row budget (sub rows = rows * _DELTA_FAN_PER_ROW) so membership
# growth inside a class never retraces; a delta filter with more
# subscribers (or rich subopts) keeps its MATCH on device and delivers
# through the host dict instead.
_DELTA_CLASSES = (16, 128, 512)
_OVERLAY_MAX = _DELTA_CLASSES[-1]
_DELTA_FAN_PER_ROW = 8
_DELTA_MATCH_CAP = 16
_DELTA_FANOUT_CAP = 64


def _topic_keys(enc: np.ndarray, lens: np.ndarray,
                dollar: np.ndarray) -> np.ndarray:
    """[N, L] interned rows + [N] lens + [N] is_dollar -> [N] void16 keys.

    Two independent 64-bit folds over the level ids (vectorized down the
    batch axis), finalized with the lens and the '$'-root flag — 128 bits
    per topic, the dedup/cache identity. Interned ids are stable for the
    process lifetime (ops/intern.py only ever appends), so equal keys
    mean equal device inputs; distinct unseen words all encode to UNKNOWN
    and are identical to the device anyway. Collision posture matches
    ops/shapes.py's 2x32-bit path hashes, two levels up: ~2^-128 per key
    pair, negligible against the cache's bounded live set."""
    n = enc.shape[0]
    h1 = np.full(n, 0x9E3779B97F4A7C15, np.uint64)
    h2 = np.full(n, 0xC2B2AE3D27D4EB4F, np.uint64)
    m1 = np.uint64(0x100000001B3)
    m2 = np.uint64(0xFF51AFD7ED558CCD)
    for level in range(enc.shape[1]):
        w = enc[:, level].astype(np.uint64)
        h1 = (h1 ^ (w + np.uint64(level * 0x9E3779B1 + 1))) * m1
        h2 = (h2 ^ (w * m1 + np.uint64(level + 1))) * m2
    fin = lens.astype(np.uint64) * np.uint64(2) + dollar.astype(np.uint64)
    h1 = (h1 ^ fin) * m2
    h2 = (h2 ^ (fin * m1)) * m1
    h1 ^= h1 >> np.uint64(29)
    h2 ^= h2 >> np.uint64(31)
    return np.ascontiguousarray(
        np.stack([h1, h2], axis=1)).view("V16").reshape(-1)


class _CachePlan:
    """One deduplicated (optionally cache-backed) dispatch: the device
    inputs (the compacted miss lanes, the host-filled base rows, and the
    scatter/gather indexing that rebuilds full window width) and what
    the host keeps about them."""

    __slots__ = ("dev", "dbase", "Bm", "n_miss", "n_hit")

    def __init__(self, dev, dbase, Bm, n_miss, n_hit):
        self.dev = dev        # router_engine.WindowPlan of numpy arrays
        # delta-overlay base rows (overlay ROW-index space): None unless
        # the window fuses the overlay (ISSUE 4)
        self.dbase = dbase
        self.Bm = Bm
        self.n_miss = n_miss
        self.n_hit = n_hit


class _CacheInfo:
    """Post-materialize cache population: (key, flat lane) per unique
    topic the cache did not have, pinned to the dispatching snapshot.
    `version` pins the match-cache's delta version at plan time: an
    overlay insert/delete while this window was in flight makes its
    readback rows stale (they predate the filter change), so put_many
    drops the batch on a version mismatch — the delta-aware analog of
    the snapshot-id check."""

    __slots__ = ("sid", "inserts", "version")

    def __init__(self, sid, inserts, version=None):
        self.sid = sid
        self.inserts = inserts
        self.version = version


class _CsrRes:
    """Host side of one compacted readback (ISSUE 3): the CSR planes
    materialize transferred instead of the dense result planes, plus the
    always-small dense overflow/occur planes consume needs anyway.
    finish_sub dispatches on this type vs the dense 8-tuple."""

    __slots__ = ("off", "c3", "pay", "overflow", "occur")

    def __init__(self, off, c3, pay, overflow, occur):
        self.off = off            # [W, B+1] combined payload offsets
        self.c3 = c3              # [W, B, 3] (match, fanout, shared)
        self.pay = pay            # [W, P] flat payload
        self.overflow = overflow  # [W, B] host-fallback lanes
        self.occur = occur        # [W, G] cursor writeback input


class _Overlay:
    """One immutable VERSION of the delta overlay (ISSUE 4): the device
    DeltaTables plus the host-side index consume/plan need. Handles pin
    the version they dispatched against, so an overlay refresh mid-batch
    can neither re-index an in-flight decode nor swap the arrays under a
    dispatch — the same pinning discipline as `_Built`."""

    __slots__ = ("dev", "fid_set", "row_of", "seg_of", "hostfan",
                 "version", "cap", "n")

    def __init__(self, dev, fid_set, row_of, seg_of, hostfan, version,
                 cap, n):
        self.dev = dev            # device DeltaTables (row class `cap`)
        self.fid_set = fid_set    # frozenset of delta fids in the table
        self.row_of = row_of      # fid -> overlay row index
        self.seg_of = seg_of      # fid -> device fan-row segment length
        self.hostfan = hostfan    # fids delivering host-side (rich/big)
        self.version = version    # overlay clock stamp at build
        self.cap = cap            # row class (jit signature component)
        self.n = n                # live rows


class _WindowClass(NamedTuple):
    """One compiled class of `router_engine.route_window`: what the jit
    key of a dispatch is made of, and so what warmth, demand and the
    cold gates are tracked by. `sig` is the snapshot's shape signature
    (`_tables_sig`), (W, Bp) the padded window; the last three are the
    program's optional stages, None when the stage is off: `Bm` the
    match-cache plan's miss class, `dC` the delta overlay's row class,
    `P` the CSR readback's payload class."""
    sig: tuple
    W: int
    Bp: int
    Bm: Optional[int] = None
    dC: Optional[int] = None
    P: Optional[int] = None

    @property
    def plain(self) -> bool:
        """No optional stage: `match → scan(fanout, shared)` alone."""
        return self.Bm is None and self.dC is None and self.P is None

    @property
    def label(self) -> str:
        """The class in a compile-context label (`warm W8xB1024mB256d16
        c4096`): the key space of snapshot()["compiles"]["by_shape"]
        and of the cost registry's rows."""
        return (f"W{self.W}xB{self.Bp}"
                + (f"mB{self.Bm}" if self.Bm is not None else "")
                + (f"d{self.dC}" if self.dC is not None else "")
                + (f"c{self.P}" if self.P is not None else ""))

    @property
    def warm_order(self) -> tuple:
        """Where the background warm takes a demanded class: plain
        (oversized batch) classes, then overlay-only, then planned,
        then compact ones, each group in ascending sizes."""
        group = 3 if self.P is not None else 2 if self.Bm is not None \
            else 1 if self.dC is not None else 0
        return (group, self.W, self.Bp, self.Bm or 0, self.P or 0,
                self.dC or 0)


class _DeltaRes:
    """Dense host views of one window's delta-overlay planes."""

    __slots__ = ("fids", "counts", "moverflow", "rows", "opts",
                 "overflow")

    def __init__(self, fids, counts, moverflow, rows, opts, overflow):
        self.fids = fids          # [W, B, Dm] delta fids
        self.counts = counts      # [W, B]
        self.moverflow = moverflow  # [W, B] match-capacity overflow
        self.rows = rows          # [W, B, Dc]
        self.opts = opts          # [W, B, Dc]
        self.overflow = overflow  # [W, B] combined (match | fan-out)


class _DeltaCsr:
    """CSR host views of one window's delta planes (same payload layout
    as the main CSR with an empty shared family — csr_slices decodes
    both), plus the always-small dense count/overflow planes."""

    __slots__ = ("off", "c3", "pay", "counts", "moverflow", "overflow")

    def __init__(self, off, c3, pay, counts, moverflow, overflow):
        self.off = off
        self.c3 = c3
        self.pay = pay
        self.counts = counts
        self.moverflow = moverflow
        self.overflow = overflow


def _pack_opts(opts: dict) -> int:
    return ((int(opts.get("qos", 0)) & 0x3)
            | ((1 if opts.get("nl") else 0) << 2)
            | ((1 if opts.get("rap") else 0) << 3)
            | ((int(opts.get("rh", 0)) & 0x3) << 4))


def _unpack_opts(b: int) -> dict:
    return {"qos": b & 0x3, "nl": (b >> 2) & 1, "rap": (b >> 3) & 1,
            "rh": (b >> 4) & 0x3}


def _is_rich(opts: dict) -> bool:
    """Subopts that the packed byte cannot carry (v5 subscription ids etc.)
    force the filter onto the host dict path."""
    return any(k not in _PACKED_KEYS and k != "share" and v is not None
               for k, v in opts.items())


def _next_pow2(x: int) -> int:
    return 1 << max(2, (x - 1).bit_length())


# device member ids at/above this are remote refs: they index the built
# snapshot's remote_members list instead of a local session row (int32-safe;
# local sids are small dense ints)
_REMOTE_SID_BASE = 1 << 30


def capture_shared(broker, f: str) -> dict:
    """Per-filter shared-group capture for a device snapshot (used by the
    single-chip engine AND the mesh ShardedRouteServer).

    Standalone: the local SharedGroup members with their subopts.
    Clustered: the CLUSTER-WIDE membership (cluster._members — the
    same sorted (origin, sid) view the host pick uses), with local
    members carrying subopts and remote members captured as
    ((origin, sid), None) refs that the build turns into
    reserved-range device sids. Remote-only groups known purely via
    replication are captured too — every device-supported strategy's
    pick runs on device regardless of where members live (reference
    semantics: emqx_shared_sub.erl:239-268 + replicated group routes
    :312-320).

    For the `sticky` strategy the returned cursor is the sticky member's
    INDEX in the members list (establishing affinity on the first
    capture if none exists) — the device kernel reinterprets the cursor
    as the affinity pointer and never advances it (ops.shared).

    Sticky-seeding invariant (ADVICE r5): establishing affinity is the
    ONE write this otherwise read-only capture performs (grp.sticky /
    cluster._shared_sticky), and it is IDEMPOTENT by construction —
    it only runs when no live member holds affinity, and every writer
    derives the same deterministic value from the same source
    (members[0] of the insertion-ordered members dict standalone;
    refs[0] of cluster._members' SORTED (origin, sid) view clustered).
    Two captures racing on different threads (a sync rebuild on a
    route_batch(wait=True) thread vs a loop-side chunked capture)
    therefore converge on the same member: the race is benign, the
    seeded snapshots agree, and re-running capture never moves an
    established affinity (the `not in` guards below). Do not replace
    the guarded writes with unconditional ones — that is what keeps
    concurrent captures convergent."""
    cluster = broker.cluster
    sticky_mode = broker.shared_strategy == "sticky"
    local = broker.shared.get(f) or {}
    if cluster is None:
        out = {}
        for g, grp in local.items():
            if not grp.members:
                continue
            members = list(grp.members.items())
            cursor = grp.cursor
            if sticky_mode:
                if grp.sticky not in grp.members:
                    grp.sticky = members[0][0]   # establish affinity
                cursor = next(i for i, (sid, _) in enumerate(members)
                              if sid == grp.sticky)
            out[g] = (members, cursor)
        return out
    names = set(local) | cluster._groups_by_real.get(f, set())
    me = cluster.rpc.node
    out = {}
    for g in sorted(names):
        grp = local.get(g)
        members = []
        refs = []                      # (origin, sid) per kept member
        for origin, sid in cluster._members(broker, f, g):
            if origin == me:
                opts = grp.members.get(sid) if grp else None
                if opts is not None:
                    members.append((sid, opts))
                    refs.append((origin, sid))
            else:
                members.append(((origin, sid), None))
                refs.append((origin, sid))
        if not members:
            continue
        cursor = grp.cursor if grp else 0
        if sticky_mode:
            want = cluster._shared_sticky.get((f, g))
            if want not in refs:
                want = refs[0]         # establish cluster-wide affinity
                cluster._shared_sticky[(f, g)] = want
            cursor = refs.index(want)
        out[g] = (members, cursor)
    return out


class _CoverState:
    """Host-side subscription-covering companion of one snapshot
    (ISSUE 18): the covering-set HostTrie + root encodings answer "is
    this new filter covered?" on the subscribe path, and the numpy
    CoverTables mirror backs the expansion-CSR APPEND region (a
    covered new filter becomes an append + small device upload, not a
    rebuild). n_roots/n_covered feed stats()'s reduction factor;
    `wide` holds the roots the build found too wide to own anything
    (`ops/cover.assign_owners`), `largest_segment` the most entries one
    root's segment has."""

    __slots__ = ("trie", "root_words", "roots", "ct", "app_used",
                 "level_cap", "n_roots", "n_covered", "incomplete",
                 "wide", "largest_segment")

    def __init__(self, roots, ct, level_cap, n_covered, incomplete,
                 wide, largest_segment):
        self.wide = frozenset(int(f) for f in wide)
        self.largest_segment = largest_segment
        self.roots = roots            # root fid array (covering set)
        self.trie = None              # HostTrie over roots, built
        self.root_words = None        # lazily on the first append try
        self.ct = ct                  # numpy CoverTables (host mirror)
        self.app_used = 0             # append-region rows consumed
        self.level_cap = level_cap    # vwords width (append depth gate)
        self.n_roots = len(roots)
        self.n_covered = n_covered
        self.incomplete = incomplete  # detection-overflow filter count


class _Built:
    """One compiled snapshot (host-side indexes of the device tables)."""

    __slots__ = ("fid_of", "fid_filter", "seg_len", "slot_of", "slot_key",
                 "n_slots", "backend", "remote_members", "seg_np",
                 "fid_shared", "fid_rich", "sid", "match_width", "cover",
                 "cover_decision", "sub_start", "sub_row", "sub_opts",
                 "slot_fid", "picks")

    def __init__(self):
        self.fid_of: dict[str, int] = {}
        self.fid_filter: list[str] = []
        self.seg_len: list[int] = []
        self.slot_of: dict[tuple, int] = {}       # (filter, group) -> slot
        self.slot_key: list[tuple] = []           # slot -> (filter, group)
        self.slot_fid = np.zeros(0, np.int64)     # slot -> its filter's fid
        self.n_slots = 0
        # what the delivery lanes need to serve a device-picked member
        # of these groups as a row of a plan (broker/deliver.GroupPicks)
        self.picks = None
        # remote shared members: device sid _REMOTE_SID_BASE+i -> (origin,
        # remote_sid); consume forwards picks for these over RPC
        self.remote_members: list[tuple] = []
        self.backend = "trie"
        # snapshot identity: the match-cache key space (match rows are a
        # pure function of (sid, topic) — see broker/match_cache.py)
        self.sid = next(_snapshot_ids)
        # width of one match row ([B, match_width] out of the match
        # stage): shape capacity for the shapes backend, match_cap for
        # the trie NFA — the cache's row width for this snapshot
        self.match_width = 0
        # vectorized-consume companions (set once at build):
        self.seg_np = np.zeros(0, np.int64)       # seg_len as an array
        self.fid_shared = np.zeros(0, bool)       # fid has shared groups
        self.fid_rich = np.zeros(0, bool)         # fid has rich subopts
        # the device SubTable's normal-subscriber CSR as the build made
        # it (fid -> sub_start[fid]..[fid + 1] of sub_row / sub_opts; 5 B
        # a subscription): a filter with more than `fanout_cap`
        # subscribers travels by reference (ops/fanout.fanout_normal),
        # and consume reads its rows from here
        self.sub_start = np.zeros(1, np.int32)
        self.sub_row = np.zeros(0, np.int32)
        self.sub_opts = np.zeros(0, np.int8)
        # subscription covering (ISSUE 18): _CoverState when this
        # snapshot matched the covering set only, else None. With
        # covering engaged, seg_np/fid_shared/fid_rich are padded to
        # filter_cap so APPENDED fids (cover-set churn) index safely.
        self.cover: Optional[_CoverState] = None
        # why the build engaged covering or did not: "off" (the knob),
        # "fits_shapes" / "too_deep" (ops/cover.covering_decision),
        # "none_covered", "engaged"
        self.cover_decision = "off"


class _Handle:
    """One in-flight dispatched WINDOW of 1..W publish micro-batches
    (prepare → dispatch → materialize → finish_sub per batch). A single
    batch is a window of 1 — one unified device path. Host-side metadata
    pins the snapshot the dispatch ran against; the engine defers
    snapshot swaps until no handle is outstanding. `refs` counts the
    attached sub-batches: the handle releases (outstanding--) when every
    sub has been finished or abandoned."""

    __slots__ = ("subs", "built", "dev_shared", "enc", "res", "np_res",
                 "np_counts", "np_mov", "np_fov", "np_cov", "error", "refs",
                 "t0",
                 "plan", "cache_info",
                 "pcap", "cres", "delta", "dres", "dcres", "np_delta",
                 "trace", "sub_traces")

    def __init__(self, subs, built, dev_shared):
        self.subs = subs          # list of (msgs, words_list, too_long)
        self.built = built
        self.dev_shared = dev_shared
        self.res = None       # device RouteResult, fields [W, ...]
        self.np_res = None    # host views: dense tuple OR _CsrRes
        self.np_counts = None  # match_counts [W, B] (cache population)
        self.np_mov = None    # match-stage overflow [W, B]: read back
                              # only from a trie window with a flagged lane
        self.np_fov = None    # fan-out stage overflow [W, B], read back
                              # only from a window with a flagged lane
        self.np_cov = None    # a covering snapshot's expansion overflow
                              # [W, B], read back on the same condition
        self.error = None
        self.refs = len(subs)
        self.t0 = None        # consumer-side window processing start
        self.plan = None      # _CachePlan: dedup/cached dispatch inputs
        self.cache_info = None  # _CacheInfo: rows to insert post-readback
        self.pcap = None      # payload class: CSR-compact this dispatch
        self.cres = None      # device CompactPlanes (set by dispatch)
        self.delta = None     # _Overlay this dispatch fused (ISSUE 4)
        self.dres = None      # device DeltaPlanes (set by dispatch)
        self.dcres = None     # device delta CompactPlanes
        self.np_delta = None  # host views: _DeltaRes or _DeltaCsr
        self.trace = 0        # flight-recorder trace id (ISSUE 7):
        #                       the LEAD entry's window trace — rides
        #                       the StepTraceAnnotation so the device
        #                       timeline joins the host one
        self.sub_traces = None  # per-sub-batch trace ids (fused windows)


class DeviceRouteEngine:
    def __init__(self, node, *, rebuild_threshold: Optional[int] = None,
                 max_levels: int = 16, frontier_cap: int = 16,
                 match_cap: int = 64, fanout_cap: int = 128,
                 slot_cap: int = 16, shape_cap: int = 32,
                 match_cache_size: Optional[int] = None,
                 dedup: Optional[bool] = None,
                 compact_readback: Optional[bool] = None,
                 delta_overlay: Optional[bool] = None,
                 subscription_covering: Optional[bool] = None,
                 supervisor=None, ledger=None,
                 dispatch_depth: Optional[int] = None):
        self.node = node
        self.broker = node.broker
        self.router = node.broker.router
        from emqx_tpu.broker.trace import spans_of
        self.spans = spans_of(node)
        self.rebuild_threshold = resolve_rebuild_threshold(
            rebuild_threshold)
        self.max_levels = max_levels
        self.frontier_cap = frontier_cap
        self.match_cap = match_cap
        self.fanout_cap = fanout_cap
        self.slot_cap = slot_cap
        self.shape_cap = shape_cap
        # the most candidates a covering snapshot's expansion holds for
        # one topic (`CoverTables.cand_pad`); a build takes less where
        # its largest segment lets it, and lets a root own only what
        # the plane holds beside the other slots of the roots' match
        # row (`_build_from_capture`). A topic whose matched roots own
        # more between them flags `cover_overflow` and host-routes
        self.cover_cand_cap = min(4096,
                                  _next_pow2(max(256, 4 * match_cap)))

        self.intern = I.InternTable()
        self._built: Optional[_Built] = None
        self._tables = None            # device RouterTables/ShapeRouterTables
        self._cursors = None           # device [G]
        self.dirty_filters: set[str] = set()
        self.dirty_slots: set[tuple] = set()
        self.new_slots_by_filter: dict[str, set[str]] = {}
        # hostside-mask memo (ISSUE 5 satellite): _fast_deliver used to
        # rebuild fid_rich + dirty-scatter on EVERY batch while any
        # filter was dirty; the mask only changes when the dirty set or
        # the snapshot does, so it is memoized on (snapshot id, dirty
        # version) — the version bumps on subscribe/unsubscribe churn
        # (_mark_dirty), never per batch
        self._dirty_ver = 0
        self._hostside_memo: Optional[tuple] = None
        from emqx_tpu.ops.trie import HostTrie
        self._delta_trie = HostTrie()
        self._delta_filter: dict[int, str] = {}
        self._delta_fid_of: dict[str, int] = {}
        self._next_delta_fid = 0

        # per-filter cluster shared-group union, invalidated on membership
        # change (avoids per-message set unions on the consume path)
        self._cluster_groups_cache: dict[str, tuple] = {}
        # compile-class readiness: the BATCHER only routes a batch to
        # the device when its (W, Bp) class is known-warm for the current
        # snapshot signature — an in-path XLA compile stalls live
        # traffic for seconds (observed: 5s+ first-QoS1-ack under a
        # cold-start flood). Classes become warm via background warm
        # tasks or any successful dispatch (route_batch warmups).
        self._warm_classes: set[_WindowClass] = set()
        # classes beyond the standard ladder that the serving path asked
        # for: demand-driven (a window whose plan, overlay or payload
        # class is cold dispatches without that stage and registers the
        # class here; so does an oversized batch), warmed by the same
        # background thread as the standard ladder
        self._wanted: set[_WindowClass] = set()
        self._cur_sig: tuple = ()
        self._fuse_warm_task = None
        # background rebuild machinery (round-2 weak #7)
        self._outstanding = 0          # dispatched-but-unfinished handles
        self._journal: Optional[list] = None   # churn while a build runs
        self._building = False
        self._pending_swap = None      # (built, tables, cursors, rich)
        self._rebuild_task = None

        # reuse layers (ISSUE 2 tentpole): in-window unique-topic dedup
        # and the cross-batch snapshot-keyed match cache. Config beats
        # env beats default; cache size 0 / dedup False disable a layer.
        if dedup is None:
            dedup = _ENV_DEDUP
        if match_cache_size is None:
            match_cache_size = resolve_match_cache_size()
        self.dedup = bool(dedup)
        self._match_cache: Optional[MatchCache] = \
            MatchCache(match_cache_size, node.metrics) \
            if (self.dedup and match_cache_size > 0) else None

        # CSR readback compaction (ISSUE 3 tentpole): materialize ships
        # offsets + actual entries instead of the padded result planes.
        # Config beats env beats default-on; payload capacity quantizes
        # onto _PAYLOAD_MULTS * Bp classes sized by a peak-biased EWMA
        # of recent window totals, with a dense-readback fallback when a
        # window outgrows its class (row_overflow).
        if compact_readback is None:
            compact_readback = _ENV_COMPACT
        self.compact_readback = bool(compact_readback)
        self._pay_ewma: dict[int, float] = {}   # Bp -> peak entry total
        self._pay_mult: dict[int, int] = {}     # Bp -> the class held

        # delta overlay (ISSUE 4 tentpole): post-snapshot filters match
        # ON DEVICE via a small linear overlay table fused into the
        # route programs, instead of host-routing until the next full
        # rebuild. Config beats env beats default-on.
        if delta_overlay is None:
            delta_overlay = _ENV_DELTA
        self.delta_overlay = bool(delta_overlay)
        self._overlay: Optional[_Overlay] = None  # current serving table

        # subscription covering (ISSUE 18 tentpole): the snapshot match
        # tables hold only the COVERING set; a fused expansion CSR
        # (ops/cover) re-expands matched covers after the match stage.
        # Config beats env beats default-on. On = the build MAY engage
        # it, and does only where the full set overflows the shape-hash
        # table (cover.covering_decision); =0 always builds the full set.
        if subscription_covering is None:
            subscription_covering = _ENV_COVERING
        self.subscription_covering = bool(subscription_covering)
        # new filters that could NOT ride the expansion-CSR append path
        # (they cover others / nothing covers them): they serve through
        # the overlay, but each one left in place erodes the covering
        # reduction — past a budget the snapshot recompacts
        # (_compaction_reason "covering")
        self._cover_churn = 0

        # double-buffered window pipeline (ISSUE 9 tentpole): at
        # dispatch_depth >= 2 the serving dispatch starts the
        # device→host transfers of every readback plane at dispatch
        # return (copy_to_host_async-style), so materialize is
        # consume-on-arrival under the next window's dispatch. Depth 1
        # restores the synchronous readback exactly — the A/B
        # baseline. Config beats env beats default 2.
        from emqx_tpu.broker.batcher import resolve_dispatch_depth
        self.dispatch_depth = resolve_dispatch_depth(dispatch_depth)
        self._pipelined = self.dispatch_depth > 1
        self._overlay_stale = False     # journal entries pending apply
        self._overlay_clock = 0         # monotonic overlay mutation clock
        self._overlay_uncovered = 0     # live delta filters NOT in the
                                        # overlay (too deep / past cap)
        # fid -> clock of its last MEMBERSHIP change: an overlay version
        # older than the entry has stale fan rows for that fid, so
        # consume delivers it host-side (the overlay's dirty_filters)
        self._fid_member_clock: dict[int, int] = {}
        # journal-driven incremental capture (ISSUE 4): the previous
        # build's capture + the set of filters touched since it — a
        # compaction refreshes only the touched filters instead of
        # re-walking the world (see _capture_state_incremental)
        self._last_capture = None
        self._touched: set[str] = set()
        self._built_deleted: set[str] = set()  # snapshot tombstones
        self._enc_cache: dict[str, list] = {}  # filter -> interned words
        # columnar-ingress burst pre-encode (ISSUE 11): one vectorized
        # native intern pass over a read burst's unique topics, consumed
        # by prepare_window's gather path. Guarded by the intern-table
        # length — intern ids are append-only, so an unchanged length
        # proves the cached rows are what a fresh encode would produce
        # (a filter word interned between burst and window would turn a
        # cached UNKNOWN stale — the guard drops the whole memo then).
        self._burst_enc = None          # (idx: dict, enc, lens, dollar,
                                        #  too_long, intern_len)

        # fault-domain supervision (ISSUE 6): injection points at every
        # stage boundary, breaker-gated degradation (the reuse layers
        # stand down at rung 1, the whole device path at rung 2 — the
        # batcher reads the rung), contained cache/overlay/swap faults.
        # None (knob off) restores the pre-ISSUE-6 unwind exactly.
        self.sup = supervisor if supervisor is not None \
            else getattr(node, "supervisor", None)
        if self.sup is not None:
            self.sup.register_probe("dispatch", self._probe_dispatch)
            self.sup.register_probe("materialize",
                                    self._probe_materialize)

        # HBM ledger (ISSUE 8): every persistent device allocation this
        # engine makes — snapshot tables/cursors, per-version delta
        # overlays — registers through _hold; dispatch handles pin the
        # window clock for the stale-pin sentinel. None (knob off)
        # restores the untracked behavior exactly.
        self.ledger = ledger if ledger is not None \
            else getattr(node, "hbm_ledger", None)

        # wire change notifications
        self.router.on_route_change = self.note_route_change
        self.broker.device_engine = self
        tele = getattr(node, "pipeline_telemetry", None)
        if tele is not None:
            tele.rebuild_state_fn = self.rebuild_state

    # ---- churn tracking -------------------------------------------------
    def staleness(self) -> int:
        """Distinct stale entities vs the snapshot (filters/slots serving
        host-side) — the rebuild trigger. A set-size measure, so repeated
        churn on one filter counts once and the subscribe path's double
        notification (route change + member change) cannot double-count.
        With the delta overlay on (ISSUE 4), post-snapshot filters are
        matched AND delivered on device, so they no longer count toward
        the full-rebuild trigger — overlay overflow and the snapshot
        tombstone ratio trigger compactions instead
        (_compaction_reason). DELETED built filters likewise move to
        the tombstone-ratio trigger: a tombstone costs a slow-path
        consume only for messages that still match it (it delivers
        nothing), so under rolling subscribe/unsubscribe churn it must
        not drip the churn counter over the threshold — that would
        recreate exactly the rebuild cadence the overlay exists to
        demote."""
        base = (len(self.dirty_filters) + len(self.dirty_slots)
                + sum(len(v) for v in self.new_slots_by_filter.values()))
        if self.delta_overlay:
            base -= len(self._built_deleted)    # ⊆ dirty_filters
            # delta filters the overlay CANNOT hold (deeper than
            # max_levels, or past the top row class) serve host-side
            # and disable the fast consume — they must keep counting
            # toward the rebuild trigger exactly like the overlay-off
            # path, or one deep filter would degrade every message's
            # consume forever with nothing ever healing it
            base += self._overlay_uncovered
        else:
            base += len(self._delta_filter)
        return base

    def journal_depth(self) -> int:
        """Filters touched since the last capture — the incremental
        compaction's pending work (exported via the rebuild telemetry
        section)."""
        return len(self._touched)

    def _mark_dirty(self, f: str) -> None:
        """dirty_filters.add with the hostside-memo version bump (only
        on actual growth — the subscribe path's double notification
        must not churn the memo key twice for one event)."""
        if f not in self.dirty_filters:
            self.dirty_filters.add(f)
            self._dirty_ver += 1

    def _hostside_mask(self, b) -> np.ndarray:
        """Per-fid host-side delivery mask of snapshot `b` (rich subopts
        OR dirty membership), memoized on (snapshot id, dirty version).
        Invalidated by subscribe/unsubscribe churn and snapshot swaps,
        not per batch."""
        if not self.dirty_filters:
            return b.fid_rich
        key = (b.sid, self._dirty_ver)
        memo = self._hostside_memo
        if memo is not None and memo[0] == key:
            return memo[1]
        hs = b.fid_rich.copy()
        for f in self.dirty_filters:
            fid = b.fid_of.get(f)
            if fid is not None:
                hs[fid] = True
        self._hostside_memo = (key, hs)
        return hs

    def _enc_filter(self, f: str) -> list:
        """Interned level ids of a filter, memoized across builds: word
        ids are append-only for the process lifetime (ops/intern.py), so
        the encoding never goes stale and the compaction path reuses the
        previous build's work instead of re-tokenizing the universe."""
        w = self._enc_cache.get(f)
        if w is None:
            w = self._enc_cache[f] = self.intern.encode_filter(
                T.tokens(f))
        return w

    def _overlay_changed(self, words, deleted_fid=None) -> None:
        """Bookkeeping shared by delta insert and delete: bump the
        overlay clock, mark the table stale, and make the match cache
        delta-aware — drop exactly the cached topics the changed filter
        matches (host-side check over the stored encoded topics) plus
        bump the cache's delta version so in-flight readbacks that
        predate this change cannot insert stale rows."""
        self._overlay_clock += 1
        self._overlay_stale = True
        if deleted_fid is not None:
            self._fid_member_clock.pop(deleted_fid, None)
        cache = self._match_cache
        if cache is not None:
            from emqx_tpu.ops.delta import np_filter_match
            cache.bump_delta_version()
            if len(cache):
                cache.drop_where(
                    self._built.sid if self._built else None,
                    lambda encs, lens, dols: np_filter_match(
                        words, encs, lens, dols))

    def note_route_change(self, topic_filter: str, added: bool) -> None:
        """Router filter-universe change (local subscribe path and
        cluster-replicated remote routes both land here)."""
        if self._journal is not None:
            self._journal.append(("route", topic_filter, added))
        self._touched.add(topic_filter)
        removed_words = None
        if not added:
            # read the memo BEFORE evicting it: the delete path below
            # needs the encoding and must not re-tokenize per delete
            # under rolling unsubscribe churn
            removed_words = self._enc_cache.pop(topic_filter, None)
        if self._built is None:
            return
        if added:
            if topic_filter in self._built.fid_of:
                self._mark_dirty(topic_filter)
                self._built_deleted.discard(topic_filter)
            elif topic_filter not in self._delta_fid_of:
                words = self._enc_filter(topic_filter)
                if self._try_cover_append(topic_filter, words):
                    return
                fid = self._next_delta_fid
                self._next_delta_fid += 1
                self._delta_trie.insert(words, fid)
                self._delta_filter[fid] = topic_filter
                self._delta_fid_of[topic_filter] = fid
                if self.delta_overlay:
                    self._overlay_changed(words)
        else:
            if topic_filter in self._built.fid_of:
                self._mark_dirty(topic_filter)
                self._built_deleted.add(topic_filter)
            fid = self._delta_fid_of.pop(topic_filter, None)
            if fid is not None:
                words = removed_words if removed_words is not None \
                    else self.intern.encode_filter(T.tokens(topic_filter))
                self._delta_trie.delete(words)
                self._delta_filter.pop(fid, None)
                if self.delta_overlay:
                    self._overlay_changed(words, deleted_fid=fid)

    def note_member_change(self, real: str, group: Optional[str]) -> None:
        """Broker membership change (subscribe/unsubscribe/opts update)."""
        if self._journal is not None:
            self._journal.append(("member", real, group))
        self._touched.add(real)
        self._cluster_groups_cache.pop(real, None)
        if self._built is None:
            return
        if group is None:
            if real in self._built.fid_of:
                self._mark_dirty(real)
            elif self.delta_overlay:
                fid = self._delta_fid_of.get(real)
                if fid is not None:
                    # overlay fan rows for this fid are stale: versions
                    # at/below this clock deliver it host-side until the
                    # next overlay apply refreshes the row (match rows
                    # are membership-independent — no cache action)
                    self._overlay_clock += 1
                    self._fid_member_clock[fid] = self._overlay_clock
                    self._overlay_stale = True
        else:
            if (real, group) in self._built.slot_of:
                self.dirty_slots.add((real, group))
            elif real in self._built.fid_of:
                self.new_slots_by_filter.setdefault(real, set()).add(group)
            # delta filters' shared groups dispatch host-side via the
            # consume sweep over live broker.shared — nothing to track

    # ---- subscription covering: cover-set churn (ISSUE 18) --------------
    def _cover_index(self, b) -> "_CoverState":
        """The snapshot's host covering index (HostTrie over the roots
        + their encodings), built lazily on the first append attempt —
        the steady-state serving path never needs it, so builds don't
        pay O(roots) host-dict construction up front."""
        cs = b.cover
        if cs.trie is None:
            from emqx_tpu.ops.trie import HostTrie
            t = HostTrie()
            rw: dict[int, list] = {}
            for fid in cs.roots:
                w = self._enc_filter(b.fid_filter[int(fid)])
                t.insert(w, int(fid))
                rw[int(fid)] = w
            cs.trie, cs.root_words = t, rw
        return cs

    def _try_cover_append(self, f: str, words: list) -> bool:
        """Cover-set churn fast path: a NEW filter covered by a built
        covering root becomes an expansion-CSR append — a spare padded
        fid + a small device upload of the append region — instead of
        an overlay row or a rebuild. The appended fid matches on device
        from the next dispatch (sorted after every built filter, which
        is exactly where the covering-off twin's overlay rows deliver)
        and delivers host-side through the fid_rich path (its padded
        SubTable segment is empty, so device fan-out ships nothing for
        it). Returns False → the caller takes the overlay path, which
        is always correct; a False on an *eligible* snapshot counts
        toward the "covering" compaction reason (uncovered new filters
        erode the covering reduction until a recompaction). Of the
        roots that cover the filter it rides the smallest-fid one that
        owns (a wide root's row would ride every topic under it), and
        a wide one where nothing else covers it: the append region is
        apart from the candidate plane, so that costs no lane."""
        b = self._built
        if b is None or b.cover is None or self._tables is None \
                or not self.subscription_covering:
            return False
        m = self.node.metrics
        cs = b.cover
        ct = cs.ct
        if (len(words) > cs.level_cap
                or cs.app_used >= ct.app_root.shape[0]
                or len(b.fid_filter) >= len(b.seg_np)):
            self._cover_churn += 1
            m.inc("routing.cover.append_rejects")
            return False
        cs = self._cover_index(b)
        from emqx_tpu.ops.cover import host_covering_roots, rank_base
        roots = host_covering_roots(cs.trie, cs.root_words, words,
                                    f.startswith("$"))
        if not roots:
            self._cover_churn += 1
            m.inc("routing.cover.append_rejects")
            return False

        fid = len(b.fid_filter)
        k = cs.app_used
        # under the narrowest company it can keep: a root that owns
        # (an area's historian) before a wide one over it (the
        # tenant's `#`, which every topic of the tenant would carry
        # the row for); under the wide root where nothing else covers
        ct.app_root[k] = min([r for r in roots if r not in cs.wide]
                             or roots)
        ct.app_fid[k] = fid
        # dense order rank past every built filter's: appends deliver
        # in arrival order after the snapshot set, mirroring the
        # off-twin's overlay order (see build_cover_tables ranking)
        ct.app_key[k] = np.int32(rank_base(ct) + k)
        ct.app_words[k, :len(words)] = words
        ct.app_lens[k] = len(words)
        cs.app_used += 1
        b.fid_of[f] = fid
        b.fid_filter.append(f)
        b.seg_len.append(0)
        b.fid_rich[fid] = True       # deliver via the live broker dict
        self._dirty_ver += 1         # hostside-mask memo must refresh

        # upload ONLY the append-region leaves (same shapes → no
        # retrace, warm classes stay valid); in-flight handles keep the
        # old immutable arrays, so the swap is safe mid-pipeline
        import jax
        if b.backend == "shapes":
            dev_cover = self._tables.shapes.cover
        else:
            dev_cover = self._tables.trie.cover
        put = self._hold("cover_csr", jax.device_put(
            (ct.app_root, ct.app_fid, ct.app_key, ct.app_words,
             ct.app_lens)), owner=f"sid{b.sid}")
        dev_cover = dev_cover._replace(
            app_root=put[0], app_fid=put[1], app_key=put[2],
            app_words=put[3], app_lens=put[4])
        if b.backend == "shapes":
            self._tables = self._tables._replace(
                shapes=self._tables.shapes._replace(cover=dev_cover))
        else:
            self._tables = self._tables._replace(
                trie=self._tables.trie._replace(cover=dev_cover))

        # match-cache invalidation walks the EXPANDED set: cached
        # topics that match the NEW covered filter (a member of the
        # expanded result, never of the covering match set) must drop
        # so their next dispatch includes the appended fid; the delta
        # version bump keeps in-flight readbacks from re-inserting
        # pre-append rows
        cache = self._match_cache
        if cache is not None:
            from emqx_tpu.ops.delta import np_filter_match
            cache.bump_delta_version()
            if len(cache):
                cache.drop_where(
                    b.sid,
                    lambda encs, lens, dols: np_filter_match(
                        words, encs, lens, dols))
        m.inc("routing.cover.appends")
        return True

    # ---- snapshot compile ----------------------------------------------
    def _observe_rebuild(self, stage: str, t0: float) -> None:
        tele = getattr(self.node, "pipeline_telemetry", None)
        if tele is not None:
            tele.observe_rebuild(stage, time.perf_counter() - t0)

    def rebuild(self) -> None:
        """Compile router+broker state into fresh device tables and swap,
        synchronously (first build / callers without a loop). The background
        path is `maybe_background_rebuild`. Reuses the previous build's
        capture + the touched-filter journal when the overlay machinery
        is on (the incremental-compaction path — see
        _capture_state_incremental)."""
        t0 = time.perf_counter()
        if self._can_capture_incremental():
            capture = self._capture_state_incremental()
        else:
            capture = self._capture_state_sync()
        self._observe_rebuild("capture", t0)
        t0 = time.perf_counter()
        result = self._build_from_capture(capture)
        self._observe_rebuild("build", t0)
        t0 = time.perf_counter()
        self._apply_build(result, journal=())
        self._observe_rebuild("swap", t0)

    def _capture_shared(self, f: str) -> dict:
        return capture_shared(self.broker, f)

    def _note_captured(self, capture) -> None:
        """A capture just completed: it becomes the incremental
        baseline. Called from every capture path BEFORE mutations racing
        the build can land (those re-enter _touched via note_*)."""
        if self.delta_overlay:
            self._last_capture = capture

    def _can_capture_incremental(self) -> bool:
        return self.delta_overlay and self._last_capture is not None

    def _incremental_refresh_set(self) -> set:
        """Filters the incremental capture must re-walk: everything
        touched since the last capture, plus every shared-group filter
        (old and new) — shared captures carry CURSOR state that advances
        on every dispatch without a note_* notification, so reusing a
        stale shared capture would reset round-robin rotation at each
        compaction. Shared filters are a small slice of the universe, so
        this keeps the capture o(touched + shared), never O(N)."""
        refresh = set(self._touched)
        self._touched = set()   # re-touches during the capture re-add
        _e, _w, _subs, shared0 = self._last_capture
        refresh |= set(shared0)
        refresh |= set(self.broker.shared)
        return refresh

    def _apply_refresh(self, subs: dict, shared: dict, fs) -> None:
        """Refresh one chunk of filters from live state into the capture
        dicts (shared by the sync and async incremental captures)."""
        broker = self.broker
        for f in fs:
            s = broker.subs.get(f)
            if s:
                subs[f] = list(s.items())
            else:
                subs.pop(f, None)
            cap = self._capture_shared(f)
            if cap:
                shared[f] = cap
            else:
                shared.pop(f, None)

    def _capture_state_incremental(self):
        """Journal-driven capture (ISSUE 4): start from the previous
        build's capture and re-walk ONLY the filters touched since (plus
        the shared set — see _incremental_refresh_set), instead of the
        full O(N) state walk. The filter universe lists are re-snapshotted
        live (two atomic C calls); _build_from_capture keys everything
        else off them, so filters added/removed since the baseline are
        picked up/dropped by construction."""
        router = self.router
        exact, wild = list(router.exact), list(router.wildcards)
        _e, _w, subs0, shared0 = self._last_capture
        subs, shared = dict(subs0), dict(shared0)
        self._apply_refresh(subs, shared, self._incremental_refresh_set())
        capture = (exact, wild, subs, shared)
        self._note_captured(capture)
        return capture

    async def _capture_state_incremental_async(self, chunk: int = 1024):
        """Chunked incremental capture (the background-compaction
        flavor): same refresh set, yielding between chunks; mutations
        landing mid-capture re-enter _touched AND the build journal, so
        they converge at swap exactly like the full capture's races."""
        import asyncio
        router = self.router
        exact, wild = list(router.exact), list(router.wildcards)
        _e, _w, subs0, shared0 = self._last_capture
        subs, shared = dict(subs0), dict(shared0)
        refresh = sorted(self._incremental_refresh_set())
        for i in range(0, len(refresh), chunk):
            self._apply_refresh(subs, shared, refresh[i:i + chunk])
            await asyncio.sleep(0)
        capture = (exact, wild, subs, shared)
        self._note_captured(capture)
        return capture

    def _capture_state_sync(self):
        """Point-in-time copy of the routing state (sync, may stall)."""
        broker, router = self.broker, self.router
        self._touched = set()
        exact, wild = list(router.exact), list(router.wildcards)
        filters = exact + wild
        subs = {f: list(broker.subs[f].items())
                for f in filters if broker.subs.get(f)}
        shared = {}
        for f in filters:
            cap = self._capture_shared(f)
            if cap:
                shared[f] = cap
        capture = (exact, wild, subs, shared)
        self._note_captured(capture)
        return capture

    async def _capture_state_async(self, chunk: int = 1024):
        """Chunked capture: yields to the loop between chunks so serving
        continues; mutations landing mid-capture are journaled and replayed
        at swap, so a half-captured filter at worst serves host-side.
        (Sorting — O(n log n) over every filter string — happens on the
        build thread, not here: list() of a set is a single atomic C call.)
        """
        import asyncio
        broker, router = self.broker, self.router
        self._touched = set()
        exact, wild = list(router.exact), list(router.wildcards)
        filters = exact + wild
        subs: dict = {}
        shared: dict = {}
        for i in range(0, len(filters), chunk):
            for f in filters[i:i + chunk]:
                s = broker.subs.get(f)
                if s:
                    subs[f] = list(s.items())
                cap = self._capture_shared(f)
                if cap:
                    shared[f] = cap
            await asyncio.sleep(0)
        capture = (exact, wild, subs, shared)
        self._note_captured(capture)
        return capture

    def _build_from_capture(self, capture, time_upload: bool = False):
        """Compile a captured state into device tables (loop-free: safe on
        an executor thread). Returns (built, dev_tables, cursors_np, rich)
        or None when the filter set is empty. `time_upload` (the
        background build only, never the inline build on the loop) waits
        for the host→device copy and records it as the `upload` stage;
        the warm pass that follows on the same thread waits for it
        anyway."""
        import jax

        from emqx_tpu.models.router_engine import (RouterTables,
                                                   ShapeRouterTables)
        from emqx_tpu.ops.fanout import build_subtable
        from emqx_tpu.ops.shapes import ShapeCapacityError, build_shape_tables
        from emqx_tpu.ops.trie import build_tables

        exact, wild, subs_cap, shared_cap = capture
        filters = sorted(exact) + sorted(wild)
        if not filters:
            return None

        b = _Built()
        b.fid_of = {f: i for i, f in enumerate(filters)}
        b.fid_filter = filters
        n = len(filters)
        # memoized encodings (ISSUE 4): a compaction re-encodes only
        # filters it has never seen, not the universe
        words = [self._enc_filter(f) for f in filters]
        L = max(1, max(len(w) for w in words))
        rows = np.zeros((n, L), np.int32)
        lens = np.zeros(n, np.int64)
        for i, w in enumerate(words):
            rows[i, :len(w)] = w
            lens[i] = len(w)

        normal: dict[int, list] = {}
        filter_slots: dict[int, list] = {}
        shared_members: dict[int, list] = {}
        cursors0: list[int] = []
        slot_fid: list[int] = []
        rich: set[str] = set()
        seg_len = [0] * n
        for f, fid in b.fid_of.items():
            subs = subs_cap.get(f)
            if subs:
                entries = []
                for sid, opts in subs:
                    if _is_rich(opts):
                        rich.add(f)
                    entries.append((sid, _pack_opts(opts)))
                normal[fid] = entries
                seg_len[fid] = len(entries)
            for g in sorted(shared_cap.get(f, {})):
                members_raw, cursor = shared_cap[f][g]
                slot = len(b.slot_key)
                b.slot_of[(f, g)] = slot
                b.slot_key.append((f, g))
                slot_fid.append(fid)
                members = []
                for sid, opts in members_raw:
                    if isinstance(sid, tuple):
                        # remote member ref: reserve a device sid that
                        # indexes remote_members; opts live on its node
                        dev_sid = _REMOTE_SID_BASE + len(b.remote_members)
                        b.remote_members.append(sid)
                        members.append((dev_sid, 0))
                        continue
                    if _is_rich(opts):
                        rich.add(f)
                    members.append((sid, _pack_opts(opts)))
                shared_members[slot] = members
                filter_slots.setdefault(fid, []).append(slot)
                cursors0.append(cursor)
        b.seg_len = seg_len
        b.n_slots = len(b.slot_key)
        b.slot_fid = np.asarray(slot_fid, np.int64)
        b.picks = GroupPicks(b.slot_key, self._host_shared_dispatch)
        b.seg_np = np.asarray(seg_len, np.int64)
        b.fid_shared = np.zeros(max(1, n), bool)
        for fid in filter_slots:
            b.fid_shared[fid] = True
        b.fid_rich = np.zeros(max(1, n), bool)
        for f in rich:
            b.fid_rich[b.fid_of[f]] = True

        # pow2 capacity classes: recompile only when a class grows
        filter_cap = _next_pow2(n)

        # subscription covering (ISSUE 18 tentpole): detect cover
        # relations over the interned columnar table and shrink the
        # match set to the ROOTS (uncovered filters); the expansion CSR
        # re-expands matched covers after the match stage (ops/cover).
        # Engaged only where the FULL set does not fit the shape-hash
        # backend (cover.covering_decision, evaluated BEFORE detection:
        # a set the shape table holds whole builds cover-free and skips
        # detection, owners, the expansion CSR and the padding below),
        # and only when something is covered — always correct, covering
        # is a pure optimization.
        from emqx_tpu.ops import cover as cover_mod
        cover_np = None
        cover_state = None
        sub_ids = None                 # fids the match tables hold
        cover_shapes = False
        engage = False
        if self.subscription_covering:
            engage, b.cover_decision = cover_mod.covering_decision(
                cover_mod.full_shape_count(rows, lens), self.shape_cap, L)
        if engage:
            covered = ()
            if n >= 2:
                dollar = np.fromiter((f.startswith("$") for f in filters),
                                     bool, n)
                covs, inc = cover_mod.detect_covers(rows, lens, dollar)
                # a root owns what the candidate plane can hold beside
                # a root of its own in every other slot of the roots'
                # match row (a slot a shape, or the NFA's match row:
                # the roots' backend is not known yet), or nothing: no
                # owning root passes the plane by its own segment
                budget = max(0, self.cover_cand_cap
                             - max(self.shape_cap, self.match_cap))
                owner = cover_mod.assign_owners(covs, inc,
                                                own_budget=budget)
                covered = np.flatnonzero(owner >= 0)
                wide = np.flatnonzero(cover_mod.fan_in(covs) > budget)
                self.node.metrics.inc("routing.cover.wide_roots",
                                      len(wide))
            if not len(covered):
                b.cover_decision = "none_covered"
            else:
                # the off twin runs the trie NFA here, so the order keys
                # and the expanded row width are the trie's. The backend
                # that matches the ROOTS is free: the expansion stage
                # re-sorts every candidate by the per-filter order key,
                # and two DISTINCT filters matching the same topic
                # always carry distinct keys (equal key + same topic
                # forces equal literals), so the expanded row reproduces
                # the off twin's order whatever backend matched the
                # roots. Match them under shapes whenever the ROOT
                # subset fits: that is the covering win on populations
                # whose full diversity overflows the shape cap into
                # the trie
                sub_ids = np.flatnonzero(owner < 0)
                root_shapes = cover_mod.full_shape_count(
                    rows[sub_ids], lens[sub_ids])
                cover_shapes = L <= cover_mod.SHAPE_MAX_LEVELS \
                    and root_shapes <= self.shape_cap
                # room for the largest segment (a root and what it
                # owns) beside a root of its own in every other slot
                # of the roots' match row. Every candidate lane costs
                # a row gather and a sort key a topic: where a segment
                # holds 50, a sub-batch of 1024 took a v5e 55.6 ms at
                # the fixed 256 lanes, 34.5 at 128, 25.4 at 64 (PR 36's
                # builder), and at 256 the chooser left the chip
                # (PERF.md, PR 38). A topic under two roots that own
                # much overflows and host-routes, counted; under one it
                # cannot (the budget above)
                seg_max = 1 + int(np.bincount(owner[covered]).max())
                slots = root_shapes if cover_shapes else self.match_cap
                cover_np = cover_mod.build_cover_tables(
                    rows, lens, owner,
                    cover_mod.trie_order_keys(rows, lens),
                    fid_cap=filter_cap, out_width=self.match_cap,
                    cand_cap=min(self.cover_cand_cap,
                                 _next_pow2(seg_max + slots - 1)))
                cover_state = _CoverState(
                    sub_ids, cover_np, L, len(covered), int(inc.sum()),
                    wide, seg_max)
                # pad the consume companions to filter_cap: cover-set
                # churn APPENDS fids past n (spare padded SubTable rows
                # deliver host-side via fid_rich), and the consume walk
                # indexes these arrays by matched fid
                pad = filter_cap - n
                b.seg_np = np.concatenate(
                    [b.seg_np, np.zeros(pad, np.int64)])
                b.fid_shared = np.concatenate(
                    [b.fid_shared[:n], np.zeros(pad, bool)])
                b.fid_rich = np.concatenate(
                    [b.fid_rich[:n], np.zeros(pad, bool)])
        b.cover = cover_state

        total_subs = sum(seg_len)
        total_members = sum(len(m) for m in shared_members.values())
        subs_tbl = build_subtable(
            filter_cap, normal, filter_slots, shared_members,
            slot_cap=_next_pow2(max(1, b.n_slots)),
            sub_rows_cap=_next_pow2(max(1, total_subs)),
            fs_rows_cap=_next_pow2(max(1, b.n_slots)),
            member_rows_cap=_next_pow2(max(1, total_members)))
        b.sub_start, b.sub_row, b.sub_opts = \
            subs_tbl.sub_start, subs_tbl.sub_row, subs_tbl.sub_opts

        tables = None
        if cover_np is not None:
            # covering path: match tables over the ROOT subset, with
            # the roots keeping their original dense fids (SubTable /
            # fan-out CSR / consume indexing are untouched — covered
            # fids simply never leave the match stage un-expanded)
            roots = sub_ids
            if cover_shapes:
                st = build_shape_tables(rows[roots], lens[roots],
                                        filter_ids=roots,
                                        shape_cap=self.shape_cap)
                tables = ShapeRouterTables(shapes=st, subs=subs_tbl)
                b.backend = "shapes"
                # the EXPANDED row is as wide as the covering-off
                # twin's (the trie NFA's match_cap), so the
                # cache/compact/consume row width matches it exactly
                b.match_width = int(cover_np.out_pad.shape[0])
            else:
                node_cap = _next_pow2(
                    max(256, 2 * (int(lens[roots].sum()) + 1)))
                trie = build_tables(rows[roots], lens[roots],
                                    filter_ids=roots,
                                    node_capacity=node_cap,
                                    slot_capacity=4 * node_cap)
                tables = RouterTables(trie=trie, subs=subs_tbl)
                b.backend = "trie"
                b.match_width = self.match_cap
        if tables is None and L <= 20:
            try:
                st = build_shape_tables(rows, lens, shape_cap=self.shape_cap)
                tables = ShapeRouterTables(shapes=st, subs=subs_tbl)
                b.backend = "shapes"
                b.match_width = int(st.shape_plus_mask.shape[0])
            except ShapeCapacityError:
                tables = None
        if tables is None:
            node_cap = _next_pow2(max(256, 2 * (int(lens.sum()) + 1)))
            trie = build_tables(rows, lens, node_capacity=node_cap,
                                slot_capacity=4 * node_cap)
            tables = RouterTables(trie=trie, subs=subs_tbl)
            b.backend = "trie"
            b.match_width = self.match_cap

        cur = np.zeros(max(1, len(cursors0)), np.int32)
        if cursors0:
            cur[:len(cursors0)] = cursors0
        t_up = time.perf_counter()
        dev_tables = self._hold("snapshot_tables", jax.device_put(tables),
                                owner=f"sid{b.sid}")
        dev_cursors = self._hold("snapshot_cursors", jax.device_put(cur))
        if cover_np is not None:
            # expansion-CSR buffers ride their own ledger category
            # ("cover_csr") so the HBM report prices covering separately
            # from the match tables; attached post-put so the
            # snapshot_tables category does not double-count the leaves
            dev_cover = self._hold("cover_csr", jax.device_put(cover_np),
                                   owner=f"sid{b.sid}")
            if b.backend == "shapes":
                dev_tables = dev_tables._replace(
                    shapes=dev_tables.shapes._replace(cover=dev_cover))
            else:
                dev_tables = dev_tables._replace(
                    trie=dev_tables.trie._replace(cover=dev_cover))
        if time_upload:
            # analysis: ok(loop-affinity) — only the background build
            # passes time_upload, and it runs on an executor thread; the
            # inline build on the loop never waits here
            jax.block_until_ready((dev_tables, dev_cursors))
            self._observe_rebuild("upload", t_up)
        return b, dev_tables, dev_cursors, rich

    def _hold(self, category: str, tree, owner: Optional[str] = None):
        """Register a persistent device allocation with the HBM ledger
        (ISSUE 8); identity passthrough when the ledger is off."""
        if self.ledger is not None:
            return self.ledger.hold(category, tree, owner=owner)
        return tree

    def _apply_build(self, result, journal) -> None:
        """Swap a finished build in and rebase churn tracking onto it by
        replaying the journal of mutations that happened during the build."""
        if self.sup is not None:
            # ISSUE 6 injection point: a swap failure is contained by
            # _try_swap / poll_rebuild — serving stays on the old
            # snapshot + host deltas (whose churn tracking is still
            # current: journaled note_* calls also ran live against it)
            self.sup.fire("snapshot_swap")
        self._reset_deltas()
        if result is None:
            self._built = None
            self._tables = None
            self._cursors = None
            self._cur_sig = ()
        else:
            b, tables, cursors, _rich = result
            if b.cover_decision == "fits_shapes":
                # covering allowed, not engaged: the shape-hash table
                # holds the full set (counted here, on the loop — the
                # build itself may run on an executor thread)
                self.node.metrics.inc("routing.cover.skipped_builds")
            self._built = b
            self._tables = tables
            self._cursors = cursors
            self._cur_sig = self._tables_sig(tables)
            # evict warmth of superseded signatures (unbounded set
            # otherwise under churn); a re-warm for a returning capacity
            # class is a jit-cache hit, not a fresh trace
            self._warm_classes = {c for c in self._warm_classes
                                  if c.sig == self._cur_sig}
            # demand for a stage's classes resets with the snapshot too:
            # classes still in use re-register on their next window, and
            # stale ones must not be background-recompiled after every
            # swap for the rest of the process lifetime. An oversized
            # batch class stays wanted: the batcher's setting made it
            self._wanted = {
                c._replace(sig=self._cur_sig) for c in self._wanted
                if c.plain}
        # match-cache invalidation: wholesale, HERE — and, with the
        # delta overlay on, at overlay inserts/deletes where ONLY the
        # cached topics matching the changed filter drop
        # (_overlay_changed; ISSUE 4's delta-aware invalidation).
        # Invariant: within one snapshot's lifetime the MAIN device
        # tables are immutable — subscription churn marks filters/slots
        # dirty and those deliver host-side against the PINNED snapshot
        # (the dirty/delta scheme above), so a cached MAIN row can never
        # go stale between swaps; the cached DELTA rows are kept exact
        # by the selective drop + the put-side delta-version check. The
        # id check inside the cache then makes serving rows across
        # snapshot ids structurally impossible.
        if self._match_cache is not None:
            self._match_cache.attach(
                self._built.sid if self._built is not None else None)
        # replay churn that raced the build: journaled note_* calls are
        # idempotent against the fresh snapshot (worst case marks a filter
        # that the build already captured as dirty — correct, just host-side
        # until the next rebuild)
        for entry in journal:
            if entry[0] == "route":
                self.note_route_change(entry[1], entry[2])
            else:
                self.note_member_change(entry[1], entry[2])
        self.node.metrics.inc("routing.device.rebuilds")

    def _reset_deltas(self) -> None:
        from emqx_tpu.ops.trie import HostTrie
        self._cluster_groups_cache = {}
        self.dirty_filters = set()
        self._dirty_ver += 1
        self._hostside_memo = None
        self.dirty_slots = set()
        self.new_slots_by_filter = {}
        self._delta_trie = HostTrie()
        self._delta_filter = {}
        self._delta_fid_of = {}
        self._next_delta_fid = 0
        self._built_deleted = set()
        # the fresh snapshot subsumes every overlay row: reset the
        # overlay (version monotonicity rides the clock, which is NOT
        # reset — in-flight handles pinned to an old overlay keep their
        # consistent view)
        self._overlay = None
        self._overlay_stale = False
        self._overlay_uncovered = 0
        self._fid_member_clock = {}
        self._cover_churn = 0   # the fresh snapshot re-detected covers

    def _compaction_reason(self) -> Optional[str]:
        """Why the current snapshot should recompile, or None.

        Overlay off: the pre-ISSUE-4 policy — distinct stale entities
        (incl. every delta filter) past the threshold. Overlay on: delta
        filters serve on device, so the full rebuild is demoted to a
        rare COMPACTION triggered by (a) overlay row overflow, (b) the
        snapshot's delete-tombstone ratio — deleted built filters still
        burn match work and dirty-set checks every batch, or (c)
        membership churn on built filters/slots (still host-side) past
        the threshold."""
        if self._built is None:
            return None
        if not self.delta_overlay:
            return "churn" if self.staleness() >= self.rebuild_threshold \
                else None
        if len(self._delta_filter) > _OVERLAY_MAX:
            return "overflow"
        dead = len(self._built_deleted)
        if dead >= 64 and 2 * dead >= len(self._built.fid_filter):
            return "tombstones"
        if self._cover_churn >= 64:
            # new COVERING filters (or uncovered ones) that could not
            # ride the expansion-CSR append path serve through the
            # overlay; each erodes the covering reduction, so past a
            # budget the snapshot recompacts and re-detects covers
            return "covering"
        if self.staleness() >= self.rebuild_threshold:
            return "churn"
        return None

    def _count_compaction(self, reason: str) -> None:
        m = self.node.metrics
        m.inc("routing.device.compactions")
        m.inc(f"routing.device.compaction.{reason}")

    # ---- background rebuild (double-buffered, round-2 weak #7) ----------
    def poll_rebuild(self) -> None:
        """The one rebuild policy, called on the batch cadence: a small
        first build runs inline (milliseconds — the first batch already
        rides the device); a big first build or a compaction trigger
        (_compaction_reason) runs double-buffered in the background."""
        if self._building:
            return
        if self.sup is not None:
            # supervision tick rides the batch cadence like the rebuild
            # policy: launch any due half-open probes (off-path)
            self.sup.poll()
        if self._built is None:
            n = len(self.router.exact) + len(self.router.wildcards)
            if n == 0:
                return
            if self.sup is not None and not self.sup.rebuild_enabled():
                return      # swap breaker open: host-route until probed
            if n <= 4096 or not self.maybe_background_rebuild():
                if self.sup is None:
                    self.rebuild()
                    return
                try:
                    self.rebuild()
                except Exception as e:  # noqa: BLE001 — contained
                    # first-build fault (ISSUE 6): serving stays
                    # host-side (no snapshot → prepare returns None)
                    # until the snapshot_swap breaker's probe re-admits
                    # rebuild attempts
                    self.sup.note_fault("snapshot_swap", e)
                    self.node.metrics.inc(
                        "routing.device.rebuild_failed")
        else:
            reason = self._compaction_reason()
            if reason is not None and self.maybe_background_rebuild():
                self._count_compaction(reason)

    def maybe_background_rebuild(self, executor=None) -> bool:
        """Kick a background rebuild when churn crossed a compaction
        trigger. Returns True when one is running/queued after the call.
        Requires a running loop; sync callers use rebuild()."""
        import asyncio
        if self._building:
            return True
        if self.sup is not None and not self.sup.rebuild_enabled():
            # snapshot_swap breaker open (ISSUE 6): no rebuild attempts
            # until the half-open probe succeeds — the old snapshot +
            # host deltas keep serving correctly meanwhile
            return False
        if self._built is not None \
                and self._compaction_reason() is None:
            return False
        if self._built is None \
                and not (self.router.exact or self.router.wildcards):
            return False    # nothing to compile yet
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return False
        self._building = True
        self._journal = []
        from emqx_tpu.broker.supervise import guard_task
        self._rebuild_task = guard_task(
            loop.create_task(self._background_rebuild(executor)),
            "device-rebuild", self.node.metrics)
        return True

    async def _background_rebuild(self, executor=None) -> None:
        import asyncio
        loop = asyncio.get_running_loop()
        try:
            t0 = time.perf_counter()
            if self._can_capture_incremental():
                capture = await self._capture_state_incremental_async()
            else:
                capture = await self._capture_state_async()
            self._observe_rebuild("capture", t0)
            t0 = time.perf_counter()
            result = await loop.run_in_executor(
                executor, self._build_from_capture, capture, True)
            self._observe_rebuild("build", t0)
            if result is not None:
                t0 = time.perf_counter()
                await loop.run_in_executor(executor, self._warm_compile,
                                           result)
                self._observe_rebuild("warm", t0)
            self._pending_swap = (result,)   # 1-tuple: result may be None
            self._try_swap()
        except Exception:
            import logging
            logging.getLogger("emqx.device").exception(
                "background snapshot rebuild failed; serving stays on the "
                "old snapshot + host deltas")
            self._journal = None
            self._building = False
            self._pending_swap = None
            self.node.metrics.inc("routing.device.rebuild_failed")

    def _warm_compile(self, result) -> None:
        """Pre-jit the route window for the new tables' shapes across
        the standard (window, batch) classes, so neither the swap nor a
        later first-use of a bigger class stalls serving on an XLA
        trace/compile (tracing holds the GIL even on an executor thread;
        cached compiles don't)."""
        b, tables, cursors, _rich = result
        std = self._std_classes(self._tables_sig(tables))
        for c in std:
            self._warm_class(c, b, tables, cursors)
        # this snapshot's classes are warm: once IT is serving, the
        # batcher may dispatch/fuse (readiness is per shape
        # signature, so an old snapshot still serving cannot run
        # into cold shapes)
        self._warm_classes.update(std)

    def _try_swap(self) -> None:
        """Apply a finished background build if no dispatch is in flight
        (handles pin the snapshot they were dispatched against)."""
        if not self._building or self._pending_swap is None \
                or self._outstanding > 0:
            return
        (result,) = self._pending_swap
        journal = self._journal or ()
        self._pending_swap = None
        self._journal = None
        self._building = False
        t0 = time.perf_counter()
        if self.sup is None:
            self._apply_build(result, journal)
        else:
            try:
                self._apply_build(result, journal)
            except Exception as e:  # noqa: BLE001 — contained domain
                # swap fault (ISSUE 6): the old snapshot keeps serving
                # (its dirty/delta tracking ran live during the build,
                # so dropping the failed result loses nothing); the
                # breaker gates further rebuild attempts until a probe
                self.sup.note_fault("snapshot_swap", e)
                self.node.metrics.inc("routing.device.rebuild_failed")
                self._observe_rebuild("swap", t0)
                return
            self.sup.note_ok("snapshot_swap")
        self._observe_rebuild("swap", t0)

    # ---- supervision probes (ISSUE 6: off-the-serving-path health
    #      checks the half-open breaker runs on an executor thread) ----
    def _probe_dispatch(self) -> None:
        """End-to-end health check of the dispatch stage: run the plain
        route window over an all-pad batch against the live tables —
        the same call the demand-warm passes already make from executor
        threads, so thread-safety and jit-cache behavior are identical.
        Matches nothing, advances nothing (the probe's new_cursors are
        dropped; an all-pad batch has zero occur)."""
        b, tables, cursors = self._built, self._tables, self._cursors
        if b is None or tables is None:
            return      # nothing to probe: vacuous health
        c = self._class_of(*self._STD_CLASSES[0])
        jax.block_until_ready(
            self._run_window(c, b, tables, cursors).match_counts)

    def _probe_materialize(self) -> None:
        """Health check of the readback stage: one small device→host
        transfer proves the link."""
        import jax.numpy as jnp
        np.asarray(jnp.zeros((8,), jnp.int32))

    # ---- the serving path ----------------------------------------------
    def device_shared_active(self) -> bool:
        """Device picks serve all device-supported strategies, clustered
        or standalone — the snapshot holds the cluster-wide membership
        with remote members as forwardable refs (round-4: previously
        groups with remote members fell back to host dispatch; round-2
        before that, ANY cluster disabled the whole on-device path)."""
        from emqx_tpu.ops.shared import STRATEGIES
        return self.broker.shared_strategy in STRATEGIES

    def _host_shared_dispatch(self, f: str, gname: str, msg) -> bool:
        """One group's host-side dispatch: cluster-wide pick under a
        cluster, local strategy pick standalone."""
        broker = self.broker
        if broker.cluster is not None:
            return broker.cluster._dispatch_one_group(broker, f, gname,
                                                      msg)
        g = broker.shared.get(f, {}).get(gname)
        return bool(g and g.members
                    and broker._shared_pick_deliver(gname, f, g, msg))

    # ---- delta overlay (ISSUE 4) ----------------------------------------
    def _overlay_class(self, n: int) -> int:
        for c in _DELTA_CLASSES:
            if n <= c:
                return c
        return _DELTA_CLASSES[-1]

    @staticmethod
    def _delta_payload_cap(Bp: int) -> int:
        """Delta CSR payload class, a fixed multiple of Bp (so it adds
        no warm-class dimension): overlay matches are sparse — most
        lanes match zero post-snapshot filters — so one entry per lane
        of headroom covers realistic churn; a window that still outgrows
        it reads the dense delta planes of the same dispatch."""
        return max(64, Bp)

    def _overlay_sync(self) -> None:
        """Apply pending journal entries to the overlay (see
        _overlay_sync_inner for the mechanics). Under supervision
        (ISSUE 6) this is the overlay_apply fault domain: a raising
        apply is CONTAINED — the overlay stays stale and its filters
        serve through the host delta trie (exactly the pre-overlay
        fallback, counted by routing.device.host_delta) while the
        breaker opens toward rung 1. Without supervision the exception
        propagates out of prepare (the pre-ISSUE-6 behavior: the whole
        group host-routes via the batcher's produce catch)."""
        if not self.delta_overlay or not self._overlay_stale:
            return
        sup = self.sup
        if sup is None:
            self._overlay_sync_inner()
            return
        try:
            sup.fire("overlay_apply")
            self._overlay_sync_inner()
        except Exception as e:  # noqa: BLE001 — contained fault domain
            sup.note_fault("overlay_apply", e)
        else:
            sup.note_ok("overlay_apply")

    def _overlay_sync_inner(self) -> None:
        """Rebuild the small host table from the live delta dicts and
        upload a fresh DeltaTables version. The table is a few hundred
        rows of numpy — microseconds, safe on the loop; the EXPENSIVE
        part (the fused program compile for a new row class) is
        demand-warmed off the serving path like the cached/compact
        ladders (_gate_delta). Versions are immutable: in-flight
        handles keep the table they dispatched with, and per-fid
        membership staleness is judged against the pinned version's
        clock stamp at consume."""
        t0 = time.perf_counter()
        from emqx_tpu.ops.delta import build_delta_tables
        live = sorted(self._delta_filter.items())   # fid order = age
        entries = []
        fid_set = set()
        row_of: dict[int, int] = {}
        seg_of: dict[int, int] = {}
        hostfan: set[int] = set()
        for fid, f in live:
            if len(entries) >= _OVERLAY_MAX:
                break       # overflow: the rest host-route until the
                            # compaction this state has already triggered
            words = self._enc_filter(f)
            if len(words) > self.max_levels:
                continue    # too deep for the device planes: host path
            fan = []
            subs = self.broker.subs.get(f)
            host_side = False
            if subs:
                if len(subs) > _DELTA_FAN_PER_ROW:
                    host_side = True    # oversized fan-out: match on
                else:                   # device, deliver via host dict
                    for sid, opts in subs.items():
                        if _is_rich(opts):
                            host_side = True
                            break
                        fan.append((sid, _pack_opts(opts)))
            if host_side:
                fan = []
                hostfan.add(fid)
            row_of[fid] = len(entries)
            seg_of[fid] = len(fan)
            fid_set.add(fid)
            entries.append((words, fid, fan))
        self._overlay_uncovered = len(live) - len(fid_set)
        if not entries:
            self._overlay = None
            self._overlay_stale = False
            return
        cap = self._overlay_class(len(entries))
        dt = build_delta_tables(entries, row_cap=cap,
                                level_cap=self.max_levels,
                                fan_per_row=_DELTA_FAN_PER_ROW)
        import jax
        # each overlay version is its own ledgered allocation: pinned
        # versions show up as distinct owners until their handles drain
        dev = self._hold("delta_overlay", jax.device_put(dt),
                         owner=f"v{self._overlay_clock}")
        self._overlay = _Overlay(dev, frozenset(fid_set), row_of, seg_of,
                                 hostfan, self._overlay_clock, cap,
                                 len(entries))
        self._overlay_stale = False
        self.node.metrics.inc("routing.device.delta_applies")
        self._observe_rebuild("delta_apply", t0)

    def _gate_delta(self, Wp: int, Bp: int,
                    gate_cold: bool) -> Optional[_Overlay]:
        """Choose + warm-gate the overlay for one dispatch. Returns the
        pinned _Overlay, or None to dispatch WITHOUT the fused overlay
        (overlay off/empty, or its class is cold on the serving path —
        the pre-overlay host fallback stays correct meanwhile and the
        routing.device.host_delta counter measures exactly that gap)."""
        if not self.delta_overlay:
            return None
        self._overlay_sync()
        ov = self._overlay
        if ov is None:
            return None
        if self._cold(self._class_of(Wp, Bp, ov=ov), gate_cold,
                      "routing.device.cold_delta_class"):
            return None
        return ov

    def _delta_pending(self, ov: Optional[_Overlay]) -> bool:
        """True when some live delta filter is NOT served by `ov` (no
        overlay this dispatch, or filters landed/overflowed past it) —
        consume must then run the host delta trie for the uncovered
        remainder and the vectorized fast path stands down."""
        if not self._delta_filter:
            return False
        if ov is None:
            return True
        return not self._delta_filter.keys() <= ov.fid_set

    def prepare(self, msgs: list[Message], gate_cold: bool = True):
        """Stage 1 (event loop): encode ONE micro-batch (window of 1)."""
        return self.prepare_window([msgs], gate_cold=gate_cold)

    def _plan_window(self, b, enc4, len4, dol4, gate_cold: bool,
                     ov: Optional[_Overlay] = None):
        """Dedup + match-cache analysis for one encoded window.

        Collapses the [Wp, Bp] lanes to unique encoded topics (padding
        lanes all share one sentinel key, so under-filled fused windows
        still win), consults the snapshot-keyed cache for each unique
        topic, and compacts the remainder into a miss sub-batch whose
        size is quantized onto the SAME pow2 batch-class ladder the warm
        machinery already compiles.

        Returns (plan, cache_info): `plan` is the cached-dispatch device
        input set (None = dispatch the plain program), `cache_info` the
        post-readback insert list (kept even when the plan is rejected —
        the plain path's readback must still seed the cache, or a cold
        hot-set would never start hitting)."""
        Wp, Bp, L = enc4.shape
        if Wp == 1 and Bp <= self._STD_CLASSES[0][1]:
            # a single window at the smallest batch class can never
            # engage (Bm floors at that same class, so Bm < Bp is
            # impossible): skip the whole analysis — trickle traffic
            # must not pay hashing/unique/lookup for zero possible
            # payoff (measured 0.88x at batch 64 otherwise)
            return None, None
        n_lanes = Wp * Bp
        encf = enc4.reshape(n_lanes, L)
        lenf = len4.reshape(n_lanes)
        dolf = dol4.reshape(n_lanes)
        keys_v = _topic_keys(encf, lenf, dolf)
        uniq, first_idx, inv = np.unique(keys_v, return_index=True,
                                         return_inverse=True)
        Bu = len(uniq)
        pad_u = lenf[first_idx] == 0          # [Bu] the sentinel pad lane
        real = int((lenf > 0).sum())
        uniq_real = Bu - int(pad_u.sum())
        if Bu > Bp:
            # window more diverse than the Bp-wide unique arrays can
            # hold: dedup would not pay anyway — plain dispatch
            return None, None
        cache = self._match_cache
        keys = [None if pad_u[u] else uniq[u].tobytes()
                for u in range(Bu)]
        # the cache lookup runs before the engage decision by necessity
        # (the miss count IS the decision input), and misses must seed
        # the cache even from plain-dispatched windows or a cold hot-set
        # would never start hitting; the base rows themselves are only
        # materialized once the plan engages
        hit_rows: list = [None] * Bu
        miss_u: list[int] = []
        inserts: list[tuple] = []
        if cache is not None:
            rows = cache.get_many(b.sid,
                                  [k for k in keys if k is not None])
            it = iter(rows)
            for u, k in enumerate(keys):
                if k is None:
                    continue
                row = next(it)
                if row is not None and ov is not None:
                    # delta-fused dispatch: a usable hit must carry the
                    # overlay base triple (rows inserted from a window
                    # that dispatched without the overlay store None
                    # there) and its fids must map into the pinned
                    # table (deleted fids are swept by the delta-aware
                    # invalidation, so a miss here is a transient race,
                    # not a leak)
                    if len(row) < 6 or row[3] is None or not all(
                            int(df) in ov.row_of for df in row[3]
                            if df >= 0):
                        row = None
                if row is None:
                    miss_u.append(u)
                    inserts.append((k, int(first_idx[u])))
                else:
                    hit_rows[u] = row
        else:
            miss_u = [u for u in range(Bu) if keys[u] is not None]
        info = _CacheInfo(
            b.sid, inserts,
            cache.delta_version if cache is not None
            and self.delta_overlay else None) if inserts else None
        n_miss = len(miss_u)
        n_hit = uniq_real - n_miss
        Bm = self._batch_class(max(1, n_miss))
        # engage only when the deduplicated dispatch removes real match
        # work: the miss sub-batch quantizes to a SMALLER class than the
        # full batch, or a fused window (whose plain match would run Wp
        # full-width batches). Hits alone don't qualify — at Bm == Bp
        # the match runs the same lane count either way and the cached
        # program would only add gather overhead (and pointless warm
        # traces for its class).
        if not (Bm < Bp or Wp > 1):
            return None, info
        # ... and only where repeats and hits together take two fifths
        # of the lanes off the match: a window whose topics are mostly
        # new ones (a quarter broadcasts on a few hot topics, the rest
        # per-device commands) would run the plan's gathers, and ask
        # for a program of its own class, to skip little. The cells
        # whose plan pays remove more than half (Zipf keys: 55-64 %)
        if 5 * (real - n_miss) < 2 * real:
            return None, info
        if self._cold(self._class_of(Wp, Bp, ov=ov)._replace(Bm=Bm),
                      gate_cold, "routing.device.cold_cached_class"):
            return None, info
        base_m = np.full((Bp, b.match_width), -1, np.int32)
        base_c = np.zeros(Bp, np.int32)
        base_o = np.zeros(Bp, bool)
        if ov is not None:
            base_dm = np.full((Bp, _DELTA_MATCH_CAP), -1, np.int32)
            base_dc = np.zeros(Bp, np.int32)
            base_do = np.zeros(Bp, bool)
        for u, row in enumerate(hit_rows):
            if row is not None:
                base_m[u] = row[0]
                base_c[u] = row[1]
                base_o[u] = row[2]
                if ov is not None:
                    # cached delta triples are FID-space (stable across
                    # overlay row reassignments); map onto the pinned
                    # table's row indices for the device-side merge
                    dm = row[3]
                    for j, df in enumerate(dm):
                        if df >= 0:
                            base_dm[u, j] = ov.row_of[int(df)]
                    base_dc[u] = row[4]
                    base_do[u] = row[5]
        miss_topics = np.full((Bm, L), I.PAD, np.int32)
        miss_lens = np.zeros(Bm, np.int32)
        miss_dollar = np.zeros(Bm, bool)
        # pad = Bp (out of range for the [Bp]-wide base arrays): dropped
        # by the device scatter. NOT -1 — jax wraps negative indices
        # before the bounds check, which would clobber unique row Bp-1
        # with the empty pad match whenever Bu == Bp
        miss_pos = np.full(Bm, Bp, np.int32)
        if n_miss:
            src = first_idx[miss_u]
            miss_topics[:n_miss] = encf[src]
            miss_lens[:n_miss] = lenf[src]
            miss_dollar[:n_miss] = dolf[src]
            miss_pos[:n_miss] = miss_u
        from emqx_tpu.models.router_engine import WindowPlan
        plan = _CachePlan(
            WindowPlan(miss_topics, miss_lens, miss_dollar, base_m, base_c,
                       base_o, miss_pos,
                       inv.reshape(Wp, Bp).astype(np.int32)),
            (base_dm, base_dc, base_do) if ov is not None else None,
            Bm, n_miss, n_hit)
        # telemetry is recorded ONLY for engaged plans, so the exported
        # dedup ratio / hit rate describe match work actually removed
        # from dispatches — not lookups whose window went plain (those
        # would inflate the attribution the counters exist to ground)
        tele = getattr(self.node, "pipeline_telemetry", None)
        if tele is not None and real:
            tele.record_dedup(real, uniq_real)
        if cache is not None:
            cache.count_lookups(n_hit, n_miss)
        return plan, info

    # window sub-batch count classes: each (W, Bp) pair is one XLA
    # compile; quantizing W the same way as the batch axis keeps the
    # compile count bounded (empty padding sub-batches match nothing)
    _W_CLASSES = (1, 8)

    @staticmethod
    def _tables_sig(tables) -> tuple:
        """Shape signature of a device table pytree: the jit cache key's
        shape component. Fusion readiness is tracked PER SIGNATURE — a
        snapshot whose capacity classes differ from the warmed one would
        otherwise cold-compile the window program on the serving path."""
        import jax
        return tuple(tuple(x.shape) for x in jax.tree.leaves(tables))

    def max_fuse(self) -> int:
        """How many batches the serving path may fuse per dispatch right
        now: 1 until the CURRENT snapshot's fused window class is warm,
        then the largest class (either backend: the window programs
        scan the trie NFA's step as they do the shape hash's)."""
        W, Bp = self._STD_CLASSES[-1]
        if self._built is None \
                or self._class_of(W, Bp) not in self._warm_classes:
            return 1
        return W

    def _caps_kw(self, backend: str) -> dict:
        """The static caps a route program of `backend` is traced with
        (the trie NFA's own only where the trie matches)."""
        kw = dict(fanout_cap=self.fanout_cap, slot_cap=self.slot_cap)
        if backend != "shapes":
            kw.update(frontier_cap=self.frontier_cap,
                      match_cap=self.match_cap)
        return kw

    def _batch_class(self, n_msgs: int) -> int:
        """Quantize a batch size onto the standard Bp ladder (derived
        from _STD_CLASSES), or the next pow2 beyond it."""
        for _w, Bp in self._STD_CLASSES:
            if _w == 1 and n_msgs <= Bp:
                return Bp
        return _next_pow2(n_msgs)

    def batch_class_warm(self, n_msgs: int) -> bool:
        """True when a single batch of n_msgs would dispatch into an
        already-compiled (1, Bp) class for the CURRENT snapshot — the
        batcher routes host-side (and kicks the background warm)
        otherwise, so serving never stalls on an XLA compile."""
        if self._built is None:
            return False
        Bp = self._batch_class(n_msgs)
        c = self._class_of(1, Bp)
        if c in self._warm_classes:
            return True
        if Bp > self._STD_CLASSES[-1][1]:
            # oversized batch class (max_publish_batch > 1024): queue it
            # for the background warm, or it would be locked out forever
            self._wanted.add(c)
        return False

    _STD_CLASSES = ((1, 64), (1, 256), (1, 1024), (8, 1024))

    def _std_classes(self, sig: tuple) -> list:
        """The standard ladder as classes of the snapshot signed `sig`."""
        return [_WindowClass(sig, W, Bp) for W, Bp in self._STD_CLASSES]

    # payload classes are multiples of the batch class Bp (entries per
    # message budget): 8 covers trickle fan-out, 32 the fan-out ≤ ~10
    # regime the motivation targets, 128 heavy fan-out. Beyond 128 the
    # compacted payload approaches the dense planes and compaction stops
    # paying — the chooser returns None (dense readback).
    _PAYLOAD_MULTS = (8, 32, 128)

    def _dense_msg_entries(self, b=None) -> int:
        """Dense readback cost per message lane in int32-equivalent
        entries: match plane + fan rows/opts + shared slot/row/opts."""
        b = b or self._built
        return b.match_width + 2 * self.fanout_cap + 3 * self.slot_cap

    def _choose_payload_cap(self, Bp: int) -> Optional[int]:
        """Payload class for a (·, Bp) dispatch, or None for dense.

        Sized by a peak-biased EWMA of recent per-window-row entry
        totals (adopts an upward sample outright, decays slowly — see
        _note_payload) with 2x headroom, quantized onto the
        _PAYLOAD_MULTS * Bp ladder so the compile-class count stays
        bounded. A window that still outgrows its class falls back to
        the dense readback of the SAME dispatch (row_overflow), so an
        undershoot costs bytes, never correctness. A class is held
        against a smaller one until that one would hold the EWMA with
        a quarter to spare: an EWMA that sits on a step of the ladder
        (`umbrella-cover.flood`: 3,900-4,700 entries against 2 x 4,096)
        otherwise changes class every few windows, each class of every
        (W, plan) pair met cold once and warmed behind the traffic."""
        if not self.compact_readback or self._built is None:
            return None
        dense = self._dense_msg_entries()
        mults = [m for m in self._PAYLOAD_MULTS if m < dense]
        if not mults:
            return None         # tiny caps: nothing to compact away
        ew = self._pay_ewma.get(Bp)
        if ew is None:
            # no traffic measured at this class yet: start mid-ladder
            # (the first window's offsets seed the EWMA either way)
            return mults[min(1, len(mults) - 1)] * Bp
        held = self._pay_mult.get(Bp)
        for m in mults:
            if m * Bp >= 2.0 * ew and (
                    held is None or m >= held
                    or 0.75 * m * Bp >= 2.0 * ew):
                self._pay_mult[Bp] = m
                return m * Bp
        self._pay_mult.pop(Bp, None)
        return None             # sustained heavy fan-out: dense wins

    def _note_payload(self, Bp: int, totals: np.ndarray) -> None:
        """Feed the EWMA from one window's actual per-row entry totals
        (read from the offsets plane — available on the overflow
        fallback too, which is exactly when learning matters most)."""
        s = float(totals.max()) if totals.size else 0.0
        ew = self._pay_ewma.get(Bp)
        # peak-biased: adopt growth immediately (the next window must
        # not overflow again), decay shrinkage slowly (a lull must not
        # trigger a class downshift and an overflow on the next burst)
        self._pay_ewma[Bp] = s if (ew is None or s > ew) \
            else 0.8 * ew + 0.2 * s

    def _gate_compact(self, Wp: int, Bp: int, plan, gate_cold: bool,
                      ov: Optional[_Overlay] = None) -> Optional[int]:
        """Choose + warm-gate the payload class for one dispatch.
        Returns the class, or None to read back dense (compaction off,
        unprofitable, or the class is cold on the serving path)."""
        pcap = self._choose_payload_cap(Bp)
        if pcap is None:
            return None
        if self._cold(self._class_of(Wp, Bp, plan, ov, pcap), gate_cold,
                      "routing.device.cold_compact_class"):
            return None
        return pcap

    def _class_of(self, Wp: int, Bp: int, plan=None,
                  ov: Optional[_Overlay] = None,
                  P: Optional[int] = None) -> _WindowClass:
        """The class of the CURRENT snapshot that a (Wp, Bp) window
        with these stages (a `_CachePlan`, the pinned `_Overlay`, a
        payload class) dispatches into."""
        return _WindowClass(self._cur_sig, Wp, Bp,
                            plan.Bm if plan is not None else None,
                            ov.cap if ov is not None else None, P)

    def _cold(self, c: _WindowClass, gate_cold: bool, counter: str) -> bool:
        """True where dispatching class `c` would stall the serving
        path on an in-path XLA compile: the window then runs without
        the stage that asked (its warm fallback: the plain match, the
        host delta trie, the dense readback), counted under `counter`,
        and the class is registered for the background warm to bring
        online (same policy as batch_class_warm)."""
        if not gate_cold or c in self._warm_classes:
            return False
        self._wanted.add(c)
        self._kick_class_warm()
        self.node.metrics.inc(counter)
        return True

    def _window_call(self, c: _WindowClass, b: _Built, live=None) -> tuple:
        """`route_window`'s arguments after (tables, cursors), and its
        statics, for class `c` of snapshot `b`: from a live window
        (`live` = (handle, msg_hash, strategy)) or, for a warm pass or
        a probe, zero-filled of the class's shapes (all that matters
        to the trace). The one place that knows how a class maps onto
        the program, and the rule that goes with it: numpy and device
        arguments do not share a jit fast-path entry, so a dummy is a
        device array exactly where the live path passes one (the
        overlay's tables, `device_put` by _overlay_sync_inner) and
        numpy everywhere else, or the first live window of the class
        would re-trace in-path."""
        from emqx_tpu.models.router_engine import WindowDelta, WindowPlan
        from emqx_tpu.ops.shared import STRATEGY_ROUND_ROBIN
        L = self.max_levels
        if live is not None:
            h, msg_hash, strat = live
            lanes = h.enc
            plan, dbase = (h.plan.dev, h.plan.dbase) \
                if h.plan is not None else (None, None)
            dev = h.delta.dev if h.delta is not None else None
        else:
            lanes = (np.zeros((c.W, c.Bp, L), np.int32),
                     np.zeros((c.W, c.Bp), np.int32),
                     np.zeros((c.W, c.Bp), bool))
            msg_hash = np.zeros((c.W, c.Bp), np.int32)
            strat = np.int32(STRATEGY_ROUND_ROBIN)
            plan = dbase = dev = None
            if c.Bm is not None:
                plan = WindowPlan(
                    np.full((c.Bm, L), I.PAD, np.int32),
                    np.zeros(c.Bm, np.int32), np.zeros(c.Bm, bool),
                    np.full((c.Bp, b.match_width), -1, np.int32),
                    np.zeros(c.Bp, np.int32), np.zeros(c.Bp, bool),
                    np.full(c.Bm, c.Bp, np.int32),   # pad = Bp: dropped
                    np.zeros((c.W, c.Bp), np.int32))
                if c.dC is not None:
                    dbase = (np.full((c.Bp, _DELTA_MATCH_CAP), -1, np.int32),
                             np.zeros(c.Bp, np.int32), np.zeros(c.Bp, bool))
            if c.dC is not None:
                from emqx_tpu.ops.delta import empty_delta_tables
                # an all-empty table of the row class is the cheapest
                # valid instance
                # hbm: transient — freed when the call it feeds returns
                dev = jax.device_put(empty_delta_tables(
                    c.dC, L, fan_per_row=_DELTA_FAN_PER_ROW))
        if plan is not None:
            lanes = (None, None, None)      # the plan holds the lanes
        kw = self._caps_kw(b.backend)
        delta = None
        if dev is not None:
            delta = WindowDelta(dev, dbase)
            kw.update(delta_match_cap=_DELTA_MATCH_CAP,
                      delta_fanout_cap=_DELTA_FANOUT_CAP)
        if c.P is not None:
            kw["payload_cap"] = c.P
            if dev is not None:
                kw["d_payload_cap"] = self._delta_payload_cap(c.Bp)
        return lanes + (msg_hash, strat, plan, delta), kw

    def _run_window(self, c: _WindowClass, b: _Built, tables, cursors,
                    live=None):
        """One call of the route window program for class `c`: a live
        window's (`_dispatch_inner`), or the class's zero-filled one
        (the warm passes, the dispatch probe)."""
        from emqx_tpu.models.router_engine import route_window
        args, kw = self._window_call(c, b, live)
        return route_window(tables, cursors, *args, **kw)

    def _warm_class(self, c: _WindowClass, b: _Built, tables,
                    cursors) -> None:
        """Compile class `c` off the serving path, under its `warm`
        compile-context label."""
        tele = getattr(self.node, "pipeline_telemetry", None)
        ctx = tele.compile_context(f"warm {c.label}") \
            if tele is not None else contextlib.nullcontext()
        with ctx:
            r = self._run_window(c, b, tables, cursors)
            jax.block_until_ready(r.match_counts)
            self._last_cursors(r)

    def _kick_class_warm(self) -> None:
        """Warm every standard (W, Bp) class AND every demand-registered
        class (a plan's, an overlay's, a payload's, an oversized
        batch's) the CURRENT snapshot is missing, off the serving path.
        Re-kicks after a failure and after any swap to unwarmed
        capacity classes. Both backends alike: the gates hold each
        stage back until its class is warm."""
        import asyncio
        if self._fuse_warm_task is not None or self._built is None:
            return
        sig = self._cur_sig
        wanted = self._std_classes(sig) \
            + sorted((c for c in self._wanted if c.sig == sig),
                     key=lambda c: c.warm_order)
        missing = [c for c in wanted if c not in self._warm_classes]
        if not missing:
            return
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return
        b, tables, cursors = self._built, self._tables, self._cursors

        def warm():
            for c in missing:
                self._warm_class(c, b, tables, cursors)
                self._warm_classes.add(c)

        async def run():
            try:
                await loop.run_in_executor(None, warm)
            except Exception:  # noqa: BLE001 — classes stay cold, retry
                import logging
                logging.getLogger("emqx.device").exception(
                    "class warm-compile failed; affected classes stay "
                    "host-routed until the next attempt")
                self.node.metrics.inc("routing.device.warm_failed")
            finally:
                self._fuse_warm_task = None

        from emqx_tpu.broker.supervise import guard_task
        self._fuse_warm_task = guard_task(loop.create_task(run()),
                                          "device-class-warm",
                                          self.node.metrics)

    def preencode_burst(self, topics: list) -> None:
        """ISSUE 11: intern a read burst's topics in ONE vectorized
        native pass (split + hash + id-probe in C over the unique
        strings), memoized for prepare_window's encode. The memo is
        replaced wholesale per burst (no growth) and is only consumed
        while the intern table length is unchanged — intern ids are
        append-only, so equal length proves bit-identical encodings."""
        from emqx_tpu.ops.match import encode_topics_str
        uniq = list(dict.fromkeys(topics))
        try:
            enc, lens, dollar, too_long = encode_topics_str(
                self.intern, uniq, self.max_levels)
        except Exception:  # noqa: BLE001 — a failed pre-encode only
            self._burst_enc = None        # means the window re-encodes
            return
        self._burst_enc = ({t: i for i, t in enumerate(uniq)},
                           enc, lens, dollar, too_long,
                           len(self.intern))

    def _encode_publish_batch(self, topics: list):
        """One batch's topic encode: the burst memo's vectorized gather
        when every topic pre-encoded under the current intern length,
        else the normal one-native-call path (bit-identical outputs
        either way — the memo IS a cache of that call)."""
        from emqx_tpu.ops.match import encode_topics_str
        be = self._burst_enc
        if be is not None and be[5] == len(self.intern):
            idx_map, enc, lens, dollar, too_long = be[:5]
            idxs = [idx_map.get(t, -1) for t in topics]
            if -1 not in idxs:
                return (enc[idxs], lens[idxs], dollar[idxs],
                        too_long[idxs])
        return encode_topics_str(self.intern, topics, self.max_levels)

    def prepare_window(self, lives: list[list[Message]],
                       gate_cold: bool = True):
        """Stage 1 (event loop): encode 1..W micro-batches as one fused
        dispatch window (models.router_engine.route_window). The
        per-dispatch cost — dominant on high-latency links — is paid
        once for the whole window. When dedup is on, the window is also
        compacted to unique topics + match-cache hits (_plan_window) so
        the dispatch runs the NFA/shape hash only on miss lanes.

        `gate_cold=False` (sync callers: route_batch, tests, warmup)
        lets a cold cached class compile in-path instead of falling back
        to the plain program.

        Returns a _Handle, or None when the engine has no snapshot to
        serve (caller routes host-side; a background rebuild may be
        warming up).
        """
        with self.spans.span("prepare_window", meta={"W": len(lives)}):
            return self._prepare_window(lives, gate_cold)

    def _prepare_window(self, lives: list, gate_cold: bool):
        self.poll_rebuild()
        if self._built is None or not lives:
            return None
        self._kick_class_warm()
        b = self._built
        subs = []
        encs = []
        Bp = 64
        for msgs in lives:
            # one native call per batch (split+hash+probe in C) — or
            # the burst memo's gather when submit_burst pre-encoded
            # this burst's topics (ISSUE 11); word lists are tokenized
            # lazily in _consume_one only when the delta-trie path
            # actually needs them
            enc, lens, dollar, too_long = self._encode_publish_batch(
                [m.topic for m in msgs])
            subs.append((msgs, None, too_long))
            encs.append((enc, lens, dollar))
            Bp = max(Bp, self._batch_class(len(msgs)))
        if len(lives) > 1:
            # fused windows run ONLY in the warmed (W, Bp) top standard
            # class: any other pair would cold-compile on the serving
            # path (padding compute is the price of never stalling)
            Bp = max(Bp, self._STD_CLASSES[-1][1])
        for Wp in self._W_CLASSES:
            if len(lives) <= Wp:
                break
        else:
            Wp = _next_pow2(len(lives))
        W = len(lives)
        enc4 = np.full((Wp, Bp, self.max_levels), I.PAD, np.int32)
        len4 = np.zeros((Wp, Bp), np.int32)
        dol4 = np.zeros((Wp, Bp), bool)
        for k, (enc, lens, dollar) in enumerate(encs):
            n = enc.shape[0]
            enc4[k, :n] = enc
            len4[k, :n] = lens
            dol4[k, :n] = dollar
        h = _Handle(subs, b, self.device_shared_active())
        h.enc = (enc4, len4, dol4)
        # degradation ladder rung 1 (ISSUE 6): with the cache_insert or
        # overlay_apply breaker open, the reuse layers stand down and
        # this window dispatches the PLAIN program — device-plain is
        # the middle rung between full-featured and host-trie
        degraded = self.sup is not None and not self.sup.reuse_enabled()
        if not degraded:
            # delta overlay for this dispatch (None = host fallback for
            # post-snapshot filters, exactly the pre-overlay behavior)
            h.delta = self._gate_delta(Wp, Bp, gate_cold)
        if self.dedup and not degraded:
            h.plan, h.cache_info = self._plan_window(b, enc4, len4, dol4,
                                                     gate_cold, h.delta)
        if not degraded:
            # CSR readback class for this dispatch (None = dense)
            h.pcap = self._gate_compact(Wp, Bp, h.plan, gate_cold,
                                        h.delta)
        self._outstanding += 1
        if self.ledger is not None:
            # pin sentinel (ISSUE 8): this handle pins the snapshot —
            # a pin outliving pin_warn_windows prepared windows fires
            # the stale-pin warning (counter + hook + recorder event)
            self.ledger.note_window()
            self.ledger.pin(id(h), h)
        self.node.metrics.inc("routing.device.windows")
        # topics the match stage matches, whatever matcher the snapshot
        # has: every real lane or, under a dedup plan, its misses only
        lanes = h.plan.n_miss if h.plan is not None \
            else sum(len(msgs) for msgs in lives)
        self.node.metrics.inc("routing.device.match_lanes", lanes)
        if b.backend != "shapes":
            # matched by the trie NFA (ops/match.match_batch)
            self.node.metrics.inc("routing.device.nfa_windows")
            self.node.metrics.inc("routing.device.nfa_lanes", lanes)
        # sub-batches held against the class's W: 1 - subs / slots is
        # the share of scan steps the window program skipped as padding
        self.node.metrics.inc("routing.device.window_subs", W)
        self.node.metrics.inc("routing.device.window_slots", Wp)
        b = self._built
        if b is not None and b.cover is not None:
            # windows matched against the covering set (expansion fused
            # after the match stage), and the per-window match-work
            # saved: covered filters the root match never visited
            self.node.metrics.inc("pipeline.cover.windows")
            self.node.metrics.inc("pipeline.cover.filters_skipped",
                                  b.cover.n_covered)
        tele = getattr(self.node, "pipeline_telemetry", None)
        if tele is not None:
            # batch occupancy per shape class: how much of the padded
            # (Wp, Bp) program each dispatch actually fills — low fill
            # means padding compute dominates (shrink the window /
            # batch class), high fill means the class is saturated
            for msgs in lives:
                tele.record_occupancy(f"b{Bp}", len(msgs) / Bp)
            if Wp > 1:
                tele.record_occupancy(f"w{Wp}", W / Wp)
        return h

    # ---- device-side tracing (SURVEY §5.1 mapping) -------------------
    def start_device_trace(self, log_dir: str) -> bool:
        """Begin a jax.profiler trace capturing the device-side route
        steps (each dispatch is annotated as one profiler step, so the
        trace decomposes device execution from host time). Returns
        False when the backend has no profiler support."""
        try:
            jax.profiler.start_trace(log_dir)
            return True
        except Exception:  # noqa: BLE001 — a backend may lack it
            return False

    def stop_device_trace(self) -> None:
        try:
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001 — no session to stop
            pass

    # ---- ISSUE 9: async readback helpers -------------------------------
    def _readback_planes(self, h) -> list:
        """The device arrays materialize will transfer for this handle
        — exactly those, so the async start never wastes link bandwidth
        on planes the CSR compaction made redundant (a later overflow
        fallback to the dense planes still transfers synchronously;
        correctness never depends on the prefetch)."""
        out = []
        res, cp = h.res, h.cres
        dp, dcp = h.dres, h.dcres
        if dp is not None:
            out += [dp.counts, dp.moverflow, dp.overflow]
            if dcp is not None:
                out += [dcp.offsets, dcp.counts3, dcp.row_overflow,
                        dcp.payload]
            else:
                out += [dp.fids, dp.rows, dp.opts]
        if cp is not None:
            out += [cp.offsets, cp.counts3, cp.row_overflow, cp.payload,
                    res.overflow, res.occur]
        else:
            out += [res.matches, res.rows, res.opts, res.shared_sids,
                    res.shared_rows, res.shared_opts, res.overflow,
                    res.occur]
            if h.cache_info is not None and self._match_cache is not None:
                out.append(res.match_counts)
        for counted in (res.nfa_wide_steps, res.cover_candidates,
                        res.cover_roots):
            if counted is not None:
                out.append(counted)
        return out

    def _start_readback(self, h) -> None:
        """ISSUE 9: start the device→host transfer of every plane
        materialize will read, AT DISPATCH RETURN — the readback
        crosses the link while dispatch(W+1) computes, and materialize
        becomes consume-on-arrival. The in-flight result buffers
        register with the HBM ledger under `pipeline_buffers` (they are
        pinned HBM for up to dispatch_depth windows; release is
        automatic when the handle dies). Backends without async copies
        keep the synchronous transfer in materialize — the prefetch is
        an overlap optimization, never a correctness input."""
        if self.ledger is not None:
            self._hold("pipeline_buffers", h.res)
        for a in self._readback_planes(h):
            try:
                a.copy_to_host_async()
            except AttributeError:
                return      # backend has no async copy: sync readback
            except Exception:  # noqa: BLE001 — best-effort prefetch
                return

    def dispatch(self, h) -> None:
        """Stage 2 (executor thread): run the jitted route step — an
        async enqueue, off the event loop either way. Under an
        active jax.profiler trace every dispatch is one annotated step.
        The span lands in the `dispatch` stage histogram — or
        `dispatch_cached` for a deduplicated/cache-backed dispatch, so
        the cached-vs-uncached match latency split is directly
        comparable in the exported percentiles; any jit-cache miss
        inside it is attributed to this window's (W, B[, Bm]) class as
        an IN-PATH recompile (the kind the warm gates exist to
        prevent)."""
        tele = getattr(self.node, "pipeline_telemetry", None)
        Wp, Bp = h.enc[0].shape[0], h.enc[0].shape[1]
        cached = h.plan is not None
        with self.spans.span(
                "dispatch", h.trace,
                stage="dispatch_cached" if cached else "dispatch",
                track="dispatch",
                meta={"W": Wp, "B": Bp, "cached": cached}):
            if tele is not None:
                label = f"dispatch W{Wp}xB{Bp}mB{h.plan.Bm}cached" \
                    if cached else f"dispatch W{Wp}xB{Bp}"
                with tele.compile_context(label):
                    self._dispatch_annotated(h)
            else:
                self._dispatch_annotated(h)
            if self._pipelined and h.res is not None:
                # ISSUE 9: start the async readback while this thread
                # still owns the dispatch slot — the transfer hides
                # under the NEXT window's dispatch
                self._start_readback(h)

    def _dispatch_annotated(self, h) -> None:
        # the step_num IS the window's flight-recorder trace id
        # (ISSUE 7): the device timeline of any jax.profiler capture
        # joins the host-side spans on the same key (0 for a window
        # that carries no trace)
        with jax.profiler.StepTraceAnnotation("route_step",
                                              step_num=h.trace):
            self._dispatch_inner(h)

    def _msg_hashes(self, msgs, strat_id) -> list[int]:
        from emqx_tpu.ops.shared import (STRATEGY_HASH_CLIENT,
                                         STRATEGY_HASH_TOPIC,
                                         STRATEGY_ROUND_ROBIN)
        if strat_id == STRATEGY_HASH_TOPIC:
            return [zlib.crc32(m.topic.encode()) & 0x7FFFFFFF
                    for m in msgs]
        if strat_id == STRATEGY_HASH_CLIENT:
            return [zlib.crc32((m.from_ or "").encode()) & 0x7FFFFFFF
                    for m in msgs]
        if strat_id == STRATEGY_ROUND_ROBIN:
            return [0] * len(msgs)
        return [(id(m) >> 4) & 0x7FFFFFFF for m in msgs]  # random

    def _dispatch_inner(self, h) -> None:
        """Run the route window program for this window's class: the
        plain window, with up to three optional fused stages — dedup
        plan (ISSUE 2), CSR readback (ISSUE 3), delta overlay
        (ISSUE 4) — each independently warm-gated at prepare."""
        if self.sup is not None:
            # ISSUE 6 injection point: an exception here propagates to
            # the batcher's consumer, which notes the fault, replays the
            # window host-side and advances the dispatch breaker; a hang
            # is caught by the consumer's watchdog deadline
            self.sup.fire("dispatch")
        from emqx_tpu.ops.shared import (STRATEGIES, STRATEGY_ROUND_ROBIN)
        broker = self.broker
        # pin the table/cursor pair ONCE for this whole dispatch: a
        # watchdog timeout (ISSUE 6) abandons the handle while this
        # thread is still running, which releases the swap gate — a
        # zombie dispatch must neither mix old and new tables mid-call
        # nor clobber the new snapshot's cursors with a late write (the
        # identity guard at the end, mirroring the mesh's `_builts is
        # h.built` discipline in parallel/serving.py)
        tables, cursors = self._tables, self._cursors
        enc4, len4, dol4 = h.enc
        Wp, Bp = enc4.shape[0], enc4.shape[1]
        c = self._class_of(Wp, Bp, h.plan, h.delta, h.pcap)
        strat_id = STRATEGIES.get(broker.shared_strategy,
                                  STRATEGY_ROUND_ROBIN)
        msg_hash = np.zeros((Wp, Bp), np.int32)
        for k, (msgs, _w, _t) in enumerate(h.subs):
            msg_hash[k, :len(msgs)] = self._msg_hashes(msgs, strat_id)
        res = self._run_window(c, h.built, tables, cursors,
                               live=(h, msg_hash, np.int32(strat_id)))
        if h.plan is not None:
            # deduplicated dispatch: matched the miss lanes only, merged
            # with the cache-hit base rows, scattered back to window
            # width before the cursor-dependent post stage
            self.node.metrics.inc("routing.device.cached_windows")
        h.dres, h.cres, h.dcres = res.delta, res.compact, res.d_compact
        if self._tables is tables:   # no swap raced this dispatch
            self._cursors = self._hold("snapshot_cursors",
                                       self._last_cursors(res))
        self._warm_classes.add(c)
        h.res = res

    @staticmethod
    def _last_cursors(res):
        """The cursors a dispatch adopts: the window's last row. This is
        an eager slice, jit-compiled once per [W, G] shape — the
        standard-class warm passes run it too, so the first live
        dispatch of a class does not compile it in the dispatch path."""
        return res.new_cursors[-1]

    def _materialize_delta(self, h) -> int:
        """Read back the delta-overlay planes (when this dispatch fused
        the overlay): the small count/overflow planes always, plus
        either the delta CSR payload or — on delta payload overflow, or
        without a payload class — the dense fid/row/opts planes of the
        same program. Returns the transferred byte count (billed into
        the window's readback bucket by the caller)."""
        dp = h.dres
        if dp is None:
            return 0
        counts = np.asarray(dp.counts)
        mov = np.asarray(dp.moverflow)
        ovf = np.asarray(dp.overflow)
        nbytes = counts.nbytes + mov.nbytes + ovf.nbytes
        dcp = h.dcres
        if dcp is not None:
            off = np.asarray(dcp.offsets)
            c3 = np.asarray(dcp.counts3)
            rovf = np.asarray(dcp.row_overflow)
            nbytes += off.nbytes + c3.nbytes + rovf.nbytes
            if rovf.any():
                self.node.metrics.inc(
                    "routing.device.delta_compact_overflow")
                dcp = None      # dense delta planes below
            else:
                pay = np.asarray(dcp.payload)
                nbytes += pay.nbytes
                h.np_delta = _DeltaCsr(off, c3, pay, counts, mov, ovf)
                return nbytes
        fids = np.asarray(dp.fids)
        rows = np.asarray(dp.rows)
        opts = np.asarray(dp.opts)
        nbytes += fids.nbytes + rows.nbytes + opts.nbytes
        h.np_delta = _DeltaRes(fids, counts, mov, rows, opts, ovf)
        return nbytes

    def _delta_cache_fields(self, h, lane: int, Bp: int) -> tuple:
        """Fields 3.. of a match-cache row under the delta overlay:
        (delta fids, delta count, MATCH-level delta overflow, encoded
        topic, len, is_dollar) — the overlay base triple in FID space
        (stable across overlay row reassignment) plus the topic encoding
        the delta-aware invalidation matches against. Empty () with the
        overlay knob off, so the pre-overlay 3-tuple rows (and their
        tests) are bit-exact."""
        if not self.delta_overlay:
            return ()
        enc4, len4, dol4 = h.enc
        w, bb = divmod(lane, Bp)
        topic = (enc4[w, bb].copy(), int(len4[w, bb]),
                 bool(dol4[w, bb]))
        nd = h.np_delta
        if nd is None:
            if self._delta_filter:
                # overlay exists but this dispatch ran without it (cold
                # class): the delta part of this topic is UNKNOWN — a
                # None marker keeps the main row usable while making the
                # row ineligible as a cached delta base (_plan_window)
                return (None, 0, False) + topic
            dm = np.full(_DELTA_MATCH_CAP, -1, np.int32)
            return (dm, 0, False) + topic
        if isinstance(nd, _DeltaCsr):
            o = int(nd.off[w, bb])
            cm = int(nd.c3[w, bb, 0])
            dm = np.full(_DELTA_MATCH_CAP, -1, np.int32)
            dm[:cm] = nd.pay[w, o:o + cm]
        else:
            dm = nd.fids[w, bb].copy()
        return (dm, int(nd.counts[w, bb]), bool(nd.moverflow[w, bb])) \
            + topic

    def materialize(self, h) -> None:
        """Stage 3 (executor thread): blocking device→host readbacks.
        Every field is [W, ...] (window-stacked). Also the match-cache
        population point: the rows for this window's cache-missed unique
        topics come straight out of the readback the consume stage needs
        anyway — no extra device round trip.

        With a payload class attached (h.cres — ISSUE 3) the transfer is
        the CSR planes (offsets + counts3 + flat payload) plus the small
        overflow/occur planes, instead of the padded match/fan-out/shared
        planes: >90% of the dense transfer is `-1` padding at low
        fan-out. A window whose entries outgrew its payload class reads
        the dense planes of the SAME dispatch instead (they are outputs
        of the same fused program — the fallback re-dispatches nothing).
        Both paths meter actual transferred bytes into the
        pipeline.readback.* counters all four exporters carry."""
        with self.spans.span("materialize", h.trace,
                             track="materialize"):
            self._materialize(h)

    def _materialize(self, h) -> None:
        metrics = self.node.metrics
        corrupt = None
        if self.sup is not None:
            # ISSUE 6 injection point (executor thread): exceptions
            # propagate to the consumer (fault noted + window replayed
            # host-side), hangs are caught by its watchdog deadline,
            # and "corrupt" shape-corrupts the readback below — the
            # consume stage then blows up exactly like a real
            # wrong-shape transfer would, and the supervisor's replay
            # path must recover the window
            corrupt = self.sup.fire("materialize", corrupt_ok=True)
        res = h.res
        cp = h.cres
        self._count_nfa_steps(h)
        if res.cover_candidates is not None:
            # a covering snapshot's window: the candidates the
            # expansion verified and the matched roots they came from,
            # a [W] plane each
            metrics.inc("routing.device.cover_candidates",
                        int(np.asarray(res.cover_candidates).sum()))
            metrics.inc("routing.device.cover_roots",
                        int(np.asarray(res.cover_roots).sum()))
        delta_bytes = self._materialize_delta(h)
        csr_probe_bytes = 0
        if cp is not None:
            off = np.asarray(cp.offsets)
            c3 = np.asarray(cp.counts3)
            rovf = np.asarray(cp.row_overflow)
            # EWMA learns from the offsets either way — on the overflow
            # fallback the totals are exactly what resizes the class up
            self._note_payload(off.shape[1] - 1, off[:, -1])
            if rovf.any():
                metrics.inc("routing.device.compact_overflow")
                # the CSR probe planes already crossed the link; bill
                # them to the dense window below or the exported
                # reduction overstates exactly the overflowing workloads
                csr_probe_bytes = off.nbytes + c3.nbytes + rovf.nbytes
                h.cres = None           # dense readback below
            else:
                overflow = np.asarray(res.overflow)
                occur = np.asarray(res.occur)
                pay = np.asarray(cp.payload)
                h.np_res = _CsrRes(off, c3, pay, overflow, occur)
                self._read_overflow_stages(h, overflow)
                metrics.inc("pipeline.readback.bytes.compact",
                            off.nbytes + c3.nbytes + pay.nbytes
                            + overflow.nbytes + occur.nbytes
                            + delta_bytes)
                metrics.inc("pipeline.readback.windows.compact")
                info = h.cache_info
                if info is not None and self._match_cache is not None:
                    # cache population from the CSR view: a reconstructed
                    # row is the hole-compacted valid prefix + -1 pad.
                    # Equivalent to the dense row by the hole-insensitivity
                    # contract (ops/compact.py): fan-out/shared expansion
                    # and consume only see valid entries in order, and the
                    # stored count cm == match_counts for both backends.
                    mw = h.built.match_width
                    Bp = off.shape[1] - 1
                    o_flat = overflow.reshape(-1)
                    items = []
                    for key, lane in info.inserts:
                        w, bb = divmod(lane, Bp)
                        cm = int(c3[w, bb, 0])
                        row = np.full(mw, -1, np.int32)
                        row[:cm] = pay[w, off[w, bb]:off[w, bb] + cm]
                        items.append((key, (row, cm, bool(o_flat[lane]))
                                      + self._delta_cache_fields(h, lane,
                                                                 Bp)))
                    self._cache_put(info.sid, items,
                                    version=info.version)
                if corrupt:
                    self._corrupt_readback(h)
                return
        h.np_res = (np.asarray(res.matches), np.asarray(res.rows),
                    np.asarray(res.opts), np.asarray(res.shared_sids),
                    np.asarray(res.shared_rows), np.asarray(res.shared_opts),
                    np.asarray(res.overflow), np.asarray(res.occur))
        self._read_overflow_stages(h, h.np_res[6])
        dense_bytes = sum(a.nbytes for a in h.np_res) + csr_probe_bytes \
            + delta_bytes
        info = h.cache_info
        if info is not None and self._match_cache is not None:
            # the match_counts readback is only paid when there are rows
            # to insert — consume never reads it, so windows with no
            # cache work skip the extra [W, B] transfer entirely
            h.np_counts = np.asarray(res.match_counts)
            dense_bytes += h.np_counts.nbytes
            matches, overflow = h.np_res[0], h.np_res[6]
            Bp = matches.shape[1]
            mw = matches.shape[-1]
            mflat = matches.reshape(-1, mw)
            cflat = h.np_counts.reshape(-1)
            oflat = overflow.reshape(-1)
            # overflow cached as the COMBINED flag (match|fanout|slot):
            # all three are pure functions of (snapshot, topic), and
            # post_match re-ORs the fan-out/slot parts, so the merged
            # result stays bit-identical to a cold match
            self._cache_put(
                info.sid,
                [(k, (mflat[i].copy(), int(cflat[i]), bool(oflat[i]))
                  + self._delta_cache_fields(h, i, Bp))
                 for k, i in info.inserts], version=info.version)
        metrics.inc("pipeline.readback.bytes.dense", dense_bytes)
        metrics.inc("pipeline.readback.windows.dense")
        if corrupt:
            self._corrupt_readback(h)

    @staticmethod
    def _read_overflow_stages(h, overflow: np.ndarray) -> None:
        """Which stage flagged a window's flagged lanes: the NFA itself
        (frontier or match_cap; a trie window's), for
        routing.device.match_overflow, the fan-out stage, for
        routing.device.fanout_overflow, and a covering snapshot's
        expansion (candidates past `cand_cap`, or more verified matches
        than the row holds), for routing.device.cover_overflow. One
        more small plane each, and only when a lane was flagged at all:
        a window without overflow reads nothing."""
        if not overflow.any():
            return
        if h.built.backend != "shapes" \
                and h.res.match_overflow is not None:
            h.np_mov = np.asarray(h.res.match_overflow)
        if h.res.fanout_overflow is not None:
            h.np_fov = np.asarray(h.res.fanout_overflow)
        if h.res.cover_overflow is not None:
            h.np_cov = np.asarray(h.res.cover_overflow)

    def _count_nfa_steps(self, h) -> None:
        """How many level steps the NFA took for a trie window, and how
        many of them at a narrow width (ops/match.NARROW_WIDTHS): the
        program's [W] plane holds the steps that ran at `frontier_cap`.
        A plain window walks once for every sub-batch that holds a
        topic (`route_window` skips the step of any other), a
        match-cache plan once over its miss lanes."""
        wide = h.res.nfa_wide_steps
        if wide is None:        # a shape-hash program has no such plane
            return
        enc4, len4, _dol4 = h.enc
        walks = 1 if h.plan is not None \
            else int((len4 > 0).any(axis=1).sum())
        steps = walks * (enc4.shape[-1] + 1)
        self.node.metrics.inc("routing.device.nfa_steps", steps)
        self.node.metrics.inc("routing.device.nfa_narrow_steps",
                              steps - int(np.asarray(wide).sum()))

    def _note_host_fallback(self, h, k: int, i: int) -> None:
        """Lane i of sub-batch k goes to the host trie (too deep, or a
        device capacity overflowed): count it, and separately the lanes
        the NFA's own caps sent there, those whose narrow fan-out
        rows alone passed `fanout_cap` (a filter wider than the cap is
        not among them: it travels by reference) and those a covering
        snapshot's expansion flagged."""
        self.node.metrics.inc("routing.device.host_fallback")
        if h.np_mov is not None and h.np_mov[k][i]:
            self.node.metrics.inc("routing.device.match_overflow")
        if h.np_fov is not None and h.np_fov[k][i]:
            self.node.metrics.inc("routing.device.fanout_overflow")
        if h.np_cov is not None and h.np_cov[k][i]:
            self.node.metrics.inc("routing.device.cover_overflow")

    def _corrupt_readback(self, h) -> None:
        """Apply the injected corrupt-shape fault: truncate the window
        axis of the host views so the consume stage fails exactly like
        a real wrong-shape readback (an IndexError at the first plane
        access) — the supervisor's window replay must then re-route the
        window host-side with zero loss."""
        nr = h.np_res
        if isinstance(nr, _CsrRes):
            h.np_res = _CsrRes(nr.off[:0], nr.c3[:0], nr.pay[:0],
                               nr.overflow[:0], nr.occur[:0])
        elif nr is not None:
            h.np_res = tuple(a[:0] for a in nr)

    def _cache_put(self, sid, items, version=None) -> None:
        """Match-cache population with the cache_insert fault domain
        (ISSUE 6): under supervision a raising insert is CONTAINED —
        the cache is an optimization, so a cache bug must cost the
        reuse layer (breaker opens → rung 1, plain dispatches), never
        the window. Without supervision the exception propagates out of
        materialize exactly as before (dispatch_failed → host
        fallback)."""
        cache = self._match_cache
        if cache is None:
            return
        sup = self.sup
        if sup is None:
            cache.put_many(sid, items, version=version)
            return
        try:
            sup.fire("cache_insert")
            cache.put_many(sid, items, version=version)
        except Exception as e:  # noqa: BLE001 — contained fault domain
            sup.note_fault("cache_insert", e)
        else:
            sup.note_ok("cache_insert")

    def finish_sub(self, h, k: int, defer: bool = True) -> list[int]:
        """Stage 4 (event loop): consume sub-batch k of the window into
        deliveries. Releases one handle reference (deferred to plan
        completion when the lanes own the deliveries — the snapshot
        swap gate must cover in-flight lane work).

        The clean common case — local node, no delta/dirty filters, no
        shared involvement for the message beyond a member the device
        picked in a group that is as the snapshot has it — is consumed
        by ONE vectorized pre-pass over the whole sub-batch
        (_consume_batch_fast): the per-message Python walk over
        match/fan-out rows used to cost more than the entire host route
        (24ms vs 22ms per 1024-batch at 50k filters), which made the
        device unable to win e2e no matter how fast the chip was.

        With the delivery lanes active (ISSUE 5; `defer=True` and a
        DeliveryLanePool on the node), this stage only BUILDS the
        delivery plan: clean messages' rows, the device's `$share`
        picks among them (ISSUE 35), are bucketed into session-affine
        lanes; slow messages (too long, overflowed, overlay-matched, a
        rich or dirty filter, a group whose membership changed or that
        was created since the snapshot or that has a remote member,
        anything under a cluster or with uncovered delta filters)
        become ordered closures behind the plan's barrier
        (`pipeline.deliver.slow_msgs` / `.barriers`), and the returned
        LaneCounts is
        back-filled when the plan completes (the `deliver` stage
        histogram then measures plan construction; the delivery time
        itself lands in the per-lane deliver_lane{i} histograms).
        `defer=False` (sync callers: route_batch/finish) keeps the
        inline consume — counts are final on return."""
        with self.spans.span(
                "finish_sub",
                h.sub_traces[k] if h.sub_traces and k < len(h.sub_traces)
                else h.trace, stage="deliver", track="consume"):
            return self._finish_sub(h, k, defer)

    def _finish_sub(self, h, k: int, defer: bool):
        plan = None
        deferred = False
        try:
            nr = h.np_res
            msgs, words_list, too_long = h.subs[k]
            b = h.built
            csr = isinstance(nr, _CsrRes)
            if csr:
                overflow_k, occur_k = nr.overflow[k], nr.occur[k]
            else:
                (matches, rows, opts, shared_sids, shared_rows,
                 shared_opts, overflow, occur) = nr
                overflow_k, occur_k = overflow[k], occur[k]
            nd = h.np_delta
            d_counts_k = None
            if nd is not None:
                # a delta-plane overflow (match cap or fan cap) means
                # the message's post-snapshot matches are incomplete:
                # full host fallback, same contract as the main planes
                overflow_k = overflow_k | nd.overflow[k]
                d_counts_k = nd.counts[k]
            pending = self._delta_pending(h.delta)
            if h.dev_shared and b.n_slots:
                self._writeback_cursors(occur_k, b)
            metrics = self.node.metrics
            broker = self.broker
            if defer:
                pool = getattr(self.node, "deliver_lanes", None)
                if pool is not None and pool.active():
                    plan = pool.new_plan(msgs)  # None without a loop
                    if plan is not None:
                        plan.routed_device = True
                        # causal propagation (ISSUE 7): the plan
                        # carries its sub-batch's trace, so lane items
                        # record against the right window — and KEEP it
                        # across a lane-worker restart (queue items
                        # hold the plan, the plan holds the trace)
                        plan.trace = h.sub_traces[k] \
                            if h.sub_traces and k < len(h.sub_traces) \
                            else h.trace
            if csr:
                fast = self._consume_batch_fast_csr(
                    msgs, nr.off[k], nr.c3[k], nr.pay[k], too_long,
                    overflow_k, h.dev_shared, b, d_counts_k, pending,
                    plan=plan)
            else:
                fast = self._consume_batch_fast(
                    msgs, matches[k], rows[k], opts[k], shared_sids[k],
                    shared_rows[k], shared_opts[k], too_long, overflow_k,
                    h.dev_shared, b, d_counts_k, pending, plan=plan)
            dev_shared, ov = h.dev_shared, h.delta
            counts: list[int] = []
            for i, msg in enumerate(msgs):
                f_i = fast[i]
                if f_i is DEFERRED:
                    counts.append(0)      # back-filled at plan finalize
                    continue
                if f_i is not None:
                    counts.append(f_i)
                    continue
                if plan is not None:
                    # slow path under lanes: an ordered closure behind
                    # the plan's barrier — it runs with every prior
                    # fast delivery done and nothing overtaking, the
                    # exact interleaving of the inline loop
                    counts.append(0)
                    plan.add_slow(i, self._make_slow_fn(
                        h, k, i, msg, b, csr, nr, nd, words_list,
                        too_long, overflow_k, dev_shared, ov, pending))
                    continue
                if too_long[i] or overflow_k[i]:
                    self._note_host_fallback(h, k, i)
                    counts.append(broker._route(
                        msg, self.router.match(msg.topic)))
                    continue
                if csr:
                    # per-message CSR views: the valid entries of every
                    # plane in order, no pad — _consume_one's walk is
                    # layout-agnostic (it skips -1 and slices fan rows
                    # by the built segment lengths, which the payload's
                    # fan section concatenates exactly)
                    row6 = csr_slices(nr.off[k], nr.c3[k], nr.pay[k], i)
                else:
                    row6 = (matches[k][i], rows[k][i], opts[k][i],
                            shared_sids[k][i], shared_rows[k][i],
                            shared_opts[k][i])
                drow = None
                if nd is not None:
                    if isinstance(nd, _DeltaCsr):
                        drow = csr_slices(nd.off[k], nd.c3[k],
                                          nd.pay[k], i)[:3]
                    else:
                        drow = (nd.fids[k][i], nd.rows[k][i],
                                nd.opts[k][i])
                counts.append(self._consume_one(
                    msg, *row6,
                    words_list[i] if words_list is not None else None,
                    h.dev_shared, b, drow=drow, ov=h.delta,
                    pending=pending))
            metrics.inc("routing.device.batches")
            if plan is not None:
                out = LaneCounts(counts)
                out.plan = plan
                plan.target = out
                # the handle stays pinned until the lanes finish: slow
                # closures read live engine state against this snapshot,
                # and _try_swap must not rebase it under them
                plan.add_done_callback(lambda: self._release_one(h))
                pool.submit(plan)
                deferred = True
                return out
            return counts
        finally:
            if not deferred:
                self._release_one(h)

    def _make_slow_fn(self, h, k: int, i: int, msg, b, csr, nr, nd,
                      words_list, too_long, overflow_k, dev_shared,
                      ov, pending):
        """Build the deferred slow-path consume for one message (runs
        behind the plan barrier; the handle is pinned until then)."""
        def run() -> int:
            if too_long[i] or overflow_k[i]:
                self._note_host_fallback(h, k, i)
                return self.broker._route(
                    msg, self.router.match(msg.topic))
            if csr:
                row6 = csr_slices(nr.off[k], nr.c3[k], nr.pay[k], i)
            else:
                row6 = (nr[0][k][i], nr[1][k][i], nr[2][k][i],
                        nr[3][k][i], nr[4][k][i], nr[5][k][i])
            drow = None
            if nd is not None:
                if isinstance(nd, _DeltaCsr):
                    drow = csr_slices(nd.off[k], nd.c3[k],
                                      nd.pay[k], i)[:3]
                else:
                    drow = (nd.fids[k][i], nd.rows[k][i],
                            nd.opts[k][i])
            return self._consume_one(
                msg, *row6,
                words_list[i] if words_list is not None else None,
                dev_shared, b, drow=drow, ov=ov, pending=pending)
        return run

    def _consume_batch_fast(self, msgs, m_k, r_k, o_k, ss_k, sr_k, so_k,
                            too_long, overflow_k, dev_shared: bool, b,
                            d_counts_k=None, pending: bool = False,
                            plan=None):
        """Vectorized consume for provably-clean messages. Returns a list
        with per-message delivery counts, or None where the slow path
        must run. Clean requires, globally: standalone node (no cluster
        forward / cluster group sweep), no delta filters beyond the
        fused overlay (`pending`), no post-snapshot shared groups; per
        message: no too-long/overflow, no dirty/rich matched filter, no
        delta-overlay match, and no shared involvement but a pick the
        device made in a group the snapshot still describes
        (`_fast_deliver`)."""
        if (self.broker.cluster is not None or pending
                or self.new_slots_by_filter):
            return [None] * len(msgs)
        B = len(msgs)
        mask = m_k[:B] >= 0
        mi = np.nonzero(mask)[0]
        fids = m_k[:B][mask]
        reported = ss_k[:B] >= 0

        def fetch(row_msg, col):
            return r_k[row_msg, col], o_k[row_msg, col]

        def fetch_picks():
            at = np.nonzero(reported)   # message-major, `ss_row` order
            return at[0], ss_k[at], sr_k[at], so_k[at]

        return self._fast_deliver(msgs, mi, fids, too_long, overflow_k,
                                  reported.any(axis=1), fetch, fetch_picks,
                                  dev_shared, b, d_counts_k, plan=plan)

    def _consume_batch_fast_csr(self, msgs, off_k, c3_k, pay_k, too_long,
                                overflow_k, dev_shared: bool, b,
                                d_counts_k=None, pending: bool = False,
                                plan=None):
        """_consume_batch_fast over one window row's CSR planes: same
        clean-message proof and the same vectorized delivery walk, with
        the 2-D plane gathers replaced by flat payload gathers at each
        message's family base offsets."""
        if (self.broker.cluster is not None or pending
                or self.new_slots_by_filter):
            return [None] * len(msgs)
        B = len(msgs)
        cm = c3_k[:B, 0].astype(np.int64)
        cf = c3_k[:B, 1].astype(np.int64)
        cs = c3_k[:B, 2].astype(np.int64)
        base = off_k[:B].astype(np.int64)
        total_m = int(cm.sum())
        mi = np.repeat(np.arange(B), cm)
        if total_m:
            mcum = np.cumsum(cm) - cm
            fids = pay_k[np.arange(total_m) - np.repeat(mcum, cm)
                         + np.repeat(base, cm)]
        else:
            fids = np.zeros(0, np.int32)
        fbase = base + cm           # fan rows start, per message
        obase = base + cm + cf      # fan opts start, per message

        def fetch(row_msg, col):
            return (pay_k[fbase[row_msg] + col],
                    pay_k[obase[row_msg] + col])

        def fetch_picks():
            # the shared family: `cs` slots (the valid ones, a prefix
            # of the plane), then as many picked sids, then as many
            # packed opts (ops/compact.csr_slices)
            n = np.repeat(cs, cs)
            at = np.repeat(base + cm + 2 * cf, cs) + np.arange(n.size) \
                - np.repeat(np.cumsum(cs) - cs, cs)
            return (np.repeat(np.arange(B), cs), pay_k[at],
                    pay_k[at + n], pay_k[at + 2 * n])

        return self._fast_deliver(msgs, mi, fids, too_long, overflow_k,
                                  cs > 0, fetch, fetch_picks, dev_shared, b,
                                  d_counts_k, plan=plan)

    @staticmethod
    def _attribute_rows(mi_f, fids_f, seg, total: int, plane_seg=None):
        """Row attribution shared by the inline loop and the lane plan:
        within each message the fan-out rows are the concatenation of
        per-filter CSR segments in match order. Returns (row_msg, col,
        row_fid, row_local): for every fan-out row, its message index,
        its column within that message's fan-out PLANE, the filter it
        came from and its place in that filter's segment. `plane_seg`
        is what each match's segment takes up of the plane where that
        is not `seg`: 0 for a segment that travelled by reference,
        whose rows are in no plane (their `col` is not a place to
        read) and are read at `row_local` of the snapshot's own CSR."""
        csum = np.cumsum(seg) - seg                # global exclusive
        starts = np.flatnonzero(np.r_[True, mi_f[1:] != mi_f[:-1]])
        row_msg = np.repeat(mi_f, seg)
        row_local = np.arange(total) - np.repeat(csum, seg)
        row_fid = np.repeat(fids_f, seg)
        # a match's offset in its message's plane: the expanded
        # segments before it
        psum = csum if plane_seg is None \
            else np.cumsum(plane_seg) - plane_seg
        base = np.repeat(psum[starts], np.diff(np.r_[starts,
                                                     mi_f.size]))
        col = np.repeat(psum - base, seg) + row_local
        return row_msg, col, row_fid, row_local

    def _fast_rows(self, mi_f, fids_f, fetch, b):
        """Every fan-out row of the clean messages' matches (`mi_f`,
        `fids_f`: message index and filter id, in match order), as
        (row_msg, sid, opt, row_fid) arrays in delivery order and
        whether any segment was wide, or (None, False) when there is
        no row. A narrow segment's rows come from the device planes
        through `fetch`; a segment wider than `fanout_cap` travelled by
        reference and is read from the snapshot's own CSR, at its place
        in match order."""
        seg = b.seg_np[fids_f]
        total = int(seg.sum())
        if not total:
            return None, False
        wide = seg > self.fanout_cap
        any_wide = bool(wide.any())
        row_msg, col, row_fid, row_local = self._attribute_rows(
            mi_f, fids_f, seg, total,
            np.where(wide, 0, seg) if any_wide else None)
        if not any_wide:
            sid, opt = fetch(row_msg, col)
        else:
            by_ref = np.repeat(wide, seg)
            narrow = ~by_ref
            at = b.sub_start[row_fid[by_ref]] + row_local[by_ref]
            sid = np.empty(total, np.int32)
            opt = np.empty(total, np.int32)
            sid[by_ref], opt[by_ref] = b.sub_row[at], b.sub_opts[at]
            sid[narrow], opt[narrow] = fetch(row_msg[narrow], col[narrow])
            metrics = self.node.metrics
            metrics.inc("routing.device.wide_segments", int(wide.sum()))
            metrics.inc("routing.device.wide_rows", len(at))
        valid = sid >= 0
        return (row_msg[valid], sid[valid], opt[valid],
                row_fid[valid]), any_wide

    def _with_picked_rows(self, rows, picked, fast_ok, b):
        """The plan's rows with the device's `$share` picks among them:
        one row a reported slot of a clean message whose group has a
        member, (message, picked sid, its packed opts, the fid of the
        slot's filter), after the message's plain rows. Returns (rows,
        slot): the slot of every row's group, -1 on a plain one."""
        p_msg, p_slot, p_sid, p_opt = picked
        keep = fast_ok[p_msg] & (p_sid >= 0)
        if not keep.any():
            return rows, None
        p_msg, p_slot = p_msg[keep], p_slot[keep].astype(np.int64)
        self.node.metrics.inc("routing.device.shared_lane_rows",
                              len(p_msg))
        p_rows = (p_msg, p_sid[keep], p_opt[keep], b.slot_fid[p_slot])
        if rows is None:
            return p_rows, p_slot
        # both are in message order: a stable sort on the message keeps
        # a message's plain rows ahead of its picks
        order = np.argsort(np.concatenate((rows[0], p_msg)),
                           kind="stable")
        slot = np.concatenate((np.full(len(rows[0]), -1, np.int64),
                               p_slot))[order]
        return tuple(np.concatenate((a, p))[order]
                     for a, p in zip(rows, p_rows)), slot

    def _fast_deliver(self, msgs, mi, fids, too_long, overflow_k,
                      shared_any, fetch, fetch_picks, dev_shared: bool, b,
                      d_counts_k=None, plan=None):
        """Shared tail of the vectorized fast consume (dense and CSR):
        per-message clean proof, row attribution, and delivery. `mi`/
        `fids` list every valid match (message index, filter id) in
        match order; `fetch(row_msg, col)` gathers the (sid, packed
        opts) of fan-out entry `col` within message `row_msg`;
        `shared_any` marks the messages for which the device reports a
        `$share` slot and `fetch_picks()` gathers those reports, message by
        message in the planes' order: (message index, slot, picked sid,
        packed opts).

        With `plan` attached (ISSUE 5: deliver lanes active) this stops
        looping entirely: the gathered (row_msg, sid, opt, fid) arrays
        are handed to the plan, which buckets them into session-affine
        lane slices — delivery (and the no-subscriber bookkeeping for
        these messages) then overlaps the next window's dispatch. A
        member the device picked is such a row too (ISSUE 35), after
        its message's plain rows as `_consume_one` delivers it, where
        the group is as the snapshot describes it.
        `plan=None` is the inline A/B baseline (deliver_lanes=0 or no
        running loop): the per-row loop below, unchanged semantics,
        every shared message through `_consume_one`."""
        broker = self.broker
        B = len(msgs)
        # per-fid host-side mask, memoized on (snapshot, dirty version)
        hostside = self._hostside_mask(b)

        slow = np.asarray(too_long[:B]) | (overflow_k[:B] != 0)
        if d_counts_k is not None:
            # overlay-matched messages walk the slow path (delta fan-out
            # is per-filter segmented like the main rows, but mixing the
            # two fid spaces into one vectorized gather isn't worth the
            # complexity for the churn tail — only DELTA-matched lanes
            # pay, everything else stays fast)
            slow |= d_counts_k[:B] > 0
        if fids.size:
            # where the host picks, a filter with a group is the host's;
            # where the device did, what it reports below says it all
            np.logical_or.at(slow, mi, hostside[fids] if dev_shared
                             else hostside[fids] | b.fid_shared[fids])
        picked = None
        if dev_shared and shared_any.any():
            # a remote member in the snapshot with no cluster to forward
            # to (torn down since the build) leaves the pick to the
            # host: for the whole snapshot, so that a topic's messages
            # stay on one side of the barrier
            if plan is None or b.remote_members:
                slow |= shared_any
            else:
                picked = fetch_picks()
                if self.dirty_slots:
                    # membership changed since the snapshot: the host's
                    stale = np.zeros(b.n_slots, bool)
                    stale[[b.slot_of[key] for key in self.dirty_slots
                           if key in b.slot_of]] = True
                    p_msg, p_slot = picked[:2]
                    slow[p_msg[stale[p_slot]]] = True

        out: list = [None] * B
        fast_ok = ~slow
        if not fast_ok.any():
            return out
        keep = fast_ok[mi]
        rows, any_wide = self._fast_rows(mi[keep], fids[keep], fetch, b)
        if plan is not None:
            # lane hand-off: one gather pass, zero Python per-row work
            # here — the lanes deliver these messages off this stage
            fast_idx = np.flatnonzero(fast_ok)
            plan.register_fast(fast_idx)
            slot = None
            if picked is not None:
                rows, slot = self._with_picked_rows(rows, picked,
                                                    fast_ok, b)
            if rows is not None:
                plan.add_rows(*rows, b.fid_filter, slot, b.picks)
            for i in fast_idx.tolist():
                out[i] = DEFERRED
            return out
        counts = np.zeros(B, np.int64)
        delivered = 0
        pool = getattr(self.node, "deliver_lanes", None) if any_wide \
            else None
        if pool is not None:
            # a sync caller's window with a wide filter in it: hundreds
            # of rows a message, so one row at a time (a message copy
            # and a socket write each) is what the lanes exist to
            # avoid. The same rows, walked session by session as a lane
            # slice is: per-session order is the inline loop's
            counts = pool.deliver_now(msgs, *rows, b.fid_filter)
        elif rows is not None:
            fid_filter = b.fid_filter
            deliver = broker._deliver
            # the 64-entry OPT_TABLE replaces the old per-call
            # opt_cache (ISSUE 5 satellite); the dict copy stays on
            # this inline path because _deliver plants the dict into
            # the delivered copy's headers — the lane path instead
            # shares the frozen table entry through the DeliveryView
            for bi, s, ob, fd in zip(*(a.tolist() for a in rows)):
                if deliver(s, fid_filter[fd], msgs[bi],
                           dict(OPT_TABLE[ob & 0x3F])):
                    counts[bi] += 1
                    delivered += 1
        if delivered:
            self.node.metrics.inc("messages.routed.device", delivered)
        metrics = self.node.metrics
        hooks = broker.hooks
        for i in np.flatnonzero(fast_ok).tolist():
            n = int(counts[i])
            if n == 0 and not msgs[i].is_sys:
                metrics.inc("messages.dropped")
                metrics.inc("messages.dropped.no_subscribers")
                hooks.run("message.dropped", (msgs[i], "no_subscribers"))
            out[i] = n
        return out

    def finish(self, h) -> list[int]:
        """Stage 4 for single-batch callers (route_batch): window of 1.
        Sync callers need final counts on return, so the consume stays
        inline (the lanes serve the pipelined path via finish_sub)."""
        return self.finish_sub(h, 0, defer=False)

    def _release_one(self, h) -> None:
        """Drop one sub-batch reference; the handle releases at zero."""
        if h is None or h.built is None:
            return
        h.refs -= 1
        if h.refs <= 0:
            h.built = None
            self._outstanding -= 1
            if self.ledger is not None:
                self.ledger.unpin(id(h))
            if self._building:
                self._try_swap()

    def abandon(self, h) -> None:
        """Release a handle ENTIRELY (error path: the caller falls back
        to the host route for every remaining sub-batch). Idempotent."""
        if h is not None and h.built is not None:
            h.refs = 0
            h.built = None
            self._outstanding -= 1
            if self.ledger is not None:
                self.ledger.unpin(id(h))
            if self._building:
                self._try_swap()

    def route_batch(self, msgs: list[Message]) -> Optional[list[int]]:
        """Route+deliver a micro-batch through the fused device step,
        synchronously (publish_batch / tests / warmup). The pipelined
        serving path drives the four stages separately via PublishBatcher.

        Returns per-message delivery counts, or None when the engine has no
        tables to serve (caller falls back to the host path).
        """
        # a sync rebuild must honor the handle pin: swapping _tables while
        # the batcher has a dispatch in flight on the dispatch thread would
        # hand that dispatch the new tables under the old _Built metadata
        # (outstanding > 0 implies a snapshot exists, so serving stale +
        # host deltas meanwhile is always correct)
        if self._outstanding == 0 \
                and (self._built is None
                     or (not self._building
                         and self._compaction_reason() is not None)):
            if self._built is not None:
                self._count_compaction(self._compaction_reason())
            self.rebuild()
        # sync callers compile in-path by design — let a cold cached
        # class trace instead of bouncing to the plain program
        h = self.prepare(msgs, gate_cold=False)
        if h is None:
            return None
        try:
            self.dispatch(h)
            self.materialize(h)
        except Exception:
            self.abandon(h)
            raise
        return self.finish(h)

    def _writeback_cursors(self, occur: np.ndarray, b=None) -> None:
        """Mirror device round-robin cursor advances into the host
        SharedGroup state so the host path and the next rebuild stay fair."""
        if self.broker.shared_strategy != "round_robin":
            return
        b = b or self._built
        for slot in np.flatnonzero(occur[:b.n_slots]):
            f, gname = b.slot_key[slot]
            g = self.broker.shared.get(f, {}).get(gname)
            if g is not None and g.members:
                # for mixed local/remote groups this folds the device's
                # full-membership advance onto the local cursor — an
                # approximation that keeps the host fallback fair, not a
                # correctness input (the device cursor itself is
                # authoritative while the snapshot serves)
                g.cursor = (g.cursor + int(occur[slot])) % len(g.members)

    def _consume_one(self, msg, m_row, r_row, o_row, ss_row, sr_row, so_row,
                     words, dev_shared: bool, b=None, drow=None, ov=None,
                     pending: bool = False) -> int:
        """Turn one message's RouteResult rows into deliveries.

        `drow` = (delta fids, delta fan rows, delta fan opts) when the
        dispatch fused the delta overlay `ov` (ISSUE 4): post-snapshot
        filters deliver straight from the device planes; `pending`
        marks live delta filters the overlay does NOT cover (just
        subscribed / overflowed / too deep) — only those still walk the
        host trie, and overlay-covered fids are skipped there so nothing
        delivers twice."""
        broker = self.broker
        metrics = self.node.metrics
        b = b or self._built
        n = 0
        matched: list[str] = []
        off = 0
        for fid in m_row:
            if fid < 0:
                continue
            f = b.fid_filter[fid]
            seg = b.seg_len[fid]
            matched.append(f)
            # a segment wider than the lane's cap travelled by
            # reference: its rows are in the snapshot's own CSR, not in
            # `r_row`, whose columns the narrow segments alone take up
            wide = seg > self.fanout_cap
            if wide:
                lo, r_f, o_f = int(b.sub_start[fid]), b.sub_row, b.sub_opts
            else:
                lo, r_f, o_f = off, r_row, o_row
                off += seg
            # rich-ness is snapshot state: read it from the handle's
            # pinned _Built (fid_rich), never from engine-level state —
            # one source of truth shared with the vectorized fast path
            if f in self.dirty_filters or b.fid_rich[fid]:
                n += broker.dispatch(f, msg)
                continue
            if wide:
                metrics.inc("routing.device.wide_segments")
                metrics.inc("routing.device.wide_rows", seg)
            for k in range(lo, lo + seg):
                sid = int(r_f[k])
                if sid < 0:
                    continue
                if broker._deliver(sid, f, msg,
                                   _unpack_opts(int(o_f[k]))):
                    n += 1
                    metrics.inc("messages.routed.device")

        # filters added since the snapshot (ISSUE 4): the fused overlay
        # planes deliver them from device rows; only uncovered filters
        # (no overlay this dispatch, overlay overflow, too-deep) walk
        # the host trie — the routing.device.host_delta counter measures
        # exactly those host-side deliveries (the pre-overlay behavior)
        if ov is not None and drow is not None:
            d_fids, d_rows, d_opts = drow
            doff = 0
            for raw in d_fids:
                dfid = int(raw)
                if dfid < 0:
                    continue
                seg = ov.seg_of.get(dfid, 0)
                f = self._delta_filter.get(dfid)
                if f is None:       # deleted while this batch flew
                    doff += seg
                    continue
                matched.append(f)
                if dfid in ov.hostfan \
                        or self._fid_member_clock.get(dfid, -1) \
                        > ov.version:
                    # rich/oversized fan-out, or membership changed
                    # after this overlay version was built: the match
                    # stands, delivery comes from the live host dict
                    n += broker.dispatch(f, msg)
                else:
                    for j in range(doff, doff + seg):
                        sid = int(d_rows[j])
                        if sid < 0:
                            continue
                        if broker._deliver(sid, f, msg,
                                           _unpack_opts(int(d_opts[j]))):
                            n += 1
                            metrics.inc("messages.routed.device")
                doff += seg
        if self._delta_filter and (ov is None or pending):
            if words is None:   # prepare defers tokenization (native
                words = T.tokens(msg.topic)[:self.max_levels]  # encode)
            ids = self.intern.encode_topic(words)
            dol = words[0].startswith("$") if words else False
            host_hit = False
            for dfid in self._delta_trie.match(ids, dol):
                if ov is not None and dfid in ov.fid_set:
                    continue    # the overlay planes already served it
                f = self._delta_filter.get(dfid)
                if f is None:
                    continue
                matched.append(f)
                n += broker.dispatch(f, msg)
                host_hit = True
            if host_hit:
                metrics.inc("routing.device.host_delta")

        # shared subscriptions
        if dev_shared:
            handled: set[tuple] = set()
            for k, slot in enumerate(ss_row):
                if slot < 0:
                    continue
                f, gname = b.slot_key[slot]
                handled.add((f, gname))
                if (f, gname) in self.dirty_slots:
                    if self._host_shared_dispatch(f, gname, msg):
                        n += 1
                    continue
                sid = int(sr_row[k])
                if sid >= _REMOTE_SID_BASE:
                    # device picked a remote member: directed forward,
                    # the host path's cross-node dispatch with the pick
                    # already done on device
                    cluster = broker.cluster
                    if cluster is not None:
                        origin, rsid = \
                            b.remote_members[sid - _REMOTE_SID_BASE]
                        cluster._spawn_fwd(
                            origin, "shared.deliver_fwd",
                            [f, gname, rsid, msg.to_wire()],
                            key=msg.topic)
                        n += 1
                        metrics.inc("messages.routed.device")
                        metrics.inc("messages.routed.device.remote_shared")
                    elif self._host_shared_dispatch(f, gname, msg):
                        # cluster torn down since the build: host decides
                        n += 1
                elif sid >= 0:
                    if broker._deliver(
                            sid, f, msg,
                            dict(_unpack_opts(int(so_row[k])),
                                 share=gname)):
                        n += 1
                        metrics.inc("messages.routed.device")
                    else:
                        # re-dispatch ONLY when the picked member is
                        # actually gone (in-flight churn window) or the
                        # ack protocol is on — a nack from a live member
                        # with dispatch_ack off is final, matching the
                        # host pick's semantics (for sticky the re-pick
                        # is also where affinity re-homes,
                        # emqx_shared_sub.erl:269-283)
                        grp = broker.shared.get(f, {}).get(gname)
                        gone = grp is None or sid not in grp.members
                        if (gone or broker.shared_dispatch_ack) and \
                                self._host_shared_dispatch(f, gname,
                                                           msg):
                            n += 1
            cluster = broker.cluster
            for f in matched:
                # groups created after the snapshot on matched filters
                for gname in self.new_slots_by_filter.get(f, ()):
                    if (f, gname) in handled:
                        continue
                    handled.add((f, gname))
                    if self._host_shared_dispatch(f, gname, msg):
                        n += 1
                # delta filters' groups (host dispatch covers them all)
                if f in self._delta_fid_of:
                    for gname in list(broker.shared.get(f, {})):
                        if (f, gname) not in handled:
                            handled.add((f, gname))
                            if self._host_shared_dispatch(f, gname, msg):
                                n += 1
                if cluster is not None:
                    # groups excluded from the snapshot (remote members)
                    # and remote-only groups known via replication;
                    # cached per filter — membership changes invalidate
                    groups = self._cluster_groups_cache.get(f)
                    if groups is None:
                        groups = tuple(
                            set(broker.shared.get(f, ()))
                            | cluster._groups_by_real.get(f, set()))
                        self._cluster_groups_cache[f] = groups
                    for gname in groups:
                        if (f, gname) in handled:
                            continue
                        handled.add((f, gname))
                        if self._host_shared_dispatch(f, gname, msg):
                            n += 1
        else:
            n += broker._dispatch_shared(msg, matched)

        if broker.cluster:
            n += broker.cluster.forward(msg, matched)
        if n == 0 and not msg.is_sys:
            metrics.inc("messages.dropped")
            metrics.inc("messages.dropped.no_subscribers")
            broker.hooks.run("message.dropped", (msg, "no_subscribers"))
        return n

    def rebuild_state(self) -> dict:
        """Live rebuild/overlay gauges for the telemetry snapshot's
        `rebuild` section (PipelineTelemetry.rebuild_state_fn): counts
        ride the Metrics registry; these are the point-in-time values a
        counter can't carry."""
        ov = self._overlay
        return {
            "journal_depth": self.journal_depth(),
            "building": self._building,
            "staleness": self.staleness(),
            "tombstones": len(self._built_deleted),
            "delta_overlay": self.delta_overlay,
            "overlay_rows": ov.n if ov is not None else 0,
            "overlay_class": ov.cap if ov is not None else 0,
            "overlay_version": ov.version if ov is not None else None,
            "overlay_uncovered": self._overlay_uncovered,
            "delta_filters": len(self._delta_filter),
        }

    def stats(self) -> dict:
        b = self._built
        ov = self._overlay
        return {
            **(getattr(self.node, "device_info", None) or {}),
            "built": b is not None,
            "backend": b.backend if b else None,
            "nfa_steps": self.node.metrics.val("routing.device.nfa_steps"),
            "nfa_narrow_steps": self.node.metrics.val(
                "routing.device.nfa_narrow_steps"),
            # topics the match stage matched (any matcher), and of a
            # covering snapshot's expansion the roots that entered it,
            # the candidates it verified and the lanes its own caps
            # sent to the host route
            "match_lanes": self.node.metrics.val(
                "routing.device.match_lanes"),
            "cover_roots": self.node.metrics.val(
                "routing.device.cover_roots"),
            "cover_candidates": self.node.metrics.val(
                "routing.device.cover_candidates"),
            "cover_overflow": self.node.metrics.val(
                "routing.device.cover_overflow"),
            # sub-batches the device windows held, and the scan steps
            # of their classes (the rest were padding, skipped)
            "window_subs": self.node.metrics.val(
                "routing.device.window_subs"),
            "window_slots": self.node.metrics.val(
                "routing.device.window_slots"),
            # filters wider than `fanout_cap`, served from the device
            # window by reference, and the lanes fan-out still sent to
            # the host route
            "wide_segments": self.node.metrics.val(
                "routing.device.wide_segments"),
            "wide_rows": self.node.metrics.val("routing.device.wide_rows"),
            "fanout_overflow": self.node.metrics.val(
                "routing.device.fanout_overflow"),
            # `$share` members the device picked, handed to the lanes as
            # rows, and those the host picked again at delivery
            "shared_lane_rows": self.node.metrics.val(
                "routing.device.shared_lane_rows"),
            "shared_repick": self.node.metrics.val(
                "routing.device.shared_repick"),
            "filters": len(b.fid_filter) if b else 0,
            "shared_slots": b.n_slots if b else 0,
            "churn": self.staleness(),
            "dirty_filters": len(self.dirty_filters),
            "delta_filters": len(self._delta_filter),
            "building": self._building,
            "outstanding": self._outstanding,
            "dedup": self.dedup,
            "match_cache": self._match_cache.stats()
            if self._match_cache is not None else None,
            "compact_readback": self.compact_readback,
            "dispatch_depth": self.dispatch_depth,
            "payload_ewma": {k: round(v, 1)
                             for k, v in self._pay_ewma.items()},
            "delta_overlay": self.delta_overlay,
            "overlay": {"rows": ov.n, "class": ov.cap,
                        "version": ov.version,
                        "hostfan": len(ov.hostfan)}
            if ov is not None else None,
            "journal_depth": self.journal_depth(),
            "subscription_covering": self.subscription_covering,
            "cover_decision": b.cover_decision if b else None,
            "cover": {"roots": b.cover.n_roots,
                      "covered": b.cover.n_covered,
                      "appends": b.cover.app_used,
                      "incomplete": b.cover.incomplete,
                      # roots too wide to own anything, the most
                      # entries one root's segment holds, and the
                      # candidate plane the build sized for it
                      "wide_roots": len(b.cover.wide),
                      "largest_segment": b.cover.largest_segment,
                      "cand_cap": int(b.cover.ct.cand_pad.shape[0]),
                      "reduction": round(
                          (b.cover.n_roots + b.cover.n_covered)
                          / max(1, b.cover.n_roots), 2)}
            if b is not None and b.cover is not None else None,
        }
