"""Device-side PUBLISH fan-out: matched filters → subscriber delivery rows.

Replaces the reference's per-message fold over ETS subscriber bags
(emqx_broker.erl dispatch/2 :282-308, incl. the >1024-subscriber shard
special-case in emqx_broker_helper.erl) with a batched CSR segment-gather:
subscribers live in one columnar table (filter-id → contiguous row range);
fan-out for a whole topic batch is a vmapped searchsorted over per-topic
segment offsets. No shard special-case is needed: capacity is explicit, a
segment wider than it travels by reference where the caller asks
(`fanout_normal(wide_by_ref=True)`: the host, which keeps the same CSR,
reads that filter's rows from its own copy), and a topic whose narrow
segments alone pass the capacity falls back to the host route.

Outputs are *session rows* (int32 indices into the host session registry) +
packed subscription options, not pids: the host delivers to sockets.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class SubTable(NamedTuple):
    """Columnar subscriber store, a JAX pytree.

    sub_start: [F+1] CSR offsets per filter id (F = filter capacity).
    sub_row:   [S] session row per subscription entry.
    sub_opts:  [S] packed subopts: qos | nl<<2 | rap<<3 | rh<<4 (SubOpts.to_byte).
    fs_start:  [F+1] CSR offsets: filter id → shared-slot list.
    fs_slot:   [FS] shared-slot ids ((group, filter) pairs get dense slot ids).
    shared_start: [G+1] CSR offsets: shared slot → member list.
    shared_row:   [SM] session row per shared member.
    shared_opts:  [SM] packed subopts per shared member.
    """

    sub_start: jax.Array
    sub_row: jax.Array
    sub_opts: jax.Array           # int8: packed subopts fit 6 bits

    fs_start: jax.Array
    fs_slot: jax.Array
    shared_start: jax.Array
    shared_row: jax.Array
    shared_opts: jax.Array


class FanoutResult(NamedTuple):
    rows: jax.Array      # [B, D] session rows, -1 padded
    opts: jax.Array      # [B, D] packed subopts
    counts: jax.Array    # [B] true count of the expanded rows (may
    #                      exceed D; without the segments left by reference)
    overflow: jax.Array  # [B] bool


def _segment_expand(starts: jax.Array, values: jax.Array, seg_ids: jax.Array,
                    cap: int, skip_wide: bool = False):
    """Expand CSR segments selected per batch row into fixed-width outputs.

    starts: [F+1] CSR. values: [S]. seg_ids: [B, M] segment (filter) ids, -1
    padded. Returns (out [B, cap] gathered values (-1 pad), idx [B, cap] flat
    indices into `values` (-1 pad), counts [B], overflow [B]).

    `skip_wide` (static): a segment longer than `cap` could never fit a
    row, so it is left out of the expansion (zero length: it takes no
    slot, moves no later segment's rows and raises no overflow) for a
    caller that serves it from the segment id alone.
    """
    B, M = seg_ids.shape
    valid = seg_ids >= 0
    safe = jnp.clip(seg_ids, 0, starts.shape[0] - 2)
    seg_lo = jnp.where(valid, starts[safe], 0)
    seg_len = jnp.where(valid, starts[safe + 1] - seg_lo, 0)  # [B, M]
    if skip_wide:
        with jax.named_scope("wide"):
            seg_len = jnp.where(seg_len > cap, 0, seg_len)
    # exclusive prefix of segment lengths per row → output offsets
    ends = jnp.cumsum(seg_len, axis=1)            # [B, M] inclusive
    offs = ends - seg_len                         # [B, M] exclusive
    total = ends[:, -1]
    # for each output slot d: which segment covers it?
    d = jnp.arange(cap, dtype=jnp.int32)
    # searchsorted per row over the inclusive ends: first segment with end > d
    seg_of = jax.vmap(lambda e: jnp.searchsorted(e, d, side="right"))(ends)
    seg_of = jnp.minimum(seg_of, M - 1)
    in_range = d[None, :] < total[:, None]
    lo = jnp.take_along_axis(seg_lo, seg_of, axis=1)
    off = jnp.take_along_axis(offs, seg_of, axis=1)
    idx = lo + (d[None, :] - off)
    idx = jnp.where(in_range, idx, -1)
    out = jnp.where(in_range, values[jnp.clip(idx, 0)], -1)
    return out, idx, total.astype(jnp.int32), total > cap


@functools.partial(jax.jit, static_argnames=("fanout_cap", "wide_by_ref"))
def fanout_normal(table: SubTable, matches: jax.Array, *,
                  fanout_cap: int = 128,
                  wide_by_ref: bool = False) -> FanoutResult:
    """Gather normal (non-shared) subscriber rows for matched filters.

    matches: [B, M] matched filter ids from match_batch, -1 padded.

    `wide_by_ref`: a filter with more than `fanout_cap` subscribers
    travels by reference. Its fid is in `matches` already and its
    segment is `sub_start[fid] .. sub_start[fid + 1]` of a CSR the host
    built, so its rows are not expanded here: `rows` / `opts` are the
    concatenation of the OTHER matched filters' segments in match
    order, `counts` counts those, and `overflow` is raised only when
    they alone pass `fanout_cap`. The served window program asks for
    it (`models/router_engine.route_window`); without it a wide filter
    overflows its lane, as the mesh's programs still have it.
    """
    rows, idx, counts, overflow = _segment_expand(
        table.sub_start, table.sub_row, matches, fanout_cap,
        skip_wide=wide_by_ref)
    opts = jnp.where(idx >= 0, table.sub_opts[jnp.clip(idx, 0)],
                     jnp.int8(0))
    return FanoutResult(rows=rows, opts=opts, counts=counts, overflow=overflow)


def _csr(n_segs: int, seg_map: dict, cap_rows: int):
    """dict seg→list[(a, b)] → (starts [n_segs+1], a[], b[]) padded to cap."""
    starts = np.zeros(n_segs + 1, np.int32)
    for s, entries in seg_map.items():
        starts[s + 1] = len(entries)
    np.cumsum(starts, out=starts)
    total = int(starts[-1])
    cap = max(cap_rows, total, 1)
    a = np.full(cap, -1, np.int32)
    b = np.zeros(cap, np.int32)
    for s, entries in seg_map.items():
        lo = starts[s]
        for i, (x, y) in enumerate(entries):
            a[lo + i] = x
            b[lo + i] = y
    return starts, a, b


def build_subtable(filter_cap: int,
                   normal: dict,
                   filter_slots: dict,
                   shared_members: dict,
                   slot_cap: int = 1,
                   sub_rows_cap: int = 1,
                   fs_rows_cap: int = 1,
                   member_rows_cap: int = 1) -> SubTable:
    """Host builder: python dicts → columnar SubTable (numpy arrays).

    normal: filter id → list[(session_row, packed_opts)].
    filter_slots: filter id → list[shared_slot_id].
    shared_members: shared_slot_id → list[(session_row, packed_opts)].

    The *_cap arguments set minimum array capacities so that independently
    built shards stack to one leading-axis array (parallel.sharded) and jit
    shapes stay stable across rebuilds.
    """
    sub_start, sub_row, sub_opts = _csr(filter_cap, normal, sub_rows_cap)
    fs_map = {f: [(s, 0) for s in slots] for f, slots in filter_slots.items()}
    fs_start, fs_slot, _ = _csr(filter_cap, fs_map, fs_rows_cap)
    n_slots = max(slot_cap, 1 + max(shared_members.keys(), default=-1),
                  1 + int(fs_slot.max(initial=-1)))
    shared_start, shared_row, shared_opts = _csr(n_slots, shared_members,
                                                 member_rows_cap)
    # packed subopts fit 6 bits: an int8 plane quarters the HBM traffic of
    # the opts gathers + outputs (round-2 VERDICT perf item)
    sub_opts = sub_opts.astype(np.int8)
    shared_opts = shared_opts.astype(np.int8)
    return SubTable(sub_start=sub_start, sub_row=sub_row, sub_opts=sub_opts,
                    fs_start=fs_start, fs_slot=fs_slot,
                    shared_start=shared_start, shared_row=shared_row,
                    shared_opts=shared_opts)


@functools.partial(jax.jit, static_argnames=("slot_cap",))
def shared_slots(table: SubTable, matches: jax.Array, *,
                 slot_cap: int = 16):
    """Expand matched filters into shared-subscription slot ids.

    Returns (sids [B, slot_cap] shared-slot ids (-1 pad), overflow [B]).
    """
    sids, _idx, _counts, overflow = _segment_expand(
        table.fs_start, table.fs_slot, matches, slot_cap)
    return sids, overflow
