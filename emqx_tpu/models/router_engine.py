"""The flagship device program: one fused PUBLISH route step.

This is the TPU replacement for the reference broker's per-message hot path
(emqx_broker:publish/1 → emqx_router:match_routes → emqx_trie:match →
dispatch fold, emqx_broker.erl:199-308): for a whole micro-batch of publishes
it runs, in one jitted program,

  1. wildcard NFA match over the compiled trie        (ops.match)
  2. normal-subscriber fan-out segment-gather         (ops.fanout)
  3. shared-subscription member selection + cursors   (ops.shared)

State model: `RouterTables` is immutable (rebuilt/double-buffered by the host
router on subscription churn); `cursors` is the only mutable device state and
is threaded functionally through each step.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from emqx_tpu.ops.compact import CompactPlanes, compact_result
from emqx_tpu.ops.delta import (DeltaPlanes, DeltaTables, delta_expand,
                                delta_match)
from emqx_tpu.ops.fanout import FanoutResult, SubTable, fanout_normal, shared_slots
from emqx_tpu.ops.match import MatchResult, match_batch, merge_match_results
from emqx_tpu.ops.shapes import ShapeTables, shape_match
from emqx_tpu.ops.shared import SharedPickResult, pick_members
from emqx_tpu.ops.trie import TrieTables


class RouterTables(NamedTuple):
    """Device routing state for the trie-NFA backend (general shapes)."""
    trie: TrieTables
    subs: SubTable


class ShapeRouterTables(NamedTuple):
    """Device routing state for the shape-hash backend (the fast path)."""
    shapes: ShapeTables
    subs: SubTable


class RouteResult(NamedTuple):
    matches: jax.Array        # [B, M] matched filter ids
    match_counts: jax.Array   # [B]
    rows: jax.Array           # [B, D] normal delivery session rows
    opts: jax.Array           # [B, D] packed subopts
    fan_counts: jax.Array     # [B]
    shared_sids: jax.Array    # [B, K] matched shared-slot ids (-1 pad)
    shared_rows: jax.Array    # [B, K] shared picks (session rows)
    shared_opts: jax.Array    # [B, K]
    overflow: jax.Array       # [B] any capacity overflow → host fallback
    new_cursors: jax.Array    # [G]
    occur: jax.Array          # [G] shared-slot occurrences this batch
    # [B] the match stage's own part of `overflow` (the NFA's frontier
    # or match_cap; a cached row's stored flag): what
    # routing.device.match_overflow counts. None where a program does
    # not report it (the mesh's sharded step)
    match_overflow: jax.Array = None
    # trie programs only: level steps of the sub-batch's NFA walk that
    # ran at `frontier_cap` (`MatchResult.wide_steps`; 0 for a padding
    # sub-batch, whose step is skipped). [W] from a window program; a
    # window with a plan walks once and reports it in row 0
    nfa_wide_steps: jax.Array = None
    # [B] the fan-out stage's own part of `overflow` (the expanded rows
    # passed `fanout_cap`): what routing.device.fanout_overflow counts.
    # None where a program does not report it (the mesh's sharded step)
    fanout_overflow: jax.Array = None
    # a covering snapshot's programs only (`MatchResult`'s fields of the
    # same names): the candidates `cover_expand` verified for the
    # sub-batch ([W] from a window program; a window with a plan expands
    # once, over its miss lanes, and reports it in row 0), and [B] the
    # expansion's own part of `overflow`: what
    # routing.device.cover_candidates / .cover_overflow count; and, as
    # `cover_candidates`, the matched roots that entered the expansion
    # (routing.device.cover_roots)
    cover_candidates: jax.Array = None
    cover_overflow: jax.Array = None
    cover_roots: jax.Array = None
    # `route_window`'s optional stages, None where the stage did not
    # run. The fid spaces of `matches` (built-snapshot fids) and
    # `delta.fids` (the engine's delta fids) are disjoint by
    # construction: the host consume walks both, so a filter subscribed
    # one window ago delivers from THIS dispatch (ISSUE 4)
    delta: DeltaPlanes = None         # overlay planes, each [W, B, ...]
    compact: CompactPlanes = None     # CSR of the main planes
    d_compact: CompactPlanes = None   # CSR of `delta`'s


class WindowPlan(NamedTuple):
    """The match cache's plan for one DEDUPLICATED window, as
    `route_window` takes it: every lane of the [W, B] window is a
    duplicate of a miss lane, a cache hit served from the base rows, or
    padding collapsed onto the shared sentinel row."""
    miss_topics: jax.Array    # [Bm, L] the compacted miss lanes
    miss_lens: jax.Array      # [Bm]
    miss_dollar: jax.Array    # [Bm]
    base_matches: jax.Array   # [B, M] per-unique-topic rows: cache hits
    base_counts: jax.Array    # [B]    filled by the host, the rest empty
    base_overflow: jax.Array  # [B]
    miss_pos: jax.Array       # [Bm] unique row of each miss lane (pad = B)
    inv: jax.Array            # [W, B] unique row of each window lane


class WindowDelta(NamedTuple):
    """The delta overlay one window fuses: its tables and, under a
    plan, the cache hits' overlay base rows (overlay ROW indices,
    counts, MATCH-level overflow; each [B, ...])."""
    tables: DeltaTables
    base: tuple = None


class ExchangeAux(NamedTuple):
    """Per-shard static companions the exchange stage (ISSUE 15) needs
    on device, stacked on the 'route' axis next to RouterTables. Built
    once per snapshot from the same capture as the shard tables (the
    host `_ShardBuilt` index), slice-updated by the per-shard churn
    path exactly like the tables."""
    seg_len: jax.Array   # [R, F_cap] int32: fan-out segment length per fid
    fid_slow: jax.Array  # [R, F_cap] bool: rich subopts / snapshot slots
    fid_off: jax.Array   # [R] int32: global-fid base per shard


class ExchangeResult(NamedTuple):
    """Output of the device-to-device exchange stage: each (dp, dest)
    device's final delivery plan — ONLY the rows whose sessions it owns
    (sid % R == dest), received from every source shard around the
    'route' ring. Rows are (msg, sid, gfid | packed_opt << 24) int32
    triples in (source shard asc, msg asc, row asc) order — the exact
    per-session interleaving the host gather/merge path produces."""
    plan: jax.Array      # [dp, R_dst, E, 3] int32, -1 pad
    plan_cnt: jax.Array  # [dp, R_dst] int32 (clamped to E)
    src_cnt: jax.Array   # [dp, R_dst, R_src] int32 segment boundaries
    ok: jax.Array        # [dp, R] int32 bitmask: 1=msgs clean, 2=caps fit


def post_match(subs: SubTable, mr: MatchResult, cursors: jax.Array,
               msg_hash: jax.Array, strategy: jax.Array, *,
               fanout_cap: int, slot_cap: int,
               wide_by_ref: bool = False) -> RouteResult:
    """Fan-out + shared-sub selection on a MatchResult (backend-agnostic).
    `wide_by_ref`: `ops.fanout.fanout_normal`'s; the caller's host side
    then serves a filter wider than `fanout_cap` from its fid."""
    with jax.named_scope("fanout"):
        fr: FanoutResult = fanout_normal(subs, mr.matches,
                                         fanout_cap=fanout_cap,
                                         wide_by_ref=wide_by_ref)
    with jax.named_scope("shared"):
        sids, slot_oflow = shared_slots(subs, mr.matches,
                                        slot_cap=slot_cap)
        sp: SharedPickResult = pick_members(subs, cursors, sids, strategy,
                                            msg_hash)
    overflow = mr.overflow | fr.overflow | slot_oflow
    return RouteResult(
        matches=mr.matches, match_counts=mr.counts,
        rows=fr.rows, opts=fr.opts, fan_counts=fr.counts,
        shared_sids=sids, shared_rows=sp.rows, shared_opts=sp.opts,
        overflow=overflow, new_cursors=sp.new_cursors, occur=sp.occur,
        match_overflow=mr.overflow, nfa_wide_steps=mr.wide_steps,
        fanout_overflow=fr.overflow, cover_candidates=mr.cover_candidates,
        cover_overflow=mr.cover_overflow, cover_roots=mr.cover_roots)


@functools.partial(
    jax.jit,
    static_argnames=("frontier_cap", "match_cap", "fanout_cap", "slot_cap"))
def route_step(tables: RouterTables, cursors: jax.Array, topics: jax.Array,
               lens: jax.Array, is_dollar: jax.Array, msg_hash: jax.Array,
               strategy: jax.Array, *, frontier_cap: int = 16,
               match_cap: int = 64, fanout_cap: int = 128,
               slot_cap: int = 16) -> RouteResult:
    """Trie-NFA route step: match + fan-out + shared picks (general shapes)."""
    with jax.named_scope("match"):
        mr = match_batch(tables.trie, topics, lens, is_dollar,
                         frontier_cap=frontier_cap, match_cap=match_cap)
    return post_match(tables.subs, mr, cursors, msg_hash, strategy,
                      fanout_cap=fanout_cap, slot_cap=slot_cap)


@functools.partial(jax.jit, static_argnames=("fanout_cap", "slot_cap"))
def route_step_shapes(tables: ShapeRouterTables, cursors: jax.Array,
                      topics: jax.Array, lens: jax.Array,
                      is_dollar: jax.Array, msg_hash: jax.Array,
                      strategy: jax.Array, *, fanout_cap: int = 128,
                      slot_cap: int = 16) -> RouteResult:
    """Shape-hash route step: one bucket gather per (topic, shape)."""
    with jax.named_scope("match"):
        mr = shape_match(tables.shapes, topics, lens, is_dollar)
    return post_match(tables.subs, mr, cursors, msg_hash, strategy,
                      fanout_cap=fanout_cap, slot_cap=slot_cap)


def _is_trie(tables) -> bool:
    """Which matcher a window program traces: the tables' type is part
    of the jit key (a pytree structure), so one window program serves
    both backends and each compiles only its own matcher."""
    return isinstance(tables, RouterTables)


def _match_holes(tables) -> bool:
    """Whether the match rows a window program hands its compact stage
    can hold interior `-1` holes, which `ops.compact.compact_result`
    then closes: only the shape-hash matcher's own rows do (one slot a
    shape, empty where the shape's bucket held no match). The trie NFA
    emits a packed prefix, and a covering snapshot's rows come out of
    `ops.cover.cover_expand` packed whatever matched the roots (sorted,
    valid keys first); under a plan a cached base row is that same row
    or the CSR's prefix. Static like `_is_trie`: the tables' pytree
    structure."""
    return not _is_trie(tables) and tables.shapes.cover is None


def _match_stage(tables, topics: jax.Array, lens: jax.Array,
                 is_dollar: jax.Array, *, frontier_cap: int,
                 match_cap: int) -> MatchResult:
    """The backend's matcher over [B] lanes: the trie NFA for
    `RouterTables` (the caps are its own), one bucket gather per shape
    for `ShapeRouterTables` (which takes no cap)."""
    if _is_trie(tables):
        return match_batch(tables.trie, topics, lens, is_dollar,
                           frontier_cap=frontier_cap, match_cap=match_cap)
    return shape_match(tables.shapes, topics, lens, is_dollar)


# the planes of a `RouteResult` row that read -1 where nothing matched
# (ids and session rows); every other plane reads 0 / False there
_EMPTY_IS_MINUS_1 = frozenset(
    ("matches", "rows", "shared_sids", "shared_rows"))


def _empty_step(like: RouteResult, cursors: jax.Array) -> RouteResult:
    """The row a scan step returns for a sub-batch in which no lane
    matches anything: what `post_match` computes from an all-empty
    `MatchResult`, plane for plane (ids and rows -1, counts and flags
    0 / False, the cursors as they came, no occurrence, no wide step,
    no candidate), without computing it. `like`: the full step's
    result as shapes (`jax.eval_shape`)."""
    r = RouteResult(*[
        s if s is None else jnp.full(
            s.shape, -1 if name in _EMPTY_IS_MINUS_1 else 0, s.dtype)
        for name, s in zip(RouteResult._fields, like)])
    return r._replace(new_cursors=cursors)


def _compact_stage(r: RouteResult, dp, payload_cap: int,
                   d_payload_cap, match_holes: bool) -> tuple:
    """The fused CSR readback (ops.compact) of a window's main planes
    and, where the overlay ran, of its delta planes: (compact,
    d_compact). The dense planes stay in the result as free outputs of
    the same program; the host reads them back only when a CSR's
    `row_overflow` fires (payload class too small for this window), so
    the dense fallback needs no re-dispatch.

    match_holes (`_match_holes`): True for a shape-hash snapshot
    without cover state (matches carry interior holes at unmatched
    shape slots), False for the trie NFA and for a covering snapshot
    (their rows are packed prefixes already, the hole-closing stage
    compiles away). The delta family reuses `compact_result` with a
    width-1 all-empty shared family (cs == 0 in every row), so
    `csr_slices` decodes both with one code path; delta matches are
    always prefix-compacted."""
    with jax.named_scope("compact"):
        cp = compact_result(r.matches, r.rows, r.opts, r.fan_counts,
                            r.shared_sids, r.shared_rows, r.shared_opts,
                            payload_cap=payload_cap,
                            match_holes=match_holes)
        if dp is None:
            return cp, None
        W, B = dp.fids.shape[:2]
        no_slot = jnp.full((W, B, 1), -1, jnp.int32)
        zero32 = jnp.zeros((W, B, 1), jnp.int32)
        zero8 = jnp.zeros((W, B, 1), jnp.int8)
        dcp = compact_result(dp.fids, dp.rows, dp.opts, dp.fan_counts,
                             no_slot, zero32, zero8,
                             payload_cap=d_payload_cap, match_holes=False)
    return cp, dcp


def _window_delta(delta: DeltaTables, topics: jax.Array, lens: jax.Array,
                  is_dollar: jax.Array, *, dmatch_cap: int,
                  dfan_cap: int) -> DeltaPlanes:
    """Overlay planes for a full [W, B] window: the linear matcher is
    cursor-independent, so it runs ONCE over the flattened lanes instead
    of per scan step."""
    W, B = topics.shape[:2]
    with jax.named_scope("delta"):
        mr = delta_match(delta, topics.reshape(W * B, -1),
                         lens.reshape(W * B), is_dollar.reshape(W * B),
                         match_cap=dmatch_cap)
        dp = delta_expand(delta, mr, fanout_cap=dfan_cap)
        return DeltaPlanes(*[x.reshape((W, B) + x.shape[1:])
                             for x in dp])


def _cached_delta(delta: DeltaTables, plan: WindowPlan, base: tuple, *,
                  dmatch_cap: int, dfan_cap: int) -> DeltaPlanes:
    """Overlay planes for a DEDUPLICATED dispatch: the linear matcher
    runs only on the [Bm] miss lanes; cache-hit unique topics ride in as
    host-filled base rows (overlay ROW indices + counts + MATCH-level
    overflow) merged with the same scatter as the main match
    (ops.match.merge_match_results), then fan-out expands the merged
    unique rows against the CURRENT overlay CSR — so cached rows carry
    no membership state and a subscriber change can never stale them —
    and `inv` gathers back to full width."""
    with jax.named_scope("delta"):
        mr = delta_match(delta, plan.miss_topics, plan.miss_lens,
                         plan.miss_dollar, match_cap=dmatch_cap)
        um = merge_match_results(*base, mr, plan.miss_pos)
        dp_u = delta_expand(delta, um, fanout_cap=dfan_cap)
        return DeltaPlanes(*[x[plan.inv] for x in dp_u])


@functools.partial(
    jax.jit,
    static_argnames=("frontier_cap", "match_cap", "fanout_cap", "slot_cap"))
def _window_scan(tables, cursors, topics, lens, is_dollar, msg_hash,
                 strategy, plan, *, frontier_cap, match_cap, fanout_cap,
                 slot_cap) -> RouteResult:
    """`route_window`'s `match → scan(fanout, shared)`, the part every
    class of one (W, B[, Bm]) shares. A jit of its own inside the
    program (XLA inlines it) only so that those classes share its
    trace, as the parent's compact and delta twins shared the window
    program they called: traced afresh for every payload class, a trie
    snapshot's direct warm took 12.1–13.4 s for 9.5–10.1 on a v5e's
    host (my chip runs, PR 30)."""
    nfa = dict(frontier_cap=frontier_cap, match_cap=match_cap)
    if plan is None:
        lanes = (topics, lens, is_dollar)

        def occupied():
            """[W] a sub-batch that holds a topic (a padding lane's
            length is 0)."""
            return (lens > 0).any(axis=1)

        def matched(lane):
            return _match_stage(tables, *lane, **nfa)
    else:
        with jax.named_scope("match"):
            mr = _match_stage(tables, plan.miss_topics, plan.miss_lens,
                              plan.miss_dollar, **nfa)
            um = merge_match_results(plan.base_matches, plan.base_counts,
                                     plan.base_overflow, mr, plan.miss_pos)

        def occupied():
            """[W] a sub-batch with a lane on a unique row that holds
            a match or a flag (`cover_overflow` is part of `overflow`).
            Every lane of a padding sub-batch sits on the sentinel row,
            which holds neither: nothing matched it, no hit filled
            it."""
            return ((um.counts > 0) | um.overflow)[plan.inv].any(axis=1)

        def in_row_0(count):
            """The one match over the miss lanes: what it counted (the
            trie's wide steps, a cover's candidates and roots), reported
            by the window's first sub-batch."""
            return None if count is None else jnp.zeros(
                plan.inv.shape[0], jnp.int32).at[0].set(count)

        lanes = (plan.inv, in_row_0(mr.wide_steps),
                 in_row_0(mr.cover_candidates), in_row_0(mr.cover_roots))

        def matched(lane):
            inv_k, wide_k, cand_k, roots_k = lane
            return MatchResult(
                matches=um.matches[inv_k], counts=um.counts[inv_k],
                overflow=um.overflow[inv_k], wide_steps=wide_k,
                cover_candidates=cand_k,
                cover_overflow=None if um.cover_overflow is None
                else um.cover_overflow[inv_k], cover_roots=roots_k)

    def routed(cur, lane, mh_k):
        with jax.named_scope("match"):
            mr_k = matched(lane)
        r = post_match(tables.subs, mr_k, cur, mh_k, strategy,
                       fanout_cap=fanout_cap, slot_cap=slot_cap,
                       wide_by_ref=True)
        return r.new_cursors, r

    def skipped(cur, lane, mh_k):
        r = _empty_step(jax.eval_shape(routed, cur, lane, mh_k)[1], cur)
        if plan is not None:
            # the one match's counts ride in row 0 whatever it holds
            r = r._replace(nfa_wide_steps=lane[1], cover_candidates=lane[2],
                           cover_roots=lane[3])
        return cur, r

    # a window is padded to its class's W and every stage of the step is
    # fixed-shape, so a sub-batch of padding would cost what a full one
    # does: the step is skipped where the sub-batch is not occupied. A
    # class of W = 1 has no padding sub-batch and carries no predicate
    def step(cur, xs):
        lane, mh_k, occupied_k = xs
        if occupied_k is None:
            return routed(cur, lane, mh_k)
        return jax.lax.cond(occupied_k, routed, skipped, cur, lane, mh_k)

    with jax.named_scope("scan"):
        _, r = jax.lax.scan(step, cursors, (
            lanes, msg_hash, None if msg_hash.shape[0] == 1 else occupied()))
    return r


@functools.partial(
    jax.jit,
    static_argnames=("frontier_cap", "match_cap", "fanout_cap", "slot_cap",
                     "delta_match_cap", "delta_fanout_cap", "payload_cap",
                     "d_payload_cap"))
def route_window(tables, cursors: jax.Array, topics: jax.Array,
                 lens: jax.Array, is_dollar: jax.Array,
                 msg_hash: jax.Array, strategy: jax.Array,
                 plan: "WindowPlan | None" = None,
                 delta: "WindowDelta | None" = None, *,
                 frontier_cap: int = 16, match_cap: int = 64,
                 fanout_cap: int = 128, slot_cap: int = 16,
                 delta_match_cap: int = 16, delta_fanout_cap: int = 64,
                 payload_cap: "int | None" = None,
                 d_payload_cap: "int | None" = None) -> RouteResult:
    """The served path's device window: W fused route steps in ONE
    dispatch, `match → scan(fanout, shared)`, returning the stacked
    RouteResult (every field [W, ...]; a single batch is a window of
    W = 1). Cursors thread through the scan exactly as through W
    sequential `route_step_shapes` / `route_step` calls, so
    `new_cursors` / `occur` in row k reflect state after sub-batch k and
    round-robin fairness holds across the whole window (oracle-tested
    bit for bit, every combination of stages). One difference from
    those step programs: a filter with more than `fanout_cap`
    subscribers is not a lane overflow here. It travels by reference
    (`ops.fanout.fanout_normal(wide_by_ref=True)`): its fid is among
    `matches` (and in the CSR payload's match section), its rows are in
    none of the fan-out planes, and the engine reads them from the
    snapshot's own CSR on the host, at the segment's place in match
    order.

    Either backend: the tables' type picks the matcher (`_is_trie`; the
    trie NFA takes `frontier_cap` / `match_cap`). A window costs what it
    holds: where W > 1 the scan step of a sub-batch in which nothing
    can match (the class's padding: no topic, or under a plan no lane
    on a row with a match or a flag) is skipped under one `lax.cond`
    and returns the row the full step computes there (`_empty_step`).
    Up to three optional stages, each chosen by
    what the caller passes; `None`-ness of a pytree argument and the
    value of a static are part of the jit key like the tables' type, so
    a class compiles only its own stages:

    - `plan` (the match cache's, `WindowPlan`): the match runs ONCE over
      the [Bm] compacted miss lanes, merges with the cache-hit base rows
      and `plan.inv` gathers the unique rows back to window width per
      scan step. `topics` / `lens` / `is_dollar` are None then: the
      plan holds the window's lanes.
    - `delta` (`WindowDelta`): the overlay of post-snapshot filters
      matches and expands in the same dispatch, into `RouteResult.delta`.
    - `payload_cap` (with `d_payload_cap` where the overlay runs): the
      CSR readback of `_compact_stage`, into `.compact` / `.d_compact`."""
    r = _window_scan(tables, cursors, topics, lens, is_dollar, msg_hash,
                     strategy, plan, frontier_cap=frontier_cap,
                     match_cap=match_cap, fanout_cap=fanout_cap,
                     slot_cap=slot_cap)
    dp = None
    if delta is not None:
        dcaps = dict(dmatch_cap=delta_match_cap, dfan_cap=delta_fanout_cap)
        dp = _window_delta(delta.tables, topics, lens, is_dollar, **dcaps) \
            if plan is None else \
            _cached_delta(delta.tables, plan, delta.base, **dcaps)
    cp = dcp = None
    if payload_cap is not None:
        cp, dcp = _compact_stage(r, dp, payload_cap, d_payload_cap,
                                 match_holes=_match_holes(tables))
    return r._replace(delta=dp, compact=cp, d_compact=dcp)


def route_digest(r: RouteResult) -> jax.Array:
    """Scalar int32 reduction over EVERY RouteResult output plane.

    Benchmarks close a dispatch window with one scalar readback; summing
    every plane here (not a subset) stops XLA dead-code-eliminating any
    stage of the step out of the measurement. One definition shared by the
    fused window, bench.py's single-step path, and the oracle test, so the
    two measurements can never silently diverge."""
    return (r.matches.sum(dtype=jnp.int32)
            + r.rows.sum(dtype=jnp.int32)
            + r.opts.sum(dtype=jnp.int32)
            + r.fan_counts.sum(dtype=jnp.int32)
            + r.shared_sids.sum(dtype=jnp.int32)
            + r.shared_rows.sum(dtype=jnp.int32)
            + r.shared_opts.sum(dtype=jnp.int32)
            + r.match_counts.sum(dtype=jnp.int32)
            + r.overflow.sum(dtype=jnp.int32)
            + r.occur.sum(dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("fanout_cap", "slot_cap"))
def route_window_shapes(tables: ShapeRouterTables, cursors: jax.Array,
                        topics: jax.Array, lens: jax.Array,
                        is_dollar: jax.Array, msg_hash: jax.Array,
                        strategy: jax.Array, *, fanout_cap: int = 128,
                        slot_cap: int = 16):
    """W fused route steps in ONE dispatch: scan over a [W, B, ...] window.
    `bench.py`'s raw window, digests only; the served path's is
    `route_window`.

    Per-dispatch overhead (the runtime's launch cost) is paid once for W
    batches instead of W times. Cursors
    thread through the scan exactly as through W sequential calls
    (bit-identical; oracle-tested), so round-robin fairness holds across
    the whole window.

    Returns (new_cursors, digest [W] int32) — route_digest per step forces
    the full routing computation while keeping the device→host readback
    scalar-sized.
    """
    def step(cur, batch):
        t, l, d, h = batch
        r = route_step_shapes(tables, cur, t, l, d, h, strategy,
                              fanout_cap=fanout_cap, slot_cap=slot_cap)
        return r.new_cursors, route_digest(r)

    with jax.named_scope("scan"):
        new_cursors, digests = jax.lax.scan(
            step, cursors, (topics, lens, is_dollar, msg_hash))
    return new_cursors, digests


def compile_stats() -> dict[str, int]:
    """Jit-cache entry counts per route-step program. Each entry is one
    compiled (shape, dtype, static-args) variant, so a growing number
    under steady traffic means the serving path is re-tracing — the
    recompile signal pipeline telemetry surfaces via
    `GET /api/v5/pipeline/stats` and the bench telemetry snapshot.
    The per-class flop/byte/compile-time decomposition of the same
    programs lives in `cost_stats()` (the ISSUE-8 cost registry)."""
    out = {}
    for fn in (route_step, route_step_shapes, route_window_shapes,
               route_window):
        try:
            out[fn.__name__] = fn._cache_size()
        except Exception:  # noqa: BLE001 — cache introspection is best-effort
            pass
    # the ISSUE-15 exchange programs live in parallel.sharded (one per
    # segment-capacity class); fold them in without forcing the import
    import sys
    sh = sys.modules.get("emqx_tpu.parallel.sharded")
    if sh is not None:
        try:
            out.update(sh.exchange_compile_stats())
        except Exception:  # noqa: BLE001 — introspection is best-effort
            pass
    return out


# ---- jit-program cost registry (ISSUE 8) --------------------------------
# Every fused route program records, per compiled (W, B[, Bm][, dC][, P])
# class, its compile wall-time and — on demand — the lowered program's
# cost_analysis() (flops, bytes accessed). This is the per-program cost
# table the ROADMAP-item-2 stage-graph builder needs as its oracle, and
# the compiled-program leg of the ISSUE-8 device-resource observatory
# (the HBM ledger meters data; this meters programs).
#
# Mechanics: each public program is wrapped so a call that GREW the
# jit cache (a fresh compile) registers one row keyed by the active
# telemetry compile-context label (the same "warm W8xB1024" /
# "dispatch W1xB256" key space as snapshot()["compiles"]["by_shape"]),
# with the args saved as ShapeDtypeStructs — no device data retained.
# The flop/byte analysis itself is LAZY: `cost_stats(analyze=True)`
# re-lowers from the saved avals (tracing only, no backend compile, no
# jit-cache growth) the first time each row is queried, so the serving
# path never pays for it; re-traces run outside any compile_context,
# so telemetry's recompile counters are not inflated. Calls made while
# tracing (a program fused inside another) bypass the bookkeeping
# entirely — the outer program owns the compile.

_COSTS: dict[str, dict[str, dict]] = {}
_costs_lock = threading.Lock()
_cost_programs: dict[str, object] = {}

# The registry rides the observatory knob: EMQX_TPU_HBM_LEDGER=0 must
# restore pre-ISSUE-8 behavior EXACTLY, and the route programs are
# bound at import time, so this leg resolves the env half of the knob
# once here (the per-node `broker.hbm_ledger` config gates the per-node
# ledger; this registry is process-wide like the programs themselves).
# Off means: programs stay unwrapped, zero per-call introspection, no
# `program_costs` section in snapshots.
from emqx_tpu.broker.hbm_ledger import resolve_hbm_ledger as _resolve_hbm

COST_REGISTRY_ON = _resolve_hbm(None)


def cost_registry_enabled() -> bool:
    """Whether the route programs are wrapped with compile detection —
    telemetry gates the `program_costs` snapshot section on this."""
    return COST_REGISTRY_ON


def _thread_compile_seq():
    """Telemetry's per-thread compile-event counter (None when no
    jax.monitoring listener is installed — no confirmation signal)."""
    try:
        from emqx_tpu.broker import telemetry as _T
        return _T.thread_compile_seq()
    except Exception:  # noqa: BLE001 — confirmation is best-effort
        return None


def _active_cost_label() -> "str | None":
    """The thread's telemetry compile-context label, if any — keeps the
    registry keyed the same way as the recompile counters."""
    try:
        from emqx_tpu.broker import telemetry as _T
        ctx = getattr(_T._tls, "ctx", None)
        if ctx is not None:
            return ctx[1]
    except Exception:  # noqa: BLE001 — labeling is best-effort
        pass
    return None


def _avals_of(args, kwargs):
    """(args, kwargs) with array leaves replaced by ShapeDtypeStructs
    (statics pass through) — enough to re-lower, nothing pinned."""
    def one(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
        return x
    return jax.tree.map(one, (args, dict(kwargs)))


def record_program_cost(program: str, label: str, *,
                        compile_ms: float = 0.0, flops=None,
                        bytes_accessed=None, avals=None) -> None:
    """Register/extend one (program, class) cost row. The wrapped route
    programs call this on compile detection; an external harness can
    use it to put its own kernels in the same table."""
    with _costs_lock:
        row = _COSTS.setdefault(program, {}).setdefault(
            label, {"compiles": 0, "compile_ms": 0.0})
        row["compiles"] += 1
        row["compile_ms"] = round(row["compile_ms"] + compile_ms, 3)
        if flops is not None:
            row["flops"] = flops
        if bytes_accessed is not None:
            row["bytes_accessed"] = bytes_accessed
        if avals is not None:
            row["_avals"] = avals


def _analyze_lowered(lowered) -> tuple:
    """(flops, bytes_accessed) out of a Lowered's cost_analysis(), or
    (None, None) where the backend provides none."""
    try:
        ca = lowered.cost_analysis()
    except Exception:  # noqa: BLE001 — analysis availability varies
        return None, None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None, None
    flops = ca.get("flops")
    ba = ca.get("bytes accessed")
    return (float(flops) if flops is not None else None,
            float(ba) if ba is not None else None)


def cost_stats(analyze: bool = False) -> dict:
    """The per-program cost table: {program: {class_label: {compiles,
    compile_ms[, flops, bytes_accessed]}}}. `analyze=True` fills any
    missing flop/byte rows by re-lowering from the saved avals —
    tracing cost only, meant for off-path consumers (tools) — and
    drops the avals afterwards. The default
    is cheap and is what snapshot()["program_costs"] embeds."""
    if analyze:
        with _costs_lock:
            todo = [(prog, label, row["_avals"])
                    for prog, rows in _COSTS.items()
                    for label, row in rows.items()
                    if "_avals" in row and "flops" not in row]
        for prog, label, avals in todo:
            fn = _cost_programs.get(prog)
            if fn is None:
                continue
            a, kw = avals
            try:
                flops, ba = _analyze_lowered(fn.lower(*a, **kw))
            except Exception:  # noqa: BLE001 — a stale aval set (deleted
                continue       # program variant) must not break the table
            with _costs_lock:
                row = _COSTS.get(prog, {}).get(label)
                if row is not None:
                    if flops is not None:
                        row["flops"] = flops
                    if ba is not None:
                        row["bytes_accessed"] = ba
                    row.pop("_avals", None)
    with _costs_lock:
        return {prog: {label: {k: v for k, v in row.items()
                               if not k.startswith("_")}
                       for label, row in rows.items()}
                for prog, rows in _COSTS.items()}


def reset_cost_stats() -> None:
    """Drop every registered row (test isolation)."""
    with _costs_lock:
        _COSTS.clear()


def _with_cost_registry(fn):
    """Wrap one jitted program with compile detection (see the registry
    comment above). Transparent to every existing caller: __name__,
    _cache_size and lower() delegate to the wrapped jit function.
    Identity when the observatory knob is off (EMQX_TPU_HBM_LEDGER=0):
    the program flows through unwrapped, exactly pre-ISSUE-8."""
    if not COST_REGISTRY_ON:
        return fn
    name = fn.__name__

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        # no `except` around these two reads: an API the installed jax
        # lacks has to fail the first call, not switch the registry off
        if not jax.core.trace_ctx.is_top_level():
            # fused inside another program's trace: the outer program
            # owns this compile
            return fn(*args, **kwargs)
        before = fn._cache_size()
        seq0 = _thread_compile_seq()
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        if fn._cache_size() > before \
                and not (seq0 is not None
                         and _thread_compile_seq() == seq0):
            # the seq check: jit compiles run on the calling thread, so
            # a cache grown with NO compile event on this thread was
            # another thread's concurrent compile of this program — its
            # row, not ours to record under this class label
            try:
                label = _active_cost_label()
                if label is None:
                    shapes = [tuple(x.shape) for x in
                              jax.tree.leaves(args)
                              if hasattr(x, "shape") and
                              getattr(x, "ndim", 0) >= 2][:1]
                    label = f"adhoc {shapes[0] if shapes else '()'}"
                record_program_cost(
                    name, label,
                    compile_ms=(time.perf_counter() - t0) * 1000.0,
                    avals=_avals_of(args, kwargs))
            except Exception:  # noqa: BLE001 — `out` is already computed:
                # a bookkeeping bug drops one cost row, loudly, and never
                # fails the dispatch that just succeeded
                logging.getLogger("emqx.device").exception(
                    "cost registry: could not record the compile of %s",
                    name)
        return out

    wrapped._fun = fn
    wrapped._cache_size = fn._cache_size
    wrapped.lower = fn.lower
    _cost_programs[name] = fn
    return wrapped


# rebind the public programs through the registry wrapper — callers
# (device_engine, serving, benches, tests) see the same names with
# identical call/introspection surfaces
route_step = _with_cost_registry(route_step)
route_step_shapes = _with_cost_registry(route_step_shapes)
route_window_shapes = _with_cost_registry(route_window_shapes)
route_window = _with_cost_registry(route_window)
# `benchmark/populations/mixed_depth.py` asks the loaded program whether
# `route_window_full` takes the trie NFA's `frontier_cap`
route_window_full = route_window


def empty_router_tables(filter_cap: int = 16) -> RouterTables:
    """A valid all-empty RouterTables (useful before first build)."""
    from emqx_tpu.ops.fanout import build_subtable
    from emqx_tpu.ops.trie import build_tables
    trie = build_tables(np.zeros((0, 1), np.int32), np.zeros(0, np.int64))
    subs = build_subtable(filter_cap, {}, {}, {})
    return RouterTables(trie=trie, subs=subs)
