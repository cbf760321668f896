"""Batched wildcard topic matching on TPU: a level-stepped NFA over TrieTables.

Replaces the reference's per-message recursive trie walk
(emqx_trie.erl:208-266) with one jitted program that matches a whole batch of
publish topics at once:

  - the *batch* is the parallel axis (vectorized over topics),
  - topic *levels* are the time axis, advanced with `lax.scan`,
  - each topic carries a fixed-capacity NFA *frontier* of live trie nodes;
    per level every frontier node expands into its exact-word child (hash
    table probe) and its '+' child, and emits its '#' child's filter,
  - a level step is as wide as the batch's live frontier: live nodes are
    a prefix of the frontier, and a lane costs its 29 scalar gathers
    (8 probes x 3 edge-table planes + 5 node planes) dead or alive, so
    each level runs, under `lax.switch`, the narrowest of `NARROW_WIDTHS`
    that holds every topic's live lanes, or `frontier_cap` where none
    does. `frontier_cap` is capacity for the worst topic, paid only at
    the levels of the batches that hold one. On a v5e, 1,024 topics of
    `mixed-zipf` (at most 2 live paths a topic) against its 125,000
    filters: 79.2 ms with every step at 16 lanes, 23.9 with a rung at 4,
    13.4 with rungs at 2 and 4 (my chip runs, PR 29),
  - matches are compacted into a fixed [B, match_cap] output with per-topic
    counts; capacity overflow is reported per topic so the host can fall back
    to `HostTrie` for those rare topics (static shapes stay static).

Semantics match emqx_topic.erl match/2 incl. the root-level '$' exclusion
(topics whose first level starts with '$' skip root '+'/'#' branches) and
"sport/# matches sport" ('#' matches zero levels).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from emqx_tpu.ops.intern import PAD, UNKNOWN
from emqx_tpu.ops.trie import MAX_PROBES, TrieTables, mix_hash


# Widths a level step may run at below `frontier_cap`, narrowest first.
# A step at width w leaves at most 2w live lanes, so a rung is used only
# where 2w <= frontier_cap (it can then never overflow the frontier).
NARROW_WIDTHS = (2, 4)


class MatchResult(NamedTuple):
    matches: jax.Array   # [B, match_cap] int32 filter ids, -1 padded
    counts: jax.Array    # [B] int32 true match count (may exceed match_cap)
    overflow: jax.Array  # [B] bool — frontier or match capacity exceeded
    # scalar int32: level steps of the NFA's walk that ran at
    # `frontier_cap` (of topics.shape[1] + 1). None where no NFA walked
    # (the shape-hash and overlay matchers, cached rows)
    wide_steps: jax.Array = None
    # a covering snapshot only (`ops/cover.cover_expand`; None from any
    # other matcher): scalar int32, the candidates the expansion
    # verified for this batch (each matched root's own entry, its owned
    # filters and the append rows riding a lane; padding excluded), and
    # [B] bool, the expansion's own part of `overflow` (a lane's
    # candidates passed `cand_cap`, or its verified matches the row),
    # and scalar int32, the matched roots that entered the expansion
    cover_candidates: jax.Array = None
    cover_overflow: jax.Array = None
    cover_roots: jax.Array = None


def edge_lookup(tables: TrieTables, parent: jax.Array, word: jax.Array) -> jax.Array:
    """Hash-table edge probe: child node id or -1. Shapes broadcast."""
    S = tables.slot_parent.shape[0]
    mask = jnp.uint32(S - 1)
    h = mix_hash(parent, word) & mask
    child = jnp.full(jnp.broadcast_shapes(parent.shape, word.shape), -1, jnp.int32)
    for p in range(MAX_PROBES):
        idx = ((h + np.uint32(p)) & mask).astype(jnp.int32)
        hit = ((parent >= 0) & (tables.slot_parent[idx] == parent)
               & (tables.slot_word[idx] == word))
        child = jnp.where(hit & (child < 0), tables.slot_child[idx], child)
    return child


def _gather_node(arr: jax.Array, idx: jax.Array) -> jax.Array:
    """arr[idx] with -1 indices yielding -1."""
    safe = jnp.clip(idx, 0, arr.shape[0] - 1)
    return jnp.where(idx >= 0, arr[safe], -1)


@functools.partial(jax.jit,
                   static_argnames=("frontier_cap", "match_cap", "_rungs"))
def match_batch(tables: TrieTables, topics: jax.Array, lens: jax.Array,
                is_dollar: jax.Array, *, frontier_cap: int = 16,
                match_cap: int = 64,
                _rungs: tuple = NARROW_WIDTHS) -> MatchResult:
    """Match a batch of publish topics against the compiled trie.

    topics: [B, L] int32 interned level ids (PAD beyond lens[b]).
    lens: [B] int32 level counts. is_dollar: [B] bool ('$'-rooted topics).
    `_rungs` is the tests' and probes' handle on the step's widths
    (`()` walks every level at `frontier_cap`); no caller sets it.
    """
    B, L = topics.shape
    F, M = frontier_cap, match_cap
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    widths = tuple(w for w in _rungs if 2 * w <= F) + (F,)

    # rows with lens == 0 are batch padding: start with an empty frontier
    root0 = jnp.where(lens > 0, 0, -1).astype(jnp.int32)
    frontier0 = jnp.full((B, F), -1, jnp.int32).at[:, 0].set(root0)
    out0 = jnp.full((B, M), -1, jnp.int32)
    count0 = jnp.zeros(B, jnp.int32)
    oflow0 = jnp.zeros(B, bool)

    # scan steps l = 0..L inclusive; word input only consumed while l < len
    words_t = jnp.concatenate(
        [topics.T, jnp.full((1, B), PAD, topics.dtype)], axis=0)
    steps = jnp.arange(L + 1, dtype=jnp.int32)

    def step_at(w, frontier, out, count, oflow, l, word):
        """One level over lanes [0, w) of the frontier. Every live lane
        is among them (the caller's choice of w), a dead lane emits and
        expands nothing, and emissions and candidates keep their lane
        order: the result is the step at `frontier_cap`'s, bit for bit."""
        frontier = frontier[:, :w]
        active = frontier >= 0

        # --- emissions at depth l ---
        hc = _gather_node(tables.hash_child, frontier)
        skip_root_wild = (is_dollar & (l == 0))[:, None]
        hash_fid = _gather_node(tables.node_filter, hc)
        hash_emit = active & (hash_fid >= 0) & ~skip_root_wild
        exact_fid = _gather_node(tables.node_filter, frontier)
        exact_emit = active & (exact_fid >= 0) & (l == lens)[:, None]
        emit_fid = jnp.concatenate([hash_fid, exact_fid], axis=1)
        emit_mask = jnp.concatenate([hash_emit, exact_emit], axis=1)

        pos = count[:, None] + jnp.cumsum(emit_mask, axis=1) - 1
        pos = jnp.where(emit_mask, pos, M)  # out-of-range → dropped
        out = out.at[rows, pos].set(emit_fid, mode="drop")
        count = count + emit_mask.sum(axis=1, dtype=jnp.int32)

        # --- frontier expansion with this level's word ---
        expanding = active & (l < lens)[:, None]
        parent = jnp.where(expanding, frontier, -1)
        c_exact = edge_lookup(tables, parent, word[:, None])
        c_plus = jnp.where(expanding & ~skip_root_wild,
                           _gather_node(tables.plus_child, frontier), -1)
        cand = jnp.concatenate([c_exact, c_plus], axis=1)  # [B, 2w]
        order = jnp.argsort(cand < 0, axis=1, stable=True)  # valid lanes first
        cand = jnp.take_along_axis(cand, order, axis=1)
        if 2 * w <= F:      # a narrow rung: every candidate has a lane
            frontier = jnp.pad(cand, ((0, 0), (0, F - 2 * w)),
                               constant_values=-1)
        else:
            frontier = cand[:, :F]
            oflow = oflow | (cand[:, F:] >= 0).any(axis=1)
        return frontier, out, count, oflow

    def step(carry, xs):
        *state, wide = carry
        frontier = state[0]
        # the narrowest rung with no live lane at or beyond it holds
        # them all (live lanes are a prefix: the sort above)
        rung = sum(((frontier[:, w:] >= 0).any().astype(jnp.int32)
                    for w in widths[:-1]), jnp.int32(0))
        state = jax.lax.switch(
            rung, [functools.partial(step_at, w) for w in widths],
            *state, *xs)
        return (*state, wide + (rung == len(widths) - 1)), None

    (frontier, out, count, oflow, wide), _ = jax.lax.scan(
        step, (frontier0, out0, count0, oflow0, jnp.int32(0)),
        (steps, words_t))

    oflow = oflow | (count > M)
    mr = MatchResult(matches=out, counts=jnp.minimum(count, M),
                     overflow=oflow, wide_steps=wide)
    if tables.cover is not None:
        # subscription covering: the trie held the covering set only —
        # re-expand matched covers into the exact full-set row (fused
        # CSR gather + verify + order-key sort; ops/cover). Trace-time
        # branch: cover-carrying snapshots are a distinct pytree
        # structure, so covering-off programs are byte-identical to
        # before.
        from emqx_tpu.ops.cover import cover_expand
        mr = cover_expand(tables.cover, mr, topics, lens, is_dollar)
    return mr


def merge_match_results(base_matches: jax.Array, base_counts: jax.Array,
                        base_overflow: jax.Array, mr: MatchResult,
                        miss_pos: jax.Array) -> MatchResult:
    """Scatter a miss sub-batch's fresh MatchResult into cached base rows.

    base_*: [U, ...] per-unique-topic rows (cache hits filled by the host,
    everything else garbage-initialized to the empty row). mr: the match
    output for the [Bm] compacted miss lanes. miss_pos: [Bm] destination
    row of each miss lane in the unique array; padding lanes MUST carry
    an out-of-range POSITIVE index (>= U) so mode="drop" discards them —
    a -1 pad would WRAP (jax wraps negative dynamic indices before the
    bounds check) and clobber row U-1 with the empty pad match. The
    match stage is a pure function of the
    immutable table snapshot, so a cached row and a fresh row for the same
    (snapshot, topic) are bit-identical by construction — merging is a
    plain last-writer scatter, no reconciliation needed. A cached row
    stores one flag, so `cover_overflow` (a covering snapshot's) is the
    miss lanes' alone: a hit whose stored flag was the expansion's
    still goes to the host, uncounted by stage."""
    return MatchResult(
        matches=base_matches.at[miss_pos].set(mr.matches, mode="drop"),
        counts=base_counts.at[miss_pos].set(mr.counts, mode="drop"),
        overflow=base_overflow.at[miss_pos].set(mr.overflow, mode="drop"),
        cover_overflow=None if mr.cover_overflow is None else
        jnp.zeros_like(base_overflow).at[miss_pos].set(
            mr.cover_overflow, mode="drop"))


def encode_topics_str(intern, topics: list, max_levels: int):
    """Encode publish topics from their raw strings — ONE native call
    for the whole batch when the library + mirror are available (split,
    hash, and id-probe per level in C; emqx_tpu/native.py
    topic_encode_batch), else the python per-word path. Same outputs as
    encode_topics: (ids [B,L], lens [B], is_dollar [B], too_long [B])."""
    h = intern.mirror_handle()
    if h is not False:
        from emqx_tpu import native
        out = native.topic_encode_batch(h, topics, max_levels,
                                        UNKNOWN, PAD)
        if out is not None:
            return out
    from emqx_tpu.utils.topic import tokens
    # NOT pre-truncated: encode_topics must see the real level count so
    # deeper-than-L topics get the too_long host-fallback flag (a
    # truncated topic could falsely match a filter on its prefix)
    return encode_topics(intern, [tokens(t) for t in topics], max_levels)


def encode_topics(intern, topic_words: list, max_levels: int):
    """Host helper: list of word-lists → (topics [B,L], lens [B], is_dollar [B]).

    Topics longer than max_levels are truncated and flagged via the returned
    `too_long` mask — the caller must route those to the host fallback.
    """
    B = len(topic_words)
    L = max_levels
    topics = np.full((B, L), PAD, np.int32)
    lens = np.zeros(B, np.int32)
    dollar = np.zeros(B, bool)
    too_long = np.zeros(B, bool)
    for i, ws in enumerate(topic_words):
        n = len(ws)
        if n > L:
            too_long[i] = True
            n = L
        lens[i] = n
        dollar[i] = ws[0].startswith("$") if ws else False
        topics[i, :n] = [intern.lookup(w) for w in ws[:n]]
    return topics, lens, dollar, too_long
