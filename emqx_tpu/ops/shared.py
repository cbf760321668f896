"""Shared-subscription member selection on device.

The reference picks one group member per message with pluggable strategies
(emqx_shared_sub.erl:239-290 — random, round_robin, sticky, hash_clientid,
hash_topic; round_robin keeps a per-group counter in the worker's process
dictionary). Here selection is *batched and deterministic*: each (group,
filter) pair is a dense "shared slot" with a persistent cursor; for a batch
of messages, every occurrence of a slot gets successive cursor offsets in
batch order (an associative rank-over-equal-slots computed by sort — SURVEY
§7 hard-part 4), so round-robin semantics hold within and across batches
with no sequential loop.

Strategies round_robin / random / hash_* map onto the same primitive by
choosing the base offset (cursor, message hash) — see pick_members.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from emqx_tpu.ops.fanout import SubTable

STRATEGY_ROUND_ROBIN = 0
STRATEGY_RANDOM = 1       # pseudo-random: hash of (msg seed, slot)
STRATEGY_HASH_TOPIC = 2   # stable per topic-hash
STRATEGY_HASH_CLIENT = 3  # stable per publisher-hash
STRATEGY_STICKY = 4       # persistent per-slot member (cursor = affinity)
STRATEGIES = {
    "round_robin": STRATEGY_ROUND_ROBIN,
    "random": STRATEGY_RANDOM,
    "hash_topic": STRATEGY_HASH_TOPIC,
    "hash_clientid": STRATEGY_HASH_CLIENT,
    # sticky rides the SAME cursor state as round_robin, reinterpreted:
    # the host seeds each slot's cursor with its sticky member's index
    # (device_engine.capture_shared) and the kernel never advances it —
    # every message in every batch picks cursor % size, so affinity
    # holds within and across batches with zero feedback from the
    # device. Re-picks (member death/unsubscribe) are feedback-dependent
    # and stay host-side: the consume fallback picks a new member, the
    # host record updates, and the next snapshot re-seeds the cursor
    # (reference: emqx_shared_sub.erl:269-283).
    "sticky": STRATEGY_STICKY,
}


class SharedPickResult(NamedTuple):
    rows: jax.Array         # [B, K] picked member session row, -1 pad
    opts: jax.Array         # [B, K] packed subopts of picked member
    new_cursors: jax.Array  # [G] updated round-robin cursors
    occur: jax.Array        # [G] occurrences of each slot in this batch
                            # (lets a data-parallel caller psum across shards
                            # and rebase cursors consistently)


# block width of the sort-free rank scan: larger blocks mean fewer
# sequential scan steps but a quadratically larger [L, L] in-block
# compare — sweepable on hardware via env (a device trace shows the
# rank/occur stage cost under the `shared` scope)
import os as _os


def resolve_rank_block(configured=None) -> int:
    """The one rank-block resolution: an explicit width (callers use
    ``set_rank_block``) beats ``EMQX_TPU_RANK_BLOCK`` beats 512.
    Import-time knob — config cannot reach module import, so the env is
    the deploy-time sweep handle; must be an integer >= 8 (a narrower
    block degenerates the in-block compare), anything else fails
    loudly."""
    raw = configured if configured is not None \
        else _os.environ.get("EMQX_TPU_RANK_BLOCK", 512)
    try:
        block = int(raw)
    except (TypeError, ValueError) as _e:
        raise ValueError(
            f"EMQX_TPU_RANK_BLOCK must be an integer, got "
            f"{raw!r}") from _e
    if block < 8:
        raise ValueError(
            f"EMQX_TPU_RANK_BLOCK must be >= 8, got {block}")
    return block


_RANK_BLOCK = resolve_rank_block()


def set_rank_block(width: int) -> None:
    """Set the default block width for subsequently TRACED programs
    (bench.py self-tunes this on the target hardware before tracing its
    main step — the optimum is hardware-specific: CPU lowers the [L, L]
    compare to scalar loops and wants small blocks, the TPU VPU wants
    fewer scan steps). Already-jitted programs keep their width."""
    global _RANK_BLOCK
    if width < 8:
        raise ValueError(f"rank block width must be >= 8, got {width}")
    _RANK_BLOCK = width


def _rank_and_occur_blocked(sids: jax.Array, n_slots: int,
                            block: int | None = None):
    """Sort-free rank/occur for TPU (round-3): the round-2 argsort of the
    whole flattened batch measured as the fused step's dominant cost
    (~2/3 of the batch time; TPU sorts are bitonic-network expensive).
    The flat array is scanned in `block`-wide blocks (default
    _RANK_BLOCK; static — a sweep jits one program per width): within a
    block, rank is a strictly-lower-triangular equality reduction (one
    [L, L] compare + masked row-sum on the VPU — the associative
    formulation of SURVEY §7 hard-part 4); across blocks a per-slot
    count table is carried, gathered for the block's base and advanced
    with a unique-index scatter at each slot's LAST in-block occurrence.
    The carried table's final state IS `occur`.
    """
    B, K = sids.shape
    flat = sids.reshape(-1)
    n = flat.shape[0]
    L = _RANK_BLOCK if block is None else block
    if L < 8:
        raise ValueError(f"rank block width must be >= 8, got {L}")
    nb = -(-n // L)
    pad = nb * L - n
    blocks = jnp.pad(flat, (0, pad), constant_values=-1).reshape(nb, L)

    def step(carry, s):
        valid = s >= 0
        safe = jnp.where(valid, s, 0)
        base = jnp.where(valid, carry[safe], 0)           # [L] gather
        eq = (s[:, None] == s[None, :]) & valid[:, None]  # [L, L]
        idx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
        jdx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
        rank_in = (eq & (jdx < idx)).sum(axis=1, dtype=jnp.int32)
        is_last = ~(eq & (jdx > idx)).any(axis=1)
        carry = carry.at[
            jnp.where(valid & is_last, s, jnp.int32(n_slots))
        ].add(rank_in + 1, mode="drop", unique_indices=True)
        return carry, base + rank_in

    occur, rank_blocks = jax.lax.scan(
        step, jnp.zeros(n_slots, jnp.int32), blocks)
    rank = rank_blocks.reshape(-1)[:n]
    return rank.reshape(B, K), occur


def _rank_and_occur_sorted(sids: jax.Array, n_slots: int):
    """Sort-based rank/occur (the XLA-CPU winner: its sort is fast and
    the [L, L] block reduction lowers to scalar loops there). Every
    scatter has provably unique live indices; `occur` derives from run
    ends instead of a non-unique scatter-add."""
    from emqx_tpu.ops.scan_ops import cumsum_blocked

    B, K = sids.shape
    flat = sids.reshape(-1)
    n = flat.shape[0]
    order = jnp.argsort(flat, stable=True)
    sorted_sids = flat[order]
    is_start = jnp.concatenate(
        [jnp.ones(1, bool), sorted_sids[1:] != sorted_sids[:-1]])
    is_end = jnp.concatenate(
        [sorted_sids[1:] != sorted_sids[:-1], jnp.ones(1, bool)])
    pos = jnp.arange(n, dtype=jnp.int32)
    run_id = cumsum_blocked(is_start.astype(jnp.int32)) - 1
    starts = jnp.zeros(n, jnp.int32).at[
        jnp.where(is_start, run_id, n)].set(pos, mode="drop",
                                            unique_indices=True)
    rank_sorted = pos - starts[run_id]
    rank = jnp.zeros(n, jnp.int32).at[order].set(rank_sorted,
                                                 unique_indices=True)
    # occur: at each run END the rank is (count-1); one unique scatter
    occur = jnp.zeros(n_slots, jnp.int32).at[
        jnp.where(is_end & (sorted_sids >= 0), sorted_sids, n_slots)
    ].set(rank_sorted + 1, mode="drop", unique_indices=True)
    return rank.reshape(B, K), occur


def _rank_and_occur(sids: jax.Array, n_slots: int):
    """rank[b,k] = #occurrences of sids[b,k] earlier in flattened batch
    order; occur[g] = occurrences of slot g in the batch. -1 entries get
    rank 0 (unused). Backend-selected implementation (identical results;
    oracle-tested): blockwise equality reduction on accelerators, sort
    on CPU."""
    import jax as _jax
    if _jax.default_backend() == "cpu":
        return _rank_and_occur_sorted(sids, n_slots)
    return _rank_and_occur_blocked(sids, n_slots)


@functools.partial(jax.jit, static_argnames=())
def pick_members(table: SubTable, cursors: jax.Array, sids: jax.Array,
                 strategy: jax.Array, msg_hash: jax.Array) -> SharedPickResult:
    """Pick one member per matched shared slot, batched.

    cursors: [G] persistent per-slot round-robin counters (device state).
    sids: [B, K] matched shared-slot ids (-1 pad) from shared_slots().
    strategy: scalar int32 (STRATEGY_*).
    msg_hash: [B] int32 per-message hash (topic/publisher hash or seed),
      used by random/hash strategies.
    """
    B, K = sids.shape
    valid = sids >= 0
    safe = jnp.clip(sids, 0)
    lo = table.shared_start[safe]
    size = table.shared_start[safe + 1] - lo  # [B, K] members per slot
    nonempty = valid & (size > 0)

    rank, occur = _rank_and_occur(sids, cursors.shape[0])
    base_rr = cursors[safe] + rank
    base_hash = (msg_hash[:, None].astype(jnp.uint32)
                 * jnp.uint32(0x9E3779B1) ^ safe.astype(jnp.uint32)).astype(jnp.int32)
    base = jnp.where(strategy == STRATEGY_ROUND_ROBIN, base_rr,
                     jnp.where(strategy == STRATEGY_STICKY,
                               cursors[safe],      # affinity, no rank
                               jnp.abs(base_hash)))
    member = jnp.where(nonempty, base % jnp.maximum(size, 1), 0)
    idx = lo + member
    rows = jnp.where(nonempty, table.shared_row[jnp.clip(idx, 0)], -1)
    opts = jnp.where(nonempty, table.shared_opts[jnp.clip(idx, 0)],
                     jnp.zeros((), table.shared_opts.dtype))

    # advance cursors by per-slot occurrence counts (round_robin only)
    new_cursors = jnp.where(strategy == STRATEGY_ROUND_ROBIN,
                            cursors + occur.astype(cursors.dtype), cursors)
    return SharedPickResult(rows=rows, opts=opts, new_cursors=new_cursors,
                            occur=occur.astype(cursors.dtype))
