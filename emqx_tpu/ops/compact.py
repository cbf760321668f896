"""Device-side readback compaction: RouteResult planes → one CSR payload.

The route pipeline's `materialize` stage ships the full padded result
planes over the device→host link every window — `[W, B, match_cap]`
matches plus `[W, B, fanout_cap]` row/opts planes plus three
`[W, B, slot_cap]` shared planes — even though the median MQTT publish
matches a handful of filters, so at low fan-out the transfer is >90%
`-1` padding over the slowest link in the system (PR-1 stage spans; the
per-message transfer overhead the edge-broker benchmarking literature
identifies as the scaling cliff — PAPERS.md, and the actual-cardinality
match payloads of the subscription-aggregation line of work).

This op compacts the result ON DEVICE, fused after match + fan-out:
per-message valid-entry counts, a prefix-sum across the batch axis, and
a scatter of every valid entry into one dense payload buffer:

    offsets  [W, B+1] int32   combined per-message payload offsets
    counts3  [W, B, 3] int32  (match, fanout, shared) entry counts
    payload  [W, P]   int32   per message, at offsets[w, i]:
                              [ matched fids   : cm ]
                              [ fan-out rows   : cf ]
                              [ fan-out opts   : cf ]  (int8 widened)
                              [ shared slots   : cs ]
                              [ shared rows    : cs ]
                              [ shared opts    : cs ]  (int8 widened)
    row_overflow [W] bool     a row's total entries exceeded P — the
                              caller reads the DENSE planes for that
                              window instead (they are outputs of the
                              same fused program; transferring them is
                              the fallback, computing them is free)

Bit-identity contract (oracle-tested in tests/test_compact_readback.py):
the valid entries of every plane are preserved IN ORDER. Matches may
carry interior `-1` holes (the shape-hash backend emits at most one
filter per shape SLOT), and hole positions are NOT preserved — but every
consumer is hole-insensitive by construction: fan-out rows are the
concatenation of per-filter segments over valid matches in match order
(holes contribute zero-length segments), and the host consume walks
exactly that concatenation. `cm` equals `match_counts` for both
backends, so delivery decisions and cache rows are unchanged.

A filter wider than the lane's `fanout_cap` puts its fid into the match
section and nothing into the fan-out sections (the served window
program expands by `ops/fanout.fanout_normal(wide_by_ref=True)`): `cf`
counts the narrow segments' rows alone, so a message's rows are the
concatenation of its matched filters' segments in match order with
each wide filter's segment read, at its place, from the CSR the host
kept from the same build. A 1,280-subscriber message is 4 bytes of
payload.

Capacity P is a static arg (one XLA program per payload class); callers
quantize it onto a small pow2-multiple ladder sized by an EWMA of recent
window totals (broker/device_engine.py) so recompiles stay bounded.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class CompactPlanes(NamedTuple):
    offsets: jax.Array       # [W, B+1] int32
    counts3: jax.Array       # [W, B, 3] int32 (match, fanout, shared)
    payload: jax.Array       # [W, P] int32, -1 where unwritten
    row_overflow: jax.Array  # [W] bool


def _rows_searchsorted(sorted_rows: jax.Array, queries: jax.Array,
                       span: int) -> jax.Array:
    """Per-row searchsorted(side='right') over [R, X] rows with [R, Q]
    queries, as ONE flat searchsorted call: rows are offset-encoded into
    a single monotonic array (row r shifted by r * span, where `span`
    strictly bounds every in-row value AND query). A vmapped per-row
    searchsorted pays a per-row dispatch that measured 5x the flat call
    on XLA CPU. int32 throughout (x64 is disabled repo-wide), so the
    caller's R * span must fit — asserted here at trace time."""
    R, X = sorted_rows.shape
    assert R * span < 2**31, (R, span)
    shift = jnp.arange(R, dtype=jnp.int32)[:, None] * jnp.int32(span)
    enc = (sorted_rows + shift).reshape(-1)
    q = (queries + shift).reshape(-1)
    flat = jnp.searchsorted(enc, q, side="right").astype(jnp.int32)
    # flat indexes the concatenated rows; rebase to in-row indices
    return (flat.reshape(R, -1)
            - jnp.arange(R, dtype=jnp.int32)[:, None] * X)


def compact_result(matches: jax.Array, rows: jax.Array, opts: jax.Array,
                   fan_counts: jax.Array, shared_sids: jax.Array,
                   shared_rows: jax.Array, shared_opts: jax.Array, *,
                   payload_cap: int,
                   match_holes: bool = True) -> CompactPlanes:
    """Compact window-stacked RouteResult planes ([W, B, ...]) into CSR.

    GATHER formulation: for each payload slot the owning message comes
    from one searchsorted over the per-row offset ends (the same
    output-driven pattern as ops/fanout._segment_expand), the family
    from comparing the in-message offset against the (cm, cf, cs)
    boundaries, and the value from one fancy gather per family. A
    scatter formulation (valid entries → destinations) lowers to a
    serial bounds-checked loop on XLA CPU and measured 14ms/window at
    B=1024 — ~20x the route step it compacts; the gather form is
    ~0.7ms and vectorizes on every backend.

    Every plane's valid entries are a PREFIX except `matches` from the
    shape-hash matcher's own rows (one filter per shape SLOT → interior
    holes), closed with a rank→position searchsorted over the validity
    cumsum — valid ids keep their match order, which is the order
    fan-out segments concatenate in. That stage is W·B·M queries into
    an array as long (19 gather rounds at 8 x 1,024 x 64), so it is run
    only where holes can be: the trie NFA emits prefix-compacted
    matches, and so does a covering snapshot whatever matched its roots
    (`ops.cover.cover_expand` sorts valid keys first; a cached row is
    that row or the CSR's prefix): for both pass `match_holes=False`
    (static) and the whole hole-closing stage compiles away
    (`models/router_engine._match_holes`).
    """
    W, B, M = matches.shape
    D = rows.shape[-1]
    K = shared_sids.shape[-1]
    P = payload_cap

    valid_m = matches >= 0                                   # [W, B, M]
    cm = valid_m.sum(-1, dtype=jnp.int32)                    # [W, B]
    cf = jnp.minimum(fan_counts, D).astype(jnp.int32)
    cs = (shared_sids >= 0).sum(-1, dtype=jnp.int32)

    n = cm + 2 * cf + 3 * cs
    ends = jnp.cumsum(n, axis=1)                             # [W, B]
    offsets = jnp.pad(ends, ((0, 0), (1, 0)))                # [W, B+1]
    row_overflow = ends[:, -1] > P
    base = offsets[:, :-1]                                   # [W, B]

    if match_holes:
        # hole-compact: position of the (k+1)-th valid entry per row is
        # searchsorted_left(cumsum(valid), k+1) == searchsorted_right(·, k)
        cv = jnp.cumsum(valid_m, axis=-1, dtype=jnp.int32)
        ks = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32), (W * B, M))
        pos = _rows_searchsorted(cv.reshape(W * B, M), ks, M + 1)
        pos = jnp.minimum(pos, M - 1).reshape(W, B, M)
        mcomp = jnp.take_along_axis(matches, pos, axis=-1)
        mcomp = jnp.where(
            jnp.arange(M, dtype=jnp.int32) < cm[..., None], mcomp, -1)
    else:
        mcomp = matches      # trie NFA / cover_expand: a packed prefix

    opts32 = opts.astype(jnp.int32)
    sopts32 = shared_opts.astype(jnp.int32)

    j = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (W, P))
    span = max(P, B * (M + 2 * D + 3 * K)) + 1
    i = jnp.minimum(_rows_searchsorted(ends, j, span), B - 1)  # [W, P]
    w_ix = jnp.arange(W, dtype=jnp.int32)[:, None]
    jj = j - jnp.take_along_axis(base, i, axis=1)
    in_pay = j < ends[:, -1:]

    def g(plane, col):
        colc = jnp.clip(col, 0, plane.shape[-1] - 1)
        return plane[w_ix, i, colc]

    cm_i = jnp.take_along_axis(cm, i, axis=1)
    cf_i = jnp.take_along_axis(cf, i, axis=1)
    cs_i = jnp.take_along_axis(cs, i, axis=1)
    c1 = cm_i
    c2 = c1 + cf_i
    c3 = c2 + cf_i
    c4 = c3 + cs_i
    c5 = c4 + cs_i
    val = jnp.where(
        jj < c1, g(mcomp, jj),
        jnp.where(jj < c2, g(rows, jj - c1),
                  jnp.where(jj < c3, g(opts32, jj - c2),
                            jnp.where(jj < c4, g(shared_sids, jj - c3),
                                      jnp.where(jj < c5,
                                                g(shared_rows, jj - c4),
                                                g(sopts32, jj - c5))))))
    pay = jnp.where(in_pay, val, -1)

    counts3 = jnp.stack([cm, cf, cs], axis=-1)
    return CompactPlanes(offsets=offsets, counts3=counts3, payload=pay,
                         row_overflow=row_overflow)


@functools.partial(jax.jit,
                   static_argnames=("payload_cap", "match_holes"))
def compact_planes_jit(matches, rows, opts, fan_counts, shared_sids,
                       shared_rows, shared_opts, *, payload_cap: int,
                       match_holes: bool = True) -> CompactPlanes:
    """Standalone jitted compaction over [B, R, ...] mesh planes.

    The mesh readback (parallel/serving.py) compacts as a SECOND small
    dispatch (the single-chip engine fuses compaction into the route
    program instead, models/router_engine.route_*_compact).
    Planes are reshaped to one [1, B*R] pseudo-window so the same op and
    the same host-side decode serve both engines; lane index = i*R + r.
    """
    def flat(a):
        return a.reshape((1, a.shape[0] * a.shape[1]) + a.shape[2:])

    return compact_result(flat(matches), flat(rows), flat(opts),
                          flat(fan_counts), flat(shared_sids),
                          flat(shared_rows), flat(shared_opts),
                          payload_cap=payload_cap,
                          match_holes=match_holes)


def csr_slices(off_row: np.ndarray, c3_row: np.ndarray,
               pay_row: np.ndarray, i: int):
    """Host-side decode: message i's (matches, rows, opts, shared_sids,
    shared_rows, shared_opts) views into one window row's flat payload.
    Slices are views — zero copies on the consume path."""
    o = int(off_row[i])
    cm, cf, cs = (int(x) for x in c3_row[i])
    m = pay_row[o:o + cm]
    r = pay_row[o + cm:o + cm + cf]
    op = pay_row[o + cm + cf:o + cm + 2 * cf]
    s0 = o + cm + 2 * cf
    return (m, r, op, pay_row[s0:s0 + cs], pay_row[s0 + cs:s0 + 2 * cs],
            pay_row[s0 + 2 * cs:s0 + 3 * cs])
