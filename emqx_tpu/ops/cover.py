"""Subscription covering: match the covering set, expand at fan-out.

Real subscription populations are cover-heavy — `sports/#` covers
`sports/+/score`, which covers `sports/f1/score` (arXiv:1811.07088's
aggregation argument, arXiv:1611.08743's subgrouping): most filters are
semantically redundant for *matching* because some broader filter
already matches a superset of their topics. This op makes the device
matcher exploit that: the NFA/shape tables are built over the COVERING
set only (the maximal filters), and a per-cover expansion CSR — the
same segment shape ops/fanout ships — re-expands each matched cover
into its covered filters right after the match stage, with a linear
per-candidate verification (ops/delta's matcher semantics) so the
expanded result is EXACTLY the full-set match, values and order.

Covering relation (exact emqx_topic.erl match/2 superset semantics):
A covers B iff every topic matching B matches A —

  - trailing `#` in A covers any suffix (incl. none): `a/#` covers `a`,
    `a/b`, `a/+/c`, `a/#`-prefixed filters with deeper prefixes;
  - `+` in A covers a literal or `+` at that level, never a trailing
    `#` (B would match deeper topics A cannot);
  - a literal in A covers only the same literal;
  - root-`$` exclusion: a `$`-rooted literal filter's topics are
    `$`-rooted, which root `+`/`#` never match — so root-wildcard
    filters cover no `$`-rooted filter.

Detection REUSES the oracle-tested matchers instead of bespoke pair
logic: A covers B exactly when A *matches the pseudo-topic* formed by
B's own interned words (trailing `#` dropped, B's `+` riding as the
reserved PLUS word id which only A's `+` branch can consume, B's
`$`-literal root as the is_dollar flag), post-filtered by the trailing
`#` rule (a `#`-filter is only covered by `#`-filters) and
self-exclusion. So covering detection is ONE batched `match_batch` run
of the filter table against itself — vectorized level-wise over the
interned columnar table, sharing semantics with the serving matcher by
construction (oracle: `covers_pair` below vs HostTrie enumeration).

Exactness & order: a matched cover does NOT imply its covered filters
match (`sports/#` matches `sports/golf` but `sports/+/score` does not),
so expansion verifies every candidate against the topic with the
linear level-wise matcher before emitting it. The expanded row is then
sorted by a per-filter ORDER KEY that reproduces the emission order of
the full set's backend — the trie NFA, wherever covering engages —
exactly: (emit step, hash-emission-before-exact, frontier lane). The
lane order of ops/match's valid-first compaction is the plus-choice
bits read LSB-first (exact children sort before plus children every
step), so the key is `((step*2 + is_exact) << level_bits) | plus_bits`.

What a root may own (`assign_owners`): a filter that covers more
filters than the candidate plane leaves a root (`own_budget`: the
engine's candidate ceiling less the other slots of the roots' match
row) is WIDE; it owns nothing, stays in the match set with a segment
of itself alone, and does not stop the filters it covers from being
roots and owners themselves. Under a tenant-wide `org/#` over
thousands of filters the area historians `org/area/#` still own their
few dozen, and no topic passes the plane because of one root's segment.

Where covering engages (`covering_decision`): only on a snapshot
whose FULL set does not fit the shape-hash backend, so the off twin
would run the trie NFA — the roots then match under shapes (where they
fit) or under a smaller trie. A full set the shape-hash table holds
whole builds cover-free even with covering allowed: the probe costs
two row gathers a SHAPE whatever the filter count, and the expansion's
floor (a 256-lane segment expand, a verify gather and a 320-key sort a
topic) is more than the most shapes the cap admits can save — on the
chip the expansion was 58 % of the route programs' time on such a
snapshot (PERF.md, PR 25). `broker.subscription_covering` /
`EMQX_TPU_COVERING` say whether the engine MAY use covering; `=0` is
"never" (the full set always builds as without this module). The
on/off twins are bit-identical on delivery counts and per-session
order by construction (oracle + A/B tested).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from emqx_tpu.ops.intern import HASH, PAD, PLUS

# order-key packing: plus-choice bits occupy the low `level_bits`;
# (step*2 + class) sits above. 24 level bits + 6 step bits + 1 class
# bit fit int32 — filters deeper than MAX_KEY_LEVELS disable covering
# for the snapshot (they could not ride the key), which is always
# correct: covering is a pure optimization over an exact baseline.
MAX_KEY_LEVELS = 24

_KEY_INVALID = np.int32(0x7FFFFFFF)


class CoverTables(NamedTuple):
    """Device expansion state for one snapshot; a clean JAX pytree.

    exp_start/exp_fid/exp_slot: per-fid expansion CSR. A cover's
      segment is [itself] + its covered filters; covered fids have
      empty segments (they never appear in the covering match set).
      exp_slot is the verify-row index, -1 = pre-verified (the cover's
      own self entry — the base match already proved it).
    vwords/vlens: covered filters' interned level ids for the
      per-candidate linear verification (delta_match semantics).
    order_key: per-fid emission order key, DENSIFIED to ranks at build
      (`trie_order_keys`, see module docstring; ranking is
      order-preserving and keeps the expansion sort in int32).
    out_pad: [M_out] zeros — static carrier of the expanded match-row
      width (the trie NFA's match_cap, so the expanded plane is
      exactly as wide as the covering-off twin's).
    cand_pad: [C] zeros — static carrier of the candidate capacity;
      a topic whose matched covers own more than C candidates flags
      overflow and host-routes (counted, never silently dropped).
    app_*: the expansion-CSR APPEND region (cover-set churn): a new
      subscription covered by a built cover lands here — matched on
      device next dispatch, no rebuild. app_root is the owning cover's
      fid (-1 = empty row), app_fid the appended filter's fid,
      app_key its order key (rank_base + arrival index — appended
      filters sort AFTER every built filter, like the off twin's
      overlay delivery order), app_words/app_lens its levels for
      verification.
    """

    exp_start: np.ndarray   # [Fc+1]
    exp_fid: np.ndarray     # [E]
    exp_slot: np.ndarray    # [E]
    vwords: np.ndarray      # [V, L]
    vlens: np.ndarray       # [V]
    order_key: np.ndarray   # [Fc]
    out_pad: np.ndarray     # [M_out]
    cand_pad: np.ndarray    # [C]
    app_root: np.ndarray    # [A]
    app_fid: np.ndarray     # [A]
    app_key: np.ndarray     # [A]
    app_words: np.ndarray   # [A, L]
    app_lens: np.ndarray    # [A]


# ---- pairwise predicate (the oracle's reference implementation) ---------

def covers_pair(wa: list, wb: list, b_dollar: bool = False) -> bool:
    """True iff filter A (interned words `wa`) covers filter B — every
    topic matching B matches A. Returns True for identical filters
    (self-cover); callers exclude by fid. `b_dollar`: B's root level is
    a `$`-prefixed literal (interned ids don't carry the prefix)."""
    la, lb = len(wa), len(wb)
    if la == 0 or lb == 0:
        return False
    a_hash = wa[-1] == HASH
    b_hash = wb[-1] == HASH
    pa = la - (1 if a_hash else 0)
    if a_hash:
        if pa > lb - (1 if b_hash else 0):
            return False
    else:
        # without a trailing '#', A matches exactly-la-level topics: it
        # can cover neither a '#'-filter nor a different-length filter
        if b_hash or la != lb:
            return False
    if b_dollar and wa[0] in (PLUS, HASH):
        return False            # root wildcards never match '$'-topics
    for l in range(pa):
        aw, bw = wa[l], wb[l]
        if aw == PLUS:
            continue            # '+' covers a literal or '+' (never a
            #                     trailing '#', excluded by the prefix
            #                     length check above)
        if aw != bw:
            return False        # literal covers only the same literal
    return True


# ---- order keys ----------------------------------------------------------

def trie_order_keys(words: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Per-filter emission order key of ops/match.match_batch (see
    module docstring). Requires every filter <= MAX_KEY_LEVELS deep."""
    words = np.asarray(words, np.int32)
    lens = np.asarray(lens, np.int64)
    F = len(lens)
    if F == 0:
        return np.zeros(0, np.int32)
    L = words.shape[1]
    ar = np.arange(F)
    has_hash = words[ar, np.maximum(lens - 1, 0)] == HASH
    plen = lens - has_hash
    bits = np.zeros(F, np.int64)
    for l in range(min(L, int(plen.max(initial=0)))):
        bits |= ((words[:, l] == PLUS) & (l < plen)).astype(np.int64) << l
    step = np.where(has_hash, plen, lens)
    cls = (~has_hash).astype(np.int64)
    key = ((step * 2 + cls) << MAX_KEY_LEVELS) | bits
    return key.astype(np.int32)


def full_shape_count(words: np.ndarray, lens: np.ndarray) -> int:
    """Distinct shapes of a filter set — what decides whether the
    shape-hash backend can hold it (`covering_decision`). A shape is
    ops/shapes' `sig_small`: the '+' mask, the '#'-less length and the
    trailing-'#' flag."""
    words = np.asarray(words, np.int32)
    lens = np.asarray(lens, np.int64)
    F = len(lens)
    if F == 0:
        return 0
    ar = np.arange(F)
    has_hash = (words[ar, np.maximum(lens - 1, 0)] == HASH).astype(np.int64)
    slen = lens - has_hash
    plus_mask = np.zeros(F, np.int64)
    for l in range(min(words.shape[1], int(slen.max(initial=0)))):
        plus_mask |= ((words[:, l] == PLUS)
                      & (l < slen)).astype(np.int64) << l
    return len(np.unique(plus_mask | (slen << 20) | (has_hash << 25)))


# the shape-hash backend's level bound (ops/shapes.build_shape_tables
# refuses deeper filters: plus_mask rides 20 bits of the signature)
SHAPE_MAX_LEVELS = 20


def covering_decision(ns_full: int, shape_cap: int,
                      L: int) -> tuple[bool, str]:
    """Should a snapshot build engage covering? -> (engage, why).

    ns_full: distinct shapes of the FULL set (`full_shape_count`);
    shape_cap: the engine's shape-hash capacity; L: the table's level
    width. Covering pays only across the shapes/trie boundary, so the
    rule is "the full set does not fit the shape-hash backend":

      - "fits_shapes": the off twin would run the shape-hash match,
        which does not get cheaper with fewer filters — build
        cover-free, skip detection altogether;
      - "too_deep": a filter too deep for the int32 order key;
      - "engaged": the off twin would run the trie NFA — detect covers
        and match the roots (the build reports "none_covered" instead
        when detection then finds no cover relation).
    """
    if L <= SHAPE_MAX_LEVELS and ns_full <= shape_cap:
        return False, "fits_shapes"
    if L > MAX_KEY_LEVELS:
        return False, "too_deep"
    return True, "engaged"


# ---- detection -----------------------------------------------------------

def detect_covers(words: np.ndarray, lens: np.ndarray,
                  dollar: np.ndarray, *, batch: int = 2048,
                  match_cap: int = 128, frontier_cap: int = 32):
    """Find, per filter, the set of OTHER filters covering it.

    Vectorized via the device NFA over the interned columnar table:
    each filter becomes a pseudo-topic (trailing '#' dropped, '+'
    riding as the PLUS word id, '$'-literal root as is_dollar) matched
    against the trie of the whole set in [batch]-lane dispatches.

    Returns (covers, incomplete): `covers` is a list of int arrays
    (covering fids, self excluded), `incomplete` a bool mask of
    filters whose cover set overflowed a capacity — those are treated
    as uncovered (kept in the covering set; always correct)."""
    import jax.numpy as jnp

    from emqx_tpu.ops.match import match_batch
    from emqx_tpu.ops.trie import build_tables

    words = np.asarray(words, np.int32)
    lens = np.asarray(lens, np.int64)
    dollar = np.asarray(dollar, bool)
    F = len(lens)
    covers: list = [np.zeros(0, np.int64) for _ in range(F)]
    incomplete = np.zeros(F, bool)
    if F == 0:
        return covers, incomplete

    L = words.shape[1]
    ar = np.arange(F)
    has_hash = words[ar, np.maximum(lens - 1, 0)] == HASH
    plen = (lens - has_hash).astype(np.int32)
    pseudo = words.copy()
    pseudo[has_hash, np.maximum(lens[has_hash] - 1, 0)] = PAD

    tables = build_tables(words, lens)
    for lo in range(0, F, batch):
        hi = min(F, lo + batch)
        B = hi - lo
        t = np.full((batch, L), PAD, np.int32)
        t[:B] = pseudo[lo:hi]
        ln = np.zeros(batch, np.int32)
        ln[:B] = plen[lo:hi]
        dl = np.zeros(batch, bool)
        dl[:B] = dollar[lo:hi]
        mr = match_batch(tables, jnp.asarray(t), jnp.asarray(ln),
                         jnp.asarray(dl), frontier_cap=frontier_cap,
                         match_cap=match_cap)
        m = np.asarray(mr.matches[:B])
        ov = np.asarray(mr.overflow[:B])
        for i in range(B):
            fid = lo + i
            if ov[i]:
                incomplete[fid] = True
                continue
            c = m[i][m[i] >= 0].astype(np.int64)
            c = c[c != fid]
            if has_hash[fid] and len(c):
                # a '#'-filter is only covered by '#'-filters; the
                # pseudo-topic also surfaces exact matches of its
                # prefix, which match the prefix but not the suffixes
                c = c[words[c, np.maximum(lens[c] - 1, 0)] == HASH]
            covers[fid] = c
    return covers, incomplete


def fan_in(covers: list) -> np.ndarray:
    """[F] how many filters each filter covers, from `detect_covers`'
    lists (an `incomplete` filter's list is empty: it counts toward
    none of its covers)."""
    F = len(covers)
    if not F:
        return np.zeros(0, np.int64)
    return np.bincount(np.concatenate(covers).astype(np.int64),
                       minlength=F)


def assign_owners(covers: list, incomplete: np.ndarray, *,
                  own_budget: int = 256) -> np.ndarray:
    """Pick one covering ROOT per covered filter → owner[fid] (-1 =
    stays in the covering set): a root owns what it can hold, or
    nothing.

    A filter is WIDE when it covers more than `own_budget` filters
    (`fan_in`). A wide filter owns nothing and is left out of every
    other filter's cover set here, so a filter that only wide filters
    cover is a root and may own (under a tenant-wide `org/#` the area
    historians `org/area/#` are roots again and own their filters).
    A wide filter is itself a root with a segment of itself alone: no
    narrower filter can cover it, since covering is transitive and its
    fan-in would be the larger. An `incomplete` filter stays a root.
    Every other covered filter's owner is its smallest-fid covering
    root among the non-wide: a maximal non-wide cover of B has no
    non-wide cover itself and appears in B's cover set, so one exists.
    A root's segment is then at most 1 + `own_budget`, whatever the
    order of the fids; on a set without a wide filter the assignment
    is what a plain "smallest-fid covering root" gives.

    Exactness: an owned filter's owner is in the match set and covers
    it, so every topic the filter matches reaches its segment, where
    it is verified; every other filter is in the match set itself."""
    F = len(covers)
    owner = np.full(F, -1, np.int64)
    sizes = np.fromiter((len(c) for c in covers), np.int64, F)
    if not sizes.any():
        return owner
    by = np.concatenate(covers).astype(np.int64)      # the covering fid
    of = np.repeat(np.arange(F), sizes)               # the covered one
    may_own = np.bincount(by, minlength=F) <= own_budget    # `fan_in`
    held = np.zeros(F, bool)        # covered by a filter that may own
    held[of[may_own[by]]] = True
    is_root = ~held | np.asarray(incomplete, bool)
    pick = may_own[by] & is_root[by] & ~is_root[of]
    first = np.full(F, F, np.int64)
    np.minimum.at(first, of[pick], by[pick])
    return np.where(first < F, first, owner)


# ---- table builder -------------------------------------------------------

def build_cover_tables(words: np.ndarray, lens: np.ndarray,
                       owner: np.ndarray, order_key: np.ndarray, *,
                       fid_cap: int, out_width: int, cand_cap: int,
                       verify_cap: Optional[int] = None,
                       append_cap: int = 64) -> CoverTables:
    """Compile owner assignments into device CoverTables (numpy; the
    caller device_puts and registers under the HBM ledger's
    `cover_csr` category). Every filter appears in EXACTLY one
    expansion segment (roots carry themselves + their owned set), so
    the CSR payload is one entry per filter."""
    words = np.asarray(words, np.int32)
    lens = np.asarray(lens, np.int64)
    owner = np.asarray(owner, np.int64)
    order_key = np.asarray(order_key, np.int32)
    F = len(lens)
    L = max(1, words.shape[1] if words.ndim == 2 else 1)
    covered = np.flatnonzero(owner >= 0)
    V = max(1, verify_cap or _next_pow2(max(1, len(covered))))
    if len(covered) > V:
        raise ValueError(f"{len(covered)} covered filters > verify "
                         f"capacity {V}")
    E = max(1, fid_cap)

    # DENSIFY the order keys to ranks: the expansion stage's final
    # ordering runs as ONE single-operand int32 sort of
    # (rank << lane_bits) | lane packed keys (5x faster than stable
    # argsort on the CPU proxy — see cover_expand), so keys must fit a
    # small bit budget. Ranking is order-preserving (equal raw keys ->
    # equal rank; the lane bits reproduce stable-sort tie order), and
    # appended filters take ranks above `rank_base` (they sort after
    # every built filter, mirroring the off-twin's overlay order).
    uniq = np.unique(order_key)
    order_key = np.searchsorted(uniq, order_key).astype(np.int32)

    exp_start = np.zeros(fid_cap + 1, np.int32)
    exp_fid = np.full(E, -1, np.int32)
    exp_slot = np.full(E, -1, np.int32)
    vwords = np.full((V, L), PAD, np.int32)
    vlens = np.zeros(V, np.int32)
    key_pad = np.full(fid_cap, _KEY_INVALID, np.int32)
    key_pad[:F] = order_key

    owned: dict[int, list] = {}
    for b in covered:
        owned.setdefault(int(owner[b]), []).append(int(b))
    slot_of: dict[int, int] = {}
    for s, b in enumerate(int(x) for x in covered):
        slot_of[b] = s
        vwords[s, :lens[b]] = words[b, :lens[b]]
        vlens[s] = lens[b]

    off = 0
    for fid in range(F):
        exp_start[fid] = off
        if owner[fid] >= 0:
            continue                      # covered: empty segment
        exp_fid[off] = fid                # self entry, pre-verified
        exp_slot[off] = -1
        off += 1
        for b in owned.get(fid, ()):
            exp_fid[off] = b
            exp_slot[off] = slot_of[b]
            off += 1
    exp_start[F:] = off

    A = max(1, append_cap)
    return CoverTables(
        exp_start=exp_start, exp_fid=exp_fid, exp_slot=exp_slot,
        vwords=vwords, vlens=vlens, order_key=key_pad,
        out_pad=np.zeros(max(1, out_width), np.int32),
        cand_pad=np.zeros(max(1, cand_cap), np.int32),
        app_root=np.full(A, -1, np.int32),
        app_fid=np.full(A, -1, np.int32),
        app_key=np.zeros(A, np.int32),
        app_words=np.full((A, L), PAD, np.int32),
        app_lens=np.zeros(A, np.int32))


def _next_pow2(x: int) -> int:
    return 1 << max(2, (x - 1).bit_length())


def rank_base(ct: CoverTables) -> int:
    """First free order rank for the append path: built filters hold
    dense ranks 0..rank_base-1 (build_cover_tables), so appended
    filters take rank_base + k and sort after every built filter."""
    valid = ct.order_key[ct.order_key != _KEY_INVALID]
    return int(valid.max()) + 1 if valid.size else 0


# ---- device expansion stage ---------------------------------------------

def _verify_rows(vwords, vlens, sel, topics, lens, is_dollar):
    """Linear wildcard verification of selected filter rows against
    each topic lane: out[b, c] = does filter row sel[b, c] match topic
    b. sel -1 = pre-verified (True). EXACT delta_match/np_filter_match
    semantics: per-level exact-or-'+', trailing-'#' prefix rule,
    root-'$' exclusion, empty rows match nothing."""
    import jax.numpy as jnp

    safe = jnp.clip(sel, 0, vwords.shape[0] - 1)
    fl = jnp.where(sel >= 0, vlens[safe], 0)            # [B, C]
    # ONE row gather [B, C, Lv] + broadcast compares: per-level
    # vwords[safe, l] gathers serialize terribly on the CPU proxy (L
    # gather kernels over the same index plane), and this stage sits on
    # the serving critical path
    res = _verify(vwords[safe], fl, topics, lens, is_dollar)
    return jnp.where(sel >= 0, res, True)


def _verify(vrow, fl, topics, lens, is_dollar):
    """`_verify_rows` on rows already in hand: vrow [B | 1, C, Lv] the
    filters' levels, fl [B | 1, C] their lengths (0 = no filter)."""
    import jax.numpy as jnp

    L = topics.shape[1]
    Lv = vrow.shape[2]
    Lc = min(L, Lv)
    last = jnp.take_along_axis(
        vrow, jnp.clip(fl - 1, 0, Lv - 1)[:, :, None], axis=2)[:, :, 0]
    last_hash = (fl > 0) & (last == HASH)
    plen = fl - last_hash.astype(fl.dtype)
    lvl = jnp.arange(Lc, dtype=fl.dtype)
    head = vrow[:, :, :Lc]
    lvl_ok = ((head == topics[:, None, :Lc]) | (head == PLUS)
              | (lvl >= plen[:, :, None]))
    ok = jnp.all(lvl_ok, axis=2)
    # filter levels beyond the topic width can never verify (the
    # engine builds vwords no wider than the topic planes, so this
    # only guards mismatched callers)
    ok &= plen <= Lc
    len_ok = jnp.where(last_hash, lens[:, None] >= plen,
                       lens[:, None] == fl)
    first = vrow[:, :, 0]
    dskip = is_dollar[:, None] & ((first == PLUS) | (first == HASH))
    return ok & len_ok & ~dskip & (fl > 0) & (lens > 0)[:, None]


def cover_expand(ct: CoverTables, mr, topics, lens, is_dollar):
    """Expand matched covers into the exact full-set MatchResult.

    Runs INSIDE the jitted match stage (ops/match.match_batch /
    ops/shapes.shape_match call this when their tables carry cover
    state): CSR-gather each matched cover's candidates, verify each
    against the topic, merge the append region, and sort by the
    per-filter order key so the output row is bit-identical to the
    covering-off twin's (values AND order). The row is PREFIX-PACKED
    by construction, valid ids first and `-1` after, with no interior
    hole whatever matcher found the roots (the sort puts every invalid
    key last): the window's compact stage relies on it and closes no
    holes over a covering snapshot
    (`models/router_engine._match_holes`). Overflow = base overflow
    | candidate-capacity overflow | true count past the output width
    (the same condition the off twin flags); the last two are reported
    apart as `cover_overflow`, beside the candidates verified and the
    roots that entered (`cover_roots`: the matched roots of the real
    lanes; a padding lane matches none). Traced under scope `cover`
    (inside the caller's `match`)."""
    import jax

    with jax.named_scope("cover"):
        return _cover_expand(ct, mr, topics, lens, is_dollar)


def _cover_expand(ct: CoverTables, mr, topics, lens, is_dollar):
    import jax.numpy as jnp

    from emqx_tpu.ops.fanout import _segment_expand
    from emqx_tpu.ops.match import MatchResult

    M = ct.out_pad.shape[0]
    C = ct.cand_pad.shape[0]
    A = ct.app_root.shape[0]

    fids, idx, _tot, cand_oflow = _segment_expand(
        ct.exp_start, ct.exp_fid, mr.matches, C)
    slots = jnp.where(idx >= 0, ct.exp_slot[jnp.clip(idx, 0)], -1)
    keys = jnp.where(fids >= 0,
                     ct.order_key[jnp.clip(fids, 0,
                                           ct.order_key.shape[0] - 1)],
                     _KEY_INVALID)
    ok = _verify_rows(ct.vwords, ct.vlens, slots, topics, lens,
                      is_dollar)
    valid = (fids >= 0) & ok

    # append region: entry a rides lane b when its owning cover is in
    # b's match row (A is small — a dense [B, M_in, A] compare)
    live = ct.app_root >= 0
    hit = ((mr.matches[:, :, None] == ct.app_root[None, None, :])
           & (mr.matches >= 0)[:, :, None]).any(axis=1)     # [B, A]
    # every lane verifies the same A rows: broadcast, no gather
    app_ok = _verify(ct.app_words[None], ct.app_lens[None], topics, lens,
                     is_dollar)
    app_valid = hit & app_ok & live[None, :]

    cand_fid = jnp.concatenate(
        [fids, jnp.broadcast_to(ct.app_fid[None, :], hit.shape)], axis=1)
    cand_key = jnp.concatenate(
        [keys, jnp.broadcast_to(ct.app_key[None, :], hit.shape)], axis=1)
    cand_valid = jnp.concatenate([valid, app_valid], axis=1)

    # final ordering: keys are dense ranks (build_cover_tables), so
    # (rank << lane_bits) | lane packs into int32 and ONE single-
    # operand sort replaces the stable argsort (5x on the CPU proxy;
    # the lane bits reproduce the stable tie order exactly). rank_bits
    # covers built ranks AND append ranks (rank_base + k <= Fc + A).
    C_tot = C + A
    lane_bits = max(1, (C_tot - 1).bit_length())
    Fc = ct.order_key.shape[0]
    rank_bits = max(2, (Fc + A + 1).bit_length())
    if lane_bits + rank_bits <= 31:
        invalid = jnp.int32((1 << rank_bits) - 1)
        lane = jnp.arange(C_tot, dtype=jnp.int32)
        sk = jnp.where(cand_valid, jnp.minimum(cand_key, invalid),
                       invalid)
        packed = jnp.sort((sk << lane_bits) | lane, axis=1)[:, :M]
        s_ok = (packed >> lane_bits) < invalid
        lanes = packed & jnp.int32((1 << lane_bits) - 1)
        s_fid = jnp.take_along_axis(cand_fid, lanes, axis=1)
    else:   # bit budget exceeded (huge shard): stable argsort fallback
        sort_key = jnp.where(cand_valid, cand_key, _KEY_INVALID)
        order = jnp.argsort(sort_key, axis=1, stable=True)
        s_fid = jnp.take_along_axis(cand_fid, order, axis=1)[:, :M]
        s_ok = jnp.take_along_axis(cand_valid, order, axis=1)[:, :M]
    out = jnp.where(s_ok, s_fid, -1)
    count = cand_valid.sum(axis=1, dtype=jnp.int32)
    own_oflow = cand_oflow | (count > M)
    return MatchResult(
        matches=out, counts=jnp.minimum(count, M),
        overflow=mr.overflow | own_oflow, wide_steps=mr.wide_steps,
        cover_candidates=(fids >= 0).sum(dtype=jnp.int32)
        + (hit & live[None, :]).sum(dtype=jnp.int32),
        cover_overflow=own_oflow,
        cover_roots=(mr.matches >= 0).sum(dtype=jnp.int32))


# ---- host-side cover lookup (append path) --------------------------------

def host_covering_roots(root_trie, root_words: dict, words: list,
                        b_dollar: bool) -> list:
    """Built ROOTS covering a new filter, via the same pseudo-topic
    trick over a HostTrie of the covering set (the engine's append
    path: covered new sub → expansion-CSR append, no rebuild).
    `root_words` maps root fid → interned words. Candidates from the
    trie walk are post-checked with `covers_pair` (trailing-'#' rule,
    identity exclusion) so the result is oracle-exact. Returns covering
    root fids; [] means the new filter takes the overlay path."""
    words = list(words)
    b_hash = len(words) > 0 and words[-1] == HASH
    pseudo = words[:-1] if b_hash else words
    fids = root_trie.match(list(pseudo), is_dollar=b_dollar)
    out = []
    for f in fids:
        wa = root_words.get(f)
        if wa is None or list(wa) == words:
            continue
        if covers_pair(list(wa), words, b_dollar):
            out.append(f)
    return out
