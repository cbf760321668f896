"""shard_map'ed route step: filter-sharded trie × batch-sharded publishes.

Each 'route' shard owns a disjoint filter subset compiled into its own
RouterTables (same array shapes, different contents — stacked on a leading
axis). Publish batches shard over 'dp'. One step computes every (dp, route)
pair's local matches/fan-out; shared-subscription round-robin cursors stay
consistent across 'dp' shards by all-gathering per-slot occurrence counts
and rebasing each shard's cursor offset by the occurrences of lower dp ranks
(deterministic global batch order), then psum-advancing.

This is the ICI data plane replacing the reference's gen_rpc cross-node
forwarding (emqx_rpc.erl:20-60): instead of shipping messages to the node
that owns the route, every shard matches its slice and results ride the
interconnect (SURVEY.md §2.4 P6, §5.8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from emqx_tpu.models.router_engine import (ExchangeResult, RouterTables,
                                           RouteResult)
from emqx_tpu.ops.fanout import fanout_normal, shared_slots
from emqx_tpu.ops.match import match_batch
from emqx_tpu.ops.shapes import shape_match
from emqx_tpu.ops.shared import STRATEGY_ROUND_ROBIN, pick_members


def stack_tables(tables_list: list) -> RouterTables:
    """Stack per-shard RouterTables on a new leading axis (host, numpy).

    All shards must share array shapes — build each with the same
    node/slot/filter capacities (the host router buckets capacities pow2).
    """
    return jax.tree.map(lambda *xs: np.stack(xs), *tables_list)


def put_sharded(mesh: Mesh, tables_stacked: RouterTables, cursors_stacked,
                ledger=None):
    """Place stacked tables/cursors with their 'route' sharding.

    `ledger` (broker.hbm_ledger.HbmLedger, ISSUE 8): when given, the
    placed pytrees register as the mesh_tables / mesh_cursors
    categories so the shard tables stop being unaccounted HBM."""
    spec = NamedSharding(mesh, P("route"))
    # hbm: held right below under mesh_tables / mesh_cursors
    tables = jax.tree.map(lambda x: jax.device_put(x, spec), tables_stacked)
    cursors = jax.device_put(cursors_stacked, spec)  # hbm: held below
    if ledger is not None:
        tables = ledger.hold("mesh_tables", tables)
        cursors = ledger.hold("mesh_cursors", cursors)
    return tables, cursors


@functools.partial(jax.jit, donate_argnums=0)
def _apply_shard_update(full, new, idx):
    """Write one shard's tables into the stacked device arrays in place
    (donated buffers; the traced index keeps ONE compilation for all
    shards). Under a 'route' sharding XLA updates only the owning
    device's slice — nothing else moves."""
    return jax.tree.map(
        lambda f, n: jax.lax.dynamic_update_index_in_dim(f, n, idx, 0),
        full, new)


@jax.jit
def _apply_shard_update_keep(full, new, idx):
    """Non-donating variant: the PREVIOUS stacked tables stay valid —
    required when in-flight consumers (pipelined serving handles, a warm
    thread) still hold the old pytree. Costs a transient second copy of
    the updated arrays."""
    return jax.tree.map(
        lambda f, n: jax.lax.dynamic_update_index_in_dim(f, n, idx, 0),
        full, new)


def update_shard(tables_stacked, shard_idx: int, shard_tables,
                 donate: bool = True):
    """Incremental churn path (SURVEY §7 hard-part 1 under the mesh):
    subscription changes in ONE filter shard rebuild that shard host-side
    (same capacities as its siblings) and re-put ONLY its slice — the
    round-1 story (rebuild one shard -> restack -> re-upload everything)
    is gone.

    tables_stacked: device pytree with leading 'route' axis (donated
    unless donate=False — pass False whenever anything else may still
    read the old arrays).
    shard_tables: the ONE shard's host pytree (no leading axis).
    Returns the updated stacked pytree; the caller must adopt it (with
    donate=True the donated input is invalid afterwards).
    """
    n_shards = jax.tree.leaves(tables_stacked)[0].shape[0]
    if not 0 <= shard_idx < n_shards:
        # dynamic_update_index_in_dim would silently CLAMP and corrupt
        # the edge shard
        raise IndexError(f"shard_idx {shard_idx} out of range "
                         f"[0, {n_shards})")
    shapes_ok = jax.tree.map(
        lambda f, n: f.shape[1:] == n.shape, tables_stacked, shard_tables)
    if not all(jax.tree.leaves(shapes_ok)):
        raise ValueError(
            "shard tables shapes diverge from the stacked capacity "
            "classes; rebuild every shard with matching capacities")
    apply = _apply_shard_update if donate else _apply_shard_update_keep
    return apply(tables_stacked, shard_tables, jnp.int32(shard_idx))


def make_sharded_route_step(mesh: Mesh, *, backend: str = "trie",
                            frontier_cap: int = 16,
                            match_cap: int = 64, fanout_cap: int = 128,
                            slot_cap: int = 16):
    """Build the jitted multi-device route step for `mesh` ('dp','route').

    backend: 'trie' (RouterTables shards) or 'shapes' (ShapeRouterTables
    shards — the fast path).

    Call signature of the returned fn:
      step(tables [R,...], cursors [R,G], topics [B,L], lens [B],
           is_dollar [B], msg_hash [B], strategy scalar) -> RouteResult
    where per-topic outputs come back as [B, R, ...] (R = route shards,
    local filter ids per shard) and cursors as [R, G].
    """
    dp_size = mesh.shape["dp"]

    def local_step(tables, cursors, topics, lens, is_dollar, msg_hash,
                   strategy):
        tables = jax.tree.map(lambda x: x[0], tables)  # this shard's slice
        cursors = cursors[0]

        # the same scope names as the one-chip programs
        # (models/router_engine.py): a device trace groups by them
        with jax.named_scope("match"):
            if backend == "shapes":
                mr = shape_match(tables.shapes, topics, lens, is_dollar)
            else:
                mr = match_batch(tables.trie, topics, lens, is_dollar,
                                 frontier_cap=frontier_cap,
                                 match_cap=match_cap)
        with jax.named_scope("fanout"):
            fr = fanout_normal(tables.subs, mr.matches,
                               fanout_cap=fanout_cap)
        with jax.named_scope("shared"):
            sids, slot_oflow = shared_slots(tables.subs, mr.matches,
                                            slot_cap=slot_cap)

            # cross-dp deterministic round-robin: rebase cursors by the
            # occurrences seen in lower dp ranks, advance by the global
            # total
            occur_local = jnp.zeros_like(cursors).at[
                jnp.clip(sids, 0).reshape(-1)].add(
                (sids >= 0).reshape(-1).astype(cursors.dtype))
            occur_all = jax.lax.all_gather(occur_local, "dp")        # [dp, G]
            my_dp = jax.lax.axis_index("dp")
            prefix = jnp.sum(jnp.where(
                jnp.arange(dp_size)[:, None] < my_dp, occur_all, 0), axis=0)
            is_rr = strategy == STRATEGY_ROUND_ROBIN
            sp = pick_members(tables.subs,
                              cursors + jnp.where(is_rr, prefix, 0),
                              sids, strategy, msg_hash)
            total_occur = occur_all.sum(axis=0)
            new_cursors = jnp.where(is_rr, cursors + total_occur, cursors)

        overflow = mr.overflow | fr.overflow | slot_oflow
        res = RouteResult(
            matches=mr.matches, match_counts=mr.counts,
            rows=fr.rows, opts=fr.opts, fan_counts=fr.counts,
            shared_sids=sids, shared_rows=sp.rows, shared_opts=sp.opts,
            overflow=overflow, new_cursors=new_cursors, occur=total_occur)
        # per-topic outputs gain a 'route' axis at dim 1; cursor state keeps
        # its leading 'route' axis
        return RouteResult(
            matches=res.matches[:, None], match_counts=res.match_counts[:, None],
            rows=res.rows[:, None], opts=res.opts[:, None],
            fan_counts=res.fan_counts[:, None],
            shared_sids=res.shared_sids[:, None],
            shared_rows=res.shared_rows[:, None],
            shared_opts=res.shared_opts[:, None],
            overflow=res.overflow[:, None],
            new_cursors=res.new_cursors[None], occur=res.occur[None])

    table_spec = P("route")
    per_topic_spec = P("dp", "route")
    out_specs = RouteResult(
        matches=per_topic_spec, match_counts=per_topic_spec,
        rows=per_topic_spec, opts=per_topic_spec, fan_counts=per_topic_spec,
        shared_sids=per_topic_spec, shared_rows=per_topic_spec,
        shared_opts=per_topic_spec,
        overflow=per_topic_spec, new_cursors=table_spec, occur=table_spec)

    in_specs = (table_spec, table_spec, P("dp"), P("dp"), P("dp"), P("dp"),
                P())
    return jax.jit(_shard_map(local_step, mesh, in_specs, out_specs))


def _shard_map(fn, mesh: Mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---- device-to-device exchange stage (ISSUE 15) -------------------------

# weak refs: the registry must not pin compiled programs (and their
# captured meshes) past their owning server's life — it exists only so
# compile_stats can read live cache sizes
_EXCHANGE_STEPS: dict = {}      # seq -> weakref to jitted exchange fn
_EXCHANGE_SEQ = [0]


def _register_exchange_step(fn) -> None:
    import weakref
    seq = _EXCHANGE_SEQ[0]
    _EXCHANGE_SEQ[0] += 1
    try:
        _EXCHANGE_STEPS[seq] = weakref.ref(
            fn, lambda _r, s=seq: _EXCHANGE_STEPS.pop(s, None))
    except TypeError:           # not weakrefable on this jax: skip stats
        pass


def exchange_compile_stats() -> dict:
    """Jit-cache entry counts of LIVE exchange programs, folded into
    models.router_engine.compile_stats' recompile accounting."""
    out: dict = {}
    for seq, ref in sorted(_EXCHANGE_STEPS.items()):
        fn = ref()
        if fn is None:
            continue
        try:
            out[f"exchange_step_{seq}"] = fn._cache_size()
        except Exception:  # noqa: BLE001 — introspection is best-effort
            pass
    return out


def ring_rotate(block, k: int, axis_name: str, size: int):
    """Rotate `block` k hops around the `axis_name` ring (inside a
    shard_map): every participant receives the block held by the
    participant k positions to its LEFT ((my - k) % size), i.e. each
    device SENDS to (my + k) % size. XLA lowers the permutation to its
    collective-permute, device-to-device over the interconnect."""
    with jax.named_scope("exchange"):
        return jax.lax.ppermute(
            block, axis_name, [(j, (j + k) % size) for j in range(size)])


def make_exchange_step(mesh: Mesh, *, seg_cap: int):
    """Build the jitted exchange program for `mesh` ('dp', 'route').

    Runs as a SECOND shard_map dispatch over the route step's result
    planes (mesh-colocated: launch cost is microseconds — the same
    posture as the CSR compaction's second call). Per (dp, route)
    device it

      1. flags its local messages clean/slow (capacity overflow, a
         shared-slot hit, or a matched fid on the slow mask) and
         psum-combines the verdict across 'route' — a message is clean
         only if EVERY shard saw it clean;
      2. attributes each fan-out row to its matched fid (the same
         flat-searchsorted trick as ops.compact), packs
         (msg, sid, gfid | opt << 24) records per OWNING delivery
         shard (sid % R — the PR 5 session-affinity discipline) into
         fixed-capacity segments [R, E, 3] with counted overflow;
      3. ring-rotates the segments R-1 rounds over 'route'
         (ring_rotate: a collective-permute) so device (dp, d) ends up
         holding exactly the rows whose sessions it owns, from every
         source shard;
      4. merges the received segments source-major into ONE per-dest
         plan [E, 3] — (src asc, msg asc, row asc), the host gather
         path's exact per-session interleaving.

    Segment counts ride one tiny all_gather (control plane, 4 bytes per
    src×dst pair); the payload moves only on the ring. `seg_cap` (E) is
    a static capacity class — callers quantize it onto a ladder sized
    by an EWMA of observed per-dest row counts, and a window outgrowing
    its class reports ok&2 == 0 (the host gathers that window instead;
    correctness never depends on the class fitting).

    Call signature of the returned fn:
      exch(matches [B,R,M], rows [B,R,F], opts [B,R,F],
           shared_sids [B,R,K], overflow [B,R],
           aux: ExchangeAux ([R,Fc], [R,Fc], [R])) -> ExchangeResult
    """
    from emqx_tpu.ops.compact import _rows_searchsorted
    R = mesh.shape["route"]
    E = int(seg_cap)

    def local(matches, rows, opts, shared_sids, overflow,
              seg_len, fid_slow, fid_off):
        matches = matches[:, 0]            # [b, M] this shard's slice
        rows_l = rows[:, 0]                # [b, F]
        opts_l = opts[:, 0]
        shared_l = shared_sids[:, 0]       # [b, K]
        ovf_l = overflow[:, 0]             # [b]
        seg_len_l = seg_len[0]             # [Fc]
        fid_slow_l = fid_slow[0]
        fid_off_l = fid_off[0]             # scalar
        b, M = matches.shape
        F = rows_l.shape[1]
        my_r = jax.lax.axis_index("route")
        my_dp = jax.lax.axis_index("dp")

        # 1. clean verdict, combined across every route shard
        valid_m = matches >= 0
        mc = jnp.clip(matches, 0)
        slowfid = jnp.where(valid_m, fid_slow_l[mc], False).any(-1)
        bad_local = ovf_l | (shared_l >= 0).any(-1) | slowfid
        bad = jax.lax.psum(bad_local.astype(jnp.int32), "route") > 0

        # 2. row -> fid attribution + per-dest segment pack
        sl = jnp.where(valid_m, seg_len_l[mc], 0).astype(jnp.int32)
        ends = jnp.cumsum(sl, axis=-1)                        # [b, M]
        js = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32), (b, F))
        fidx = jnp.minimum(_rows_searchsorted(ends, js, F + 1), M - 1)
        gfid = jnp.take_along_axis(mc, fidx, axis=-1) + fid_off_l
        total = ends[:, -1:]
        valid_row = (js < total) & (rows_l >= 0)
        msg = my_dp * b + jnp.arange(b, dtype=jnp.int32)[:, None]
        word2 = gfid | ((opts_l.astype(jnp.int32) & 0x3F) << 24)
        dest = jnp.where(valid_row, rows_l % R, -1)

        n = b * F
        flat_dest = dest.reshape(n)
        flat_msg = jnp.broadcast_to(msg, (b, F)).reshape(n)
        flat_sid = rows_l.reshape(n)
        flat_w2 = word2.reshape(n)
        ks = jnp.arange(1, E + 1, dtype=jnp.int32)
        slot_valid = jnp.arange(E, dtype=jnp.int32)
        segs = []
        cnts = []
        pair_ovf = jnp.zeros((), bool)
        for d in range(R):                 # static, R is small
            m_d = flat_dest == d
            cnt = m_d.sum(dtype=jnp.int32)
            cum = jnp.cumsum(m_d.astype(jnp.int32))
            pos = jnp.minimum(
                jnp.searchsorted(cum, ks, side="left").astype(jnp.int32),
                n - 1)
            rec = jnp.stack([flat_msg[pos], flat_sid[pos],
                             flat_w2[pos]], axis=-1)          # [E, 3]
            k_ok = slot_valid < jnp.minimum(cnt, E)
            segs.append(jnp.where(k_ok[:, None], rec, -1))
            cnts.append(cnt)
            pair_ovf = pair_ovf | (cnt > E)
        seg = jnp.stack(segs)                                 # [R, E, 3]
        cnts = jnp.stack(cnts)                                # [R]

        # 3. ring rotation: after R-1 rounds, recv[s] holds the block
        # source shard s packed for dest my_r
        cnt_all = jax.lax.all_gather(cnts, "route")       # [R_src, R_dst]
        own = jax.lax.dynamic_index_in_dim(seg, my_r, 0, keepdims=False)
        recv = jax.lax.dynamic_update_index_in_dim(
            jnp.full((R, E, 3), -1, jnp.int32), own, my_r, 0)
        for k in range(1, R):
            send = jax.lax.dynamic_index_in_dim(
                seg, jax.lax.rem(my_r + k, R), 0, keepdims=False)
            got = ring_rotate(send, k, "route", R)
            recv = jax.lax.dynamic_update_index_in_dim(
                recv, got, jax.lax.rem(my_r - k + R, R), 0)

        # 4. source-major merge into the per-dest delivery plan
        src_cnt = jnp.minimum(jnp.take(cnt_all, my_r, axis=1), E)  # [R]
        ends_s = jnp.cumsum(src_cnt)
        starts = ends_s - src_cnt
        tot = ends_s[-1]
        c = jnp.arange(E, dtype=jnp.int32)
        src_of = jnp.minimum(
            jnp.searchsorted(ends_s, c, side="right").astype(jnp.int32),
            R - 1)
        plan = recv[src_of, jnp.clip(c - starts[src_of], 0, E - 1)]
        plan_ok = c < jnp.minimum(tot, E)
        plan = jnp.where(plan_ok[:, None], plan, -1)
        ok = (jnp.where(bad.any(), 0, 1)
              | jnp.where(pair_ovf | (tot > E), 0, 2)).astype(jnp.int32)
        return ExchangeResult(
            plan=plan[None, None],
            plan_cnt=jnp.minimum(tot, E)[None, None],
            src_cnt=src_cnt[None, None],
            ok=ok[None, None])

    per_dev = P("dp", "route")
    aux_spec = P("route")
    in_specs = (per_dev, per_dev, per_dev, per_dev, per_dev,
                aux_spec, aux_spec, aux_spec)
    out_specs = ExchangeResult(plan=per_dev, plan_cnt=per_dev,
                               src_cnt=per_dev, ok=per_dev)
    fn = jax.jit(_shard_map(local, mesh, in_specs, out_specs))
    _register_exchange_step(fn)
    return fn
