#!/usr/bin/env python
"""One-shot TPU measurement matrix, in ONE process (a chip belongs to
one process at a time). Exits non-zero without a TPU or when any
section fails.

Covers, in order of importance:
  1. fold backends: xla vs lane-major pallas (match-only window)
  2. rank-scan block-width sweep (the sort-free kernel's knob)
  3. fuse-width sweep (per-dispatch overhead amortization curve)

Prints a JSON summary line at the end; everything logs to stderr as it
goes.

Usage: python tools/tpu_matrix.py [subs] [batch]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    subs = int(sys.argv[1]) if len(sys.argv) > 1 else 10_000_000
    B = int(sys.argv[2]) if len(sys.argv) > 2 else 131072

    import jax
    import jax.numpy as jnp

    from bench import (bench_subtable, device_filter_set,
                       device_topic_batch, make_window_runner,
                       require_tpu)
    from emqx_tpu.models.router_engine import ShapeRouterTables
    from emqx_tpu.ops.shapes import (build_shape_tables, shape_match,
                                     shape_match_pallas)
    from emqx_tpu.ops.shared import STRATEGY_ROUND_ROBIN

    out = {"subs": subs, "batch": B, "device": require_tpu()}
    log(f"matrix: {out}")

    fs = device_filter_set(subs)
    t0 = time.time()
    shapes = build_shape_tables(fs["rows"], fs["lens"])
    out["table_build_s"] = round(time.time() - t0, 2)
    out["table_mb"] = round(sum(np.asarray(v).nbytes
                                for v in shapes) / 1e6)
    log(f"build {out['table_build_s']}s {out['table_mb']}MB")

    subs_tbl, n_groups = bench_subtable(fs["ids"] * fs["nums"], 50)
    tables = jax.device_put(ShapeRouterTables(shapes=shapes,
                                              subs=subs_tbl))
    jax.block_until_ready(tables)
    cursors0 = jax.device_put(np.zeros(n_groups, np.int32))
    strat = jax.device_put(np.int32(STRATEGY_ROUND_ROBIN))
    rng = np.random.RandomState(7)
    staged = []
    for _ in range(8):
        tp, tl = device_topic_batch(fs, rng, B)
        staged.append((jax.device_put(tp), jax.device_put(tl),
                       jax.device_put(np.zeros(B, bool)),
                       jax.device_put(rng.randint(0, 1 << 30, B)
                                      .astype(np.int32))))
    log("staged")

    # ---- 1. fold backends --------------------------------------------
    def match_window(fn, n=16):
        acc = jax.device_put(np.int32(0))
        t0 = time.time()
        for i in range(n):
            t_, l_, d_, _ = staged[i % 8]
            r = fn(tables.shapes, t_, l_, d_)
            acc = acc + r.matches.sum(dtype=jnp.int32)
        _ = int(np.asarray(acc))
        return B * n / (time.time() - t0)

    rx = shape_match(tables.shapes, *staged[0][:3])
    rp = shape_match_pallas(tables.shapes, *staged[0][:3])
    out["pallas_bit_identical"] = bool(
        (np.asarray(rx.matches) == np.asarray(rp.matches)).all())
    match_window(shape_match, 2)
    match_window(shape_match_pallas, 2)
    out["match_xla_per_s"] = round(match_window(shape_match))
    out["match_pallas_per_s"] = round(match_window(shape_match_pallas))
    log(f"fold: xla {out['match_xla_per_s']/1e6:.1f}M/s "
        f"pallas {out['match_pallas_per_s']/1e6:.1f}M/s "
        f"identical={out['pallas_bit_identical']}")

    # ---- 3. fuse-width sweep (also yields the headline number) -------
    out["fuse_sweep"] = {}
    for fuse in (1, 2, 4, 8, 16):
        stacked = tuple(jnp.stack([staged[k % 8][i] for k in range(fuse)])
                        for i in range(4))
        run = make_window_runner(tables, cursors0, strat, stacked, 4, 2)
        run(1)
        n_calls = max(1, 32 // fuse)
        dt = run(n_calls)
        per_s = B * fuse * n_calls / dt
        out["fuse_sweep"][str(fuse)] = round(per_s)
        log(f"fuse={fuse}: {per_s/1e6:.2f}M matches/s "
            f"({dt/ (n_calls*fuse) * 1000:.2f}ms/batch)")
    out["value"] = max(out["fuse_sweep"].values())

    # ---- 2. rank-block sweep (in-process: block width is a static
    # jit arg, so one process covers the whole curve) ------------------
    import functools

    from emqx_tpu.ops.fanout import shared_slots
    from emqx_tpu.ops.shared import _rank_and_occur_blocked

    @jax.jit
    def mk_sids(tb, t, l, d):
        r = shape_match(tb.shapes, t, l, d)
        s, _ = shared_slots(tb.subs, r.matches, slot_cap=2)
        return s

    sids_staged = [mk_sids(tables, *staged[i][:3]) for i in range(8)]
    jax.block_until_ready(sids_staged)
    out["rank_sweep"] = {}
    for blk in (256, 512, 1024, 2048, 4096):
        f = jax.jit(functools.partial(
            _rank_and_occur_blocked, n_slots=n_groups, block=blk))
        def run_rank(n):
            acc = jax.device_put(np.int32(0))
            t0 = time.time()
            for i in range(n):
                r, oc = f(sids_staged[i % 8])
                acc = acc + r.sum(dtype=jnp.int32) \
                    + oc.sum(dtype=jnp.int32)
            _ = int(np.asarray(acc))
            return time.time() - t0
        run_rank(2)
        ms = run_rank(16) / 16 * 1000
        out["rank_sweep"][str(blk)] = round(ms, 2)
        log(f"rank block={blk}: {ms:.2f} ms/batch")
    out["rank_block"] = int(os.environ.get("EMQX_TPU_RANK_BLOCK", 512))

    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
