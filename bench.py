#!/usr/bin/env python
"""Raw-kernel and end-to-end measurements on the TPU.

Measures the fused shape-hash route step (shape-directed match + subscriber
fan-out + shared-sub selection) against the BASELINE.md target: >=5M
topic-matches/sec at 10M wildcard subscriptions on one v5e-1.

Filter shape mirrors the reference's own bench harness
(emqx_broker_bench.erl:25-34 `device/{{id}}/+/{{num}}/#`), scaled to
BENCH_SUBS subscriptions; BENCH_SHARED_PCT puts that share of subscriptions
into $share groups (BASELINE.md config 4).

One process holds the chip: this script starts JAX itself, names the
device in its JSON and exits non-zero when JAX finds no TPU (`--phase0`
and the full run; the `--latency-probe` / `--skew` / `--churn`
modes are CPU correctness drives and run anywhere). The
full run's `cpu_*` rows are `JAX_PLATFORMS=cpu` children — correctness
rows, never speeds; they keep the chip free for this process. One JSON
line is printed on stdout; a failed phase records `<phase>_error` in it
and makes the exit code non-zero.

Rebuilding this into a table of cells is ROADMAP Speed 1.

Throughput is measured as a pipelined window of route steps closed by one
scalar readback (total wall time / topics routed), which is also how the
broker consumes the device (queue batches, read back deliveries). The
per-batch sync round-trip is reported separately.

Env knobs: BENCH_SUBS (default 10_000_000), BENCH_BATCH (131072),
BENCH_WINDOW (32), BENCH_SHARED_PCT (50); BENCH_<ROW>=0 skips a row
(PHASE0, HBM, LATENCY0, CONFIGS, CONFIG5, E2E, SHARDED, SKEW, CHURN,
COVER, FANOUT, INGRESS, OVERLOAD); each tools/*_bench.py row reads its
own knobs.

Diagnosability: every e2e phase snapshots the node's pipeline telemetry
(stage timings, batch occupancy, compile counts —
broker.telemetry.PipelineTelemetry.snapshot()) into the result row.
`phase_wall_s` records where the minutes went, `phase_memory` the
backend's memory_stats() after each phase, `memory` the newest HBM-ledger
section.
"""

import json
import os
import subprocess
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# newest pipeline-telemetry snapshot / HBM-ledger section taken this run
# (set by run_e2e, success or failure) — embedded in the JSON so a run
# that failed after real traffic still carries its stage-level diagnosis
_LAST_TELEMETRY = None
_LAST_MEMORY = None
# per-phase wall seconds and end-of-phase memory_stats()
_PHASE_WALL: dict = {}
_PHASE_MEM: dict = {}


def _mem_row(node=None):
    """One memory accounting row: the HBM ledger's `memory` section
    when `node` carries a ledger (it embeds the device stats), else
    the bare backend memory_stats(); None when neither exists."""
    from emqx_tpu.broker.hbm_ledger import device_memory_stats
    ledger = getattr(node, "hbm_ledger", None) if node is not None else None
    if ledger is not None:
        return ledger.section()
    dev = device_memory_stats()
    return {"device": dev} if dev else None


class _phase_clock:
    """Context manager stamping one phase's wall seconds into
    _PHASE_WALL (and its end-of-phase memory row into _PHASE_MEM)
    whether the phase returns or raises."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *exc):
        _PHASE_WALL[self.name] = round(time.time() - self.t0, 1)
        mem = _mem_row()
        if mem:
            _PHASE_MEM[self.name] = mem
        return False


def device_row() -> dict:
    """The device as JAX reports it — embedded in every JSON line."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu() -> dict:
    """Start JAX in this process and refuse anything but a TPU: a rate
    from the CPU backend is not a device metric."""
    from emqx_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    dev = device_row()
    if dev["platform"] != "tpu":
        print(json.dumps({"error": "JAX found no TPU", "device": dev}),
              flush=True)
        sys.exit(2)
    return dev


def profile_device_step(run_fn, match_name: str) -> dict:
    """Capture a jax.profiler trace around `run_fn()` and extract the
    on-device execution durations of the jitted step (events named after
    the jitted function on the device tracks) -> device_step_p50/p99_ms.

    This decomposes the sync round-trip latency into device time vs
    dispatch overhead. Returns {} when the trace has no matching device
    events.
    """
    import glob
    import gzip
    import json
    import shutil
    import tempfile

    import jax

    tmp = tempfile.mkdtemp(prefix="jaxprof-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            run_fn()
        finally:
            jax.profiler.stop_trace()
        durs_by_track: dict = {}
        for path in glob.glob(tmp + "/**/*.trace.json.gz", recursive=True):
            with gzip.open(path, "rt") as f:
                data = json.load(f)
            pids = {}
            for ev in data.get("traceEvents", []):
                if ev.get("ph") == "M" and ev.get("name") == "process_name":
                    pids[ev.get("pid")] = ev.get("args", {}).get("name", "")
            for ev in data.get("traceEvents", []):
                if ev.get("ph") != "X":
                    continue
                name = ev.get("name", "")
                if match_name not in name:
                    continue
                track = pids.get(ev.get("pid"), "")
                durs_by_track.setdefault(track, []).append(
                    ev.get("dur", 0) / 1000.0)        # us -> ms
        if not durs_by_track:
            return {}
        # prefer a device track (TPU/accelerator); fall back to any
        def track_rank(t):
            tl = t.lower()
            if "tpu" in tl or "device" in tl or "xla" in tl and \
                    "host" not in tl:
                return 0
            return 1
        track = sorted(durs_by_track, key=track_rank)[0]
        durs = sorted(durs_by_track[track])
        if not durs:
            return {}
        return {
            "device_step_p50_ms": round(durs[len(durs) // 2], 3),
            "device_step_p99_ms": round(
                durs[min(len(durs) - 1, int(len(durs) * 0.99))], 3),
            "device_step_track": track,
            "device_step_samples": len(durs),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)



def device_filter_set(subs: int):
    """The reference harness's device/{{id}}/+/{{num}}/# filter set scaled
    to `subs` (emqx_broker_bench.erl:25-34) — the ONE canonical workload
    generator, shared by the main bench and the config-3 suite row so the
    two can never silently measure different workloads."""
    from emqx_tpu.ops import intern as I
    ids = max(64, int(np.sqrt(subs)))
    nums = max(1, subs // ids)
    F = ids * nums
    intern = I.InternTable()
    wd = intern.intern("device")
    id_ids = np.array([intern.intern(f"d{i}") for i in range(ids)], np.int32)
    num_ids = np.array([intern.intern(f"n{n}") for n in range(nums)],
                       np.int32)
    rows = np.zeros((F, 8), np.int32)
    lens = np.full(F, 5, np.int64)
    rows[:, 0] = wd
    rows[:, 1] = np.repeat(id_ids, nums)
    rows[:, 2] = I.PLUS
    rows[:, 3] = np.tile(num_ids, ids)
    rows[:, 4] = I.HASH
    return {"intern": intern, "rows": rows, "lens": lens, "ids": ids,
            "nums": nums, "id_ids": id_ids, "num_ids": num_ids, "wd": wd}


def device_topic_batch(fs: dict, rng, B: int):
    """One Zipf-skewed publish batch; every topic matches exactly one
    filter of device_filter_set (fid = id*nums + num)."""
    intern = fs["intern"]
    x = intern.intern("x")
    tail = intern.intern("t")
    zipf = np.minimum(rng.zipf(1.3, size=B) - 1, fs["ids"] - 1)
    tp = np.zeros((B, 8), np.int32)
    tp[:, 0] = fs["wd"]
    tp[:, 1] = fs["id_ids"][zipf]
    tp[:, 2] = x
    tp[:, 3] = fs["num_ids"][rng.randint(0, fs["nums"], B)]
    tp[:, 4] = tail
    return tp, np.full(B, 5, np.int32)



def make_window_runner(tables, cursors0, strat, stacked,
                       fan_cap: int, slot_cap: int):
    """The ONE fused-window timing kernel, shared by the main bench and
    the config suite (so the two can never measure different work).
    Returns run(n_calls) -> seconds: dispatches the W-fused window
    n_calls times with cursors threaded call-to-call, closed by a single
    scalar readback. Tables/batches ride as jit arguments — closing over
    them would bake the bucket table into the HLO."""
    import jax
    import jax.numpy as jnp

    from emqx_tpu.models.router_engine import route_window_shapes

    @jax.jit
    def wd(tb, cur, acc, topics, lens_, dollar, hashes):
        new_cur, digests = route_window_shapes(
            tb, cur, topics, lens_, dollar, hashes, strat,
            fanout_cap=fan_cap, slot_cap=slot_cap)
        return new_cur, acc + digests.sum(dtype=jnp.int32)

    def run(n_calls: int) -> float:
        cur = cursors0
        acc = jax.device_put(np.int32(0))
        t0 = time.time()
        for _ in range(n_calls):
            cur, acc = wd(tables, cur, acc, *stacked)
        _ = int(np.asarray(acc))  # one scalar D2H closes the window
        return time.time() - t0

    return run



def bench_subtable(F: int, shared_pct: int):
    """The ONE bench subscriber table (one subscriber per filter, the
    first shared_pct%% of filters also in 16-filter/8-member $share
    groups) — shared by run_bench and run_phase0 so the phase-0 number
    is a scaled-down point on the SAME workload curve, never a silently
    different one. Returns (SubTable, n_groups)."""
    from emqx_tpu.ops.fanout import SubTable
    n_shared_filters = F * shared_pct // 100
    sub_start = np.arange(F + 1, dtype=np.int32)
    sub_row = np.arange(F, dtype=np.int32)
    sub_opts = np.ones(F, np.int8)
    group_of = np.arange(n_shared_filters, dtype=np.int32) // 16
    n_groups = max(1, int(group_of.max(initial=0)) + 1)
    fs_start = np.zeros(F + 1, np.int32)
    fs_start[1:n_shared_filters + 1] = 1
    np.cumsum(fs_start, out=fs_start)
    fs_slot = group_of if n_shared_filters else np.full(1, -1, np.int32)
    shared_start = np.arange(n_groups + 1, dtype=np.int32) * 8
    shared_row = F + np.arange(n_groups * 8, dtype=np.int32)
    shared_opts = np.ones(n_groups * 8, np.int8)
    return SubTable(sub_start, sub_row, sub_opts, fs_start, fs_slot,
                    shared_start, shared_row, shared_opts), n_groups



def run_phase0(shared_pct: int = 50) -> dict:
    """Minutes-scale raw fused-window measurement at 100k subs: table
    build + upload + one compile + a timed window, no tuning sweeps, no
    profiling, no config suites (`python bench.py --phase0`).

    Same workload generator (device_filter_set) and the same fused
    timing kernel (make_window_runner) as the main bench — a scaled-down
    point on the same curve, labeled with its own metric name so it can
    never be mistaken for the headline scale.
    """
    import jax

    from emqx_tpu.models.router_engine import ShapeRouterTables
    from emqx_tpu.ops.shapes import build_shape_tables
    from emqx_tpu.ops.shared import STRATEGY_ROUND_ROBIN

    t_start = time.time()
    subs = int(os.environ.get("BENCH_PHASE0_SUBS", 100_000))
    B = int(os.environ.get("BENCH_PHASE0_BATCH", 16384))
    window = int(os.environ.get("BENCH_PHASE0_WINDOW", 8))
    fs = device_filter_set(subs)
    rows, lens = fs["rows"], fs["lens"]
    F = fs["ids"] * fs["nums"]
    shapes = build_shape_tables(rows, lens)

    subs_tbl, n_groups = bench_subtable(F, shared_pct)
    tables = jax.device_put(
        ShapeRouterTables(shapes=shapes, subs=subs_tbl))
    jax.block_until_ready(tables)
    cursors0 = jax.device_put(np.zeros(n_groups, np.int32))
    strat = jax.device_put(np.int32(STRATEGY_ROUND_ROBIN))

    import jax.numpy as jnp
    rng = np.random.RandomState(7)
    FUSE = min(4, window)
    staged = []
    for _ in range(FUSE):
        tp, tl = device_topic_batch(fs, rng, B)
        staged.append((jax.device_put(tp), jax.device_put(tl),
                       jax.device_put(np.zeros(B, bool)),
                       jax.device_put(rng.randint(0, 1 << 30, B)
                                      .astype(np.int32))))
    stacked = tuple(jnp.stack([staged[k][i] for k in range(FUSE)])
                    for i in range(4))
    runner = make_window_runner(tables, cursors0, strat, stacked,
                                int(os.environ.get("BENCH_FANOUT_CAP", 4)),
                                int(os.environ.get("BENCH_SLOT_CAP", 2)))
    runner(1)                       # compile
    window = max(FUSE, window - window % FUSE)
    dt = runner(window // FUSE)
    mps = B * window / dt
    log(f"phase0: {mps / 1e6:.2f}M topic-matches/s "
        f"({window} batches of {B} at {subs} subs, "
        f"{time.time() - t_start:.0f}s total)")
    return {
        "metric": "topic_matches_per_sec_phase0",
        "value": round(mps),
        "unit": "topic-matches/s",
        "subs": subs,
        "batch": B,
        "window": window,
        "fuse": FUSE,
        "elapsed_s": round(time.time() - t_start, 1),
        "note": ("phase-0 incremental headline at reduced scale; the "
                 "main metric row is the authoritative number when "
                 "present"),
    }



def run_bench(subs: int, B: int, window: int, shared_pct: int) -> dict:
    import jax

    from emqx_tpu.models.router_engine import (ShapeRouterTables,
                                               route_step_shapes)
    from emqx_tpu.ops.shapes import build_shape_tables
    from emqx_tpu.ops.shared import STRATEGY_ROUND_ROBIN

    log(f"bench: subs={subs} batch={B} window={window} shared={shared_pct}% "
        f"device={jax.devices()[0]}")

    # --- filter set: device/{id}/+/{num}/#  ------------------------------
    fs = device_filter_set(subs)
    intern, rows, lens = fs["intern"], fs["rows"], fs["lens"]
    ids, nums = fs["ids"], fs["nums"]
    F = ids * nums

    t0 = time.time()
    shapes = build_shape_tables(rows, lens)
    t_build = time.time() - t0
    table_mb = sum(np.asarray(v).nbytes for v in shapes) / 1e6
    log(f"shape-table build: {t_build:.1f}s, shapes={int(shapes.n_shapes)}, "
        f"buckets={shapes.buckets.shape[0]}, {table_mb:.0f}MB")

    # --- subscriber table ------------------------------------------------
    subs_tbl, n_groups = bench_subtable(F, shared_pct)

    t0 = time.time()
    tables = jax.device_put(ShapeRouterTables(shapes=shapes, subs=subs_tbl))
    jax.block_until_ready(tables)
    log(f"upload: {time.time() - t0:.1f}s")
    cursors0 = jax.device_put(np.zeros(n_groups, np.int32))
    strat = jax.device_put(np.int32(STRATEGY_ROUND_ROBIN))

    # --- pre-staged publish batches (Zipf-skewed device ids) -------------
    rng = np.random.RandomState(7)
    staged = []
    for k in range(8):
        tp, tl = device_topic_batch(fs, rng, B)
        staged.append((jax.device_put(tp),
                       jax.device_put(tl),
                       jax.device_put(np.zeros(B, bool)),
                       jax.device_put(rng.randint(0, 1 << 30, B)
                                      .astype(np.int32))))

    # capacity classes sized to the workload (the broker's device_engine
    # quantizes the same way; overflow topics fall back to the host):
    # every bench topic matches exactly one filter -> 1 normal subscriber
    # + at most 1 shared slot. Generic caps of 16/4 paid 4-16x the
    # bandwidth in fan-out/shared lanes for nothing.
    FAN_CAP = int(os.environ.get("BENCH_FANOUT_CAP", 4))
    SLOT_CAP = int(os.environ.get("BENCH_SLOT_CAP", 2))

    # --- rank-block self-tune (accelerators only) ------------------------
    # The sort-free rank kernel's block width is hardware-specific, so
    # pick it HERE, before the main step traces (set_rank_block only
    # affects programs traced after it). Explicit EMQX_TPU_RANK_BLOCK or
    # BENCH_TUNE_RANK=0 skips the sweep.
    import functools

    import jax.numpy as jnp

    from emqx_tpu.ops import shared as SH
    rank_tune: dict = {}
    tune_mode = os.environ.get("BENCH_TUNE_RANK", "1")
    if ((jax.default_backend() != "cpu" or tune_mode == "force")
            and "EMQX_TPU_RANK_BLOCK" not in os.environ
            and tune_mode != "0"):
        from emqx_tpu.ops.fanout import shared_slots
        from emqx_tpu.ops.shapes import shape_match

        @jax.jit
        def _mk_sids(tb, t, l, d):
            r = shape_match(tb.shapes, t, l, d)
            s, _ = shared_slots(tb.subs, r.matches, slot_cap=SLOT_CAP)
            return s

        sids_st = [_mk_sids(tables, *staged[i][:3]) for i in range(4)]
        jax.block_until_ready(sids_st)
        best = None
        for blk in (512, 1024, 2048):
            f = jax.jit(functools.partial(
                SH._rank_and_occur_blocked, n_slots=n_groups, block=blk))

            def _run(n):
                acc = jax.device_put(np.int32(0))
                t0 = time.time()
                for i in range(n):
                    r_, oc_ = f(sids_st[i % 4])
                    acc = acc + r_.sum(dtype=jnp.int32) \
                        + oc_.sum(dtype=jnp.int32)
                _ = int(np.asarray(acc))
                return time.time() - t0
            _run(2)
            dt = _run(8) / 8 * 1000
            rank_tune[str(blk)] = round(dt, 2)
            log(f"rank tune block={blk}: {dt:.2f} ms/batch")
            if best is None or dt < rank_tune[str(best)]:
                best = blk
        if best is not None:
            SH.set_rank_block(best)
            log(f"rank block -> {best}")

    # --- fold backend chosen by DATA, before the main step traces --------
    # Both folds are oracle-tested bit-identical, so
    # this is purely a measured race: whichever wins the match-only window
    # on THIS hardware becomes the backend the serving step traces with.
    from emqx_tpu.ops import shapes as SHP
    from emqx_tpu.ops.shapes import shape_match, shape_match_pallas

    # bit-identical cross-check ALWAYS runs (an explicitly-forced
    # EMQX_TPU_FOLD=pallas must still be verified in the JSON)
    tb_, lb_, db_, _ = staged[0]
    rx = shape_match(tables.shapes, tb_, lb_, db_)
    rp = shape_match_pallas(tables.shapes, tb_, lb_, db_)
    same = bool((np.asarray(rx.matches)
                 == np.asarray(rp.matches)).all())
    explicit = os.environ.get("EMQX_TPU_FOLD")
    pallas_fields = {"pallas_bit_identical": same,
                     "fold_backend": explicit or "xla"}

    if (jax.default_backend() != "cpu" and not explicit
            and os.environ.get("BENCH_TUNE_FOLD", "1") != "0"):
        def _match_window(fn, n=16):
            acc = jax.device_put(np.int32(0))
            t0 = time.time()
            for i in range(n):
                t_, l_, d_, _ = staged[i % 8]
                r_ = fn(tables.shapes, t_, l_, d_)
                acc = acc + r_.matches.sum(dtype=np.int32)
            _ = int(np.asarray(acc))
            return B * n / (time.time() - t0)

        _match_window(shape_match, 2)          # warm
        _match_window(shape_match_pallas, 2)
        xla_ps = _match_window(shape_match)
        pallas_ps = _match_window(shape_match_pallas)
        winner = "pallas" if (same and pallas_ps > xla_ps) else "xla"
        # clears shape_match's jit cache, so the serving step's
        # trace below really picks the winner up; effective=False
        # means the clear failed and already-traced shapes may still
        # run the loser (ISSUE 2 satellite: record it, don't guess)
        SHP.set_fold_backend(winner)
        pallas_fields.update({
            "match_xla_per_s": round(xla_ps),
            "match_pallas_per_s": round(pallas_ps),
            "fold_backend": winner,
            "fold_backend_effective": SHP.fold_backend_effective(),
        })
        log(f"fold backends: xla {xla_ps / 1e6:.1f}M/s, "
            f"pallas {pallas_ps / 1e6:.1f}M/s, bit-identical={same} "
            f"-> serving step uses {winner}")

    def step(batch, cur):
        return route_step_shapes(tables, cur, *batch, strat,
                                 fanout_cap=FAN_CAP, slot_cap=SLOT_CAP)

    # warmup / compile + correctness sanity
    r = step(staged[0], cursors0)
    jax.block_until_ready(r)
    mc = int(np.asarray(r.match_counts).sum())
    fc = int(np.asarray(r.fan_counts).sum())
    sc = int((np.asarray(r.shared_rows) >= 0).sum())
    ov = int(np.asarray(r.overflow).sum())
    log(f"sanity: matches={mc}/{B}, fan={fc}, shared={sc}, overflow={ov}")
    assert mc == B, "every bench topic must match exactly one filter"

    # sync round-trip latency distribution (single blocked batches) — the
    # BASELINE.md p99 <2ms criterion is judged on this per-batch latency
    sync = []
    for k in range(30):
        t0 = time.time()
        r = step(staged[k % 8], cursors0)
        _ = np.asarray(r.match_counts)
        sync.append(time.time() - t0)
    sync.sort()
    p50_ms = sync[len(sync) // 2] * 1000
    p99_ms = sync[min(len(sync) - 1, int(len(sync) * 0.99))] * 1000
    log(f"sync round-trip: p50 {p50_ms:.1f}ms p99 {p99_ms:.1f}ms/batch")

    # pipelined window closed by one scalar readback — sustained device
    # throughput. A digest reduction over every output array forces the full
    # routing computation; the delivery arrays stay on device (the e2e
    # phases measure the readback the broker really pays).
    import jax.numpy as jnp

    # ONE dispatch per batch: the digest reduction rides inside the same
    # jitted program as the route step
    from emqx_tpu.models.router_engine import route_digest

    @jax.jit
    def step_digest(tb, cur, acc, topics, lens_, dollar, hashes):
        # tables MUST be an argument: closing over them would bake 200MB
        # of bucket constants into the HLO
        r = route_step_shapes(tb, cur, topics, lens_, dollar, hashes,
                              strat, fanout_cap=FAN_CAP,
                              slot_cap=SLOT_CAP)
        return r.new_cursors, acc + route_digest(r)

    # W-fused window: ONE dispatch routes W whole batches (lax.scan inside
    # the jitted program, models/router_engine.route_window_shapes). The
    # per-call dispatch floor is paid once per W batches. Oracle-tested
    # bit-identical to sequential steps.
    FUSE = max(1, min(int(os.environ.get("BENCH_FUSE", 8)), len(staged),
                      window))
    if window % FUSE:
        log(f"window {window} rounded to {window - window % FUSE} "
            f"(multiple of fuse={FUSE})")
    stacked = tuple(jnp.stack([staged[k][i] for k in range(FUSE)])
                    for i in range(4))

    runner = make_window_runner(tables, cursors0, strat, stacked,
                                FAN_CAP, SLOT_CAP)

    def run_window(n):
        return runner(max(1, n // FUSE))

    window = max(FUSE, window - window % FUSE)
    run_window(FUSE)  # warm
    total = run_window(window)
    per_batch = total / window
    matches_per_sec = B * window / total
    log(f"pipelined: {per_batch * 1000:.2f}ms/batch amortized, "
        f"{matches_per_sec / 1e6:.1f}M topic-matches/s "
        f"({window} batches of {B}, {FUSE} per dispatch)")

    # device-only step time via jax.profiler: decomposes the sync
    # round-trip into device execution vs dispatch overhead
    def run_single_steps(n=12):
        cur = cursors0
        acc = jax.device_put(np.int32(0))
        for i in range(n):
            cur, acc = step_digest(tables, cur, acc, *staged[i % 8])
        _ = int(np.asarray(acc))

    run_single_steps(2)   # compile outside the trace
    step_profile = profile_device_step(run_single_steps, "step_digest")
    if step_profile:
        log(f"device step: p50 {step_profile['device_step_p50_ms']}ms "
            f"p99 {step_profile['device_step_p99_ms']}ms on "
            f"{step_profile['device_step_track']!r} — dispatch adds "
            f"~{p50_ms - step_profile['device_step_p50_ms']:.1f}ms to the "
            f"sync round-trip")

    target = 5_000_000.0
    return {
        **pallas_fields,
        **step_profile,
        "metric": f"topic_matches_per_sec_at_{subs // 1_000_000}M_subs"
                  if subs >= 1_000_000 else
                  f"topic_matches_per_sec_at_{subs // 1000}k_subs",
        "value": round(matches_per_sec),
        "unit": "topic-matches/s",
        "vs_baseline": round(matches_per_sec / target, 2),
        "per_batch_ms": round(per_batch * 1000, 2),
        "sync_p50_ms": round(p50_ms, 1),
        "sync_p99_ms": round(p99_ms, 1),
        # the sync numbers above are WINDOW granularity; the per-message
        # route tail is the latency observatory's ingress→routed p99,
        # reported by the e2e rows and summarized in route_latency
        "batch": B,
        "subs": subs,
        "fuse": FUSE,
        "rank_block": SH._RANK_BLOCK,
        **({"rank_tune_ms": rank_tune} if rank_tune else {}),
        "table_build_s": round(t_build, 1),
    }



def run_baseline_configs(B: int, window: int) -> dict:
    """BASELINE.md configs 1-3 at their stated scales, each as a fused
    window over its own compiled tables (config 4 IS the main bench;
    config 5 needs a 2-node cluster and is covered functionally by
    tests/test_cluster.py + the retainer tests, not this chip bench).

    1: 1k exact-match subs, single-level topics
    2: 100k subs with '+' wildcards, 6-level hierarchy
    3: 1M subs mixed '+'/'#', Zipf-skewed publish
    """
    import jax
    import jax.numpy as jnp

    from emqx_tpu.models.router_engine import ShapeRouterTables
    from emqx_tpu.ops import intern as I
    from emqx_tpu.ops.fanout import SubTable
    from emqx_tpu.ops.shapes import build_shape_tables
    from emqx_tpu.ops.shared import STRATEGY_ROUND_ROBIN

    rng = np.random.RandomState(13)
    out = {}

    def one(name, rows, lens, topic_of):
        F = len(lens)
        shapes = build_shape_tables(rows, lens)
        subs_tbl = SubTable(
            sub_start=np.arange(F + 1, dtype=np.int32),
            sub_row=np.arange(F, dtype=np.int32),
            sub_opts=np.ones(F, np.int8),
            fs_start=np.zeros(F + 1, np.int32),
            fs_slot=np.full(1, -1, np.int32),
            shared_start=np.zeros(2, np.int32),
            shared_row=np.full(1, -1, np.int32),
            shared_opts=np.zeros(1, np.int8))
        tables = jax.device_put(
            ShapeRouterTables(shapes=shapes, subs=subs_tbl))
        jax.block_until_ready(tables)
        L = rows.shape[1]
        W = 4
        tp = np.zeros((W, B, L), np.int32)
        tl = np.zeros((W, B), np.int32)
        for w in range(W):
            enc, ls = topic_of(rng, B)
            tp[w, :, :enc.shape[1]] = enc
            tl[w] = ls
        t4 = jax.device_put(tp)
        l4 = jax.device_put(tl)
        d4 = jax.device_put(np.zeros((W, B), bool))
        h4 = jax.device_put(rng.randint(0, 1 << 30, (W, B)).astype(np.int32))
        cur = jax.device_put(np.zeros(1, np.int32))
        strat = jax.device_put(np.int32(STRATEGY_ROUND_ROBIN))
        run = make_window_runner(tables, cur, strat, (t4, l4, d4, h4),
                                 fan_cap=4, slot_cap=2)

        # sanity: every generated topic must match exactly one filter
        from emqx_tpu.ops.shapes import shape_match
        mc = int(np.asarray(shape_match(
            tables.shapes, t4[0], l4[0], d4[0]).counts).sum())
        assert mc == B, f"config {name}: {mc}/{B} topics matched"

        run(1)   # compile
        n_calls = max(1, window // W)
        dt = run(n_calls)
        per_s = B * W * n_calls / dt
        out[name] = {"subs": F, "matches_per_s": round(per_s)}
        log(f"config {name}: {per_s / 1e6:.1f}M matches/s at {F} subs")

    # config 1: 1k exact-match, single-level
    intern = I.InternTable()
    F1 = 1000
    w1 = np.array([intern.intern(f"t{i}") for i in range(F1)], np.int32)
    rows = w1[:, None]
    lens = np.ones(F1, np.int64)

    def topics1(rng, B):
        pick = rng.randint(0, F1, B)
        return w1[pick][:, None], np.ones(B, np.int32)

    one("1_exact_1k", rows, lens, topics1)

    # config 2: 100k '+'-wildcard subs, 6-level hierarchy
    # filter: a/{i}/+/b/{j}/+  — two '+' per filter, 6 levels
    intern = I.InternTable()
    n_i, n_j = 400, 250
    F2 = n_i * n_j
    wa = intern.intern("a")
    wb = intern.intern("b")
    wi = np.array([intern.intern(f"i{i}") for i in range(n_i)], np.int32)
    wj = np.array([intern.intern(f"j{j}") for j in range(n_j)], np.int32)
    rows = np.zeros((F2, 6), np.int32)
    rows[:, 0] = wa
    rows[:, 1] = np.repeat(wi, n_j)
    rows[:, 2] = I.PLUS
    rows[:, 3] = wb
    rows[:, 4] = np.tile(wj, n_i)
    rows[:, 5] = I.PLUS
    lens = np.full(F2, 6, np.int64)
    wx = intern.intern("x")

    def topics2(rng, B):
        enc = np.zeros((B, 6), np.int32)
        enc[:, 0] = wa
        enc[:, 1] = wi[rng.randint(0, n_i, B)]
        enc[:, 2] = wx
        enc[:, 3] = wb
        enc[:, 4] = wj[rng.randint(0, n_j, B)]
        enc[:, 5] = wx
        return enc, np.full(B, 6, np.int32)

    one("2_plus_100k", rows, lens, topics2)

    # config 3: 1M mixed '+'/'#', Zipf-skewed publish — the canonical
    # device_filter_set workload at 1M (same generator as the main bench)
    fs3 = device_filter_set(1_000_000)

    def topics3(rng, B):
        return device_topic_batch(fs3, rng, B)

    one("3_mixed_1M_zipf", fs3["rows"], fs3["lens"], topics3)
    return out



def run_config5(n_routes: int, n_retained: int) -> dict:
    """BASELINE config 5: 2-node cluster route-sync + retainer replay
    burst, host-side (no chip involved — this measures the replication
    and retained-message planes the reference implements with replicated
    mnesia, emqx_router.erl:251-303 / emqx_retainer_mnesia.erl:49-55).

    Reported rows:
      route_sync_per_s   bulk route-add convergence rate onto the peer
      route_sync_p50/p99_ms   single route add → visible-on-peer latency
      replay_per_s       retained replay burst rate to a late subscriber
      stated_shape       the BASELINE row-5 10M shape: measured per-route
                         cost × 10M as extrapolated wall time

    Scales via BENCH_C5_ROUTES / BENCH_C5_RETAINED (defaults 1M / 100k).
    The stated shape is 10M: that run is TIME-bound, not memory-bound —
    replication is batched (store.add_many: one RPC frame per 4096
    routes) and scale-linear (no resync storms; anti-entropy only fires
    on real loss), so the 1M default measures the same per-route cost
    the 10M shape pays; set BENCH_C5_ROUTES=10000000 to run it in full
    (≈10-12 min on one core; the section timeout scales with the
    requested count).
    """
    import asyncio

    async def go():
        from emqx_tpu.apps.retainer import Retainer
        from emqx_tpu.broker.connection import Listener
        from emqx_tpu.broker.node import Node
        from emqx_tpu.client import Client
        from emqx_tpu.cluster import ClusterNode
        from emqx_tpu.cluster.cluster import T_ROUTE

        nodes, clusters = [], []
        for i in range(2):
            node = Node(use_device=False, name=f"b{i}@127.0.0.1")
            # 1s beats: on one core a bulk route burst can hold the loop
            # for ~100ms stretches; 0.5s beats with a 2×beat timeout
            # produced false nodedowns mid-bench → purge+resync storms
            cn = ClusterNode(node, port=0, heartbeat_s=1.0)
            await cn.start()
            nodes.append(node)
            clusters.append(cn)
        await clusters[1].join(*clusters[0].address)
        out = {}
        try:
            b0 = nodes[0].broker
            tab1 = clusters[1].store.table(T_ROUTE)

            # --- bulk route-sync: n_routes wildcard filters on node 0,
            # measure convergence onto node 1's replicated table
            class Sink:
                def deliver(self, tf, msg):
                    return True

            sink = Sink()
            sid = b0.register(sink, "c5-sink")
            base = tab1.count()
            t0 = time.perf_counter()
            for i in range(n_routes):
                b0.subscribe(sid, f"c5/d{i}/+/t/#")
                if i % 256 == 255:
                    # frequent yields keep heartbeats + the replication
                    # drain timely on one core
                    await asyncio.sleep(0)
            await clusters[0].flush()
            deadline = time.perf_counter() + max(120, n_routes // 5000)
            while time.perf_counter() < deadline:
                if tab1.count() - base >= n_routes:
                    break
                await asyncio.sleep(0.05)
            dt = time.perf_counter() - t0
            synced = tab1.count() - base
            out["route_sync"] = {
                "routes": int(synced),
                "per_s": round(synced / dt),
                "wall_s": round(dt, 2),
            }
            # BASELINE row 5's stated 10M shape at the measured linear
            # per-route cost (run it in full with BENCH_C5_ROUTES=10000000)
            out["stated_shape"] = {
                "routes": 10_000_000,
                "extrapolated_wall_s": round(10_000_000 * dt / max(1, synced)),
                "measured_at": int(synced),
            }
            log(f"config5 route-sync: {synced} routes -> peer in "
                f"{dt:.2f}s ({synced / dt / 1e3:.1f}k/s; 10M shape "
                f"≈ {out['stated_shape']['extrapolated_wall_s']}s)")

            # --- single-add propagation latency (the visible tail an
            # individual SUBSCRIBE pays before cluster-wide matching)
            lats = []
            lost = 0
            for i in range(100):
                f = f"c5lat/{i}/+"
                t1 = time.perf_counter()
                b0.subscribe(sid, f)
                await clusters[0].flush()
                # bounded per-add: one lost replication event must not
                # spin this loop into the section watchdog and discard
                # the rows already measured
                lim = t1 + 5.0
                while not tab1.lookup(f):
                    if time.perf_counter() > lim:
                        lost += 1
                        break
                    await asyncio.sleep(0)
                else:
                    lats.append(time.perf_counter() - t1)
            lats.sort()
            if lats:
                out["route_sync_p50_ms"] = round(
                    lats[len(lats) // 2] * 1000, 2)
                out["route_sync_p99_ms"] = round(
                    lats[min(len(lats) - 1,
                             int(len(lats) * 0.99))] * 1000, 2)
                log(f"config5 single-add: "
                    f"p50 {out['route_sync_p50_ms']}ms "
                    f"p99 {out['route_sync_p99_ms']}ms")
            if lost:
                out["route_sync_lost"] = lost
                log(f"config5 single-add: {lost} adds never replicated")

            # --- retainer replay burst: n_retained retained messages,
            # then a late wildcard subscriber over a REAL socket replays
            # them all
            ret = nodes[0].register_app(Retainer(nodes[0]).load())
            lst = Listener(nodes[0], bind="127.0.0.1", port=0)
            await lst.start()
            pub = Client(port=lst.port, clientid="c5-pub")
            await pub.connect()
            for i in range(n_retained):
                await pub.publish(f"c5r/{i % 64}/k{i}", b"retained-%d" % i,
                                  qos=0, retain=True)
                if i % 512 == 511:
                    await asyncio.sleep(0)
            # settle: retained table write-behind
            for _ in range(600):
                if len(ret.storage) >= n_retained:
                    break
                await asyncio.sleep(0.05)
            sub = Client(port=lst.port, clientid="c5-sub")
            await sub.connect()
            t2 = time.perf_counter()
            await sub.subscribe("c5r/#", qos=0, timeout=60)
            got = 0
            deadline = time.perf_counter() + 120
            while got < n_retained and time.perf_counter() < deadline:
                try:
                    await sub.recv(timeout=5)
                    got += 1
                except asyncio.TimeoutError:
                    break
            dt2 = time.perf_counter() - t2
            out["retainer_replay"] = {
                "retained": int(got),
                "per_s": round(got / dt2) if dt2 > 0 else 0,
                "wall_s": round(dt2, 2),
            }
            log(f"config5 replay: {got}/{n_retained} retained in "
                f"{dt2:.2f}s ({got / max(dt2, 1e-9) / 1e3:.1f}k/s)")
            await pub.disconnect()
            await sub.disconnect()
            await lst.stop()
        finally:
            for cn in clusters:
                try:
                    await cn.stop()
                except Exception:   # noqa: BLE001 — teardown best-effort
                    pass
        return out

    return asyncio.run(go())



def run_e2e(n_filters: int, n_sub_conns: int, n_pub_conns: int,
            msgs_per_pub: int, use_device: bool) -> dict:
    """End-to-end PUBLISH→deliver over real TCP sockets, at BASELINE
    config 4's workload SHAPE (scaled): `BENCH_E2E_SHARED_PCT` (default
    50) percent of the wildcard filters are owned by 2-member
    $share/bg/... groups (round-robin fan-out across different
    subscriber connections — reference semantics emqx_shared_sub.erl:
    239-283), the rest are plain subscriptions; publishes carry a QoS
    mix (every 4th is QoS1, pipelined PUBACKs). Each publish matches
    exactly one filter, and a shared match delivers to exactly one
    member, so delivered == sent checks exactly-once end to end.
    Throughput = messages delivered to subscriber sockets / wall time.
    Exercises the full serving path: frame parse → channel → publish
    batcher → fused device route step (with on-device shared picks) →
    RouteResult consumption → session → serialize → socket.
    """
    import asyncio

    node_box: dict = {}

    async def go():
        from emqx_tpu.broker.connection import Listener
        from emqx_tpu.broker.node import Node
        from emqx_tpu.client import Client

        # micro-batch window ladder (BASELINE p99 criterion tuning):
        # BENCH_WINDOW_US overrides the 200µs default
        conf = {}
        wus = os.environ.get("BENCH_WINDOW_US")
        if wus:
            conf = {"broker": {"batch_window_us": int(wus)}}
        node = node_box["node"] = Node(conf or None, use_device=use_device)
        lst = Listener(node, bind="127.0.0.1", port=0)
        await lst.start()
        from emqx_tpu.mqtt import packet as P

        shared_pct = int(os.environ.get("BENCH_E2E_SHARED_PCT", 50))
        ids = max(8, int(np.sqrt(n_filters)))
        nums = max(1, n_filters // ids)

        def is_shared(i: int, n: int) -> bool:
            return (i * nums + n) % 100 < shared_pct

        subs = []
        t0 = time.time()
        opts0 = P.SubOpts(qos=0)
        opts1 = P.SubOpts(qos=1)
        n_shared = 0
        for c in range(n_sub_conns):
            cl = Client(port=lst.port, clientid=f"esub{c}")
            await cl.connect()
            batch: list = []
            # plain filters owned by this conn + the SECOND membership of
            # the previous conn's shared groups (2 members per group, on
            # different sockets, so round robin alternates sockets)
            for cc, second in ((c, False),
                               ((c - 1) % n_sub_conns, True)):
                for i in range(cc, ids, n_sub_conns):
                    for n in range(nums):
                        f = f"device/d{i}/+/n{n}/#"
                        if is_shared(i, n):
                            n_shared += not second
                            batch.append((f"$share/bg/{f}", opts1))
                        elif not second:
                            batch.append((f, opts0))
            for k in range(0, len(batch), 512):
                await cl.subscribe(batch[k:k + 512], timeout=30)
            subs.append(cl)
        log(f"e2e: {ids * nums} filters ({n_shared} in 2-member shared "
            f"groups) over {n_sub_conns} sub conns "
            f"in {time.time() - t0:.1f}s (device={use_device})")

        pubs = []
        for c in range(n_pub_conns):
            cl = Client(port=lst.port, clientid=f"epub{c}")
            await cl.connect()
            pubs.append(cl)

        # warmup: compile the route step for this capacity class before
        # the timed window, then drain the warmup deliveries
        for k in range(64):
            await pubs[0].publish(f"device/d0/x/n{k % nums}/t", b"w", qos=0)
        for _ in range(200):
            await asyncio.sleep(0.05)
            if sum(cl.messages.qsize() for cl in subs) >= 64:
                break
        for cl in subs:
            while not cl.messages.empty():
                cl.messages.get_nowait()
        if node.device_engine is not None:
            # compile the full-size batch class before the timed window
            from emqx_tpu.broker.message import make
            warm = [make("w", 0, "warmup/none/t", b"") for _ in range(1024)]
            node.device_engine.route_batch(warm)
            # ... and wait for the background window-class warm: its
            # GIL-holding traces bill to setup here, exactly as a
            # production broker warms before taking peak traffic (only
            # shapes-backend snapshots ever fuse — a trie backend would
            # spin this loop to its timeout for nothing)
            eng = node.device_engine
            if eng._built is not None and eng._built.backend == "shapes":
                for _ in range(1200):
                    if eng.max_fuse() > 1:
                        break
                    await asyncio.sleep(0.05)

        total = n_pub_conns * msgs_per_pub
        t0 = time.time()

        # event-loop responsiveness during routing (round-2 weak #3: the
        # serving path must not stall the loop): sample scheduling jitter
        # while the flood runs
        jitter: list[float] = []

        async def heartbeat():
            while True:
                h0 = time.perf_counter()
                await asyncio.sleep(0.005)
                jitter.append(time.perf_counter() - h0 - 0.005)

        hb = asyncio.get_running_loop().create_task(heartbeat())

        # PUBLISH→deliver latency measured at the CLIENT (BASELINE.md's
        # p99<2ms criterion end to end): every payload carries its send
        # perf_counter; drainers record the delta on arrival
        import struct as _struct
        delivered_n = [0]
        lat: list[float] = []

        async def drain(cl):
            while True:
                m = await cl.messages.get()
                delivered_n[0] += 1
                if len(m.payload) == 8:
                    lat.append(time.perf_counter()
                               - _struct.unpack("d", m.payload)[0])

        drainers = [asyncio.get_running_loop().create_task(drain(cl))
                    for cl in subs]

        async def flood(cl, seed, n_msgs):
            # QoS mix: every 4th publish is QoS1 with a PIPELINED ack
            # (bounded outstanding window) — an awaited round trip per
            # message would serialize the flood on the batcher window
            r = np.random.RandomState(seed)
            acks = []
            for k in range(n_msgs):
                i = int(r.randint(0, ids))
                n = int(r.randint(0, nums))
                fut = cl.publish_start(
                    f"device/d{i}/x/n{n}/t",
                    _struct.pack("d", time.perf_counter()),
                    qos=1 if k % 4 == 0 else 0)
                if fut is not None:
                    acks.append(fut)
                if len(acks) >= 256:
                    await _await_acks(acks)
                if cl.needs_drain:
                    # qos-0 pipeline contract (client.publish_start):
                    # drain every N messages so the transport buffer
                    # stays bounded — the flood's backpressure point
                    await cl.drain()
                if k % 64 == 63:
                    # independent of drain(): below the transport
                    # high-water mark drain() returns without
                    # suspending, so this is the loop's guaranteed
                    # yield (let the batcher drain)
                    await asyncio.sleep(0)
            await _await_acks(acks)

        async def _await_acks(acks):
            # bounded: one lost PUBACK must degrade the number, not hang
            # the whole measurement window (the bench would be SIGKILLed
            # with no JSON — the exact failure mode this round fixes)
            if acks:
                try:
                    await asyncio.wait_for(
                        asyncio.gather(*acks, return_exceptions=True), 30)
                except asyncio.TimeoutError:
                    log("e2e: PUBACK wait timed out; continuing")
                acks.clear()

        try:
            await asyncio.gather(*[flood(cl, 100 + c, msgs_per_pub)
                                   for c, cl in enumerate(pubs)])
            # drain: wait until all deliveries arrive (bounded)
            deadline = time.time() + 60
            while time.time() < deadline:
                if delivered_n[0] >= total:
                    break
                await asyncio.sleep(0.05)
        finally:
            hb.cancel()
        dt = time.time() - t0
        delivered = delivered_n[0]
        main_lat = sorted(lat)
        # snapshot the batcher reservoir BEFORE the ladder mixes windows
        route_lat = (node.publish_batcher.lat_percentiles()
                     if node.publish_batcher else None)

        def pct_of(ls, p):
            return round(ls[min(len(ls) - 1, int(len(ls) * p))]
                         * 1000, 2) if ls else None

        # window ladder (BASELINE p99 criterion): re-run a shorter flood
        # at descending micro-batch windows ON THE SAME node/subscriptions
        # to find the tail-vs-throughput knee without re-paying setup
        ladder_rows = []
        if use_device and node.publish_batcher is not None \
                and os.environ.get("BENCH_E2E_LADDER", "1") != "0":
            for wus_i in (200, 100, 50, 25):
                node.publish_batcher.window_s = wus_i / 1e6
                lat.clear()
                base = delivered_n[0]
                n_l = max(64, msgs_per_pub // 4)
                lt0 = time.time()
                await asyncio.gather(*[flood(cl, 7000 + wus_i + c, n_l)
                                       for c, cl in enumerate(pubs)])
                ldeadline = time.time() + 30
                want_l = base + n_l * len(pubs)
                while time.time() < ldeadline:
                    if delivered_n[0] >= want_l:
                        break
                    await asyncio.sleep(0.05)
                ldt = time.time() - lt0
                lrow = sorted(lat)
                ladder_rows.append({
                    "window_us": wus_i,
                    "per_sec": round((delivered_n[0] - base) / ldt),
                    "lat_p50_ms": pct_of(lrow, 0.50),
                    "lat_p99_ms": pct_of(lrow, 0.99),
                })
                log(f"ladder window={wus_i}us: "
                    f"{ladder_rows[-1]['per_sec']}/s "
                    f"p50={ladder_rows[-1]['lat_p50_ms']}ms "
                    f"p99={ladder_rows[-1]['lat_p99_ms']}ms")

        for d in drainers:
            d.cancel()
        for cl in pubs + subs:
            await cl.disconnect()
        await lst.stop()
        lat = main_lat

        def pct(p):
            return pct_of(lat, p)

        out_extra = {}
        if ladder_rows:
            out_extra["window_ladder"] = ladder_rows
            measured = [r for r in ladder_rows
                        if r["lat_p99_ms"] is not None]
            if measured:
                out_extra["best_window_us"] = min(
                    measured, key=lambda r: r["lat_p99_ms"])["window_us"]
        # per-stage pipeline telemetry: stage p50/p95/p99, batch
        # occupancy per shape class, compile accounting — one schema
        # shared with GET /api/v5/pipeline/stats and the benchmark
        snap = node.pipeline_telemetry.snapshot()
        out_extra["telemetry"] = snap
        # flight-recorder overlap summary (ISSUE 7), surfaced at the top
        # of the phase row: the dispatch↔materialize overlap and the top
        # bubble attributions, with the dispatch depth next to the
        # fraction so a depth-1 A/B run is distinguishable
        tr = snap.get("trace") or {}
        if use_device or tr.get("overlap") or tr.get("bubbles"):
            out_extra["overlap"] = {
                "dispatch_materialize":
                    (tr.get("overlap") or {}).get("dispatch_materialize"),
                "dispatch_depth":
                    node.publish_batcher.dispatch_depth
                    if node.publish_batcher is not None else None,
                "windows": tr.get("windows"),
                "bubbles_top": (tr.get("bubbles") or {}).get("top"),
            }
        # per-path e2e latency distribution (ISSUE 13): the
        # observatory's ingress→routed / ingress→delivered percentiles +
        # SLO burn verdict, promoted to the top of the phase row — the
        # per-message p99 (sync_p99_ms is window granularity)
        lat_sec = snap.get("latency")
        if lat_sec:
            out_extra["latency"] = lat_sec
            slo = lat_sec.get("slo") or {}
            log(f"e2e latency: ingress→routed p99 "
                f"{slo.get('routed_p99_ms')}ms / delivered p99 "
                f"{slo.get('delivered_p99_ms')}ms vs objective "
                f"{slo.get('objective_p99_ms')}ms -> "
                f"{slo.get('verdict')} "
                f"(burn {slo.get('burn')})")
        return {
            "delivered": delivered,
            "sent": total,
            "shared_pct": shared_pct,
            "qos1_pct": 25,
            "per_sec": round(delivered / dt),
            **out_extra,
            # client-observed PUBLISH→deliver latency over the whole
            # flood (includes socket + frame + batcher window + route +
            # session + serialize) — the BASELINE.md p99 criterion's
            # honest end-to-end form
            "lat_p50_ms": pct(0.50),
            "lat_p99_ms": pct(0.99),
            # batcher-internal PUBLISH→route (enqueue → batch complete)
            "route_lat": route_lat,
            "device_routed": node.metrics.val("messages.routed.device"),
            "batches": node.metrics.val("routing.device.batches"),
            # adaptive choice: batches the measured-cost router sent to
            # the host because the device round trip would have been
            # slower
            "device_bypassed": node.metrics.val("routing.device.bypassed"),
            # loop scheduling jitter while routing: the pipelined serving
            # path keeps dispatch/readback off the loop, so this stays
            # in the milliseconds even when the device round trip is slow
            "loop_jitter_p99_ms": round(sorted(jitter)[
                min(len(jitter) - 1, int(len(jitter) * 0.99))] * 1000, 1)
            if jitter else None,
        }

    global _LAST_TELEMETRY, _LAST_MEMORY
    try:
        return asyncio.run(go())
    finally:
        # success or crash, keep the newest snapshot and HBM-ledger
        # section for the JSON: what the pipeline did and what was on
        # the device when the run ended
        node = node_box.get("node")
        if node is not None:
            _LAST_TELEMETRY = node.pipeline_telemetry.snapshot()
            _LAST_MEMORY = _mem_row(node) or _LAST_MEMORY


_TOOLS = os.path.join(HERE, "tools")

# the full run's JAX_PLATFORMS=cpu child rows: (JSON key, script, argv,
# timeout seconds). CPU correctness drives — counts, twins and oracles,
# never speeds — that leave the chip to this process.
_CPU_ROWS_EARLY = (
    # a what-if forecast for a v5e-1's 16 GiB, fitted on the CPU backend
    ("cpu_hbm", os.path.join(_TOOLS, "hbm_report.py"),
     ("--budget-gb", "16"), 600),
    ("cpu_latency0", os.path.join(HERE, "bench.py"),
     ("--latency-probe",), 420),
)
_CPU_ROWS_LATE = (
    ("cpu_sharded", os.path.join(_TOOLS, "sharded_bench.py"), (), 1200),
    ("cpu_skew", os.path.join(_TOOLS, "skew_bench.py"), (), 600),
    ("cpu_churn", os.path.join(_TOOLS, "churn_bench.py"), (), 600),
    ("cpu_ingress", os.path.join(_TOOLS, "ingress_bench.py"), (), 1500),
    ("cpu_overload", os.path.join(_TOOLS, "overload_bench.py"), (), 1200),
)
# bulky sub-sections a row drops before it is embedded
_CPU_ROW_DROP = {"cpu_skew": ("telemetry",), "cpu_churn": ("overlay",)}


def _cpu_row(key: str, script: str, argv: tuple, timeout_s: int) -> dict:
    """Run one CPU correctness row as a `JAX_PLATFORMS=cpu` child and
    return its last JSON line. Raises when the child fails or prints
    none (hbm_report's exit 2 — a leak-tainted forecast — included)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with _phase_clock(key):
        sp = subprocess.run([sys.executable, script, *argv],
                            capture_output=True, text=True, env=env,
                            timeout=timeout_s)
    row = None
    for ln in reversed(sp.stdout.splitlines()):
        if ln.strip().startswith("{"):
            row = json.loads(ln)
            break
    if row is None or sp.returncode != 0:
        raise RuntimeError(f"rc={sp.returncode}: {sp.stderr[-300:]}")
    for drop in _CPU_ROW_DROP.get(key, ()):
        row.pop(drop, None)
    return row


def _latency_probe() -> dict:
    """ISSUE 13: a small real-TCP e2e flood whose only product is the
    latency observatory's per-path ingress→routed/delivered distribution
    + SLO verdict. The full run takes it as the `cpu_latency0` child."""
    os.environ.setdefault("BENCH_E2E_LADDER", "0")
    row = run_e2e(
        int(os.environ.get("BENCH_LAT_FILTERS", 256)), 4, 4,
        int(os.environ.get("BENCH_LAT_MSGS", 1600)) // 4, True)
    return {
        "metric": "latency_probe",
        "device": device_row(),
        "latency": row.get("latency"),
        "per_sec": row.get("per_sec"),
        "lat_p99_ms": row.get("lat_p99_ms"),
        "route_lat": row.get("route_lat"),
    }


def main() -> int:
    sys.path.insert(0, _TOOLS)
    if "--latency-probe" in sys.argv:
        from emqx_tpu.utils.compile_cache import configure_compile_cache
        configure_compile_cache()
        print(json.dumps(_latency_probe()), flush=True)
        return 0
    # CPU correctness microbenches; the harnesses live in tools/
    for flag, mod in (("--skew", "skew_bench"), ("--churn", "churn_bench")):
        if flag in sys.argv:
            __import__(mod).main()
            return 0

    device = require_tpu()
    shared_pct = int(os.environ.get("BENCH_SHARED_PCT", 50))
    if "--phase0" in sys.argv:
        print(json.dumps({**run_phase0(shared_pct), "device": device}),
              flush=True)
        return 0

    subs = int(os.environ.get("BENCH_SUBS", 10_000_000))
    B = int(os.environ.get("BENCH_BATCH", 131072))
    window = int(os.environ.get("BENCH_WINDOW", 32))
    result: dict = {"device": device}
    failed = []

    def phase(key: str, fn, *args):
        """Run one phase; a failure is recorded in the JSON and fails
        the run, and the phases after it still report."""
        if os.environ.get("BENCH_" + key.removeprefix("cpu_").upper(),
                          "1") == "0":
            return None
        try:
            with _phase_clock(key):
                result[key] = fn(*args)
        except Exception as e:  # noqa: BLE001 — reported, fails the run
            traceback.print_exc(file=sys.stderr)
            result[f"{key}_error"] = f"{type(e).__name__}: {str(e)[:200]}"
            failed.append(key)
            return None
        return result[key]

    phase("phase0", run_phase0, shared_pct)
    for key, script, argv, tmo in _CPU_ROWS_EARLY:
        phase(key, _cpu_row, key, script, argv, tmo)
    core = phase("core", run_bench, subs, B, window, shared_pct)
    if core is not None:
        result.update(result.pop("core"))
    phase("configs", run_baseline_configs, min(B, 32768),
          max(8, window // 4))
    phase("config5", run_config5,
          int(os.environ.get("BENCH_C5_ROUTES", 1_000_000)),
          int(os.environ.get("BENCH_C5_RETAINED", 100_000)))
    if os.environ.get("BENCH_E2E", "1") != "0":
        ef = int(os.environ.get("BENCH_E2E_FILTERS", 100_000))
        em = int(os.environ.get("BENCH_E2E_MSGS", 32_000))
        for name, use_device in (("e2e_host", False),
                                 ("e2e_device", True)):
            if phase(name, run_e2e, ef, 16, 8, em // 8, use_device) \
                    is None and _LAST_TELEMETRY:
                # the failed phase's pipeline snapshot: the stage-level
                # diagnosis the run would otherwise lose
                result[f"{name}_telemetry"] = _LAST_TELEMETRY
    # the headline route-latency summary: the observatory's per-message
    # ingress→routed p99 placed NEXT TO (and clearly labeled against)
    # the per-window sync round-trip number
    lat_src = ((result.get("e2e_device") or {}).get("latency")
               or (result.get("e2e_host") or {}).get("latency"))
    if lat_src:
        slo = lat_src.get("slo") or {}
        result["route_latency"] = {
            "ingress_routed_p99_ms": slo.get("routed_p99_ms"),
            "ingress_delivered_p99_ms": slo.get("delivered_p99_ms"),
            "objective_p99_ms": slo.get("objective_p99_ms"),
            "verdict": slo.get("verdict"),
            "burn": slo.get("burn"),
            "sync_p99_ms": result.get("sync_p99_ms"),
            "note": ("ingress_routed_p99_ms is per-message "
                     "frame-decode→route-result (latency observatory, "
                     "ISSUE 13); sync_p99_ms is per-WINDOW — do not "
                     "compare them as one metric"),
        }
    for key, script, argv, tmo in _CPU_ROWS_LATE:
        phase(key, _cpu_row, key, script, argv, tmo)
    result["phase_wall_s"] = dict(_PHASE_WALL)
    if _PHASE_MEM:
        result["phase_memory"] = dict(_PHASE_MEM)
    if _LAST_MEMORY:
        result["memory"] = _LAST_MEMORY
    if failed:
        result["failed_phases"] = failed
    print(json.dumps(result), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
