#!/usr/bin/env python
"""Per-stage profile of the fused route step on the real TPU.

VERDICT round-2 weak #1: the fused step runs at 2.0M matches/s while the
match fold alone does 9.3M/s — ~78% of the 65ms batch is somewhere in
fan-out/shared/digest. This script times each stage in isolation using the
same pipelined-window + digest-readback methodology as bench.py, so the
numbers decompose the real batch cost instead of guessing.

Usage: python tools/profile_step.py [subs] [batch] [window]
                                    [--telemetry-out FILE]
                                    [--cost-out FILE]
                                    [--pipeline]

--pipeline (ISSUE 9 satellite) profiles the double-buffered window
pipeline instead of the kernels: drives N windows (PIPE_WINDOWS,
default 48, of PIPE_BATCH messages, default 256) through a REAL
Node → PublishBatcher → device engine at dispatch depth 1 and then
depth 2 (or EMQX_TPU_DISPATCH_DEPTH when set higher), and prints per
depth the flight-recorder dispatch↔materialize overlap fraction and
the amortized ms/window — the two numbers the ISSUE-9 acceptance
criteria gate on, measured the same way bench.py's e2e phase embeds
them.

--telemetry-out dumps the run as a pipeline-telemetry snapshot
(broker.telemetry SCHEMA — the same JSON shape bench.py embeds and
GET /api/v5/pipeline/stats serves): each profiled kernel becomes a stage
row (per-batch ms) and its warm/compile cost lands in the compile
accounting, so profiling rounds and bench rounds share one schema.

--cost-out (ISSUE 8 satellite) dumps the jit-program cost-registry
table: every profiled kernel registers its compile wall-time AND its
lowered `cost_analysis()` (flops, bytes accessed) under the same
`program_costs` section schema `snapshot()["program_costs"]` embeds —
`{program: {class_label: {compiles, compile_ms, flops,
bytes_accessed}}}` — so the ROADMAP-item-2 stage-graph builder reads
one oracle whether the numbers came from a profiling round or a
serving run (`cost_stats(analyze=True)` fills any route-program rows
recorded during this run too).

The FULL schema (ISSUE 7 satellite): the snapshot carries every
section bench rounds now emit, not just the PR-1 stages/occupancy/
compiles — `rebuild` (the table build + device upload measured as
capture/build/swap spans), `readback` (one full-step dense D2H,
actual bytes), `supervise` (a standalone supervisor's live state —
armed EMQX_TPU_FAULTS clauses included), `trace` (the flight
recorder's per-kernel spans + analysis) and `deliver` (present,
empty — no lane pool in a kernel profile), so snapshot diffs across
rounds see a stable shape.
"""

import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _parse_args(argv):
    """Positional [subs] [batch] [window] + --telemetry-out FILE
    + --cost-out FILE + --pipeline."""
    out = None
    cost_out = None
    pipeline = False
    pos = []
    it = iter(argv)
    for a in it:
        if a == "--telemetry-out":
            out = next(it, None)
        elif a.startswith("--telemetry-out="):
            out = a.split("=", 1)[1]
        elif a == "--cost-out":
            cost_out = next(it, None)
        elif a.startswith("--cost-out="):
            cost_out = a.split("=", 1)[1]
        elif a == "--pipeline":
            pipeline = True
        else:
            pos.append(a)
    return pos, out, cost_out, pipeline


def _slug(name: str) -> str:
    return re.sub(r"[^a-z0-9]+", "_", name.lower()).strip("_")


def _engine_window_loop(depth: int, windows: int, batch: int,
                        n_filters: int) -> dict:
    """One depth's engine-level window-pipeline measurement: drives N
    windows through the REAL DeviceRouteEngine stages with the REAL
    batcher concurrency contract at each depth, minus the event-loop
    work (hook folds, sockets, publish futures) that dominates a
    2-core CPU box:

    - dispatch launches AT ADMIT on one ordered thread at EVERY depth
      (the producer has done that since the round-2 pipelined serving
      path — it is part of the pre-ISSUE-9 baseline, so the depth-1
      twin must not be penalized with a serialized dispatch);
    - at depth 1 the consumer is the synchronous loop: await the
      window's dispatch, materialize it on the read pool, finish —
      strictly one window at a time (materialize(W+1) starts only
      after finish(W), the exact ordering tests/test_pipeline_depth's
      trace-shape guard pins);
    - at depth >= 2 up to ``depth`` stage tasks (await-dispatch →
      materialize on the 2-thread read pool) run concurrently ahead of
      their FIFO settle turn — admission is gated on LIVE stage tasks,
      not on settles, exactly like PublishBatcher._consume_pipelined
      (settle-gated admission collapses the effective depth to ~1).

    Each stage records a flight-recorder span, so the SAME analyzer
    that grades bench rounds computes the dispatch↔materialize overlap
    fraction."""
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    from emqx_tpu.broker.message import make
    from emqx_tpu.broker.node import Node
    from emqx_tpu.broker.trace import FlightRecorder, analyze_spans

    node = Node({"broker": {
        "dispatch_depth": depth, "device_fanout_cap": 16,
        "device_slot_cap": 4, "deliver_lanes": 0,
        "device_min_batch": 4,
        # pin the adaptive layers OFF: each one (dedup plan, match
        # cache, compact class ladder, delta overlay) switches fused
        # programs mid-run on its own count/EWMA trigger, and a cold
        # compile inside the timed loop would swamp the per-window
        # number this profile exists to compare across depths
        "topic_dedup": False, "match_cache_size": 0,
        "compact_readback": False, "delta_overlay": False}})

    class _Null:
        def deliver(self, f, m):
            return True
    b = node.broker
    for i in range(n_filters):
        b.subscribe(b.register(_Null(), f"p{i}"), f"t/{i}/+",
                    {"qos": 1})
    eng = node.device_engine
    eng.rebuild()
    rec = node.flight_recorder or FlightRecorder(node.metrics)

    def mkwin(w):
        return [make("p", 1, f"t/{(w * batch + i) % n_filters}/x",
                     b"m%07d" % (w * batch + i)) for i in range(batch)]

    # pool sizes mirror PublishBatcher: one ordered dispatch thread
    # (the engine threads cursors batch-to-batch), two readback threads
    disp_pool = ThreadPoolExecutor(1, thread_name_prefix="pipe-disp")
    read_pool = ThreadPoolExecutor(2, thread_name_prefix="pipe-read")
    # the in-flight stage-task bound: the pool's worker count IS the
    # ring's live-stage-task cap, and its FIFO queue preserves
    # admission order (depth 1 never uses it — see the settle loop)
    stage_pool = ThreadPoolExecutor(max(1, depth),
                                    thread_name_prefix="pipe-stage")
    spans = []

    def disp(h, tid):
        t0 = time.perf_counter()
        eng.dispatch(h)
        spans.append((tid, "dispatch", t0, time.perf_counter()))

    def mat(h, tid):
        m0 = time.perf_counter()
        eng.materialize(h)
        spans.append((tid, "materialize", m0, time.perf_counter()))

    def stage(h, dfut, tid):
        # one window's in-flight stages, the batcher's _run_stages
        # shape: await its admit-launched dispatch, then materialize on
        # the shared read pool
        dfut.result()
        read_pool.submit(mat, h, tid).result()

    def finish(h):
        counts = eng.finish(h)
        assert len(counts) == batch
        return sum(counts)

    # warm laps: compile every program variant the timed loop will hit.
    # The engine ADAPTS across windows (dedup/match-cache engages after
    # the cache fills, compact readback after the payload EWMA seeds),
    # each switch compiling a new fused program — so warm until three
    # consecutive windows ran compile-free (fast), not a fixed count.
    calm, t_min = 0, None
    for w in range(64):
        t_w = time.perf_counter()
        hw = eng.prepare(mkwin(w), gate_cold=False)
        assert hw is not None, "engine stood down on a warm window"
        eng.dispatch(hw)
        eng.materialize(hw)
        eng.finish(hw)
        dt = time.perf_counter() - t_w
        t_min = dt if t_min is None else min(t_min, dt)
        # compile-free = close to the best window seen (an armed hang
        # proxy inflates EVERY window equally, so relative is right)
        calm = calm + 1 if dt < max(0.02, 1.5 * t_min) else 0
        if calm >= 3:
            break

    # the producer's admit bound: how many windows may sit prepared
    # with their dispatch launched ahead of settle (the batcher's
    # _inflight queue depth)
    admit_bound = max(depth, 8)
    routed = 0
    ring: deque = deque()       # (w, handle, dispatch fut, stage fut)
    next_w = 0
    t0 = time.perf_counter()
    while next_w < windows or ring:
        while next_w < windows and len(ring) < admit_bound:
            h = eng.prepare(mkwin(next_w))
            assert h is not None, \
                f"engine stood down at window {next_w}"
            tid = rec.new_trace()
            dfut = disp_pool.submit(disp, h, tid)
            sfut = stage_pool.submit(stage, h, dfut, tid) \
                if depth > 1 else dfut
            ring.append((next_w, h, sfut, tid))
            next_w += 1
        w, h, sfut, tid = ring.popleft()
        sfut.result()
        if depth == 1:
            # synchronous consumer: materialize THIS window now, one
            # at a time
            read_pool.submit(mat, h, tid).result()
        routed += finish(h)
    wall = time.perf_counter() - t0
    disp_pool.shutdown(wait=False)
    read_pool.shutdown(wait=False)
    stage_pool.shutdown(wait=False)
    for tid, name, s0, s1 in spans:
        rec.record(tid, name, s0, s1, track=name)
    a = analyze_spans(rec.spans())
    ov = (a.get("overlap") or {})
    return {
        "dispatch_depth": depth,
        "windows": windows,
        "overlap": ov.get("dispatch_materialize"),
        "ms_per_window": round(wall / windows * 1000, 3),
        "msgs_per_s": round(windows * batch / wall),
        "wall_s": round(wall, 3),
        "routed": routed,
    }


def run_pipeline_profile(windows: int, batch: int,
                         out_path=None) -> dict:
    """ISSUE 9 satellite: the depth-1 vs depth-2 window-pipeline
    profile. Default mode drives the engine window loop directly
    (prepare/dispatch/materialize/finish ring — the device pipeline
    itself); ``PIPE_E2E=1`` instead pushes the same schedule through a
    full Node → PublishBatcher path (hook folds, lanes, publish
    futures — event-loop-bound on small boxes). Either way the flight
    recorder's analyzer reports the dispatch↔materialize overlap
    fraction and the wall clock gives amortized ms/window, per depth.
    Arm `EMQX_TPU_FAULTS="dispatch:hang:...,materialize:hang:..."` to
    emulate a slow device round trip on a CPU box (the hangs sleep with
    the GIL released)."""
    n_filters = int(os.environ.get("PIPE_FILTERS", 64))
    depths = sorted({1, max(2, int(os.environ.get(
        "EMQX_TPU_DISPATCH_DEPTH", 2) or 2))})
    rows = {}
    if os.environ.get("PIPE_E2E", "0") != "1":
        for depth in depths:
            rows[depth] = _engine_window_loop(depth, windows, batch,
                                              n_filters)
            log(f"depth {depth}: "
                f"{rows[depth]['ms_per_window']:8.2f} ms/window  "
                f"{rows[depth]['msgs_per_s']:>8d} msgs/s  "
                f"overlap={rows[depth]['overlap']}")
        base, top = rows[depths[0]], rows[depths[-1]]
        if base["wall_s"] and top["wall_s"]:
            log(f"depth {depths[-1]} vs {depths[0]}: "
                f"{base['wall_s'] / top['wall_s']:.2f}x msgs/s")
        doc = {"metric": "pipeline_profile", "mode": "engine",
               "windows": windows, "batch": batch, "depths": rows}
        print(json.dumps(doc), flush=True)
        if out_path:
            with open(out_path, "w") as f:
                json.dump(doc, f, indent=1)
        return doc
    import asyncio

    from emqx_tpu.broker.message import make
    from emqx_tpu.broker.node import Node

    for depth in depths:
        node = Node({"broker": {
            "dispatch_depth": depth,
            "device_fanout_cap": 16, "device_slot_cap": 4,
            "deliver_lanes": 2, "device_min_batch": 4,
            "batch_window_us": 2000,
            "max_publish_batch": batch + 1}})
        # pin the adaptive chooser to the device: this profile measures
        # the DEVICE window pipeline, not the host-probe cadence
        node.publish_batcher._device_worth_it = lambda n: True

        class _Null:
            def deliver(self, f, m):
                return True
        b = node.broker
        for i in range(n_filters):
            b.subscribe(b.register(_Null(), f"p{i}"), f"t/{i}/+",
                        {"qos": 1})

        async def go():
            eng = node.device_engine
            eng.rebuild()
            eng._kick_class_warm()
            if eng._fuse_warm_task is not None:
                await eng._fuse_warm_task
            # warm lap (compiles out of the timed window)
            await asyncio.gather(*[
                node.publish_async(make("p", 1, f"t/{i % n_filters}/w",
                                        b"warm"))
                for i in range(batch)])
            pool = node.deliver_lanes
            if pool is not None:
                await pool.drain()
            rec0 = node.flight_recorder
            mark = rec0.recorded() if rec0 is not None else 0
            t0 = time.perf_counter()
            futs = []
            for w in range(windows):
                futs.extend(asyncio.ensure_future(node.publish_async(
                    make("p", 1, f"t/{(w * batch + i) % n_filters}/x",
                         b"m%07d" % (w * batch + i))))
                    for i in range(batch))
            await asyncio.gather(*futs)
            if pool is not None:
                await pool.drain()
            return time.perf_counter() - t0, mark

        wall, mark = asyncio.new_event_loop().run_until_complete(go())
        rec = node.flight_recorder
        if rec is not None:
            # analyze ONLY the timed window's spans (the warm lap's
            # compile-skewed spans would poison the overlap fraction)
            from emqx_tpu.broker.trace import analyze_spans
            analysis = analyze_spans(
                [s for s in rec.spans() if s.slot >= mark])
        else:
            analysis = {}
        ov = (analysis.get("overlap") or {})
        rows[depth] = {
            "dispatch_depth": depth,
            "windows": analysis.get("windows"),
            "overlap": ov.get("dispatch_materialize"),
            "ms_per_window": round(wall / windows * 1000, 3),
            "msgs_per_s": round(windows * batch / wall),
            "wall_s": round(wall, 3),
            "device_windows":
                node.metrics.val("routing.device.batches"),
        }
        log(f"depth {depth}: {rows[depth]['ms_per_window']:8.2f} "
            f"ms/window  {rows[depth]['msgs_per_s']:>8d} msgs/s  "
            f"overlap={rows[depth]['overlap']}")
    base, top = rows[depths[0]], rows[depths[-1]]
    if base["ms_per_window"] and top["ms_per_window"]:
        log(f"depth {depths[-1]} vs {depths[0]}: "
            f"{base['ms_per_window'] / top['ms_per_window']:.2f}x "
            f"msgs/s")
    doc = {"metric": "pipeline_profile", "mode": "e2e",
           "windows": windows, "batch": batch, "depths": rows}
    print(json.dumps(doc), flush=True)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
    return doc


def main():
    pos, telemetry_out, cost_out, pipeline = _parse_args(sys.argv[1:])
    if pipeline:
        run_pipeline_profile(
            int(os.environ.get("PIPE_WINDOWS", 48)),
            int(os.environ.get("PIPE_BATCH", 256)),
            out_path=telemetry_out)
        return
    subs = int(pos[0]) if len(pos) > 0 else 1_000_000
    B = int(pos[1]) if len(pos) > 1 else 131072
    window = int(pos[2]) if len(pos) > 2 else 16

    from emqx_tpu.broker.supervise import PipelineSupervisor
    from emqx_tpu.broker.telemetry import PipelineTelemetry
    from emqx_tpu.broker.trace import FlightRecorder
    tele = PipelineTelemetry()
    # the newer snapshot sections ride this run too: supervise (armed
    # chaos clauses + breaker state), trace (per-kernel spans)
    sup = PipelineSupervisor(tele.metrics, telemetry=tele)
    tele.supervise_state_fn = sup.state
    rec = FlightRecorder(tele.metrics)
    tele.recorder = rec

    import jax
    import jax.numpy as jnp

    from emqx_tpu.models.router_engine import (ShapeRouterTables,
                                               route_step_shapes)
    from emqx_tpu.ops import intern as I
    from emqx_tpu.ops.fanout import (SubTable, fanout_normal, shared_slots)
    from emqx_tpu.ops.shapes import build_shape_tables, shape_match
    from emqx_tpu.ops.shared import (STRATEGY_ROUND_ROBIN, pick_members,
                                     _rank_and_occur)

    log(f"profile: subs={subs} B={B} window={window} dev={jax.devices()[0]}")

    # same filter set as bench.py
    ids = max(64, int(np.sqrt(subs)))
    nums = max(1, subs // ids)
    F = ids * nums
    intern = I.InternTable()
    wd = intern.intern("device")
    id_ids = np.array([intern.intern(f"d{i}") for i in range(ids)], np.int32)
    num_ids = np.array([intern.intern(f"n{n}") for n in range(nums)], np.int32)
    rows = np.zeros((F, 8), np.int32)
    lens = np.full(F, 5, np.int64)
    rows[:, 0] = wd
    rows[:, 1] = np.repeat(id_ids, nums)
    rows[:, 2] = I.PLUS
    rows[:, 3] = np.tile(num_ids, ids)
    rows[:, 4] = I.HASH

    t0 = time.time()
    shapes = build_shape_tables(rows, lens)
    # the table build is this run's `rebuild.build` — profiling and
    # bench rounds share the rebuild-stage schema (ISSUE 7 satellite)
    tele.observe_rebuild("build", time.time() - t0)
    log(f"build {time.time()-t0:.1f}s buckets={shapes.buckets.shape[0]}")

    shared_pct = 50
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
    shared_opts_a = np.ones(n_groups * 8, np.int8)
    subs_tbl = SubTable(sub_start, sub_row, sub_opts, fs_start, fs_slot,
                        shared_start, shared_row, shared_opts_a)
    t_up = time.time()
    tables = jax.device_put(ShapeRouterTables(shapes=shapes, subs=subs_tbl))
    jax.block_until_ready(tables)
    # the device upload is the profiling analog of `rebuild.swap`
    tele.observe_rebuild("swap", time.time() - t_up)
    cursors0 = jax.device_put(np.zeros(n_groups, np.int32))
    strat = jax.device_put(np.int32(STRATEGY_ROUND_ROBIN))

    x = intern.intern("x")
    tail = intern.intern("t")
    rng = np.random.RandomState(7)
    staged = []
    for k in range(8):
        zipf = np.minimum(rng.zipf(1.3, size=B) - 1, ids - 1)
        tp = np.zeros((B, 8), np.int32)
        tp[:, 0] = wd
        tp[:, 1] = id_ids[zipf]
        tp[:, 2] = x
        tp[:, 3] = num_ids[rng.randint(0, nums, B)]
        tp[:, 4] = tail
        staged.append((jax.device_put(tp),
                       jax.device_put(np.full(B, 5, np.int32)),
                       jax.device_put(np.zeros(B, bool)),
                       jax.device_put(rng.randint(0, 1 << 30, B)
                                      .astype(np.int32))))

    FAN_CAP = int(os.environ.get("BENCH_FANOUT_CAP", 4))
    SLOT_CAP = int(os.environ.get("BENCH_SLOT_CAP", 2))

    from emqx_tpu.models.router_engine import (_analyze_lowered,
                                               record_program_cost)

    def _record_cost(stage, fn, warm_ms):
        """One cost-registry row per profiled kernel (ISSUE 8
        satellite): the warm pass's compile wall-time plus the lowered
        program's cost_analysis (flops, bytes accessed) — the same
        `program_costs` table the serving path's route programs
        populate, so --cost-out and the telemetry snapshot share one
        schema. Lowering is tracing-only (no backend compile); kernels
        without .lower (the fused-window wrapper) record wall only.
        The re-lower (a full re-trace per kernel) runs only when a
        consumer asked for the table — a bare profiling run stays at
        wall-time-only rows."""
        flops = ba = None
        if cost_out or telemetry_out:
            try:
                low = fn.lower(jax.device_put(np.int32(0)), tables,
                               staged[0])
                flops, ba = _analyze_lowered(low)
            except Exception:  # noqa: BLE001 — analysis is best-effort
                pass
        record_program_cost(stage, f"profile {stage}",
                            compile_ms=warm_ms, flops=flops,
                            bytes_accessed=ba)

    def timed(name, fn, topics_per_call=B):
        """Pipelined window of `fn(acc, tables, staged[i])` closed by one
        scalar read. Tables ride as explicit jit arguments — closing over
        them would bake the bucket table into the HLO (same rule as
        bench.py's step_digest).
        topics_per_call: how many topics one call routes (a fused-window
        call routes FUSE*B — the table stays per-batch honest)."""
        batches_per_call = topics_per_call // B
        stage = _slug(name)

        def run(n):
            acc = jax.device_put(np.int32(0))
            t0 = time.time()
            for i in range(n):
                acc = fn(acc, tables, staged[i % 8])
            _ = int(np.asarray(acc))
            return time.time() - t0
        t_warm = time.perf_counter()
        with tele.compile_context(f"profile {stage}"):
            run(2)  # warm/compile (attributed to this kernel's shape)
        _record_cost(stage, fn,
                     (time.perf_counter() - t_warm) * 1000.0)
        t_meas = time.perf_counter()
        dt = run(window)
        # each timed kernel is one "window" on the flight recorder:
        # the trace section shows the measurement timeline per kernel
        rec.record(rec.new_trace(), stage, t_meas,
                   time.perf_counter(), track="profile")
        per_ms = dt / (window * batches_per_call) * 1000
        tele.observe_stage(stage, per_ms / 1000.0)
        log(f"{name:34s} {per_ms:8.2f} ms/batch   "
            f"{topics_per_call*window/dt/1e6:6.1f}M/s")
        return per_ms

    # 1. match only
    @jax.jit
    def f_match(acc, tb, batch):
        t, l, d, h = batch
        r = shape_match(tb.shapes, t, l, d)
        return acc + r.matches.sum(dtype=jnp.int32) + r.counts.sum()

    # 2. match + fanout_normal
    @jax.jit
    def f_fan(acc, tb, batch):
        t, l, d, h = batch
        r = shape_match(tb.shapes, t, l, d)
        fr = fanout_normal(tb.subs, r.matches, fanout_cap=FAN_CAP)
        return (acc + fr.rows.sum(dtype=jnp.int32) + fr.counts.sum()
                + fr.opts.sum(dtype=jnp.int32))

    # 3. match + shared_slots
    @jax.jit
    def f_slots(acc, tb, batch):
        t, l, d, h = batch
        r = shape_match(tb.shapes, t, l, d)
        sids, ov = shared_slots(tb.subs, r.matches, slot_cap=SLOT_CAP)
        return acc + sids.sum(dtype=jnp.int32) + ov.sum()

    # 4. match + slots + pick_members (full shared path)
    @jax.jit
    def f_shared(acc, tb, batch):
        t, l, d, h = batch
        r = shape_match(tb.shapes, t, l, d)
        sids, ov = shared_slots(tb.subs, r.matches, slot_cap=SLOT_CAP)
        sp = pick_members(tb.subs, cursors0, sids, strat, h)
        return (acc + sp.rows.sum(dtype=jnp.int32)
                + sp.new_cursors.sum(dtype=jnp.int32))

    # 4b. rank+occur alone (the sort-free blocked kernel on accelerators)
    @jax.jit
    def f_rank(acc, tb, batch):
        t, l, d, h = batch
        sids = jnp.stack([h % np.int32(n_groups),
                          jnp.full((B,), -1, jnp.int32)], axis=1)
        rank, occur = _rank_and_occur(sids, n_groups)
        return (acc + rank.sum(dtype=jnp.int32)
                + occur.sum(dtype=jnp.int32))

    # 4c. occur scatter-add alone
    @jax.jit
    def f_occur(acc, tb, batch):
        t, l, d, h = batch
        safe = (h % np.int32(n_groups)).astype(jnp.int32)
        occur = jnp.zeros(n_groups, jnp.int32).at[safe].add(1, mode="drop")
        return acc + occur.sum(dtype=jnp.int32)

    # 5. full fused step + digest (= the bench single-batch step)
    @jax.jit
    def f_full(acc, tb, batch):
        t, l, d, h = batch
        r = route_step_shapes(tb, cursors0, t, l, d, h, strat,
                              fanout_cap=FAN_CAP, slot_cap=SLOT_CAP)
        return (acc + r.rows.sum(dtype=jnp.int32)
                + r.fan_counts.sum(dtype=jnp.int32)
                + r.shared_rows.sum(dtype=jnp.int32)
                + r.match_counts.sum(dtype=jnp.int32)
                + r.opts.sum(dtype=jnp.int32))

    # 6. W-fused window (one dispatch per FUSE batches) — what bench.py
    # now measures; the delta vs f_full isolates per-dispatch overhead
    from emqx_tpu.models.router_engine import route_window_shapes
    FUSE = max(1, min(int(os.environ.get("BENCH_FUSE", 8)), 8))
    stacked = tuple(jnp.stack([staged[k % 8][i] for k in range(FUSE)])
                    for i in range(4))

    @jax.jit
    def f_window_impl(acc, tb, t4, l4, d4, h4):
        new_cur, digests = route_window_shapes(
            tb, cursors0, t4, l4, d4, h4, strat,
            fanout_cap=FAN_CAP, slot_cap=SLOT_CAP)
        return acc + digests.sum(dtype=jnp.int32)

    def f_window(acc, tb, _batch):
        return f_window_impl(acc, tb, *stacked)

    # 7. pallas fold backend (match-only, lane-major kernel)
    from emqx_tpu.ops.shapes import shape_match_pallas

    @jax.jit
    def f_match_pallas(acc, tb, batch):
        t, l, d, h = batch
        r = shape_match_pallas(tb.shapes, t, l, d)
        return acc + r.matches.sum(dtype=jnp.int32) + r.counts.sum()

    timed("match only", f_match)
    if jax.default_backend() == "tpu":   # Mosaic only; no interpreter row
        timed("match only (pallas fold)", f_match_pallas)
    timed("match+fanout", f_fan)
    timed("match+shared_slots", f_slots)
    timed("match+slots+pick_members", f_shared)
    timed("rank/occur alone", f_rank)
    timed("occur scatter-add alone", f_occur)
    timed("FULL route_step + digest", f_full)
    timed(f"FUSED window x{FUSE} (per batch)", f_window,
          topics_per_call=B * FUSE)

    # one full-step DENSE readback: the actual device→host transfer the
    # broker's materialize stage pays, measured here so the snapshot's
    # `readback` section carries real bytes/span next to the kernel
    # times (the digest-closed windows above deliberately avoid D2H)
    @jax.jit
    def _step_full(tb, t, l, d, h):
        return route_step_shapes(tb, cursors0, t, l, d, h, strat,
                                 fanout_cap=FAN_CAP, slot_cap=SLOT_CAP)

    with tele.compile_context("profile dense_readback"):
        r_full = _step_full(tables, *staged[0])
        jax.block_until_ready(r_full.matches)
    t_mat = time.perf_counter()
    planes = [np.asarray(x) for x in
              (r_full.matches, r_full.rows, r_full.opts,
               r_full.shared_sids, r_full.shared_rows,
               r_full.shared_opts, r_full.overflow, r_full.occur)]
    tele.observe_stage("materialize", time.perf_counter() - t_mat)
    tele.metrics.inc("pipeline.readback.bytes.dense",
                     sum(p.nbytes for p in planes))
    tele.metrics.inc("pipeline.readback.windows.dense")
    log(f"dense readback: {sum(p.nbytes for p in planes) / 1e6:.1f}MB "
        f"in {(time.perf_counter() - t_mat) * 1000:.1f}ms")

    if telemetry_out:
        snap = tele.snapshot(full=True)
        snap["profile"] = {"subs": subs, "batch": B, "window": window,
                           "fuse": FUSE}
        with open(telemetry_out, "w") as f:
            json.dump(snap, f, indent=1)
        log(f"telemetry snapshot -> {telemetry_out}")

    if cost_out:
        # the per-program cost table (ISSUE 8): analyze=True fills
        # flops/bytes for any route-program rows this run compiled
        # (tracing cost only — exactly the off-path consumer the lazy
        # analysis exists for); the profiled kernels' rows were
        # recorded eagerly above
        from emqx_tpu.broker.telemetry import SCHEMA as PIPE_SCHEMA
        from emqx_tpu.models.router_engine import cost_stats
        doc = {"schema": PIPE_SCHEMA,
               "program_costs": cost_stats(analyze=True),
               "profile": {"subs": subs, "batch": B, "window": window,
                           "fuse": FUSE}}
        with open(cost_out, "w") as f:
            json.dump(doc, f, indent=1)
        log(f"program cost table -> {cost_out}")


if __name__ == "__main__":
    main()
