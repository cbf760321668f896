"""Device-path pipeline telemetry: stage spans, occupancy, compiles.

The observability substrate for the batched PUBLISH pipeline (the
reference's layer-0 emqx_metrics/emqx_stats/emqx_tracer triplet, grown a
dimension: per-STAGE latency attribution instead of counters alone).
`PipelineTelemetry` owns log2-bucket histograms (broker.metrics.Histogram)
for every pipeline stage —

    enqueue      oldest-message wait in the submit queue before its batch
                 forms (broker/batcher._produce)
    batch_form   message.publish hook fold + live-filter per batch
    dispatch     the jitted route step, executor-thread wall time
                 (match + fan-out + shared picks all run inside it on
                 device)
    dispatch_cached  same span for deduplicated / match-cache-backed
                 dispatches (route_*_cached) — the cached-vs-uncached
                 match latency split falls straight out of comparing the
                 two histograms
    materialize  device->host readbacks
    deliver      RouteResult consumption into session deliveries (with
                 the ISSUE-5 delivery lanes active this is the PLAN
                 construction span; the delivery walk itself lands in
                 the per-lane deliver_lane{i} histograms below)
    deliver_lane{i}  one delivery-lane item (slice or barrier) on lane i
    host_route   host-path match + route span for host-routed batches
    host_match   per-message host trie match latency (sampled 1-in-32 —
                 the host-side decomposition of dispatch's match stage)
    total        oldest-enqueue -> batch completion (the reservoir
                 lat_percentiles() draws from, now exportable)

— plus batch-occupancy histograms per device shape class (fill fraction
of the padded (W, Bp) program each dispatch actually used) and JIT
compile/recompile accounting fed by jax.monitoring: every jit-cache miss
(jaxpr trace) under an instrumented span counts as one compile event,
attributed to the (W, Bp) class that triggered it, with trace + lowering
+ backend-compile durations accumulated.

Everything lands in the node's Metrics registry, so the Prometheus,
StatsD and $SYS exporters pick the histograms up with zero coupling to
this module; `snapshot()` is the JSON schema shared by
`GET /api/v5/pipeline/stats`, bench.py's embedded telemetry and the
benchmark's `telemetry` reader (`benchmark/readers/telemetry.py`).

The serving path does not call `observe_stage` at its stage boundaries
itself: it makes ONE span call (`broker/trace.py`, `Spans.span`), which
observes the stage histogram here, records the flight recorder's ring
span and puts an `emqx:<name>` annotation on the profiler's host
timeline. `observe_stage` stays the direct form for per-message
samples (`host_match`) and for harnesses.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional

from emqx_tpu.broker.metrics import Metrics

SCHEMA = "emqx_tpu.pipeline/v1"

STAGES = ("enqueue", "batch_form", "dispatch", "dispatch_cached",
          "materialize", "deliver", "host_route", "host_match", "total")

# stage histograms: 1µs floor, quarter-octave fine ladder (ISSUE 13
# satellite: the watchdog deadlines derive from these histograms' p99,
# and the plain octave ladder could not resolve the 2ms SLO objective
# — neighbouring bounds at 1.024/2.048ms). 112 quarter-octave buckets
# cover the same 1µs..~2e2s range the old 28-octave ladder did; the
# exported family names (pipeline.stage.*) are unchanged.
_STAGE_LO, _STAGE_BUCKETS, _STAGE_SUBSTEPS = 1e-6, 112, 4
# occupancy histograms: fill fraction 1/256 .. 1.0 in 9 log2 buckets
_OCC_LO, _OCC_BUCKETS = 1.0 / 256, 9

# ---- process-wide jax.monitoring listener --------------------------------
# ONE listener per process (jax.monitoring has no deregistration). A
# compile event is attributed to the instance whose compile_context() is
# active on the FIRING thread — jit traces/compiles run on the thread
# that called the jitted function, so the dispatch/warm spans in the
# engines scope attribution exactly; events outside any span are ignored
# (they belong to no pipeline).
_tls = threading.local()
_listener_installed = False
_install_lock = threading.Lock()

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
# fires once per executable XLA had to build (or load from the persistent
# cache) — unlike the trace event, which also fires for every nested jit
# of one program and for a bare fast-path miss that re-uses a trace
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE_EVENTS = (
    _TRACE_EVENT,
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    _BACKEND_EVENT,
)


def _on_jax_event(name: str, dur: float, **_kw) -> None:
    if name not in _COMPILE_EVENTS:
        return
    # per-thread compile sequence: jit compiles run on the calling
    # thread, so this is the exact "did MY call compile?" signal the
    # ISSUE-8 cost registry confirms cache-size deltas against (a
    # cache grown by ANOTHER thread's concurrent compile must not be
    # attributed to this thread's class label)
    _tls.compile_seq = getattr(_tls, "compile_seq", 0) + 1
    ctx = getattr(_tls, "ctx", None)
    if ctx is None:
        return
    tele, shape = ctx
    tele._note_compile_event(shape, dur, is_trace=(name == _TRACE_EVENT),
                             is_backend=(name == _BACKEND_EVENT))


def thread_compile_seq() -> "int | None":
    """Monotonic count of jax compile events observed on THIS thread,
    or None while no listener is installed (no confirmation signal
    available — callers fall back to cache-size-delta-only)."""
    if not _listener_installed:
        return None
    return getattr(_tls, "compile_seq", 0)


def _install_listener() -> bool:
    global _listener_installed
    with _install_lock:
        if _listener_installed:
            return True
        try:
            import jax.monitoring as M
            M.register_event_duration_secs_listener(_on_jax_event)
        except Exception:  # noqa: BLE001 — no jax / ancient jax: no-op
            return False
        _listener_installed = True
        return True


class PipelineTelemetry:
    """Per-node (or standalone) pipeline telemetry registry.

    Node wires one up as `node.pipeline_telemetry`; a harness can
    build a standalone one around its own Metrics. All hot-path entry
    points are plain histogram observes — no locks, no allocation beyond
    the first observation of a new occupancy class.
    """

    def __init__(self, metrics: Optional[Metrics] = None, *,
                 hooks=None, slow_batch_s: Optional[float] = None,
                 track_compiles: bool = True):
        self.metrics = metrics if metrics is not None else Metrics()
        self.hooks = hooks
        # live rebuild/overlay gauges provider (set by the device
        # engine): journal depth, overlay size etc. — point-in-time
        # values the counter registry can't carry. Best-effort: snapshot
        # must keep working on nodes without a device engine.
        self.rebuild_state_fn = None
        # live delivery-lane gauges provider (set by the node when the
        # ISSUE-5 DeliveryLanePool exists): lane depth, live plans
        self.deliver_state_fn = None
        # the interpreter under the pipeline (set by the node):
        # snapshot() carries what it returns as the `runtime` section,
        # today the loop's own clock (`trace.LoopWatch.state()`)
        self.runtime_state_fn = None
        # live supervision gauges provider (set by the node when the
        # ISSUE-6 PipelineSupervisor exists): breaker states, ladder
        # rung, window-journal depth, armed fault clauses
        self.supervise_state_fn = None
        # the batcher's device/host chooser (set by the node when it
        # has a PublishBatcher): snapshot() derives the `chooser`
        # section — the two cost EWMAs, the margin of the last cost
        # comparison and the verdict counts by reason — from it
        self.chooser_state_fn = None
        # the window-causal flight recorder (ISSUE 7; set by the node
        # when broker.trace / EMQX_TPU_TRACE is on): snapshot() derives
        # the `trace` section — ring state + overlap/bubble analysis —
        # from it. None restores the pre-ISSUE-7 schema exactly.
        self.recorder = None
        # the HBM ledger (ISSUE 8; set by the node when
        # broker.hbm_ledger / EMQX_TPU_HBM_LEDGER is on): snapshot()
        # derives the `memory` section — per-category device bytes,
        # pin ages, backend memory_stats cross-check — from it. None
        # restores the pre-ISSUE-8 schema exactly.
        self.ledger = None
        # the overload governor's live gauges (ISSUE 14; set by the
        # node when broker.overload / EMQX_TPU_OVERLOAD is on):
        # snapshot() derives the `overload` section — grade, armed
        # shed actions, last signal readings, hysteresis counters —
        # from it. None restores the pre-ISSUE-14 schema exactly.
        self.overload_state_fn = None
        # the latency SLO observatory (ISSUE 13; set by the node when
        # broker.latency_observatory / EMQX_TPU_LATENCY is on):
        # snapshot() derives the `latency` section — per-(qos, path)
        # ingress→routed / ingress→delivered percentiles, SLO burn
        # rates, breach exemplars — from it. None restores the
        # pre-ISSUE-13 schema exactly.
        self.observatory = None
        # the platform / device_kind / count the node bound its device
        # route path to (set by the node; None on host-only nodes):
        # snapshot() carries it as the `device` section
        self.device_info = None
        # slow-batch watch: a total span beyond this fires the
        # `batch.slow` hook (apps/tracer writes the log line) and counts
        # pipeline.slow_batches. None disables.
        self.slow_batch_s = slow_batch_s
        self.started_at = time.time()
        self._compiles_lock = threading.Lock()
        self.compiles = 0            # jit-cache misses (trace events)
        self.compile_s = 0.0         # trace + lowering + backend time
        self.compiles_by_shape: dict[str, dict] = {}
        if track_compiles:
            _install_listener()
        for s in STAGES:
            self._stage_hist(s)

    # ---- stage spans -----------------------------------------------------
    def _stage_hist(self, stage: str):
        return self.metrics.hist(f"pipeline.stage.{stage}.seconds",
                                 lo=_STAGE_LO, n_buckets=_STAGE_BUCKETS,
                                 substeps=_STAGE_SUBSTEPS)

    def observe_stage(self, stage: str, seconds: float) -> None:
        self._stage_hist(stage).observe(seconds)

    @contextlib.contextmanager
    def stage(self, stage: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.observe_stage(stage, time.perf_counter() - t0)

    def record_total(self, seconds: float, **meta) -> None:
        """The end-of-batch span: feeds the `total` histogram and the
        slow-batch watch (threshold -> batch.slow hook + counter)."""
        self.observe_stage("total", seconds)
        if self.slow_batch_s is not None and seconds > self.slow_batch_s:
            self.metrics.inc("pipeline.slow_batches")
            if self.hooks is not None:
                self.hooks.run("batch.slow",
                               (dict(meta, duration_ms=round(
                                   seconds * 1000, 3)),))

    # ---- rebuild stages (ISSUE 4) ---------------------------------------
    # capture/build/warm/swap spans of the snapshot rebuild machinery
    # (upload is the device_put slice inside build) plus delta_apply
    # (overlay refresh) — rebuilds used to be invisible
    # beyond a bare routing.device.rebuilds counter; these histograms
    # ride the registry so all four exporters carry them, and snapshot()
    # derives the `rebuild` section from them.
    REBUILD_STAGES = ("capture", "build", "upload", "warm", "swap",
                      "delta_apply")

    def observe_rebuild(self, stage: str, seconds: float) -> None:
        self.metrics.hist(f"pipeline.rebuild.{stage}.seconds",
                          lo=_STAGE_LO, n_buckets=_STAGE_BUCKETS,
                          substeps=_STAGE_SUBSTEPS).observe(seconds)

    # ---- columnar ingress (ISSUE 11) ------------------------------------
    def record_ingress_burst(self, rows: int) -> None:
        """One columnar-decoded PublishBurst of `rows` PUBLISH frames:
        feeds the burst-size histogram (pipeline.ingress.burst). The
        companion counters — pipeline.ingress.bursts / rows /
        fallback_frames / bytes and the per-lane
        pipeline.ingress.lane{i}.accepted family — are incremented at
        the connection read loop; everything rides the shared registry,
        so all four exporters carry them with zero coupling here."""
        self.metrics.hist("pipeline.ingress.burst",
                          lo=1.0, n_buckets=16,
                          unit="rows").observe(rows)

    # ---- occupancy -------------------------------------------------------
    def record_occupancy(self, cls: str, fill: float) -> None:
        """Fill fraction of one dispatched batch within its padded shape
        class (`b{Bp}` for single batches, `w{Wp}` for fused-window
        width, `host` for host-routed batches vs max_batch)."""
        self.metrics.hist(f"pipeline.occupancy.{cls}",
                          lo=_OCC_LO, n_buckets=_OCC_BUCKETS,
                          unit="ratio").observe(fill)

    # ---- dedup / match-cache (device-path reuse layers) ------------------
    def record_dedup(self, lanes: int, unique: int) -> None:
        """One dispatch window's unique-topic compaction: `lanes` real
        (non-padding) message lanes collapsed onto `unique` distinct
        encoded topics. Feeds the dedup-ratio histogram (1 - Bu/B, the
        fraction of match work the window skipped) plus running lane /
        unique counters so exporters can derive the aggregate ratio."""
        self.metrics.inc("routing.dedup.lanes", lanes)
        self.metrics.inc("routing.dedup.unique", unique)
        if lanes:
            self.metrics.hist("pipeline.dedup.ratio",
                              lo=_OCC_LO, n_buckets=_OCC_BUCKETS,
                              unit="ratio").observe(1.0 - unique / lanes)

    # ---- routing decisions ----------------------------------------------
    def record_decision(self, path: str, n: int = 1) -> None:
        """Formed batches' device/host routing outcome
        (`device` | `host` — the finer-grained reasons keep their
        existing routing.device.* counters)."""
        self.metrics.inc(f"pipeline.batches.{path}", n)

    # ---- compile accounting ---------------------------------------------
    @contextlib.contextmanager
    def compile_context(self, shape: str):
        """Scope jit compile attribution to `shape` (e.g. "W8xB1024") on
        the current thread. Every jit-cache miss inside the span counts
        as one compile event for that shape."""
        prev = getattr(_tls, "ctx", None)
        _tls.ctx = (self, shape)
        try:
            yield
        finally:
            _tls.ctx = prev

    def _note_compile_event(self, shape: str, dur: float,
                            is_trace: bool, is_backend: bool = False
                            ) -> None:
        with self._compiles_lock:
            row = self.compiles_by_shape.setdefault(
                shape, {"count": 0, "executables": 0, "total_s": 0.0})
            row["total_s"] += dur
            self.compile_s += dur
            if is_trace:
                row["count"] += 1
                self.compiles += 1
            if is_backend:
                row["executables"] += 1
        if is_trace:
            self.metrics.inc("pipeline.jit.compiles")
        self.metrics.hist("pipeline.jit.compile.seconds",
                          lo=_STAGE_LO, n_buckets=_STAGE_BUCKETS,
                          substeps=_STAGE_SUBSTEPS).observe(dur)

    # ---- the `overload` section (ISSUE 14) ------------------------------
    def overload_section(self) -> dict:
        """The standalone `overload` document: shed/reject counters +
        the governor's live state. Shared by snapshot() and
        `GET /api/v5/pipeline/overload` — the endpoint is polled
        exactly when the broker is at capacity, so it must not pay
        the full-snapshot percentile walk per request."""
        overload: dict = {}
        for k in ("sheds", "grade_changes", "qos0_shed",
                  "connects_rejected", "accepts_paused",
                  "disconnects", "retained_deferred",
                  "stuck_polls", "rebreaches"):
            v = self.metrics.val(f"pipeline.overload.{k}")
            if v:
                overload[k] = v
        by_action = {k.rsplit(".", 1)[1]: v
                     for k, v in self.metrics.all().items()
                     if k.startswith("pipeline.overload.actions.")}
        if by_action:
            overload["actions_armed_counts"] = by_action
        if self.overload_state_fn is not None:
            try:
                overload["state"] = self.overload_state_fn()
            except Exception:  # noqa: BLE001 — telemetry never raises
                pass
        return overload

    # ---- snapshot (the shared schema) -----------------------------------
    def snapshot(self, full: bool = False) -> dict:
        """The one pipeline-telemetry JSON schema: served by
        GET /api/v5/pipeline/stats, embedded in bench.py's success and
        error JSON, read by the benchmark's `telemetry` reader and
        published (piecewise) on $SYS/brokers/<node>/pipeline/#.

        ``full=True`` emits EVERY section of the schema (rebuild /
        deliver / supervise / readback / match_cache / dedup / trace),
        empty when the layer has no traffic — consumers that diff
        snapshots across rounds (offline tooling) get a
        stable shape instead of sections popping in and out."""
        stages = {}
        occupancy = {}
        prefix_s, prefix_o = "pipeline.stage.", "pipeline.occupancy."
        for name, h in self.metrics.histograms().items():
            if name.startswith(prefix_s):
                if not h.count:
                    continue
                snap = h.snapshot()
                stages[name[len(prefix_s):].removesuffix(".seconds")] = {
                    "count": snap["count"],
                    "sum_ms": round(snap["sum"] * 1000, 3),
                    "mean_ms": round(snap["mean"] * 1000, 4),
                    "p50_ms": round(snap["p50"] * 1000, 4),
                    "p95_ms": round(snap["p95"] * 1000, 4),
                    "p99_ms": round(snap["p99"] * 1000, 4),
                }
            elif name.startswith(prefix_o) and h.count:
                snap = h.snapshot()
                occupancy[name[len(prefix_o):]] = {
                    "count": snap["count"],
                    "mean_fill": round(snap["mean"], 4),
                    "p50_fill": round(min(1.0, snap["p50"]), 4),
                }
        with self._compiles_lock:
            by_shape = {k: {"count": v["count"],
                            "executables": v["executables"],
                            "total_s": round(v["total_s"], 4)}
                        for k, v in self.compiles_by_shape.items()}
            compiles = {"count": self.compiles,
                        "total_s": round(self.compile_s, 4),
                        "by_shape": by_shape}
        decisions = {
            k.rsplit(".", 1)[1]: v
            for k, v in self.metrics.all().items()
            if k.startswith("pipeline.batches.")}
        for extra in ("routing.device.bypassed", "routing.device.cold_class",
                      "routing.device.cold_cached_class",
                      "routing.device.cold_compact_class",
                      "routing.device.cached_windows",
                      "routing.device.window_subs",
                      "routing.device.window_slots",
                      "routing.device.compact_overflow",
                      "routing.device.host_fallback",
                      "routing.device.dispatch_failed",
                      "pipeline.slow_batches"):
            v = self.metrics.val(extra)
            if v:
                decisions[extra] = v
        # the chooser: why each window went where it went. `margin` is
        # dev_batch / (n * host_msg) of the last cost comparison (< 1:
        # the chip wins); `probe_gap` the device sub-batches between
        # two host probes as it stands; `verdicts` counts every decision
        # by reason (first, host_probe, device_probe, cost_device,
        # cost_host)
        chooser = {}
        if self.chooser_state_fn is not None:
            try:
                chooser = dict(self.chooser_state_fn())
            except Exception:  # noqa: BLE001 — telemetry never raises
                pass
            verdicts = {k.rsplit(".", 1)[1]: v
                        for k, v in self.metrics.all().items()
                        if k.startswith("routing.chooser.")}
            if verdicts:
                chooser["verdicts"] = verdicts
        # device-match reuse layers: cross-batch cache + in-window dedup
        # (broker/device_engine.py; counters land in the shared Metrics
        # registry, so all four exporters already carry them — this
        # section is the derived view benches and the API embed)
        cache = {}
        for k in ("hits", "misses", "inserts", "evictions",
                  "invalidations", "invalidated_rows"):
            v = self.metrics.val(f"match_cache.{k}")
            if v:
                cache[k] = v
        lookups = cache.get("hits", 0) + cache.get("misses", 0)
        if lookups:
            cache["hit_rate"] = round(cache.get("hits", 0) / lookups, 4)
        dedup = {}
        lanes = self.metrics.val("routing.dedup.lanes")
        if lanes:
            uniq = self.metrics.val("routing.dedup.unique")
            dedup = {"lanes": lanes, "unique": uniq,
                     "ratio": round(1.0 - uniq / lanes, 4)}
        # device→host readback accounting (ISSUE 3): actual transferred
        # bytes per path. `reduction` compares the two paths' measured
        # per-window byte costs — the compaction win the acceptance
        # criteria grade, derived here once for every exporter/bench
        readback = {}
        for k in ("bytes.dense", "bytes.compact",
                  "windows.dense", "windows.compact"):
            v = self.metrics.val(f"pipeline.readback.{k}")
            if v:
                readback[k.replace(".", "_")] = v
        cw, dw = readback.get("windows_compact"), \
            readback.get("windows_dense")
        if cw:
            readback["bytes_per_window_compact"] = round(
                readback.get("bytes_compact", 0) / cw)
        if dw:
            readback["bytes_per_window_dense"] = round(
                readback.get("bytes_dense", 0) / dw)
        if cw and dw and readback["bytes_per_window_compact"]:
            readback["reduction"] = round(
                readback["bytes_per_window_dense"]
                / readback["bytes_per_window_compact"], 2)
        # device-to-device exchange stage (ISSUE 15): windows served
        # from exchanged per-dest plans vs the gather fallbacks (by
        # reason), ring rounds, interconnect bytes, and host-landed
        # bytes — `reduction` compares the exchange path's measured
        # per-window landed bytes against the gather path's, the win
        # the ISSUE-15 acceptance criterion grades, derived here once
        # for every exporter/bench. Absent without exchange traffic
        # (broker.device_exchange=0 leaves it exactly pre-ISSUE-15).
        exchange = {}
        for k in ("windows", "rounds", "bytes_exchanged",
                  "host_landed_bytes", "overflow", "cold_class",
                  "probe_bytes"):
            v = self.metrics.val(f"pipeline.exchange.{k}")
            if v:
                exchange[k] = v
        fb = {k.rsplit(".", 1)[1]: v
              for k, v in self.metrics.all().items()
              if k.startswith("pipeline.exchange.fallback.") and v}
        if fb:
            exchange["fallbacks"] = fb
        xw = exchange.get("windows")
        if xw:
            exchange["host_landed_per_window"] = round(
                exchange.get("host_landed_bytes", 0) / xw)
        # deliberately NO derived reduction ratio here: in a default-on
        # run the only gather windows are the exchange's own fallbacks
        # (overflow/unclean — systematically the largest windows), so a
        # same-snapshot ratio would inflate the win. The honest number
        # is the same-traffic A/B twin in tools/sharded_bench.py.
        # rebuild machinery (ISSUE 4): stage spans + counts + compaction
        # reasons + the engine's live gauges (journal depth, overlay
        # size) — the section that makes rebuilds visible beyond the
        # bare routing.device.rebuilds counter
        rebuild = {}
        rb_stages = {}
        prefix_r = "pipeline.rebuild."
        for name, h in self.metrics.histograms().items():
            if name.startswith(prefix_r) and h.count:
                snap = h.snapshot()
                rb_stages[name[len(prefix_r):]
                          .removesuffix(".seconds")] = {
                    "count": snap["count"],
                    "mean_ms": round(snap["mean"] * 1000, 4),
                    "p95_ms": round(snap["p95"] * 1000, 4),
                }
        if rb_stages:
            rebuild["stages"] = rb_stages
        for k in ("routing.device.rebuilds",
                  "routing.device.compactions",
                  "routing.device.rebuild_failed",
                  "routing.device.warm_failed",
                  "routing.device.delta_applies",
                  "routing.device.host_delta",
                  "routing.device.cold_delta_class",
                  "routing.device.delta_compact_overflow",
                  "match_cache.delta_invalidated"):
            v = self.metrics.val(k)
            if v:
                rebuild[k.rsplit(".", 1)[1]] = v
        reasons = {k.rsplit(".", 1)[1]: v
                   for k, v in self.metrics.all().items()
                   if k.startswith("routing.device.compaction.")}
        if reasons:
            rebuild["compaction_reasons"] = reasons
        if self.rebuild_state_fn is not None:
            try:
                rebuild["state"] = self.rebuild_state_fn()
            except Exception:  # noqa: BLE001 — telemetry never raises
                pass
        # delivery-lane egress stage (ISSUE 5): coalesce/backpressure
        # counters + the pool's live gauges. `coalesce_ratio` is the
        # fraction of per-row session drains the coalescing removed
        # (rows vs actual deliver calls); lane depth rides the Stats
        # gauge table too (pipeline.deliver.lane_depth), so Prometheus/
        # StatsD/$SYS stats all carry the point-in-time value.
        deliver = {}
        for key in ("rows", "plans", "deliveries", "drains",
                    "frame_rows", "frames_built",
                    "backpressure_waits", "deliver_errors",
                    "slow_errors", "slow_msgs", "barriers"):
            v = self.metrics.val(f"pipeline.deliver.{key}")
            if v:
                deliver[key] = v
        if deliver.get("deliveries"):
            deliver["coalesce_ratio"] = round(
                1.0 - deliver.get("drains", 0) / deliver["deliveries"],
                4)
            # ISSUE 41: the share of rows that went out as a run of
            # joined shared frames, and the frames serialized a row
            deliver["frame_share"] = round(
                deliver.get("frame_rows", 0) / deliver["deliveries"], 4)
            deliver["frames_per_delivery"] = round(
                deliver.get("frames_built", 0) / deliver["deliveries"], 4)
        if self.deliver_state_fn is not None:
            try:
                deliver["state"] = self.deliver_state_fn()
            except Exception:  # noqa: BLE001 — telemetry never raises
                pass
        # fault-domain supervision (ISSUE 6): fault/trip/replay/stall
        # counters + the supervisor's live breaker/rung/journal state —
        # the section the chaos matrix and the OBSERVABILITY triage
        # order read first when a pipeline degrades
        supervise = {}
        for k in ("faults", "trips", "probes", "probe_failures",
                  "replays", "stalls", "restarts", "task_errors",
                  "rung_changes"):
            v = self.metrics.val(f"supervise.{k}")
            if v:
                supervise[k] = v
        by_point = {k.rsplit(".", 1)[1]: v
                    for k, v in self.metrics.all().items()
                    if k.startswith("supervise.faults.")}
        if by_point:
            supervise["faults_by_point"] = by_point
        by_stall = {k.rsplit(".", 1)[1]: v
                    for k, v in self.metrics.all().items()
                    if k.startswith("supervise.stalls.")}
        if by_stall:
            supervise["stalls_by_stage"] = by_stall
        if self.supervise_state_fn is not None:
            try:
                supervise["state"] = self.supervise_state_fn()
            except Exception:  # noqa: BLE001 — telemetry never raises
                pass
        # window-causal flight recorder (ISSUE 7): ring state + the
        # overlap/bubble analysis — the section bench rounds read for
        # the dispatch↔materialize overlap fraction and the top bubble
        # attributions per window
        trace = {}
        if self.recorder is not None:
            try:
                trace = self.recorder.snapshot_section()
            except Exception:  # noqa: BLE001 — telemetry never raises
                pass
        # columnar ingress (ISSUE 11): burst/row/fallback counters, the
        # burst-size histogram and per-acceptor-lane accept counts —
        # the section ingress_bench and the twin rows read. Derived
        # purely from traffic: with broker.columnar_ingress=0 nothing
        # increments, so the section is absent exactly as pre-ISSUE-11.
        ingress = {}
        for k in ("bursts", "rows", "fallback_frames", "control_packets",
                  "bytes"):
            v = self.metrics.val(f"pipeline.ingress.{k}")
            if v:
                ingress[k] = v
        rows_c = ingress.get("rows", 0)
        fb = ingress.get("fallback_frames", 0)
        if rows_c or fb:
            ingress["columnar_ratio"] = round(rows_c / (rows_c + fb), 4)
        bh = self.metrics.histograms().get("pipeline.ingress.burst")
        if bh is not None and bh.count:
            snap = bh.snapshot()
            ingress["burst_rows"] = {
                "count": snap["count"],
                "mean": round(snap["mean"], 2),
                "p50": round(snap["p50"], 2),
                "p95": round(snap["p95"], 2),
            }
        lanes_acc = {k.split(".")[2]: v
                     for k, v in self.metrics.all().items()
                     if k.startswith("pipeline.ingress.lane") and v}
        if lanes_acc:
            ingress["lanes"] = lanes_acc
        # HBM ledger (ISSUE 8): per-category device bytes + peak
        # watermarks + pin ages + the backend memory_stats cross-check
        # — the section that makes "does it fit?" answerable before
        # ROADMAP items 1/3 size anything
        memory = {}
        if self.ledger is not None:
            try:
                memory = self.ledger.section()
            except Exception:  # noqa: BLE001 — telemetry never raises
                pass
        # overload governor (ISSUE 14): grade + armed shed actions +
        # signal readings (state_fn) and the pipeline.overload.*
        # shed/reject counters — the section the overload bench and
        # the $SYS alarm consumers read. Like `latency`, the section
        # exists ONLY when the governor does (knob-off twin: absent
        # even at full=True).
        overload = self.overload_section() \
            if self.overload_state_fn is not None else {}
        # latency SLO observatory (ISSUE 13): per-(qos, path)
        # ingress→routed / ingress→delivered percentiles + the SLO
        # burn/verdict + breach exemplars — the section bench phase
        # rows embed and tools/latency_report.py grades offline
        latency = {}
        if self.observatory is not None:
            try:
                latency = self.observatory.section()
            except Exception:  # noqa: BLE001 — telemetry never raises
                pass
        out = {
            "schema": SCHEMA,
            "stages": stages,
            "occupancy": occupancy,
            "compiles": compiles,
            "decisions": decisions,
        }
        if self.device_info is not None:
            out["device"] = dict(self.device_info)
        if self.runtime_state_fn is not None:
            try:
                out["runtime"] = self.runtime_state_fn()
            except Exception:  # noqa: BLE001 — telemetry never raises
                pass
        if chooser or (full and self.chooser_state_fn is not None):
            out["chooser"] = chooser
        if supervise or full:
            out["supervise"] = supervise
        if rebuild or full:
            out["rebuild"] = rebuild
        if deliver or full:
            out["deliver"] = deliver
        if cache or full:
            out["match_cache"] = cache
        if dedup or full:
            out["dedup"] = dedup
        if readback or full:
            out["readback"] = readback
        if exchange:
            # traffic-derived ONLY (never materialized at full=True):
            # broker.device_exchange=0 increments nothing, so the
            # section is absent exactly as pre-ISSUE-15 — the schema
            # half of the =0-restores-exactly twin contract
            out["exchange"] = exchange
        if trace or full:
            out["trace"] = trace
        if ingress or full:
            out["ingress"] = ingress
        if memory or full:
            out["memory"] = memory
        if self.overload_state_fn is not None and (overload or full):
            # knob-off leaves NO overload section even at full=True:
            # the A/B twin contract is "no governor object anywhere"
            out["overload"] = overload
        if self.observatory is not None and (latency or full):
            # knob-off leaves NO latency section even at full=True: the
            # A/B twin contract is "no observatory object anywhere" —
            # unlike trace/memory, whose sections full-materialize, the
            # latency schema simply does not exist without the knob
            out["latency"] = latency
        jc = _jit_cache_sizes()
        if jc:
            out["jit_cache"] = jc
        # jit-program cost registry (ISSUE 8): per-(program, class)
        # compile wall-time — and flops/bytes once an off-path consumer
        # (cost_stats(analyze=True)) has analyzed them — keyed
        # by the same labels as compiles.by_shape. Snapshot never
        # triggers the (re-lowering) analysis itself.
        pc = _program_costs()
        if pc is not None and (pc or full):
            out["program_costs"] = pc
        return out


def _jit_cache_sizes() -> dict:
    """Jit-cache entry counts of the route-step programs — the recompile
    accounting's ground truth (each entry is one compiled (shape,
    static-args) variant). Empty when jax / the models module isn't
    loaded yet, so snapshot() never forces a jax import."""
    import sys
    mod = sys.modules.get("emqx_tpu.models.router_engine")
    if mod is None:
        return {}
    try:
        return mod.compile_stats()
    except Exception:  # noqa: BLE001 — telemetry must never raise
        return {}


def _program_costs() -> "dict | None":
    """The ISSUE-8 jit-program cost registry (compile wall per class;
    flops/bytes where analyzed) — same import discipline as
    _jit_cache_sizes: snapshot() never forces a jax import and never
    pays the lazy cost analysis (analyze=False). None when the
    observatory knob is off (EMQX_TPU_HBM_LEDGER=0): the section must
    not exist at all, exactly pre-ISSUE-8."""
    import sys
    mod = sys.modules.get("emqx_tpu.models.router_engine")
    if mod is None:
        return {}
    try:
        if not mod.cost_registry_enabled():
            return None
        return mod.cost_stats()
    except Exception:  # noqa: BLE001 — telemetry must never raise
        return {}
