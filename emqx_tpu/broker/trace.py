"""The pipeline's tracing: one span call, and the window-causal flight
recorder under it (ISSUE 7, ISSUE 24).

**One span call, three sinks** (`Spans`, wired as `node.spans`). Every
stage boundary of the serving path (batcher, engines, delivery lanes,
connection ingress) is one `with node.spans.span(name, trace_id, ...)`,
and that one call (a) observes the stage histogram of
`PipelineTelemetry` where the span is a telemetry stage, (b) records
the span on the flight recorder's ring below, under the window's trace
id and parent, and (c) wraps the stretch in
``jax.profiler.TraceAnnotation("emqx:<name>", trace_id=..., ...)``, so
it is on the host plane of ANY active profiler session, whoever started
it (a benchmark, or an operator's `emqx_ctl trace device start`), on
the same clock as the device's operations. Every dispatch also runs
under ``StepTraceAnnotation("route_step", step_num=<trace id>)``. Spans
are per window, per read (`emqx:ingress` its decode and each burst's
hand-off, `emqx:control` every packet of it that is no PUBLISH burst,
`emqx:ack` inside that a run of subscribers' PUBACKs up to the write of
what they released: `session.ack_us`) and per lane item, never per
message; on the event-loop thread no
`emqx:` span encloses a suspension (`span.released()` around an
`await`, or the coroutine awaited through `span.run()`, which releases
only while it really waits), or it would bill other coroutines' work to
itself. Waits (enqueue, lane_admit, lane_drain, the window and message
roll-ups) are recorded in retrospect (`Spans.record`): histogram and
ring only. The two stages off the loop (`dispatch`, `materialize`) also
read their thread's CPU clock (`runtime.dispatch.cpu_us`,
`runtime.readback.cpu_us`). With ``broker.trace`` off the ring is absent
and sinks (a) and (c) remain.

**The interpreter under the pipeline**, two watches that a node starts
with its first listener or timer and stops with its last. `GcWatch`
counts the interpreter's collections (`runtime.gc.*`) and makes a
generation-2 collection a span (`emqx:gc`), and reports each of those
to the process's `HeapFreeze`, which moves a heap that a long full
collection found alive into the collector's permanent generation.
`LoopWatch` is the one asyncio loop's own clock: a timed stand-in for
the running loop's selector splits every turn into the wait inside
`select()` and the work between two of them, with the loop thread's CPU
beside the work (`runtime.loop.turns`, `.wait_us`, `.busy_us`,
`.cpu_us`, `.long_turns`, gauge `runtime.loop.longest_turn_us`;
`LoopWatch.state()`, the `runtime` section of the telemetry snapshot),
and puts `emqx:loop_wait` around a `select()` that may block, which
marks the loop's line on the profiler's host timeline.

**The flight recorder.** PR 1's stage histograms aggregate away exactly
what the device-e2e gap diagnosis needs: CAUSALITY (which admit fed
which dispatch fed which delivery) and OVERLAP (how much dispatch(W+1)
actually hides materialize(W), and where the bubbles sit). The ring is
the causal layer under the histograms:

- **Window traces**: every publish window gets a trace id minted at
  batcher admit (`FlightRecorder.new_trace`) and propagated through the
  whole five-stage pipeline — batch_form, dispatch (the id rides the
  ``jax.profiler.StepTraceAnnotation`` so the device timeline joins the
  host one), materialize, plan construction, the delivery lanes, down
  to settle. Supervise replays KEEP the window's original trace id and
  link the replay as a child span (the causal chain survives the
  degradation ladder); lane-worker restarts keep the plan's trace
  (queue items carry the plan, the plan carries the trace).
- **Sampled per-message spans** ride the window trace: one in
  ``EMQX_TPU_TRACE_SAMPLE`` messages records its own enqueue→settle
  span with its topic, so tail latency decomposes per message, not
  just per batch.
- **The flight recorder**: spans land in a lock-free bounded ring
  buffer — always on at window granularity, negligible overhead
  (one ``itertools.count`` bump + one list-slot store per span under
  the GIL; no locks, no allocation beyond the span record). The ring
  retains the last ``cap`` spans, so it is dumpable POST-MORTEM after
  a wedge or a breaker trip: ``GET /api/v5/pipeline/trace?format=
  perfetto``, ``FlightRecorder.dump(path)``, or
  ``tools/trace_report.py`` on a saved dump.
- **The overlap/bubble analyzer** (`analyze_spans`): per-window stage
  occupancy, the dispatch↔materialize overlap fraction (how much of
  window W's readback the next window's dispatch hid), and gap
  attribution — every uncovered interval inside a window is billed to
  ``host_stall`` (waiting on the loop / the dispatch thread / the
  consumer), ``device_stall`` (waiting on the device or the readback
  pool) or ``lane_backpressure`` (waiting on the delivery lanes), with
  the top bubbles named per window.

Knobs: ``broker.trace`` / ``EMQX_TPU_TRACE`` (config beats env beats
default-on; ``=0`` restores the pre-ISSUE-7 behavior exactly — no
recorder object anywhere, zero hot-path cost), ``broker.trace_sample``
/ ``EMQX_TPU_TRACE_SAMPLE`` (per-message sampling 1-in-N, default 256,
0 disables message spans), ``broker.trace_ring`` (span capacity,
default 4096).

Exported three ways: the Chrome trace-event JSON above (loadable in
Perfetto / chrome://tracing), the ``trace`` section of
`PipelineTelemetry.snapshot()` (fanned through $SYS / Prometheus /
StatsD counters / `GET /api/v5/pipeline/stats`), and the
``trace.spans`` / ``trace.windows`` / ``trace.dropped`` counters in
the shared Metrics registry.
"""

from __future__ import annotations

import asyncio
import gc
import itertools
import json
import os
import time
import types
from collections import defaultdict
from typing import Optional

SCHEMA = "emqx_tpu.trace/v1"

# trace id 0 is the node scope: events that belong to no single window
# (breaker trips, rung changes, lane-worker restarts)
NODE_TRACE = 0

# gap attribution: an uncovered interval inside a window is billed by
# the span that ENDS the gap — what the window was waiting FOR
_GAP_ATTR = {
    "dispatch": "host_stall",        # formed, waiting for the dispatch
    "dispatch_cached": "host_stall",  # thread / a pipeline slot
    "batch_form": "host_stall",
    "host_route": "host_stall",
    "deliver": "host_stall",         # readback done, consumer busy
    "materialize": "device_stall",   # dispatched, device/readback pending
    "replay": "host_stall",
    "settle": "host_stall",
}
_LANE_ATTR = "lane_backpressure"
BUBBLE_CLASSES = ("host_stall", "device_stall", "lane_backpressure")


def resolve_trace(configured=None) -> bool:
    """The one tracing-knob resolution: config (``broker.trace``) beats
    ``EMQX_TPU_TRACE`` beats default-on. ``=0`` restores the
    pre-ISSUE-7 behavior exactly (no recorder anywhere) — the A/B
    baseline the shape-equivalence test compares."""
    if configured is not None:
        return bool(configured)
    return os.environ.get("EMQX_TPU_TRACE", "1") \
        not in ("0", "false", "off")


def resolve_trace_sample(configured=None) -> int:
    """Per-message span sampling: one in N messages records its own
    enqueue→settle span. Config (``broker.trace_sample``) beats
    ``EMQX_TPU_TRACE_SAMPLE`` beats the built-in 256. 0 disables
    message spans (window spans stay on)."""
    if configured is None:
        configured = os.environ.get("EMQX_TPU_TRACE_SAMPLE", "256")
    n = int(configured)
    if n < 0:
        raise ValueError(f"trace_sample must be >= 0, got {n}")
    return n


class Span:
    """One recorded span: a (trace, name, track, [t0, t1]) interval in
    the shared perf_counter time base. ``t0 == t1`` is an instant event
    (replay, rung_change, lane_restart). ``parent_id`` links causal
    children (a replay's host_route is a child of the replay span,
    which is a child of the window root)."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "track",
                 "t0", "t1", "meta", "slot")

    def __init__(self, trace_id, span_id, parent_id, name, track,
                 t0, t1, meta, slot=0):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.track = track
        self.t0 = t0
        self.t1 = t1
        self.meta = meta
        self.slot = slot    # ring write cursor at record time

    @property
    def dur(self) -> float:
        return max(0.0, self.t1 - self.t0)


class FlightRecorder:
    """Lock-free bounded span ring + the export/analysis surfaces.

    Thread-safety: ``record`` runs on the event loop AND the dispatch/
    read executor threads concurrently. Each writer claims a unique
    monotonic slot via ``itertools.count().__next__`` (atomic under the
    GIL) and stores into its own ring index — no lock, no torn reads
    (readers snapshot the buffer list and sort by span id). The
    recorded/dropped accounting is derived from the slot numbers in
    the ring at read time, so writers share no mutable counter.
    """

    def __init__(self, metrics=None, *, cap: int = 4096,
                 sample: Optional[int] = None):
        self.cap = max(16, int(cap))
        self.metrics = metrics
        self.sample = resolve_trace_sample(sample) \
            if not isinstance(sample, int) else max(0, sample)
        self._buf: list = [None] * self.cap
        self._slot = itertools.count()       # unique write cursor
        self._ids = itertools.count(1)       # trace + span ids
        self._msg_tick = itertools.count()   # message-sampling clock
        self.windows = 0                     # traces minted (approximate)
        # one shared time base for every span: ts in exports are
        # relative to epoch_perf; epoch_wall anchors them to wall clock
        self.epoch_perf = time.perf_counter()
        self.epoch_wall = time.time()

    # ---- recording (hot path) -------------------------------------------
    def new_trace(self) -> int:
        """Mint one window trace id (batcher admit)."""
        self.windows += 1
        if self.metrics is not None:
            self.metrics.inc("trace.windows")
        return next(self._ids)

    def record(self, trace_id: int, name: str, t0: float, t1: float, *,
               track: str = "pipeline", parent: int = 0,
               meta: Optional[dict] = None) -> int:
        """Record one span; returns its span id (for child linking)."""
        sid = next(self._ids)
        slot = next(self._slot)
        i = slot % self.cap
        if self.metrics is not None:
            self.metrics.inc("trace.spans")
            if self._buf[i] is not None:
                self.metrics.inc("trace.dropped")
        self._buf[i] = Span(trace_id, sid, parent, name, track,
                            t0, t1, meta, slot)
        return sid

    def event(self, trace_id: int, name: str, *,
              track: str = "events", parent: int = 0,
              meta: Optional[dict] = None) -> int:
        """Record one instant event (replay, rung change, restart)."""
        now = time.perf_counter()
        return self.record(trace_id, name, now, now, track=track,
                           parent=parent, meta=meta)

    def sample_hit(self) -> bool:
        """One global sampling decision per message: True one-in-
        ``sample`` calls (0 = never)."""
        if self.sample <= 0:
            return False
        return next(self._msg_tick) % self.sample == 0

    # ---- reading --------------------------------------------------------
    def spans(self) -> list[Span]:
        """Snapshot the ring, oldest first (span ids are monotone)."""
        return sorted((s for s in list(self._buf) if s is not None),
                      key=lambda s: s.span_id)

    def recorded(self) -> int:
        """Total spans ever recorded — derived from the highest write
        cursor present in the ring at read time, so concurrent writers
        need no shared read-modify-write on the hot path (a plain
        counter store races: a preempted writer's stale store would
        regress it). Exact once writers are quiescent; a consistent
        lower bound mid-flight (overwrites only raise slot numbers)."""
        return max((s.slot for s in list(self._buf) if s is not None),
                   default=-1) + 1

    def dropped(self) -> int:
        return max(0, self.recorded() - self.cap)

    def state(self) -> dict:
        return {"cap": self.cap, "recorded": self.recorded(),
                "dropped": self.dropped(), "sample": self.sample,
                "windows": self.windows}

    # ---- Chrome trace-event / Perfetto export ---------------------------
    def to_chrome(self, spans: Optional[list[Span]] = None) -> dict:
        """The ring as a Chrome trace-event document (Perfetto /
        chrome://tracing loadable): one process ``emqx_tpu pipeline``,
        one thread track per span track (batcher / dispatch /
        materialize / consume / lane{i} / messages / events), complete
        (``X``) events for real spans and instant (``i``) events for
        the zero-duration ones, args carrying the causal ids so
        `analyze_chrome` round-trips. The device timeline joins on the
        ``trace_id`` arg: the engine annotates every dispatch with
        ``StepTraceAnnotation("route_step", step_num=<trace id>)``, so
        a jax.profiler capture of the same run keys its device steps
        by the same ids."""
        if spans is None:
            spans = self.spans()
        pid = 1
        tids: dict[str, int] = {}
        events: list[dict] = [{
            "ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": "emqx_tpu pipeline"}}]

        def tid_of(track: str) -> int:
            t = tids.get(track)
            if t is None:
                t = tids[track] = len(tids) + 1
                events.append({"ph": "M", "pid": pid, "tid": t,
                               "name": "thread_name",
                               "args": {"name": track}})
            return t

        for sp in spans:
            args = {"trace_id": sp.trace_id, "span_id": sp.span_id}
            if sp.parent_id:
                args["parent_id"] = sp.parent_id
            if sp.meta:
                args.update(sp.meta)
            ev = {"name": sp.name, "cat": "pipeline", "pid": pid,
                  "tid": tid_of(sp.track),
                  "ts": round((sp.t0 - self.epoch_perf) * 1e6, 3),
                  "args": args}
            if sp.t1 > sp.t0:
                ev["ph"] = "X"
                ev["dur"] = round((sp.t1 - sp.t0) * 1e6, 3)
            else:
                ev["ph"] = "i"
                ev["s"] = "t"
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"schema": SCHEMA,
                              "epoch_wall": self.epoch_wall,
                              "dropped": self.dropped()}}

    def dump(self, path: str) -> str:
        """Write the Perfetto-loadable dump (post-mortem surface)."""
        doc = self.to_chrome()
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path

    # ---- analysis -------------------------------------------------------
    def analyze(self, *, top: int = 3, per_window: int = 8) -> dict:
        return analyze_spans(self.spans(), top=top,
                             per_window=per_window)

    def snapshot_section(self) -> dict:
        """The ``trace`` section of `PipelineTelemetry.snapshot()`:
        ring state + the aggregate overlap/bubble analysis (per-window
        rows capped so $SYS payloads stay bounded)."""
        out = {"schema": SCHEMA, "ring": self.state()}
        a = self.analyze(per_window=4)
        for k in ("windows", "overlap", "stage_occupancy", "bubbles",
                  "last_windows"):
            if k in a:
                out[k] = a[k]
        return out


# ---- the one span call: histogram + ring + profiler timeline ----------

PROFILER_PREFIX = "emqx:"


class _Released:
    """`with span.released():` around an `await` inside a span on the
    event-loop thread: the profiler annotation is left for the wait and
    a fresh one entered after it, so it never bills other coroutines'
    work to this span. Histogram and ring still see the whole stage;
    the stretch is added to the span's `away`."""

    __slots__ = ("_sp", "_t")

    def __init__(self, sp):
        self._sp = sp

    def __enter__(self):
        self._sp._ann.__exit__(None, None, None)
        self._t = time.perf_counter()

    def __exit__(self, *exc):
        sp = self._sp
        sp.away += time.perf_counter() - self._t
        sp._ann = sp._annotate()


class _Span:
    """One open span (see `Spans.span`). After exit `dur` is its
    seconds, `away` those of them it spent released (`dur - away` is
    what its `emqx:` annotation covered) and `sid` its ring span id (0
    when the ring took nothing), for child linking."""

    __slots__ = ("_o", "_label", "_kw", "_ann", "_cpu", "_c0", "trace",
                 "stage", "ring", "track", "parent", "meta", "t0", "dur",
                 "away", "sid")

    def __init__(self, owner, name, trace, stage, ring, track, parent,
                 meta):
        self._o = owner
        self._label = PROFILER_PREFIX + name
        kw = dict(meta) if meta else {}
        if trace:
            kw["trace_id"] = trace
        self._kw = kw
        # an off-loop stage also reads its thread's CPU clock (`Spans`)
        self._cpu = owner.cpu_counters.get(name)
        self.trace = trace
        self.stage = stage
        self.ring = ring
        self.track = track
        self.parent = parent
        self.meta = meta
        self.away = 0.0
        self.sid = 0

    def _annotate(self):
        return self._o.annotate(self._label, **self._kw)

    def released(self) -> _Released:
        return _Released(self)

    @types.coroutine
    def run(self, coro):
        """`await span.run(coro)` is `await coro` with the span released
        for as long as `coro` is suspended, and only then: a coroutine
        that runs through without waiting stays under the annotation
        whole. For awaiting code that cannot wrap its own awaits."""
        it = coro.__await__()
        try:
            waits_on = it.send(None)
            while True:
                rel = self.released()
                rel.__enter__()
                try:
                    got = yield waits_on
                except GeneratorExit:
                    rel.__exit__()
                    it.close()
                    raise
                except BaseException as e:  # noqa: BLE001 — handed on
                    rel.__exit__()
                    waits_on = it.throw(e)
                else:
                    rel.__exit__()
                    waits_on = it.send(got)
        except StopIteration as e:
            return e.value

    def __enter__(self):
        self.t0 = time.perf_counter()
        if self._cpu is not None:
            self._c0 = time.thread_time_ns()
        self._ann = self._annotate()
        return self

    def drop(self) -> None:
        """Leave the span without feeding histogram or ring (the
        stretch turned out to be no work of this stage's)."""
        self._ann.__exit__(None, None, None)

    def __exit__(self, *exc):
        self._ann.__exit__(None, None, None)
        t1 = time.perf_counter()
        self.dur = t1 - self.t0
        o = self._o
        if self._cpu is not None and o.metrics is not None:
            # entered and left on one thread by construction (a `with`)
            o.metrics.inc(self._cpu,
                          (time.thread_time_ns() - self._c0) // 1000)
        self.sid = o.record(
            self.ring, self.trace, self.t0, t1, stage=self.stage,
            track=self.track, parent=self.parent, meta=self.meta)
        return False


class Spans:
    """The pipeline's one span call, feeding three sinks:

    (a) the stage histogram of `PipelineTelemetry`, where the span names
        a telemetry stage (`stage=`, by default the name when it is one
        of `telemetry.STAGES`);
    (b) the flight-recorder ring, under the window's trace id and parent
        (skipped with `broker.trace` off, or for a window that carries
        no trace: `trace` 0);
    (c) `jax.profiler.TraceAnnotation("emqx:<name>", trace_id=...,
        **meta)`, so the stretch is on the host plane of any profiler
        session that is active, whoever started it. Inactive, it costs
        about half a microsecond.

    `span()` is for work: per window, per burst, per lane item, never
    per message; on the event-loop thread every `await` inside one is
    wrapped in `span.released()` (or awaited through `span.run()`).
    `record()` is the retrospective form for waits (enqueue,
    lane_drain, ...): sinks (a) and (b) only.

    The two stages that run off the loop (`cpu_counters`: `dispatch`
    on the dispatch thread, `materialize` on the read threads) also
    read their thread's CPU clock, into `runtime.dispatch.cpu_us` /
    `runtime.readback.cpu_us`: how much of a second the threads beside
    the loop compute, and so can hold the GIL (`LoopWatch` has the
    loop's side)."""

    cpu_counters = {"dispatch": "runtime.dispatch.cpu_us",
                    "materialize": "runtime.readback.cpu_us"}

    def __init__(self, tele=None, rec=None):
        from jax.profiler import TraceAnnotation
        from emqx_tpu.broker.telemetry import STAGES
        self.tele = tele
        self.rec = rec
        self.metrics = getattr(tele, "metrics", None)
        self._annotation = TraceAnnotation
        self._stages = frozenset(STAGES)

    def annotate(self, label: str, **stats):
        """An entered profiler annotation; the caller leaves it with
        `__exit__`. A TraceMe starts its clock when it is built, so
        each stretch gets a new one."""
        return self._annotation(label, **stats).__enter__()

    def span(self, name: str, trace: int = 0, *,
             stage: Optional[str] = None, ring: Optional[str] = None,
             track: str = "pipeline", parent: int = 0,
             meta: Optional[dict] = None) -> _Span:
        if stage is None and name in self._stages:
            stage = name
        return _Span(self, name, trace, stage, ring or stage or name,
                     track, parent, meta)

    def record(self, name: str, trace: int, t0: float,
               t1: Optional[float] = None, *,
               stage: Optional[str] = None, track: str = "pipeline",
               parent: int = 0, meta: Optional[dict] = None) -> int:
        """Observe `[t0, t1 or now]` retrospectively; returns the ring
        span id (0 when the ring took nothing)."""
        if t1 is None:
            t1 = time.perf_counter()
        if stage is not None and self.tele is not None:
            self.tele.observe_stage(stage, t1 - t0)
        if self.rec is not None and trace:
            return self.rec.record(trace, name, t0, t1, track=track,
                                   parent=parent, meta=meta)
        return 0


def spans_of(node) -> Spans:
    """A node's `Spans`; bare test-harness nodes without one get the
    sinks they do carry."""
    sp = getattr(node, "spans", None)
    if sp is None:
        sp = Spans(getattr(node, "pipeline_telemetry", None),
                   getattr(node, "flight_recorder", None))
        try:
            node.spans = sp
        except AttributeError:
            pass
    return sp


# ---- the long-lived heap, out of the collector's way (ISSUE 31) --------
#
# CPython re-walks the whole old generation every time the objects
# promoted into it pass a quarter of its size, to find that a broker's
# subscription table, router, host trie and session maps are all still
# alive. Sized on Python 3.12 (PR 31): a JAX-loaded process with a
# `Node` and no subscription tracks 89,000 containers and a full
# collection takes 27-29 ms; `mixed-zipf`'s 125,000 subscriptions make
# that 2.76M containers (22 a subscription) and 542-578 ms, 0.197 us a
# container; the benchmark's cells read 260-1,187 ms. After
# `gc.freeze()` the same collection takes 0.04 ms.

# A full collection's pause is time on what survived plus time on what
# it reclaimed; this is the second, a reclaimed object. Read off the
# cells' windows (my chip runs, PR 31): collections of `plus-100k.flood`
# that reclaimed nothing took 20.7-29 ms, those that reclaimed 24,672
# objects 26-36 ms; `mixed-zipf.flood` 20-31 ms at 3,000 and 47 ms at
# 72,000. (Those were the delivered windows: a finished `DeliveryPlan`
# and its `LaneCounts` pointed at each other until the same PR.)
FREEZE_RECLAIM_S = 0.3e-6
# Once the time on survivors is this far above what it was right after
# the last freeze, the heap has proven long-lived: above the bare
# process's 27-29 ms, a fifth of the shortest pause the cells read.
# What is in flight (8,192 PUBLISHes and their deliveries: 20-25 ms a
# collection in `plus-100k.flood`) is walked every time, frozen or not,
# so it is the base and not the heap: a freeze that took nothing off
# the pause is not tried again.
FREEZE_PAUSE_FLOOR_S = 0.050
# With the table frozen, the collector's own rule for the old generation
# (collect it once the objects promoted since the last time pass a
# quarter of it) is always met, because the quarter is of what is not
# frozen: a full collection then ran every 11th generation-1
# collection, 1.5-2.4 a second under a flood, 77-122 in a 51 s window,
# each walking what is in flight for 20-30 ms to find nothing (my chip
# runs, PR 31). While the heap is frozen the old generation waits for
# this many generation-1 collections instead of the interpreter's 10:
# one full collection every ~3 s at the cells' rates. The young
# thresholds are left alone, and the interpreter's own come back when
# the heap is thawed.
FROZEN_OLD_THRESHOLD = 50
# Only a reference cycle among frozen objects is never reclaimed (a
# closed connection's channel and session; everything else still dies
# by reference count). One unfreeze + full collection + freeze is worth
# its pause once the connections closed and subscriptions removed since
# the last freeze, at 22 containers each, reach this share of the
# frozen count.
REEVALUATE_CHURN_SHARE = 0.10
CONTAINERS_PER_SUBSCRIPTION = 22


class HeapFreeze:
    """The process's one owner of `gc.freeze()`.

    A generation-2 collection that spent long on what survived it has
    proven the heap long-lived: the survivors move into the collector's
    permanent generation on the loop's next turn, so later full
    collections walk only what was allocated since. The subscription
    table grows through ten or more such collections, so the policy
    engages during SUBSCRIBE and again whenever the heap has grown a
    new long-lived part. "Long" is the pause less the time its
    reclaimed objects took, measured from the first full collection
    after the last freeze: what is in flight is walked every time and
    no freeze takes it off the pause, so a freeze that did not help is
    not repeated and the policy converges. Decided from what the
    collector reports; there is no setting.

    The freeze is process-global and so is this object (`HEAP`): every
    started `GcWatch` attaches, every attached watch counts each freeze
    in its own node's metrics, and the last to detach unfreezes, so a
    process that embeds a node gets its heap, and its collector's
    thresholds, back. `collector` is the `gc` module or a stand-in with
    its `freeze`, `unfreeze`, `collect`, `get_freeze_count`,
    `get_threshold` and `set_threshold`."""

    def __init__(self, collector=gc):
        self.gc = collector
        self._watches: list = []
        self._due = None        # the watch whose loop owes a freeze
        self._base = 0.0        # survivor time right after the last one
        self._busy = False      # inside a collection of our own
        self.frozen = 0         # `get_freeze_count()` after the last one
        self.churn = 0          # closed + removed since the last one
        self._thresholds = None  # the interpreter's, while frozen

    def attach(self, watch) -> None:
        self._watches.append(watch)

    def detach(self, watch) -> None:
        self._watches.remove(watch)
        if self._due is watch:
            self._due = None
        if not self._watches and self._thresholds is not None:
            self.gc.unfreeze()
            self.gc.set_threshold(*self._thresholds)
            self._thresholds = None
            self.frozen = self.churn = 0
            self._base = 0.0

    def collected(self, watch, pause_s: float, reclaimed: int) -> None:
        """A generation-2 collection ended; every attached watch reports
        it from its `gc.callbacks` entry, on whichever thread ran it.
        The first report of one that was long on survivors schedules
        the freeze on that watch's loop; the first after a freeze is
        the base the next are measured from."""
        if self._due is not None or self._busy:
            return
        survived_s = pause_s - reclaimed * FREEZE_RECLAIM_S
        if self._base is None:
            self._base = survived_s
        elif survived_s - self._base >= FREEZE_PAUSE_FLOOR_S:
            # owed before it is scheduled: a collection on an executor
            # thread (a snapshot build) races the loop to the next line
            self._due = watch
            if not watch.call_soon(self._freeze, pause_s):
                self._due = None

    def _freeze(self, pause_s: float) -> None:
        if self._due is None:       # detached meanwhile
            return
        self._due = None
        self._refreeze(thaw=False)
        for w in self._watches:
            w.note("freezes", "gc_freeze", self.frozen,
                   pause_ms=round(pause_s * 1e3, 1))

    def _refreeze(self, thaw: bool) -> None:
        """`gc.freeze()`, over the thawed and collected heap where
        `thaw`. Then one full collection of the little that is younger
        than the freeze: it resets the collector's count of the old
        generation, which a bare `gc.freeze()` leaves at the frozen
        heap's size, so that the next full collection would wait for a
        quarter of that to be promoted, cycles piling up meanwhile, and
        be taken for the base long after the heap had grown again."""
        self._busy = True
        try:
            if thaw:
                self.gc.unfreeze()
                self.gc.collect()
            self.gc.freeze()
            self.gc.collect()
        finally:
            self._busy = False
        if self._thresholds is None:
            self._thresholds = young0, young1, _old = self.gc.get_threshold()
            self.gc.set_threshold(young0, young1, FROZEN_OLD_THRESHOLD)
        self._base = None
        self.frozen = self.gc.get_freeze_count()    # a walk, ~12 ms a million
        self.churn = 0

    def churned(self, n: int) -> None:
        """`n` more connections closed or subscriptions removed (a
        node's housekeeping pass): re-evaluate when it is worth it."""
        self.churn += n
        if self.frozen and self.churn * CONTAINERS_PER_SUBSCRIPTION \
                >= REEVALUATE_CHURN_SHARE * self.frozen:
            self.reevaluate()

    def reevaluate(self) -> None:
        """One full collection over the thawed heap, then freeze what
        survived: the only way a cycle among frozen objects is ever
        reclaimed. One pause of the size the freeze took away."""
        before = self.frozen
        self._refreeze(thaw=True)
        for w in self._watches:
            w.note("reevaluations", "gc_reevaluate", self.frozen,
                   frozen_before=before)


HEAP = HeapFreeze()


class GcWatch:
    """The interpreter's collections, counted where they happen: one
    `gc.callbacks` entry while the node serves (`start`/`stop` are
    counted, one per listener or timer). Every collection lands in
    `runtime.gc.pauses.gen{0,1,2}` and `runtime.gc.pause_us`; a
    generation-2 collection (hundreds of milliseconds on a broker's
    heap, with every thread stopped) is also a span: `emqx:gc` on the
    profiler timeline, entered in the callback's start phase and left
    in its stop phase (the same thread by construction), and a `gc`
    span on the ring's node trace. Each one is also reported to the
    process's `HeapFreeze` (`heap`), whose freezes and re-evaluations
    come back as `runtime.gc.freezes` / `.reevaluations`, the gauge
    `runtime.gc.frozen_objects` and `gc_freeze` / `gc_reevaluate`
    events on the node trace."""

    def __init__(self, metrics, spans: Spans):
        self.metrics = metrics
        self.spans = spans
        self.heap = HEAP
        self._users = 0
        self._loop = None
        self._t0 = 0.0
        self._ann = None
        self._closed = self._subs = 0

    def start(self) -> None:
        self._users += 1
        if self._loop is None:
            try:
                self._loop = asyncio.get_running_loop()
            except RuntimeError:
                pass            # counted, never frozen, until a loop runs
        if self._users == 1:
            for name in ("freezes", "reevaluations"):
                self.metrics.inc(f"runtime.gc.{name}", 0)
            gc.callbacks.append(self._on_gc)
            self.heap.attach(self)

    def stop(self) -> None:
        if self._users == 0:
            return
        self._users -= 1
        if self._users == 0:
            self._loop = None
            self.heap.detach(self)
            try:
                gc.callbacks.remove(self._on_gc)
            except ValueError:
                pass

    def _on_gc(self, phase: str, info: dict) -> None:
        gen = info.get("generation", 0)
        if phase == "start":
            self._t0 = time.perf_counter()
            if gen == 2:
                self._ann = self.spans.annotate(PROFILER_PREFIX + "gc",
                                                generation=2)
            return
        t1 = time.perf_counter()
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        m = self.metrics
        m.inc(f"runtime.gc.pauses.gen{gen}")
        m.inc("runtime.gc.pause_us", round((t1 - self._t0) * 1e6))
        if gen != 2:
            return
        rec = self.spans.rec
        if rec is not None:
            rec.record(NODE_TRACE, "gc", self._t0, t1, track="runtime",
                       meta={"generation": 2})
        self.heap.collected(self, t1 - self._t0, info.get("collected", 0))

    def call_soon(self, fn, *args) -> bool:
        """Run `fn` on the next turn of the loop this watch was started
        on, from any thread; False where there is none to run it."""
        loop = self._loop
        if loop is None or loop.is_closed():
            return False
        loop.call_soon_threadsafe(fn, *args)
        return True

    def note(self, counter: str, event: str, frozen: int, **meta) -> None:
        """The heap was frozen or re-evaluated while this node served."""
        self.metrics.inc(f"runtime.gc.{counter}")
        rec = self.spans.rec
        if rec is not None:
            rec.event(NODE_TRACE, event, track="runtime",
                      meta=dict(meta, frozen_objects=frozen))

    def housekeeping(self, closed: int, subscriptions: int) -> None:
        """The node's housekeeping pass: connections closed so far and
        subscriptions held now; what closed or went since the last pass
        is churn among what may be frozen."""
        churn = closed - self._closed + max(0, self._subs - subscriptions)
        self._closed, self._subs = closed, subscriptions
        if self._users and churn > 0:
            self.heap.churned(churn)

    def stats_fun(self, stats) -> None:
        stats.setstat("runtime.gc.frozen_objects", self.heap.frozen)


# ---- the loop's own clock (ISSUE 40) ------------------------------------

# A busy stretch (every callback between two `select` calls) longer than
# this counts in `runtime.loop.long_turns`: every connection waits that
# long for its next read. A constant, not a setting.
LONG_TURN_NS = 10_000_000
# The watch adds up on itself and brings its sums into the node's
# counters this often (and whenever it is read): a turn then costs one
# clock read (four, two of them of the CPU clock, where the loop went
# to sleep) and a dozen integer operations, and no dictionary.
_LOOP_FLUSH_NS = 50_000_000


class _TimedSelector:
    """The running loop's selector with `select` timed; everything else
    is the selector's own (its hot methods bound here once, the rest
    through `__getattr__`)."""

    def __init__(self, selector, watch):
        self._sel = selector
        self._watch = watch
        for name in ("register", "unregister", "modify", "get_key",
                     "get_map", "close"):
            setattr(self, name, getattr(selector, name))

    def __getattr__(self, name):
        return getattr(self._sel, name)

    def select(self, timeout=None):
        w = self._watch
        # the CPU clock costs a system call (6 us on the benchmark's
        # host, PR 40), so it is read only where the loop is about to
        # sleep and has the time, and at the one marked select a flush
        sleeps = timeout is None or timeout > 0 or w._mark
        if sleeps:
            # read inside the wall clock's stretch at both ends, so a
            # busy stretch's CPU never passes its wall time
            cpu = time.thread_time_ns()
        t0 = time.perf_counter_ns()
        busy = t0 - w._t_ret
        w.turns += 1
        w.busy_ns += busy
        if busy > w._recent_ns:
            w._recent_ns = busy
        if busy > LONG_TURN_NS:
            w.long_turns += 1
        if t0 >= w._flush_at:
            w.flush(t0)
        if not sleeps:
            # a poll: callbacks are ready and the loop only looks. That
            # is work of the loop's, not a wait: it goes to the next
            # turn's busy stretch, which starts here
            w.polls += 1
            w._t_ret = t0
            return self._sel.select(timeout)
        w._mark = False
        w.cpu_ns += cpu - w._c_ret
        ann = w.spans.annotate(PROFILER_PREFIX + "loop_wait")
        try:
            events = self._sel.select(timeout)
        finally:
            ann.__exit__(None, None, None)
        w._t_ret = t1 = time.perf_counter_ns()
        w._c_ret = time.thread_time_ns()
        w.wait_ns += t1 - t0
        return events


class LoopWatch:
    """The one asyncio loop's waits, work and CPU, timed where they
    happen. A selector loop calls its selector's `select(timeout)` once
    a turn (`BaseEventLoop._run_once`); while the node serves
    (`start`/`stop` are counted, one per listener or timer, beside
    `GcWatch`'s) a `_TimedSelector` stands in for the running loop's
    selector. No loop subclass, no policy: whoever made the loop keeps
    it. Where the running loop has no selector to wrap (uvloop, a
    proactor loop) the watch stays off, its counters stay 0 and
    `state()` says why.

    Counters: `runtime.loop.turns`; `runtime.loop.wait_us`, inside a
    `select` that may block (timeout not 0); `runtime.loop.busy_us`,
    everything else: from a `select`'s return to the next one's call
    (every callback of the turn), and the zero-timeout polls a loop
    with callbacks ready makes between them, which are its own work
    (a loop that only polls has no room, and reads 100 % busy);
    `runtime.loop.cpu_us`, the loop thread's own CPU over the busy
    stretches (read where the loop goes to sleep, so it follows
    `busy_us` by one stretch between two sleeps), so that
    `busy_us - cpu_us` is time the thread was runnable and not running
    (the GIL in another thread's hands, or the core in another
    process's); `runtime.loop.long_turns`, busy
    stretches over 10 ms. Gauge `runtime.loop.longest_turn_us`: the
    longest busy stretch since the gauges were last sampled. A `select`
    that may block is also `emqx:loop_wait` on the profiler's host
    timeline: the same stretch `wait_us` counts, on the device trace's
    clock. So is the first `select` after each flush, whatever its
    timeout, so that the line of the loop's thread carries the span at
    least twenty times a second however busy the loop is."""

    def __init__(self, metrics, spans: Spans):
        self.metrics = metrics
        self.spans = spans
        self._users = 0
        self._loop = None
        self._proxy: Optional[_TimedSelector] = None
        self._why = "not started"
        self.turns = self.polls = self.long_turns = 0
        self._mark = False      # the next select is a span, poll or not
        self.wait_ns = self.busy_ns = self.cpu_ns = 0
        self._recent_ns = self._longest_ns = 0
        self._t_ret = self._c_ret = self._flush_at = 0
        self._flushed = dict.fromkeys(self._totals(), 0)

    def _totals(self) -> dict:
        """The counters' values as the watch has them, by their names
        under `runtime.loop.`."""
        return {"turns": self.turns, "wait_us": self.wait_ns // 1000,
                "busy_us": self.busy_ns // 1000,
                "cpu_us": self.cpu_ns // 1000,
                "long_turns": self.long_turns}

    # ---- lifecycle ------------------------------------------------------
    def start(self) -> None:
        self._users += 1
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            if self._proxy is None:
                self._why = "no running loop"
            return
        if loop is self._loop and self._proxy is not None:
            return
        # the first start, or a start on another loop than the one
        # watched (tests run several loops against one node)
        self._unwrap()
        sel = getattr(loop, "_selector", None)
        if sel is None or not callable(getattr(sel, "select", None)):
            self._why = f"{type(loop).__name__} has no selector to wrap"
            return
        for name in self._flushed:
            self.metrics.inc(f"runtime.loop.{name}", 0)
        self._loop = loop
        self._why = ""
        self._t_ret = time.perf_counter_ns()
        self._c_ret = time.thread_time_ns()
        self._flush_at = self._t_ret + _LOOP_FLUSH_NS
        self._proxy = loop._selector = _TimedSelector(sel, self)

    def stop(self) -> None:
        if self._users == 0:
            return
        self._users -= 1
        if self._users == 0:
            self._unwrap()
            self._why = "stopped"

    def _unwrap(self) -> None:
        """Put the loop's own selector back: ours out of the chain,
        wherever in it another node's watch has left it."""
        proxy, loop = self._proxy, self._loop
        self._proxy = self._loop = None
        if proxy is None:
            return
        self.flush()
        at = getattr(loop, "_selector", None)
        if at is proxy:
            loop._selector = proxy._sel
            return
        while isinstance(at, _TimedSelector):
            if at._sel is proxy:
                at._sel = proxy._sel
                return
            at = at._sel

    # ---- reading --------------------------------------------------------
    def flush(self, now_ns: int = 0) -> None:
        """Bring what was added up since the last flush into the node's
        counters (whole microseconds; the remainder stays)."""
        self._flush_at = (now_ns or time.perf_counter_ns()) + _LOOP_FLUSH_NS
        self._mark = True
        if self._recent_ns > self._longest_ns:
            self._longest_ns = self._recent_ns
        done = self._flushed
        for name, total in self._totals().items():
            if total != done[name]:
                self.metrics.inc(f"runtime.loop.{name}", total - done[name])
                done[name] = total

    def state(self) -> dict:
        self.flush()
        out = {"on": self._proxy is not None, "users": self._users}
        if self._why:
            out["why"] = self._why
        out.update(self._totals(), polls=self.polls,
                   longest_turn_us=self._longest_ns // 1000)
        return out

    def stats_fun(self, stats) -> None:
        self.flush()
        stats.setstat("runtime.loop.longest_turn_us",
                      self._recent_ns // 1000)
        self._recent_ns = 0


# ---- the overlap/bubble analyzer (pure functions, reusable offline) ----

def _union_and_gaps(intervals: list[tuple], w0: float, w1: float):
    """Merge [t0, t1, name] intervals clipped to [w0, w1]; return
    (covered_seconds, gaps) where each gap is (g0, g1, next_name) —
    the name of the span that ENDS the gap (what was being waited on).
    The trailing gap (after the last span) carries next_name=None."""
    ivs = sorted((max(w0, a), min(w1, b), n)
                 for a, b, n in intervals if b > a)
    covered = 0.0
    gaps = []
    cur = w0
    for a, b, n in ivs:
        if a > cur:
            gaps.append((cur, a, n))
        if b > cur:
            covered += b - max(cur, a)
            cur = b
    if w1 > cur:
        gaps.append((cur, w1, None))
    return covered, gaps


def _attr_of(next_name: Optional[str], has_lanes: bool) -> str:
    if next_name is None:
        # trailing gap: the window sat settled-pending — on the lanes
        # when the trace shows lane work, else on the host consumer
        return _LANE_ATTR if has_lanes else "host_stall"
    if next_name.startswith("lane"):
        return _LANE_ATTR
    return _GAP_ATTR.get(next_name, "host_stall")


def analyze_spans(spans: list, *, top: int = 3,
                  per_window: int = 8) -> dict:
    """Per-window occupancy + bubbles and the global dispatch↔
    materialize overlap, from any span list (the live ring, or one
    reconstructed from a Perfetto dump by `analyze_chrome`).

    Returns::

        {"windows": N,
         "overlap": {"dispatch_materialize": 0.42,
                     "materialize_s": ..., "overlapped_s": ...},
         "stage_occupancy": {stage: {"total_s":, "mean_frac":}},
         "bubbles": {"host_stall_s":, "device_stall_s":,
                     "lane_backpressure_s":, "total_s":,
                     "top": [[label, seconds], ...]},
         "last_windows": [{"trace_id":, "span_s":, "stages": {...},
                           "bubbles": [[attr, s], ...]}, ...]}
    """
    by_trace: dict[int, list] = defaultdict(list)
    dispatches: list[tuple] = []
    materializes: list[tuple] = []
    for sp in spans:
        if sp.trace_id > NODE_TRACE:
            by_trace[sp.trace_id].append(sp)
        if sp.name in ("dispatch", "dispatch_cached") and sp.t1 > sp.t0:
            dispatches.append((sp.t0, sp.t1, sp.trace_id))
        elif sp.name == "materialize" and sp.t1 > sp.t0:
            materializes.append((sp.t0, sp.t1, sp.trace_id))

    # dispatch↔materialize overlap: how much of each window's readback
    # was hidden under ANOTHER window's dispatch (the double-buffering
    # win ROADMAP item 1 is tuned against). Fraction of total
    # materialize seconds covered by a different trace's dispatch.
    dispatches.sort()
    materializes.sort()
    mat_s = 0.0
    hidden_s = 0.0
    lo = 0
    for m0, m1, mtid in materializes:
        mat_s += m1 - m0
        # both lists are time-sorted: a dispatch ending at or before
        # this m0 can never cover this or any LATER materialize, so
        # the scan start only moves forward — amortized O(D+M) where
        # a full rescan per materialize is O(D*M) (analyze runs inside
        # snapshot() on the event loop, on every $SYS tick)
        while lo < len(dispatches) and dispatches[lo][1] <= m0:
            lo += 1
        cover: list[tuple] = []
        for j in range(lo, len(dispatches)):
            d0, d1, dtid = dispatches[j]
            if d0 >= m1:
                break
            if dtid == mtid or d1 <= m0:
                continue
            cover.append((max(d0, m0), min(d1, m1), ""))
        covered, _g = _union_and_gaps(cover, m0, m1)
        hidden_s += covered
    overlap = {}
    if materializes:
        overlap = {
            "dispatch_materialize": round(hidden_s / mat_s, 4)
            if mat_s else 0.0,
            "materialize_s": round(mat_s, 6),
            "overlapped_s": round(hidden_s, 6),
        }

    stage_tot: dict[str, float] = defaultdict(float)
    stage_frac: dict[str, list] = defaultdict(list)
    bubble_tot: dict[str, float] = dict.fromkeys(BUBBLE_CLASSES, 0.0)
    win_rows = []
    for tid in sorted(by_trace):
        sps = by_trace[tid]
        # the window interval: admit (first span start) → settle (last
        # span end); instant events bound it too (a replay marks time)
        w0 = min(s.t0 for s in sps)
        w1 = max(s.t1 for s in sps)
        span_s = w1 - w0
        if span_s <= 0:
            continue
        has_lanes = any(s.track.startswith("lane")
                        or s.name in ("lane_admit", "lane_drain")
                        for s in sps)
        stages: dict[str, float] = defaultdict(float)
        ivs = []
        for s in sps:
            if s.t1 <= s.t0 or s.name in ("window", "message"):
                continue    # events and roll-up spans don't cover work
            stages[s.name] += s.dur
            ivs.append((s.t0, s.t1, s.name))
        for name, d in stages.items():
            stage_tot[name] += d
            stage_frac[name].append(d / span_s)
        _covered, gaps = _union_and_gaps(ivs, w0, w1)
        attrs: dict[str, float] = defaultdict(float)
        for g0, g1, nxt in gaps:
            attrs[_attr_of(nxt, has_lanes)] += g1 - g0
        for k, v in attrs.items():
            bubble_tot[k] = bubble_tot.get(k, 0.0) + v
        win_rows.append({
            "trace_id": tid,
            "span_s": round(span_s, 6),
            "stages": {k: round(v, 6) for k, v in stages.items()},
            "bubbles": [[k, round(v, 6)] for k, v in
                        sorted(attrs.items(), key=lambda kv: -kv[1])
                        ][:top],
        })

    out: dict = {"windows": len(win_rows)}
    if overlap:
        out["overlap"] = overlap
    if stage_tot:
        out["stage_occupancy"] = {
            k: {"total_s": round(v, 6),
                "mean_frac": round(sum(stage_frac[k])
                                   / len(stage_frac[k]), 4)}
            for k, v in stage_tot.items()}
    bub_total = sum(bubble_tot.values())
    if win_rows:
        out["bubbles"] = {
            **{f"{k}_s": round(v, 6) for k, v in bubble_tot.items()},
            "total_s": round(bub_total, 6),
            "top": [[k, round(v, 6)] for k, v in
                    sorted(bubble_tot.items(), key=lambda kv: -kv[1])
                    if v > 0][:top],
        }
        out["last_windows"] = win_rows[-per_window:]
    return out


def analyze_chrome(doc: dict, *, top: int = 3,
                   per_window: int = 0) -> dict:
    """Rebuild spans from a Chrome trace-event dump (`to_chrome` /
    `FlightRecorder.dump`) and run the same analyzer —
    ``tools/trace_report.py``'s offline entry. per_window=0 keeps
    every window row (the offline report wants them all)."""
    spans = []
    # tid -> track from the thread_name metadata events: the analyzer's
    # lane attribution keys on span.track (has_lanes), so the offline
    # path must reconstruct it or lane_backpressure silently degrades
    # to host_stall on the very dump the post-mortem reads
    tracks: dict[tuple, str] = {}
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            tracks[(ev.get("pid"), ev.get("tid"))] = \
                (ev.get("args") or {}).get("name", "")
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") not in ("X", "i"):
            continue
        args = ev.get("args") or {}
        if "trace_id" not in args:
            continue
        t0 = float(ev.get("ts", 0)) / 1e6
        t1 = t0 + float(ev.get("dur", 0)) / 1e6
        meta = {k: v for k, v in args.items()
                if k not in ("trace_id", "span_id", "parent_id")}
        spans.append(Span(int(args["trace_id"]),
                          int(args.get("span_id", 0)),
                          int(args.get("parent_id", 0)),
                          ev.get("name", ""),
                          tracks.get((ev.get("pid"), ev.get("tid")),
                                     ""), t0, t1,
                          meta or None))
    spans.sort(key=lambda s: (s.t0, s.span_id))
    n_windows = len({s.trace_id for s in spans if s.trace_id > 0})
    return analyze_spans(spans, top=top,
                         per_window=per_window or max(1, n_windows))
