"""Publish micro-batcher: the cross-connection batching window + pipeline.

The reference amortizes per-packet costs with `{active, N}` socket reads
inside ONE connection (emqx_connection.erl:111,454-464 — SURVEY.md P10);
the TPU design needs batching ACROSS connections so the fused device route
step sees a real batch. This is that window: channels submit PUBLISHes here
and await their delivery counts; a producer task accumulates messages for at
most `window_us` (or until `max_batch`), runs the `message.publish` hook
fold per message (concurrently — exhook gRPC etc. stay async), then routes
the batch.

Round-2 rework:

- **Non-blocking**: device dispatch and device→host readback run on executor
  threads (DeviceRouteEngine.dispatch/materialize); the event loop only does
  the cheap encode (prepare) and the delivery walk (finish). A slow
  device round trip does not freeze every connection.
- **Pipelined**: up to `pipeline_depth` dispatched batches are in flight;
  a consumer task completes them strictly in FIFO order, so per-publisher
  ordering holds even when device- and host-routed batches interleave
  (host batches ride the same in-order queue and are routed at consume
  time, never early). What is in flight is also bounded in DELIVERIES
  (`_ROWS_IN_FLIGHT`, by the last plans' rows a message): no window
  forms while the formed windows and the lanes' plans stand for more
  and `max_pending` shrinks by the same measure, so at a fan-out of
  1,000 the publishers feel the lanes instead of 100k messages
  queueing; at a fan-out of 2 the bounds are idle.
- **Adaptive with live probes both ways**: the device/host choice compares
  measured EWMA costs. The host cost is refreshed by an ACTIVE probe
  (round 2's estimator starved: under steady device load the host was
  never sampled and `device_bypassed` could not fire). A probe costs
  what a measurement needs: it holds at most `_PROBE_MSGS` messages and
  `_PROBE_ROWS` deliveries (the rest of the queue forms the next device
  window at once, behind it in the same FIFO), its sample is the host
  route's own time (the turns other coroutines took inside its yields
  are not the host's cost), and it comes every `host_probe_every`
  device sub-batches only while the two costs are within a factor of
  two: while the chip wins by half the gap doubles probe by probe up
  to `_PROBE_GAP_MAX`, and falls back on the first comparison that
  reads otherwise (`_probe_due`, `_probe_gap`). The device cost is
  re-probed every `_PROBE_EVERY` bypassed batches so a transiently
  slow device is not written off forever (that re-try's sample
  replaces the estimate outright). The chip is left only where the
  host is ahead by `_LEAVE_MARGIN` and taken back at parity: two paths
  that cost about the same do not trade places by the sample.
  Pipelined device cost is sampled as completion-to-completion time (the
  amortized rate the pipeline actually delivers), not the full round-trip
  — except across an idle gap, where the round-trip is the sample, and
  never from a window the process compiled under (a class's first call
  says nothing of its next).

Round-10 rework (ISSUE 9 tentpole) — the **double-buffered window
pipeline**: at ``dispatch_depth >= 2`` the consumer becomes a bounded
in-flight settle ring. Each dispatched window's remaining stages
(await dispatch → launch + await materialize) run in their OWN task the
moment the window is admitted from the FIFO queue, up to
``dispatch_depth`` windows concurrently — so dispatch(W+1) runs while
materialize(W) is still crossing the link, and with the engine's async
readback (start-transfer at dispatch return) materialize is
consume-on-arrival. Settle order stays STRICTLY FIFO (the ring head is
always completed first), so per-publisher ordering and the journal
discipline are bit-identical to the synchronous loop. Knob:
``broker.dispatch_depth`` / ``EMQX_TPU_DISPATCH_DEPTH`` (config beats
env beats default 2); ``=1`` restores the pre-ISSUE-9 synchronous
consumer EXACTLY — same code path, the A/B baseline. Supervision: each
in-flight window's stage awaits are bounded by the watchdog deadlines
INDEPENDENTLY (one stage task per window), and a mid-pipeline death
replays exactly the journaled windows it touched through the host rung.

Ordering: submissions are FIFO; batches complete in arrival order; within a
batch messages are consumed in order — MQTT's per-publisher-per-topic
ordering is preserved end to end.
"""

from __future__ import annotations

import asyncio
import os
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

from emqx_tpu.broker.message import Message

# re-probe the device path after this many consecutive host-routed
# batches, so a transiently slow device (cold compile, a stall) is not
# written off forever
_PROBE_EVERY = 64
# the chooser leaves the chip only where the host is ahead by this
# much: leaving drops the fusion width to 1 and makes the host window
# wait out the lanes, so two costs within a quarter of each other do
# not pay for the change of path, and flapping between them is a
# run's throughput by the draw. Measured against `umbrella-cover.
# flood` on a v5e, the one cell where the two costs meet
# (`chooser_margin` 0.4-1.7): 1-8 windows bypassed a run of ~600 and
# no episode on the host in seven runs, where the parent's rule read
# 3-11 and, in two runs of six, an episode of 64 (PERF.md, PR 38)
_LEAVE_MARGIN = 1.25
# deliveries that may stand between the batch queue and the sockets (the
# windows formed and not yet settled, by their recent fan-out, plus the
# lanes' plans), and in the batch queue itself: one sub-batch of 1,024
# at a fan-out of 128, fifty at 2.5, which is more than a flood's closed
# loop holds, so at a narrow fan-out the bound is idle
_ROWS_IN_FLIGHT = 1 << 17
# what the chooser's host probe may hold (`_probe_cap`): deliveries,
# and messages. The host's cost is a message's, so 64 measure what a
# full batch does, and 64 is one un-yielded stretch of
# `_complete_host`'s walk: a probe is then one short turn of the loop
# and not a batch of ~920 messages. Measured in `plus-100k.flood` on a
# v5e's host (fan-out 2.05; PERF.md, PR 43, one seed a side on this
# tree): `host_route` 395 -> 15.8 ms a probe at 234-243 us of loop a
# message, `device_routed_share` 96.9 -> 99.99 %, the batcher's share
# of the loop 198 -> 61-91 ms/s, 41,964 -> 50,037 deliveries/s (+19 %;
# six pairs before the last edit of `_produce`: +18.2 %)
_PROBE_ROWS = 1 << 13
_PROBE_MSGS = 64
# the most device sub-batches between two host probes: the gap starts
# at `host_probe_every` and doubles, probe by probe, while the chip
# wins by half (`_probe_gap`): 32 -> 1,024 by a run's fifth probe,
# after 992 sub-batches. What the gap alone is worth, measured against
# this tree with the gap held at 32 (PERF.md, PR 43): nothing in
# `plus-100k.flood` (50,794 without, 50,037 with: 51 probes of 16 ms
# or 5), and `fleet-bcast.flood`, where a message is 110 deliveries
# and 10-15 ms of host route and a 64-message probe 0.6-1.0 s of
# loop: 409-415k deliveries/s with 9 probes a run, 439-449k with 3-4.
# A snapshot swap that makes the host cheaper is met after this many
# sub-batches at the latest (~35 s at `plus-100k.flood`'s 29 a
# second), and matters only where the host would have to get 2 x
# cheaper than its last sample to change a verdict
_PROBE_GAP_MAX = 1 << 10
# `chooser_margin` under which a landed probe doubles the gap. PR 38's
# dead band was measured at 0.4-1.7 (`_LEAVE_MARGIN`): there, and
# wherever the two costs are within a factor of two, the cadence
# stays `host_probe_every`
_PROBE_RARER_MARGIN = 0.5


def resolve_dispatch_depth(configured=None) -> int:
    """The one dispatch-depth resolution (ISSUE 9): config
    (``broker.dispatch_depth``) beats ``EMQX_TPU_DISPATCH_DEPTH`` beats
    the built-in 2. ``=1`` restores the synchronous consumer loop
    exactly — the A/B baseline every depth-twin test compares. Must be
    a positive integer; anything else is a deployment error worth
    failing loudly on."""
    if configured is None:
        env = os.environ.get("EMQX_TPU_DISPATCH_DEPTH")
        if env is None:
            return 2
        configured = env
    try:
        val = int(configured)
    except (TypeError, ValueError):
        raise ValueError(
            f"EMQX_TPU_DISPATCH_DEPTH={configured!r} is not an integer")
    if val < 1:
        raise ValueError(
            f"EMQX_TPU_DISPATCH_DEPTH must be >= 1, got {val}")
    return val


class PublishBatcher:
    def __init__(self, node, engine, *, window_us: int = 200,
                 max_batch: int = 1024, device_min_batch: int = 4,
                 max_pending: Optional[int] = None,
                 pipeline_depth: int = 8, host_probe_every: int = 32,
                 window_fuse: int = 8,
                 dispatch_depth: Optional[int] = None):
        self.node = node
        self.engine = engine
        # pipeline telemetry (stage spans / occupancy / decisions) — a
        # Node always carries one; tolerate bare test harness nodes
        self.tele = getattr(node, "pipeline_telemetry", None)
        # fault-domain supervision (ISSUE 6): the consumer's watchdog
        # deadlines, the window journal, and the device/host ladder
        # gate all hang off this. None (knob off / bare test nodes)
        # restores the pre-ISSUE-6 unwind behavior exactly.
        self.sup = getattr(node, "supervisor", None)
        # window-causal flight recorder (ISSUE 7): every window's trace
        # id is minted HERE at admit and rides the entry dict through
        # dispatch/materialize/replay/lanes to settle. None (knob off /
        # bare test nodes) restores the pre-ISSUE-7 behavior exactly.
        self.rec = getattr(node, "flight_recorder", None)
        # the one span call (broker/trace.py): stage histogram + ring +
        # profiler timeline for every stage boundary below
        from emqx_tpu.broker.trace import spans_of
        self.spans = spans_of(node)
        # latency SLO observatory (ISSUE 13): per-message ingress→
        # routed / ingress→delivered recording at settle, keyed by the
        # window's (qos, path) attribution. None (knob off / bare test
        # nodes) restores the pre-ISSUE-13 behavior exactly.
        self.obs = getattr(node, "latency_observatory", None)
        # overload governor (ISSUE 14): at grade critical the
        # shed_qos0 action drops QoS0 PUBLISHes HERE, at admit — QoS1/2
        # are never shed (at-least-once intent honored, per-session
        # order preserved). None (knob off / bare test nodes) restores
        # the pre-ISSUE-14 admit paths exactly. One plain attribute
        # read per message when armed; zero reads when gov is None.
        self.gov = getattr(node, "overload_governor", None)
        # the most recent window's trace id (0 before any window):
        # overload shed events land on this trace so the causal
        # timeline shows the ladder moving between the windows
        self.last_trace = 0
        self.window_s = window_us / 1e6
        self.max_batch = max_batch
        self.device_min_batch = device_min_batch
        self.pipeline_depth = pipeline_depth
        # ISSUE 9: how many dispatched windows may run their remaining
        # stages (dispatch-await + materialize) concurrently ahead of
        # their FIFO settle turn. 1 = the pre-ISSUE-9 synchronous
        # consumer, bit-exact (the legacy code path below).
        self.dispatch_depth = resolve_dispatch_depth(dispatch_depth)
        self.host_probe_every = host_probe_every
        # under sustained load, up to this many consecutive batches fuse
        # into ONE device dispatch (route_window) — the per-dispatch
        # cost is paid once per window, the same amortization bench.py
        # measures with BENCH_FUSE
        self.window_fuse = max(1, min(window_fuse, 8))
        # fusion slow-start (congestion-control shaped): the width grows
        # x2 per successfully completed window and resets to 1 whenever
        # the chooser bypasses — early windows stay small so a slow
        # device is discovered after ~1 batch of regret, not 8
        self._fuse_cwnd = 1
        # deliveries a routed message, as the last plans had them: what
        # is in flight is bounded in deliveries (`_ROWS_IN_FLIGHT`)
        self._rows_per_msg = 1.0
        # messages formed into entries / taken up by the consumer for
        # settle (both only grow): their difference waits in
        # `_inflight` or the settle ring
        self._formed = 0
        self._taken = 0
        # fire-and-forget backpressure bound: beyond this, enqueue() refuses
        # and the caller must await submit() (stalling its read loop)
        self.max_pending = max_pending or 8 * max_batch
        self._queue: deque = deque()
        self._task: Optional[asyncio.Task] = None
        self._consumer: Optional[asyncio.Task] = None
        self._inflight: Optional[asyncio.Queue] = None
        # one dispatch thread keeps device dispatches ordered (the engine
        # threads cursors batch-to-batch); readbacks overlap on their own
        self._dispatch_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="route-dispatch")
        self._read_pool = ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="route-read")
        # adaptive device/host choice: EWMAs of measured cost. Whether
        # the fused device step or the host path wins a given batch size
        # depends on the dispatch round trip — measure, don't assume
        # (SURVEY §7 hard-part 2's adaptive micro-batching).
        self._dev_batch_s: Optional[float] = None    # per device batch
        self._host_msg_s: Optional[float] = None     # per host message
        self._dev_spike = 0       # consecutive-outlier streaks (_ewma)
        self._host_spike = 0
        # PUBLISH→route latency reservoir (BASELINE.md's p99<2ms
        # criterion is judged on this: oldest-enqueue → batch completion,
        # which upper-bounds every message in the batch). _q_times
        # parallels _queue so the submit/enqueue tuple shape is untouched.
        self._q_times: deque = deque()
        self.route_lat: deque = deque(maxlen=8192)
        # _dev_batch_s / (n * _host_msg_s) of the last cost comparison
        self.chooser_margin: Optional[float] = None
        self._since_probe = 0         # host batches since last device try
        self._on_host = False         # the last cost comparison chose it
        self._dev_reprobe = False     # next device sample is that re-try's
        self._since_host_probe = 0    # device batches since last host probe
        # times the gap between host probes has doubled (`_probe_gap`),
        # and whether a probe's sample landed since the last comparison
        self._probe_doublings = 0
        self._probe_out = False
        self._probe_landed = False
        self._last_dev_done: Optional[float] = None
        self._compiles_seen = 0       # `tele.compiles` at the last sample
        self._consuming = False       # consumer mid-entry (fast-path gate)

    # ---- producer side --------------------------------------------------
    def _shed_qos0(self, msg: Message) -> bool:
        """ISSUE 14: True when the overload governor's shed_qos0 action
        is armed AND this message is QoS0 — the message is dropped at
        admit (counted; the publisher owes no ack, so nothing hangs).
        QoS1/2 NEVER pass this gate."""
        gov = self.gov
        if gov is not None and gov.shed_qos0 and msg.qos == 0:
            gov.count_qos0_shed()
            return True
        return False

    async def submit(self, msg: Message) -> int:
        """Queue one PUBLISH; resolves to its delivery count."""
        if self._shed_qos0(msg):
            return 0
        fut = asyncio.get_running_loop().create_future()
        self._queue.append((msg, fut))
        self._q_times.append(time.perf_counter())
        self._kick()
        return await fut

    def enqueue(self, msg: Message) -> bool:
        """Fire-and-forget submit (QoS0: the publisher owes no ack, so one
        connection can pipeline publishes into a single batch window).
        Returns False when the queue is over the backpressure bound — the
        caller must fall back to awaiting submit()."""
        if self._shed_qos0(msg):
            return True      # accepted-and-shed: no fallback submit
        if len(self._queue) >= self._pending_limit():
            return False
        self._queue.append((msg, None))
        self._q_times.append(time.perf_counter())
        self._kick()
        return True

    def submit_burst(self, rows: list) -> dict:
        """Columnar-ingress hand-off (ISSUE 11): append a whole read
        burst's messages to the batch queue in one pass. `rows` is
        [(Message, needs_count)], in publisher frame order — the queue
        is FIFO, so per-publisher order is preserved by construction.

        QoS0 rows (needs_count=False) ride WITHOUT per-message futures,
        like enqueue(); QoS1/2 rows get futures that resolve through
        the existing window journal / settle machinery. One timestamp
        covers the burst (its rows entered together), one _kick wakes
        the producer, and the burst's unique topics are interned in one
        vectorized native pass (engine.preencode_burst) so the window
        encode later hits a warm gather instead of per-window probes.

        Returns {row_index: future} for every row the caller must
        await: all QoS>=1 rows, plus the burst's LAST row when the
        queue crossed max_pending — awaiting it stalls the read loop,
        the same backpressure a refused enqueue() exerts."""
        loop = asyncio.get_running_loop()
        futs: dict = {}
        q = self._queue
        qt = self._q_times
        now = time.perf_counter()
        over = len(q) + len(rows) > self._pending_limit()
        last = len(rows) - 1
        for i, (msg, need) in enumerate(rows):
            if not need and self._shed_qos0(msg):
                # ISSUE 14: QoS0 rows shed at admit never enter the
                # queue; QoS1/2 rows (need=True) always do. Relative
                # order of the surviving rows is the row order.
                continue
            fut = None
            if need or (over and i == last):
                fut = loop.create_future()
                futs[i] = fut
            q.append((msg, fut))
            qt.append(now)
        eng = self.engine
        if eng is not None and rows:
            pre = getattr(eng, "preencode_burst", None)
            if pre is not None:
                pre([m.topic for m, _n in rows])
        self._kick()
        return futs

    def _kick(self) -> None:
        if self._inflight is None:
            self._inflight = asyncio.Queue(maxsize=self.pipeline_depth)
        from emqx_tpu.broker.supervise import guard_task
        if self._task is None or self._task.done():
            self._task = guard_task(
                asyncio.get_running_loop().create_task(self._produce()),
                "batcher-produce", self.node.metrics)
        if self._consumer is None or self._consumer.done():
            self._consumer = guard_task(
                asyncio.get_running_loop().create_task(self._consume()),
                "batcher-consume", self.node.metrics)

    async def stop(self) -> None:
        for t in (self._task, self._consumer):
            if t is not None and not t.done():
                t.cancel()
                try:
                    await t
                except asyncio.CancelledError:
                    pass
        # fail anything still queued/in flight so publishers unblock
        err = RuntimeError("publish batcher stopped")
        while self._queue:
            _m, fut = self._queue.popleft()
            if fut is not None and not fut.done():
                fut.set_exception(err)
        self._q_times.clear()
        if self._inflight is not None:
            while not self._inflight.empty():
                entry = self._inflight.get_nowait()
                if entry.get("eof"):
                    continue
                for _m, fut in entry["batch"]:
                    if fut is not None and not fut.done():
                        fut.set_exception(err)
                if entry.get("handle") is not None:
                    self.engine.abandon(entry["handle"])
                if self.sup is not None:
                    self.sup.journal_settle(entry.get("wid"))
        self._taken = self._formed      # nothing waits any more
        self._task = None
        self._consumer = None

    def close(self) -> None:
        self._dispatch_pool.shutdown(wait=False)
        self._read_pool.shutdown(wait=False)

    # ---- producer: form batches, choose path, dispatch ------------------
    async def _produce(self) -> None:
        loop = asyncio.get_running_loop()
        # messages a cut host probe left of the batch it was cut from:
        # they form the next batch at once and by themselves, as the
        # uncut batch would have. A beat's wait here is a turn of the
        # loop in which other connections' bursts land behind them, and
        # a batch that mixes two groups of connections puts them in
        # step for good (`share50-250k.flood`: every run at a fuse
        # depth of 2.0 with the loop a fifth idle; PERF.md, PR 43)
        rest = 0
        while True:
            while self._queue:
                # adaptive window: the first message opened it; give
                # concurrent connections one short beat to pile on unless
                # already full
                if not rest and len(self._queue) < self.max_batch \
                        and self.window_s > 0:
                    await asyncio.sleep(self.window_s)
                def form_entry(limit):
                    batch = []
                    rec = self.rec
                    sampled = None
                    t_enq = self._q_times[0] if self._q_times else \
                        time.perf_counter()
                    while self._queue and len(batch) < limit:
                        batch.append(self._queue.popleft())
                        tq = self._q_times.popleft()
                        if rec is not None and rec.sample_hit():
                            # sampled per-message span (ISSUE 7): this
                            # message records its own enqueue→settle
                            # interval on the window trace
                            if sampled is None:
                                sampled = []
                            sampled.append((len(batch) - 1, tq))
                    self._formed += len(batch)
                    entry = {"batch": batch, "handle": None, "sub": 0,
                             "dispatch_fut": None, "live": None,
                             "live_idx": None, "t_enq": t_enq}
                    tid = 0
                    if rec is not None:
                        # the window's trace id, minted at admit
                        tid = rec.new_trace()
                        entry["trace"] = tid
                        self.last_trace = tid
                        if sampled:
                            entry["trace_msgs"] = sampled
                    # enqueue stage: oldest-message queue wait before
                    # its batch formed (upper-bounds the batch); its
                    # ring span doubles as the root every later span
                    # parents to
                    root = self.spans.record(
                        "enqueue", tid, t_enq, stage="enqueue",
                        track="batcher", meta={"batch": len(batch)})
                    if tid:
                        entry["root_span"] = root
                    if self.sup is not None:
                        # window journal (ISSUE 6): the window is
                        # journaled the moment it is admitted to the
                        # pipeline — its (message, publisher-future)
                        # batch by reference — and settled when its
                        # counts resolve. A stage death mid-window
                        # replays exactly this manifest through the
                        # next ladder rung.
                        entry["wid"] = self.sup.journal_admit(batch)
                    return entry

                # what is in flight is bounded in deliveries: a window
                # that waits its settle turn holds no pipeline slot, so
                # with the lanes slower than the stages ahead of them
                # the consumer took in whatever was published, which
                # counted in messages is nothing at a fan-out of 2 and
                # minutes of delivery at 1,000. Held here, the queue
                # passes its limit and the read loops stall: the
                # publishers feel the lanes
                pool = getattr(self.node, "deliver_lanes", None)
                while pool is not None \
                        and self._rows_in_flight() > _ROWS_IN_FLIGHT:
                    await pool.progress()   # a plan done, or `_take`
                # a host probe is one batch at host speed, a delivery
                # at a time: bounded in messages and in deliveries
                cap = self._probe_cap()
                # the batch as it stands: what a cut probe left of the
                # last one, or the queue up to `max_batch`
                whole = rest or min(self.max_batch, len(self._queue))
                group = [form_entry(min(whole, cap or whole))]
                rest = whole - len(group[0]["batch"])
                try:
                    await self._fold_hooks(group[0])
                    if self.engine is not None:
                        # churn check rides the batch cadence: a threshold
                        # crossing kicks the background double-buffered
                        # rebuild even when batches are too small for the
                        # device path
                        self.engine.poll_rebuild()
                    if self.sup is not None:
                        # supervision tick rides the same cadence: due
                        # half-open probes launch here even when every
                        # breaker gates the engine paths shut (the
                        # probes ARE the way back up the ladder)
                        self.sup.poll()
                    live0 = group[0]["live"]
                    # the device/host DECISION runs on the first batch
                    # alone, BEFORE any fusion — a host probe (or bypass)
                    # then costs one batch at host speed, never a whole
                    # fused window
                    dispatched = False
                    use_device = (bool(live0) and self.engine is not None
                                  and len(live0) >= self.device_min_batch)
                    if use_device and self.sup is not None \
                            and not self.sup.allow_device():
                        # ladder rung 2 (ISSUE 6): the dispatch or
                        # materialize breaker is open — this window
                        # routes through the host trie; the half-open
                        # probe (off-path) steps the ladder back up
                        self.node.metrics.inc(
                            "routing.device.supervised_bypass")
                        use_device = False
                    if use_device \
                            and not self.engine.batch_class_warm(
                                len(live0)):
                        # the class would cold-compile in the dispatch
                        # path: route host-side and let the background
                        # warm bring the device online (observed: 5s+
                        # first-ack latency under a cold-start flood)
                        self.engine._kick_class_warm()
                        self.node.metrics.inc("routing.device.cold_class")
                        use_device = False
                    use_device = use_device \
                        and self._device_worth_it(len(live0))
                    if use_device:
                        # window fusion: sustained backlog folds further
                        # batches into the SAME device dispatch — capped
                        # at the largest already-compiled window class
                        # (a cold window compile would stall serving)
                        # and the slow-start width
                        # fusion runs only in the warmed (8, Bstd)
                        # class: a FIRST batch beyond the largest
                        # standard class (max_publish_batch > Bstd and a
                        # deep backlog) dispatches as a single window via
                        # its extra class, but ordinary batches still
                        # fuse — so raising max_publish_batch for burst
                        # headroom does not silently disable fusion
                        b_std = self.engine._STD_CLASSES[-1][1]
                        fuse_cap = 1 if len(live0) > b_std else \
                            min(self.window_fuse,
                                self.engine.max_fuse(),
                                self._fuse_cwnd)
                        while (len(group) < fuse_cap
                               and len(self._queue)
                               >= self.device_min_batch
                               and self._rows_in_flight()
                               <= _ROWS_IN_FLIGHT):
                            # later sub-batches must stay inside the
                            # window class too
                            e2 = form_entry(min(self.max_batch, b_std))
                            await self._fold_hooks(e2)
                            group.append(e2)
                    lives = [e["live"] for e in group if e["live"]]
                    if use_device and lives:
                        handle = self.engine.prepare_window(lives)
                        if handle is None:
                            # the device path was CHOSEN but declined
                            # (mid-rebuild, gated swap): these entries
                            # route host-side as the host_fallback
                            # latency series, not plain host — a
                            # rebuild storm shows up as its own tail
                            for e in group:
                                e["fallback"] = True
                        if handle is not None:
                            dispatched = True
                            k = 0
                            first_live = None
                            for e in group:
                                if not e["live"]:
                                    continue
                                e["handle"] = handle
                                e["sub"] = k
                                if first_live is None:
                                    first_live = e
                                k += 1
                            # probe cadence counts SUB-BATCHES, so
                            # fusion does not stretch the host-refresh
                            # interval 8x
                            self._since_host_probe += len(lives)
                            self._since_probe = 0   # device just tried
                            if self.rec is not None:
                                # causal propagation (ISSUE 7): the
                                # fused dispatch records under the LEAD
                                # entry's trace; per-sub traces ride
                                # sub_traces so deliver/lane spans land
                                # on their own window, and fused
                                # followers link to the lead
                                handle.trace = \
                                    first_live.get("trace", 0)
                                handle.sub_traces = [
                                    e.get("trace", 0) for e in group
                                    if e["live"]]
                                for e in group:
                                    if e["live"] and e is not first_live \
                                            and "trace" in e:
                                        self.rec.event(
                                            e["trace"], "fused",
                                            track="batcher",
                                            parent=e.get("root_span", 0),
                                            meta={"lead": handle.trace})
                            first_live["dispatch_fut"] = \
                                loop.run_in_executor(
                                    self._dispatch_pool,
                                    self.engine.dispatch, handle)
                    if not dispatched:
                        self._since_probe += 1
                    if self.tele is not None:
                        if dispatched:
                            # cached = the dedup/match-cache program took
                            # this window (engine attached a plan): the
                            # device/device_cached decision split lets
                            # BENCH rounds attribute throughput moves to
                            # the reuse rate (mesh handles carry no plan
                            # — the mesh bypasses the cache).
                            # device_compact = plain program with the CSR
                            # readback attached; a cached window may ALSO
                            # be compact — routing.device.compact_windows
                            # (incremented at materialize) is the
                            # authoritative compact count, this split
                            # stays the routing-decision view
                            # device_delta = the dispatch fused the
                            # churn overlay (ISSUE 4) — takes precedence
                            # in the split so churn-window throughput is
                            # attributable to the overlay engaging
                            if getattr(handle, "delta", None) is not None:
                                path = "device_delta"
                            elif getattr(handle, "plan", None) \
                                    is not None:
                                path = "device_cached"
                            elif getattr(handle, "pcap", None) \
                                    is not None:
                                path = "device_compact"
                            else:
                                path = "device"
                            self.tele.record_decision(path, len(lives))
                        else:
                            # a fused group can fall back whole (e.g.
                            # prepare_window returned None mid-rebuild):
                            # every entry in it is a host batch
                            self.tele.record_decision("host", len(group))
                            for e in group:
                                self.tele.record_occupancy(
                                    "host",
                                    len(e["batch"]) / self.max_batch)
                except asyncio.CancelledError:
                    for e in group:
                        self._fail_entry(
                            e, RuntimeError("publish batcher stopped"))
                    raise
                except Exception as e:
                    for en in group:
                        en["error"] = e
                if len(group) == 1 and group[0]["handle"] is None \
                        and self._inflight.empty() and not self._consuming:
                    # trickle fast path: nothing in flight ahead of us, so
                    # the host route runs inline — no pipeline hop, p99 at
                    # trickle rates stays where the pre-pipeline drain had
                    # it (SURVEY §7 hard-part 2's dedicated small-batch
                    # path)
                    self._take(group[0])
                    try:
                        await self._complete_host(group[0])
                    except asyncio.CancelledError:
                        # now cancellable mid-completion (chunked yields),
                        # and this entry is in neither the queue nor the
                        # pipeline — fail it or its publishers strand
                        self._fail_entry(
                            group[0],
                            RuntimeError("publish batcher stopped"))
                        raise
                    continue
                for gi, entry in enumerate(group):
                    try:
                        # FIFO hand-off; blocks when pipeline_depth
                        # batches are in flight (backpressure up to
                        # enqueue()/submit())
                        await self._inflight.put(entry)
                    except asyncio.CancelledError:
                        # stop() cancelled us mid-put: these entries are
                        # in neither the queue nor the pipeline — fail
                        # them here or their publishers hang and the
                        # handle leaks
                        for e in group[gi:]:
                            self._fail_entry(
                                e, RuntimeError("publish batcher stopped"))
                        raise
            # queue drained: park the consumer too, then re-check — a
            # publish that landed while we were suspended on this put would
            # otherwise sit unprocessed (_kick sees a live task and won't
            # restart us)
            await self._inflight.put({"eof": True})
            if not self._queue:
                return

    def _fail_entry(self, entry: dict, err: Exception) -> None:
        for _m, fut in entry["batch"]:
            if fut is not None and not fut.done():
                fut.set_exception(err)
        if entry.get("handle") is not None:
            self.engine.abandon(entry["handle"])
            entry["handle"] = None
        if self.sup is not None:
            # failed ≠ lost silently: the futures above carry the error
            # to their publishers, so the journal entry is accounted for
            self.sup.journal_settle(entry.get("wid"))

    async def _fold_hooks(self, entry: dict) -> None:
        """message.publish hook fold, concurrently across the batch."""
        broker = self.node.broker
        batch = entry["batch"]
        with self.spans.span("batch_form", entry.get("trace", 0),
                             track="batcher",
                             parent=entry.get("root_span", 0)) as sp:
            if not broker.hooks.lookup("message.publish"):
                # empty hook chain (the common ingest-bound deployment):
                # a fold would return every message unchanged — skip the
                # per-message coroutine fan-out, but keep one scheduling
                # point (the gather was an await; background warms and
                # readbacks rely on the producer yielding between
                # windows)
                with sp.released():
                    await asyncio.sleep(0)
                folded = [m for m, _f in batch]
            else:
                with sp.released():
                    folded = await asyncio.gather(*[
                        broker.hooks.run_fold_async(
                            "message.publish", (), m)
                        for m, _f in batch])
            live_idx: list[int] = []
            live: list[Message] = []
            for i, m in enumerate(folded):
                if m is None or m.get_header("allow_publish") is False:
                    continue
                broker.metrics.inc("messages.publish")
                live_idx.append(i)
                live.append(m)
            entry["live"] = live
            entry["live_idx"] = live_idx

    # ---- consumer: complete batches strictly in order --------------------
    async def _complete_host(self, entry: dict, routed=None) -> None:
        """Route an entry host-side (or publish a device result) and
        resolve its futures. Raises nothing. Yields every 64 routed
        messages (not after the last) — a 1024-message host fallback
        otherwise stalls the whole event loop for tens of ms. Safe
        against reordering: the trickle caller runs in the producer
        task (nothing can enqueue behind it while it awaits) and the
        consumer is strictly sequential."""
        batch = entry["batch"]
        counts = [0] * len(batch)
        tele = self.tele
        spans = self.spans
        obs = self.obs
        tid = entry.get("trace", 0)
        path = "host" if routed is None else "device"
        # latency path attribution (ISSUE 13): the fine-grained series
        # key. The coarse `path` above keeps its two historical values
        # (trace window meta, record_total meta) — the observatory's
        # five-way split is its own dimension.
        if routed is not None:
            lpath = "device_cached" \
                if getattr(entry.get("handle"), "plan", None) is not None \
                else "device"
        elif entry.get("replayed"):
            lpath = "replay"
        elif entry.get("fallback") or entry.get("handle") is not None:
            lpath = "host_fallback"
        else:
            lpath = "host"
        try:
            if "error" in entry:
                raise entry["error"]
            live, live_idx = entry["live"], entry["live_idx"]
            if routed is None and live:
                # deliver lanes first (ISSUE 5): a host-routed batch
                # delivers inline on the loop, so it must wait out any
                # lane-queued device deliveries — otherwise a host batch
                # could overtake an earlier device batch for the same
                # session and break the per-publisher FIFO this
                # consumer exists to preserve
                pool = getattr(self.node, "deliver_lanes", None)
                if pool is not None and pool.busy():
                    t_d = time.perf_counter()
                    await pool.drain()
                    # a real wait on the lanes: the lane-backpressure
                    # bubble, named
                    spans.record("lane_drain", tid, t_d, track="batcher",
                                 parent=entry.get("root_span", 0))
                routed = []
                broker = self.node.broker
                # a replayed window's host re-route is a CHILD of its
                # replay span — the original trace id is kept (ISSUE 7
                # satellite: causality survives the degradation ladder)
                with spans.span("host_route", tid, track="host",
                                parent=entry.get("replay_span")
                                or entry.get("root_span", 0)) as sp:
                    last = len(live) - 1
                    for j, m in enumerate(live):
                        if tele is not None and j % 32 == 0:
                            # sampled host match split: the host-side
                            # decomposition of the device program's
                            # match stage (1-in-32 keeps the hot loop
                            # cheap)
                            tm = time.perf_counter()
                            mt = broker.router.match(m.topic)
                            tele.observe_stage("host_match",
                                               time.perf_counter() - tm)
                        else:
                            mt = broker.router.match(m.topic)
                        routed.append(broker._route(m, mt))
                        if j % 64 == 63 and j < last:
                            with sp.released():
                                await asyncio.sleep(0)
                # the host's own time: what `emqx:host_route` covered,
                # not the turns other coroutines took inside its yields
                # (each ~30 ms of ready callbacks under load)
                self._host_msg_s, self._host_spike = _ewma(
                    self._host_msg_s, (sp.dur - sp.away) / len(live),
                    self._host_spike)
                if self._probe_out:
                    self._probe_out, self._probe_landed = False, True
                # a host completion breaks the device completion chain:
                # the next device sample must be a full round-trip, not
                # completion-to-completion across this host batch
                self._last_dev_done = None
            if obs is not None and live:
                # ingress→routed (ISSUE 13): the route result for every
                # live message is in hand — device windows arrive here
                # with `routed` precomputed (finish_sub just returned),
                # host/fallback/replay rungs just finished the trie
                # walk. Only socket-ingress messages carry a stamp.
                obs.record_window("routed", live, lpath,
                                  time.perf_counter_ns(),
                                  trace=entry.get("trace", 0))
            def _settle() -> None:
                with spans.span("settle", tid, track="batcher",
                                parent=entry.get("root_span", 0)):
                    _settle_inner()

            def _settle_inner() -> None:
                if live:
                    for j, i in enumerate(live_idx):
                        counts[i] = routed[j]
                for i, (_m, fut) in enumerate(batch):
                    if fut is not None and not fut.done():
                        fut.set_result(counts[i])
                if self.sup is not None:
                    self.sup.journal_settle(entry.get("wid"))
                if obs is not None and live:
                    # ingress→delivered (ISSUE 13): _settle runs when
                    # the deliveries are written — inline for host
                    # batches, via the DeliveryPlan done-callback when
                    # the PR 5 lanes own the walk
                    obs.record_window("delivered", live, lpath,
                                      time.perf_counter_ns())
                # PUBLISH→route latency sample: oldest enqueue →
                # completion (covers both host- and device-routed
                # entries — the device path funnels through here with
                # `routed` precomputed)
                t_enq = entry.get("t_enq")
                if t_enq is not None:
                    total = time.perf_counter() - t_enq
                    self.route_lat.append(total)
                    if tele is not None:
                        tele.record_total(total, batch=len(batch),
                                          path=path)
                if tid:
                    now = time.perf_counter()
                    w0 = entry.get("t_enq") or now
                    # the window roll-up span (admit → settle) + the
                    # sampled per-message enqueue→settle spans
                    spans.record("window", tid, w0, now, track="window",
                                 meta={"path": path,
                                       "batch": len(batch)})
                    for i, tq in entry.get("trace_msgs", ()):
                        m = batch[i][0]
                        spans.record("message", tid, tq, now,
                                     track="messages",
                                     parent=entry.get("root_span", 0),
                                     meta={"topic": m.topic,
                                           "qos": m.qos})

            # deliver-lane hand-off (ISSUE 5): a LaneCounts carries the
            # in-flight DeliveryPlan — publisher futures resolve when
            # the lanes finish delivering (counts are placeholders
            # until then), while THIS consumer moves on to the next
            # window. That is the overlap the egress stage buys; the
            # completion chain itself stays FIFO via the lane queues.
            plan = getattr(routed, "plan", None)
            if plan is not None and not plan.done:
                plan.add_done_callback(_settle)
            else:
                _settle()
        except Exception as e:  # route failure must not hang publishers
            for _m, fut in batch:
                if fut is not None and not fut.done():
                    fut.set_exception(e)
            if self.sup is not None:
                self.sup.journal_settle(entry.get("wid"))

    async def _consume(self) -> None:
        if self.dispatch_depth > 1:
            # ISSUE 9 tentpole: the bounded in-flight settle ring —
            # stages run ahead per window, settle stays FIFO
            await self._consume_pipelined()
            return
        loop = asyncio.get_running_loop()
        while True:
            entry = await self._inflight.get()
            if entry.get("eof"):
                if self._park_ok():
                    return
                continue
            self._take(entry)
            self._consuming = True
            try:
                routed = None
                if entry.get("handle") is not None and "error" not in entry:
                    routed = await self._complete_device(entry, loop)
                await self._complete_host(entry, routed)
            except asyncio.CancelledError:
                self._fail_entry(entry,
                                 RuntimeError("publish batcher stopped"))
                raise
            except Exception as e:
                # a failing deliver callback / hook must neither hang the
                # batch's publishers nor kill the consumer task
                self._fail_entry(entry, e)
            finally:
                self._consuming = False

    def _park_ok(self) -> bool:
        """True when the consumer may park (queue drained, producer
        done) — the legacy loop's eof exit condition, shared by the
        pipelined ring."""
        return self._inflight.empty() and not self._queue \
            and (self._task is None or self._task.done())

    async def _run_stages(self, entry: dict, loop) -> bool:
        """The in-flight stage task of ONE dispatched window (ISSUE 9):
        await its dispatch, then launch + await its materialize — ahead
        of the window's FIFO settle turn, concurrently with up to
        dispatch_depth-1 other windows' stage tasks. Returns False
        (handle abandoned, fault noted, replay counted) when the window
        must fall back to the host rung at settle; the error handling is
        the depth-1 consumer's, verbatim, so the supervision contract —
        per-window watchdog deadlines, breaker advancement, journal
        replay — is identical per in-flight window."""
        handle = entry["handle"]
        handle.t0 = time.perf_counter()
        try:
            if self.sup is None:
                try:
                    await entry["dispatch_fut"]
                    await loop.run_in_executor(
                        self._read_pool, self.engine.materialize, handle)
                except Exception as e:
                    self.engine.abandon(handle)
                    self.node.metrics.inc(
                        "routing.device.dispatch_failed")
                    self._note_replay_span(entry, "device",
                                           type(e).__name__)
                    return False
                return True
            if not await self._await_stage(entry["dispatch_fut"],
                                           "dispatch", handle, entry):
                return False
            mat = loop.run_in_executor(
                self._read_pool, self.engine.materialize, handle)
            return await self._await_stage(mat, "materialize", handle,
                                           entry)
        except asyncio.CancelledError:
            raise
        except Exception as e:  # stage machinery itself failed
            self.engine.abandon(handle)
            self.node.metrics.inc("routing.device.dispatch_failed")
            self._note_replay_span(entry, "device", type(e).__name__)
            return False

    async def _consume_pipelined(self) -> None:
        """Depth-N in-flight settle ring (ISSUE 9 tentpole).

        Admission: entries pop from the FIFO queue into the ring; a
        DISPATCHING entry (it owns a window's dispatch_fut) starts its
        stage task immediately, and admission pauses once
        ``dispatch_depth`` such windows are in flight (host batches and
        fused-window followers admit freely — they pin no extra device
        buffers). Settle: strictly the ring head, so completion order —
        and therefore per-publisher delivery order, lane drains, and
        journal settles — is bit-identical to the synchronous loop; only
        WHEN dispatch/materialize run moves. A stage task that failed
        (timeout / fault / injected chaos) already abandoned its handle
        and noted the fault; its window (and independently any other
        in-flight window the same death took down) replays through the
        host rung at its own settle turn — zero QoS>=1 loss, FIFO
        preserved."""
        from emqx_tpu.broker.supervise import guard_task
        loop = asyncio.get_running_loop()
        ring: deque = deque()
        eof_seen = False
        try:
            while True:
                while not eof_seen:
                    if ring:
                        # count LIVE stage tasks only: a window whose
                        # stages finished but which still waits its
                        # FIFO settle turn no longer occupies a
                        # pipeline slot — counting it would serialize
                        # admission behind the settle loop and collapse
                        # the effective depth to ~1 under load
                        in_flight = sum(
                            1 for e in ring
                            if e.get("stage_task") is not None
                            and not e["stage_task"].done())
                        if in_flight >= self.dispatch_depth:
                            break
                        try:
                            entry = self._inflight.get_nowait()
                        except asyncio.QueueEmpty:
                            break
                    else:
                        entry = await self._inflight.get()
                    if entry.get("eof"):
                        if ring:
                            # drain the ring first, then re-check the
                            # park condition (the producer already
                            # exited after this eof)
                            eof_seen = True
                            break
                        if self._park_ok():
                            return
                        continue
                    if entry.get("handle") is not None \
                            and entry.get("dispatch_fut") is not None \
                            and "error" not in entry:
                        entry["stage_task"] = guard_task(
                            loop.create_task(
                                self._run_stages(entry, loop)),
                            "batcher-window-stages", self.node.metrics)
                    ring.append(entry)
                    # the trickle fast path must not overtake ring
                    # entries: anything in the ring means "mid-consume"
                    self._consuming = True
                if not ring:
                    continue
                entry = ring.popleft()
                self._take(entry)
                # pipelined-cost sampling hint: more windows behind us
                # means the completion-to-completion sample is the
                # amortized rate (same rule as the depth-1 queue check)
                entry["_pipeline_busy"] = bool(ring)
                try:
                    routed = None
                    if entry.get("handle") is not None \
                            and "error" not in entry:
                        routed = await self._complete_device(entry, loop)
                    await self._complete_host(entry, routed)
                except asyncio.CancelledError:
                    self._fail_entry(
                        entry, RuntimeError("publish batcher stopped"))
                    raise
                except Exception as e:
                    self._fail_entry(entry, e)
                finally:
                    self._consuming = bool(ring)
                if eof_seen and not ring:
                    eof_seen = False
                    if self._park_ok():
                        return
        except asyncio.CancelledError:
            err = RuntimeError("publish batcher stopped")
            for e in ring:
                st = e.get("stage_task")
                if st is not None and not st.done():
                    st.cancel()
                self._fail_entry(e, err)
            self._consuming = False
            raise

    def _take(self, entry: dict) -> None:
        """The consumer takes `entry` up for settle: its messages no
        longer wait in `_inflight` or the ring, and a producer held by
        `_ROWS_IN_FLIGHT` looks again."""
        self._taken += len(entry["batch"])
        pool = getattr(self.node, "deliver_lanes", None)
        if pool is not None:
            pool.nudge()

    def _rows_in_flight(self) -> float:
        """Deliveries between the batch queue and the sockets: the
        messages formed into windows and not yet taken up for settle,
        by their recent fan-out, plus the rows of the lanes' plans."""
        pool = getattr(self.node, "deliver_lanes", None)
        return (self._formed - self._taken) * self._rows_per_msg \
            + (pool.live_rows if pool is not None else 0)

    def _probe_gap(self) -> int:
        """Device sub-batches between two scheduled host probes:
        `host_probe_every`, doubled once for every probe in a row whose
        sample left the chip ahead by half (`_device_worth_it`), up to
        `_PROBE_GAP_MAX`."""
        every = self.host_probe_every
        return max(every, min(every << self._probe_doublings,
                              _PROBE_GAP_MAX))

    def _probe_due(self) -> bool:
        """The next decision sends its batch to the host to seed or
        refresh the host's cost: a device cost is known and the host's
        is not, or `_probe_gap` sub-batches went to the chip since the
        last probe."""
        return self._dev_batch_s is not None and (
            self._host_msg_s is None
            or self._since_host_probe >= self._probe_gap())

    def _probe_cap(self) -> Optional[int]:
        """How many messages the next batch may hold where it is due to
        be the chooser's host probe (`_probe_due`): `_PROBE_MSGS`, or
        what stands for `_PROBE_ROWS` deliveries where that is fewer
        (40 at a fan-out of 200), never under `device_min_batch` (a
        smaller batch would not reach the chooser). What the probe
        leaves in the queue forms the next device window at once.
        None: no probe is due, a full batch."""
        if not self._probe_due():
            return None
        cap = max(self.device_min_batch,
                  min(_PROBE_MSGS, int(_PROBE_ROWS / self._rows_per_msg)))
        return cap if cap < self.max_batch else None

    def _pending_limit(self) -> int:
        """`max_pending`, cut where the queue alone would stand for
        more than `_ROWS_IN_FLIGHT` deliveries (never below one full
        batch)."""
        rpm = self._rows_per_msg
        if rpm * self.max_pending <= _ROWS_IN_FLIGHT:
            return self.max_pending
        return max(self.max_batch, int(_ROWS_IN_FLIGHT / rpm))

    async def _complete_device(self, entry: dict, loop) -> Optional[list]:
        """Await dispatch + readback off-loop, consume on-loop. Returns the
        per-live-message counts, or None to fall back to the host path.
        Window entries after the first reuse the already-materialized
        handle (FIFO adjacency guarantees the dispatching entry ran).

        Supervision (ISSUE 6): each stage await is bounded by the
        supervisor's watchdog deadline (p99-derived) — a hang trips the
        stage's breaker and replays the window host-side instead of
        wedging this consumer; stage exceptions are attributed to their
        fault domain; a consume failure (e.g. a corrupt readback)
        likewise replays instead of failing the window's publishers.
        Without a supervisor the pre-ISSUE-6 behavior is bit-exact:
        unbounded awaits, one catch-all host fallback for dispatch/
        materialize, consume errors fail the entry."""
        handle = entry["handle"]
        sub = entry.get("sub", 0)
        n_subs = len(handle.subs)
        sup = self.sup
        st = entry.get("stage_task")
        if st is not None:
            # pipelined mode (ISSUE 9): the window's dispatch/
            # materialize ran (watchdog-bounded) in its own in-flight
            # stage task — settle just collects the verdict
            try:
                ok = await st
            except asyncio.CancelledError:
                raise
            except Exception:  # guard_task already logged it
                ok = False
            if not ok:
                return None
        elif entry["dispatch_fut"] is not None:
            handle.t0 = time.perf_counter()
            if sup is None:
                try:
                    await entry["dispatch_fut"]
                    await loop.run_in_executor(
                        self._read_pool, self.engine.materialize, handle)
                except Exception as e:
                    self.engine.abandon(handle)
                    self.node.metrics.inc(
                        "routing.device.dispatch_failed")
                    self._note_replay_span(entry, "device",
                                           type(e).__name__)
                    return None
            else:
                if not await self._await_stage(
                        entry["dispatch_fut"], "dispatch", handle,
                        entry):
                    return None
                mat = loop.run_in_executor(
                    self._read_pool, self.engine.materialize, handle)
                if not await self._await_stage(mat, "materialize",
                                               handle, entry):
                    return None
        if handle.built is None or handle.np_res is None:
            # the window's dispatching entry failed/abandoned earlier
            return None
        if sup is None:
            counts = self.engine.finish_sub(handle, sub)
        else:
            try:
                counts = self.engine.finish_sub(handle, sub)
            except Exception as e:
                # consume died mid-window (corrupt readback / decode
                # bug): abandon the pinned snapshot and replay the
                # journaled window through the next rung — the host
                # path below re-routes every message, so QoS≥1 loses
                # nothing and per-session order holds (the host
                # completion drains the lanes first)
                self.engine.abandon(handle)
                sup.note_fault("materialize", e)
                sup.note_replay()
                self.node.metrics.inc("routing.device.dispatch_failed")
                self._note_replay_span(entry, "consume",
                                       type(e).__name__)
                return None
        plan = getattr(counts, "plan", None)
        if plan is not None and plan.msgs:
            # up at once, down by halves: it bounds what is in flight
            rpm = plan.n_rows / len(plan.msgs)
            self._rows_per_msg = max(
                rpm, (rpm + self._rows_per_msg) / 2)
        pool = getattr(self.node, "deliver_lanes", None)
        if pool is not None and pool.active():
            # backpressure: too many plans queued in the delivery lanes
            # stalls THIS consumer, which fills _inflight, which blocks
            # the producer's put, which bounces submit()/enqueue() —
            # a blocked lane therefore stalls publishers instead of
            # buffering (or dropping) deliveries unboundedly
            t_a = time.perf_counter()
            await pool.admit()
            if time.perf_counter() - t_a > 5e-4:
                # only a REAL wait is a lane-backpressure bubble worth
                # a span; the no-wait fast path stays unrecorded
                self.spans.record("lane_admit", entry.get("trace", 0),
                                  t_a, track="batcher",
                                  parent=entry.get("root_span", 0))
        done = time.perf_counter()
        if sub == n_subs - 1:
            if sup is not None:
                # one healthy window resets the stage breakers'
                # consecutive-fault counters
                sup.note_ok("dispatch")
                sup.note_ok("materialize")
            self._observe_device_cost(
                handle.t0, done, n_subs,
                not self._inflight.empty()
                or bool(entry.get("_pipeline_busy")))
            # slow-start growth: this window completed, widen the next
            self._fuse_cwnd = min(8, max(2, 2 * n_subs))
        return counts

    async def _await_stage(self, fut, stage: str, handle,
                           entry: Optional[dict] = None) -> bool:
        """Await one off-loop stage under the supervisor's watchdog
        deadline. Returns False (handle abandoned, fault noted, replay
        counted — caller falls back to the host rung) on timeout or
        stage exception; True on success. The deadline derives from the
        stage histogram's p99, so a legitimately slow stage earns a
        proportionally longer leash (supervise.deadline)."""
        sup = self.sup
        try:
            await asyncio.wait_for(fut, sup.deadline(stage))
        except asyncio.CancelledError:
            raise
        except asyncio.TimeoutError:
            # the executor thread may still be wedged inside the stage —
            # the breaker keeps further windows off the device while it
            # is; this consumer moves on instead of wedging with it
            self.engine.abandon(handle)
            self.node.metrics.inc("routing.device.dispatch_failed")
            sup.note_stall(stage)
            sup.note_replay()
            self._note_replay_span(entry, stage, "stall")
            return False
        except Exception as e:
            self.engine.abandon(handle)
            self.node.metrics.inc("routing.device.dispatch_failed")
            sup.note_fault(stage, e)
            sup.note_replay()
            self._note_replay_span(entry, stage, type(e).__name__)
            return False
        return True

    def _note_replay_span(self, entry: Optional[dict], stage: str,
                          kind: str) -> None:
        """ISSUE 7 satellite: a window re-routed through the host rung
        KEEPS its original trace id; the replay itself is linked as a
        child span of the window root, and the host_route that follows
        parents to the replay — the causal chain survives the
        supervise replay."""
        if entry is not None:
            # latency path attribution (ISSUE 13): a supervised journal
            # replay lands in the `replay` series, an unsupervised
            # device failure in `host_fallback` — independent of the
            # flight-recorder knob below
            entry["replayed" if self.sup is not None
                  else "fallback"] = True
        rec = self.rec
        if rec is None or entry is None or "trace" not in entry:
            return
        entry["replay_span"] = rec.event(
            entry["trace"], "replay", track="batcher",
            parent=entry.get("root_span", 0),
            meta={"stage": stage, "kind": kind})

    def lat_percentiles(self) -> Optional[dict]:
        """PUBLISH→route latency percentiles (ms) over the reservoir."""
        if not self.route_lat:
            return None
        s = sorted(self.route_lat)
        return {
            "p50_ms": round(s[len(s) // 2] * 1000, 3),
            "p99_ms": round(
                s[min(len(s) - 1, int(len(s) * 0.99))] * 1000, 3),
            "samples": len(s),
        }

    def _observe_device_cost(self, t0: Optional[float], done: float,
                             n_subs: int, busy: bool) -> None:
        """ONE cost sample per completed WINDOW, divided by its width —
        sampling per entry would count the near-instant later subs of a
        window as full batches and drag the EWMA to ~zero (the chooser
        then never bypasses a slow device). Pipelined cost =
        completion-to-completion when the pipeline was `busy`; full
        latency (from `t0`, the window's stage start) otherwise — and
        never across an idle gap: a window that started after the last
        completion did not wait behind it (the first window of a burst
        would otherwise sample the whole pause before it). No sample
        where the telemetry counted a compile since the last one."""
        start = t0 or done
        if busy and self._last_dev_done is not None:
            start = max(start, self._last_dev_done)
        sample = (done - start) / n_subs
        self._last_dev_done = done
        tele = self.tele
        if tele is not None and tele.compiles != self._compiles_seen:
            # a window the process compiled under is no cost sample
            # (a first call of a class: hundreds of ms that say
            # nothing of the next window). Two in a row used to read
            # as a sustained slowdown and write the device off for
            # `_PROBE_EVERY` host batches; the host's sample hid that
            # while a cold first yield was billed to it (PERF.md, PR 43)
            self._compiles_seen = tele.compiles
            return
        if self._dev_reprobe:
            # the scheduled re-try of a written-off device measures an
            # estimate no sample has touched for _PROBE_EVERY host
            # batches: adopt it, do not blend it in at alpha (a
            # pessimized estimate would otherwise take several probe
            # periods of host routing to decay)
            self._dev_reprobe = False
            self._dev_batch_s, self._dev_spike = sample, 0
        else:
            self._dev_batch_s, self._dev_spike = _ewma(
                self._dev_batch_s, sample, self._dev_spike)

    def _device_worth_it(self, n: int) -> bool:
        """Measured-cost routing choice with active probes BOTH ways: the
        device is re-tried every _PROBE_EVERY host batches, and the host is
        re-sampled every `_probe_gap` device sub-batches (otherwise the host
        estimate starves under steady device load and the bypass can never
        engage — round-2 weak #2). The decision runs on the FIRST batch of
        a prospective window (n = its live count) before any fusion;
        _dev_batch_s is the amortized per-sub-batch completion cost, so the
        single-sub-batch comparison is the per-sub-batch comparison."""
        count = self.node.metrics.inc
        if self._dev_batch_s is None:
            count("routing.chooser.first")
            return True      # optimistic: measure the device first
        if self._probe_due():
            # active host probe: route this one host-side to seed/refresh
            # the estimate (costs one batch of `_probe_cap` messages at
            # host speed). Without it the host cost is never measured
            # under steady device load and the bypass can never engage
            # (round-2 weak #2). Counters reset at DECISION time —
            # resetting at consume time would turn one scheduled probe
            # into a pipeline_depth-long probe burst.
            self._since_host_probe = 0
            # the seeding probe's lone sample makes no probe rarer
            self._probe_out = self._host_msg_s is not None
            count("routing.chooser.host_probe")
            # messages the probes routed; outside `routing.chooser.*`,
            # which holds verdicts alone (they are summed as such)
            count("routing.host_probe.msgs", n)
            return False
        if self._since_probe >= _PROBE_EVERY:
            self._since_probe = 0
            self._dev_reprobe = True
            count("routing.chooser.device_probe")
            return True
        host_s = n * self._host_msg_s
        if host_s > 0:
            # < 1: the chip wins this window by the two measured costs
            self.chooser_margin = self._dev_batch_s / host_s
        # host probes come rarer while the chip wins by half: one
        # doubling a probe whose sample has landed, and back to
        # `host_probe_every` at the first comparison that reads closer
        if self._dev_batch_s >= host_s * _PROBE_RARER_MARGIN:
            self._probe_doublings = 0
        elif self._probe_landed \
                and self._probe_gap() < _PROBE_GAP_MAX:
            self._probe_doublings += 1
        self._probe_landed = False
        # a dead band: back on the chip at parity, off it past the margin
        if self._dev_batch_s <= host_s * (
                1.0 if self._on_host else _LEAVE_MARGIN):
            self._on_host = False
            count("routing.chooser.cost_device")
            return True
        self._on_host = True
        count("routing.chooser.cost_host")
        count("routing.device.bypassed")
        self._fuse_cwnd = 1      # re-enter fusion carefully next time
        return False

    def chooser_state(self) -> dict:
        """The `chooser` section of `PipelineTelemetry.snapshot()`: the
        two cost EWMAs, the margin of the last cost comparison and the
        device sub-batches between two host probes as it stands."""
        out = {}
        if self._dev_batch_s is not None:
            out["dev_batch_ms"] = round(self._dev_batch_s * 1e3, 4)
        if self._host_msg_s is not None:
            out["host_msg_us"] = round(self._host_msg_s * 1e6, 4)
        if self.chooser_margin is not None:
            out["margin"] = round(self.chooser_margin, 4)
        out["probe_gap"] = self._probe_gap()
        return out


def _ewma(cur: Optional[float], sample: float, streak: int = 0,
          alpha: float = 0.2) -> tuple[Optional[float], int]:
    """Cost estimate: pessimize fast — but not on ONE bad sample. A first
    sample >3x the estimate is DISCARDED (estimate unchanged) and arms the
    outlier streak; a second consecutive >3x sample — still measured
    against the same un-drifted baseline — is a sustained slowdown and is
    adopted outright. A lone spike (a GC pause, one stall) can no
    longer rewrite a path's cost and misroute traffic for up to
    _PROBE_EVERY batches; a real 3x+ slowdown is adopted on its second
    window. A wrongly-pessimized estimate still self-corrects: the active
    probes re-measure both paths on a bounded cadence.
    Returns (estimate, outlier_streak)."""
    if cur is None:
        return sample, 0
    if sample > 3 * cur:
        if streak >= 1:
            return sample, streak + 1
        return cur, 1
    return (1 - alpha) * cur + alpha * sample, 0
