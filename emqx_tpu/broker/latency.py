"""End-to-end message latency SLO observatory (ISSUE 13).

The missing observability leg after time-per-stage (PR 1), window
causality (PR 7) and space/cost (PR 8): the latency a *message* actually
experiences from socket read to delivery write, decomposed by path —
the end-to-end percentile framing the IoT broker benchmarking study
(arXiv:2603.21600, PAPERS.md) compares brokers on, and the number the
north star's **p99 < 2ms PUBLISH→route** criterion is judged against.
bench.py's sync p99 is window-granularity; this module measures per
message and starts the clock at frame decode.

Mechanics:

- **Ingress stamp**: ``mqtt.frame.FrameParser`` stamps
  ``perf_counter_ns`` at frame decode — one clock read per read burst
  (the PR 11 columnar path stores it on the ``PublishBurst``, the
  per-packet fallback on each ``Publish`` packet, so the A/B ingress
  twins stay comparable) — and the channel carries it onto
  ``Message.ingress_ns``.
- **Two legs**: ``ingress→routed`` (frame decode → route result in
  hand; the SLO objective's leg) and ``ingress→delivered`` (frame
  decode → every delivery written, i.e. the PR 5 delivery plan
  settled). Both recorded for every message at batch settle — a
  window at once, by its (burst stamp, QoS) groups: ``record_window``
  — keyed by ``(qos, path)`` where path ∈ {device, device_cached, host,
  host_fallback, replay} — a breaker-driven journal replay and a
  prepare-time device fallback each land in their OWN series, so a
  latency regression names its rung.
- **Fine histograms**: the sub-millisecond log2 ladder
  (``metrics.Histogram(substeps=4)``) — quarter-octave buckets from
  1µs, so a 2ms objective resolves to ~19% instead of the plain
  ladder's factor-of-2.
- **SLO engine**: configurable objective (``broker.slo_route_p99_ms``
  / ``EMQX_TPU_SLO_ROUTE_P99_MS``, default 2.0 — the ROADMAP
  criterion), rolling multi-window error-budget burn rates (1m/5m/30m;
  burn 1.0 = spending the 1% p99 budget exactly at the sustainable
  rate), and **breach exemplars**: a message exceeding the objective
  records a bounded exemplar carrying its window's PR 7 flight-
  recorder trace id, its window lands ONE ``slo_breach`` instant event
  on that trace (``count`` = the breaching messages it stands for),
  and fires a throttled ``latency.breach`` hook so the tracer
  logs the causal chain (queue wait vs dispatch vs materialize vs lane
  backpressure) for the exact slow message, not an aggregate.

Knobs: ``broker.latency_observatory`` / ``EMQX_TPU_LATENCY`` (config
beats env beats default-on; ``=0`` restores the pre-ISSUE-13 behavior
exactly — no observatory object, no ``latency`` snapshot section, REST
404) and ``broker.slo_route_p99_ms`` / ``EMQX_TPU_SLO_ROUTE_P99_MS``.

Exported four ways like every other section: ``latency`` in
`PipelineTelemetry.snapshot()` ($SYS ``pipeline/latency``), the
``pipeline.latency.*`` histogram families (Prometheus buckets, StatsD
timers ride the shared registry) and ``GET /api/v5/pipeline/latency``.
``tools/latency_report.py`` renders the same schema offline from a
bench JSON or checkpoint.
"""

from __future__ import annotations

import os
import time
from collections import deque
from itertools import compress, groupby, islice
from functools import partial
from operator import attrgetter, eq
from typing import Optional

SCHEMA = "emqx_tpu.latency/v1"

# the per-message path attribution (batcher settle decides):
#   device         routed by a fused device window (plain/compact/delta)
#   device_cached  device window with the dedup/match-cache plan attached
#   host           host-routed by decision (probe, bypass, min-batch,
#                  trickle, or a node with no batcher at all)
#   host_fallback  a prepared device window that fell back to the host
#                  path WITHOUT a supervision replay (prepare_window
#                  declined mid-rebuild, fused follower of a dead lead,
#                  unsupervised dispatch failure)
#   replay         a journaled window re-routed through the host rung by
#                  the ISSUE 6 supervisor (breaker trip, watchdog stall,
#                  injected fault)
PATHS = ("device", "device_cached", "host", "host_fallback", "replay")
LEGS = ("routed", "delivered")

# latency histograms: 1µs floor, quarter-octave (substeps=4) ladder,
# 112 buckets -> ~1µs..220s. The plain 28-bucket octave ladder cannot
# resolve a 2ms objective (neighbouring bounds 1.024/2.048ms).
_LAT_LO, _LAT_BUCKETS, _LAT_SUBSTEPS = 1e-6, 112, 4

# SLO burn accounting: breach/total counts in 10s slots, ring bounded
# to the widest burn window (30m)
_SLOT_S = 10.0
_BURN_WINDOWS = (("1m", 6), ("5m", 30), ("30m", 180))
# the error budget at a p99 objective: 1% of messages may exceed it
_P99_BUDGET = 0.01

_EXEMPLAR_CAP = 16
_HOOK_MIN_INTERVAL_S = 1.0

# what a window's record reads of a message, and nothing else
_STAMP = attrgetter("ingress_ns")
_QOS = attrgetter("qos")
_ONE = (0,)


def resolve_latency_observatory(configured=None) -> bool:
    """The one latency-observatory resolution (ISSUE 13): config
    (``broker.latency_observatory``) beats ``EMQX_TPU_LATENCY`` beats
    default-on. ``=0`` restores the pre-ISSUE-13 observable behavior —
    no observatory object anywhere, no ``latency`` snapshot section,
    REST ``/pipeline/latency`` 404, bit-identical delivery counts and
    per-publisher order (the A/B twin test pins all four). The frame-
    decode ingress stamp itself is NOT gated: messages always carry
    ``ingress_ns`` (one clock read per read burst + one attribute per
    PUBLISH — negligible against the parse cost) so the stamp path
    cannot drift untested between twins; the knob gates everything
    that READS the stamp."""
    if configured is not None:
        return bool(configured)
    return os.environ.get("EMQX_TPU_LATENCY", "1") \
        not in ("0", "false", "off")


def resolve_slo_route_p99_ms(configured=None) -> float:
    """The SLO objective: config (``broker.slo_route_p99_ms``) beats
    ``EMQX_TPU_SLO_ROUTE_P99_MS`` beats the built-in 2.0 (the ROADMAP
    **p99 < 2ms PUBLISH→route** criterion). Must be a positive number;
    anything else is a deployment error worth failing loudly on."""
    if configured is None:
        env = os.environ.get("EMQX_TPU_SLO_ROUTE_P99_MS")
        if env is None:
            return 2.0
        configured = env
    try:
        val = float(configured)
    except (TypeError, ValueError):
        raise ValueError(
            f"EMQX_TPU_SLO_ROUTE_P99_MS={configured!r} is not a number")
    if val <= 0:
        raise ValueError(
            f"EMQX_TPU_SLO_ROUTE_P99_MS must be > 0, got {val}")
    return val


class LatencyObservatory:
    """Per-node end-to-end latency recorder + SLO engine.

    Hot-path contract: ``record_window`` (batcher settle: a window at
    once, by its (burst stamp, QoS) groups) and its one-message forms
    ``record_routed`` / ``record_delivered`` (host publish path) run on
    the event loop only — one histogram observe a group plus, on the
    routed leg, one slot-counter bump a call; no locks. A breaching
    window costs at most 16 exemplars, one ring event and one hook
    fire, whatever it holds. Everything else (burn rates, the section
    document) is read-side."""

    def __init__(self, metrics, *, hooks=None, recorder=None,
                 objective_ms: Optional[float] = None):
        self.metrics = metrics
        self.hooks = hooks
        # the PR 7 flight recorder: breach exemplars land a
        # `slo_breach` instant event on the slow message's window trace
        # so the causal chain is one trace-id lookup away. None (trace
        # knob off) degrades to exemplars without trace linkage.
        self.recorder = recorder
        self.objective_ms = resolve_slo_route_p99_ms(objective_ms)
        self._objective_s = self.objective_ms / 1000.0
        self._hist: dict = {}      # (leg, qos, path) -> Histogram
        self._slots: deque = deque(maxlen=_BURN_WINDOWS[-1][1])
        self.samples = 0           # routed-leg observations
        self.breaches = 0
        # how the routed leg was taken: windows recorded by the window
        # form and (stamp, QoS) groups observed by either form, so
        # groups / samples is the sharing (~0.01 in a flood, 1.0 on a
        # trickle or a batcher-less node)
        self.windows = 0
        self.groups = 0
        self.exemplars: deque = deque(maxlen=_EXEMPLAR_CAP)
        self.hook_fires = 0
        self.hook_throttled = 0
        self._last_hook = 0.0
        # overload sampling clamp (ISSUE 14): >1 records 1-in-clamp
        # messages. Set/restored ONLY by the overload governor's
        # clamp_sampling shed action; burn rates stay unbiased under
        # the clamp because they are breach FRACTIONS (uniform
        # sampling preserves a ratio).
        self.clamp = 1
        self._clamp_tick = 0
        self._clamp_tick_d = 0
        self.clamped = 0

    # ---- recording (event loop) -----------------------------------------
    def _h(self, leg: str, qos: int, path: str):
        key = (leg, qos, path)
        h = self._hist.get(key)
        if h is None:
            # written as two explicit literals (not one f-string over
            # `leg`) so the doc-drift gate can resolve the documented
            # family templates against the source
            name = f"pipeline.latency.routed.q{qos}.{path}" \
                if leg == "routed" else \
                f"pipeline.latency.delivered.q{qos}.{path}"
            h = self.metrics.hist(name, lo=_LAT_LO,
                                  n_buckets=_LAT_BUCKETS,
                                  substeps=_LAT_SUBSTEPS)
            self._hist[key] = h
        return h

    def record_window(self, leg: str, msgs, path: str, t_ns: int,
                      trace: int = 0) -> None:
        """A window's messages (a sequence) at once, on one leg
        (``"routed"`` or ``"delivered"``), all at the one clock read
        ``t_ns``. A window of 840 messages from 7 read bursts holds ~7
        distinct latencies (``ingress_ns`` is one stamp a burst), so
        the record is taken once a (stamp, QoS) group: C-level passes
        read the two attributes and count the stamps, and everything
        after costs a group, not a message. Messages without a stamp
        (internal publishes) are left out. The numbers are those of
        one ``record_routed`` / ``record_delivered`` call a stamped
        message, in order."""
        if self.clamp > 1:
            # the per-message tick's own sample: it counts stamped
            # messages only, and its phase carries across windows
            msgs = self._sampled(
                leg, list(compress(msgs, map(_STAMP, msgs))))
        qoses = set(map(_QOS, msgs))
        groups = []
        for qos in qoses:
            part = msgs if len(qoses) == 1 else compress(
                msgs, map(partial(eq, qos), map(_QOS, msgs)))
            # a burst's rows are one run of the window (or a few, where
            # QoS or a yield cut it): counted a run, merged a stamp
            tally: dict = {}
            for ing, run in groupby(map(_STAMP, part)):
                if ing:
                    tally[ing] = tally.get(ing, 0) + len(list(run))
            groups += [((t_ns - ing) / 1e9, qos, n, ing)
                       for ing, n in tally.items()]
        if not groups:
            return
        if leg == "routed":
            self.windows += 1
            self.metrics.inc("pipeline.latency.windows")
        over = self._observe(leg, path, groups)
        if over:
            # the last few messages that carry a breaching stamp, in
            # message order: a lazy scan from the window's end that
            # stops at the cap (16 steps where everything breaches)
            last = list(islice(compress(reversed(msgs), map(
                set(over[3]).__contains__,
                map(_STAMP, reversed(msgs)))), _EXEMPLAR_CAP))
            self._breach(path, trace, over,
                         [(m, (t_ns - m.ingress_ns) / 1e9)
                          for m in reversed(last)])

    def record_routed(self, msg, path: str, seconds: float,
                      trace: int = 0) -> None:
        """One message's ingress→routed latency (the SLO leg): the
        one-message form of ``record_window`` for a caller that holds
        the latency itself (the batcher-less host path)."""
        if self.clamp > 1 and not self._sampled("routed", _ONE):
            return
        over = self._observe("routed", path,
                             ((seconds, msg.qos, 1, None),))
        if over:
            self._breach(path, trace, over, ((msg, seconds),))

    def record_delivered(self, msg, path: str, seconds: float) -> None:
        """One message's ingress→delivered latency (route + the PR 5
        delivery-lane walk / inline delivery, settled)."""
        if self.clamp > 1 and not self._sampled("delivered", _ONE):
            return
        self._h("delivered", min(msg.qos, 2), path).observe(seconds)

    def _sampled(self, leg: str, seq):
        """The members of ``seq`` (stamped messages, in order) that the
        1-in-``clamp`` tick takes. The delivered leg keeps its OWN
        phase: deliveries settle asynchronously (lane done-callbacks),
        so reusing the routed tick would sample in window-sized clumps
        decided by whichever routed call last moved it."""
        n = len(seq)
        if leg == "routed":
            tick = self._clamp_tick
            self._clamp_tick = tick + n
        else:
            tick = self._clamp_tick_d
            self._clamp_tick_d = tick + n
        taken = seq[(-tick - 1) % self.clamp::self.clamp]
        if leg == "routed":
            self.clamped += n - len(taken)
        return taken

    def _observe(self, leg: str, path: str, groups):
        """What both forms share: the histograms and, on the routed
        leg, the SLO slot. ``groups``: ``(seconds, qos, n, key)``,
        ``n`` messages of one QoS that waited ``seconds``. Where a
        routed group is over the objective, returns what ``_breach``
        needs of them: the slot's clock, their messages' count, the
        worst latency, their keys."""
        objective = self._objective_s
        total = n_over = 0
        worst = 0.0
        over = []
        for seconds, qos, n, key in groups:
            self._h(leg, min(qos, 2), path).observe_n(seconds, n)
            total += n
            if seconds > objective:
                n_over += n
                over.append(key)
                if seconds > worst:
                    worst = seconds
        if leg != "routed":
            return None
        self.samples += total
        self.groups += len(groups)
        self.metrics.inc("pipeline.latency.groups", len(groups))
        now = time.monotonic()
        sid = int(now / _SLOT_S)
        slots = self._slots
        if not slots or slots[-1][0] != sid:
            slots.append([sid, 0, 0])
        cur = slots[-1]
        cur[1] += total
        if not n_over:
            return None
        cur[2] += n_over
        self.breaches += n_over
        self.metrics.inc("pipeline.latency.breaches", n_over)
        return now, n_over, worst, over

    def _breach(self, path: str, trace: int, over, tail) -> None:
        """A record's breach (``over``: ``_observe``'s): exemplars for
        the last of its messages (``tail``: ``(msg, seconds)`` in
        message order; the deque holds 16, so no more are built), ONE
        ``slo_breach`` instant event on their window's flight-recorder
        trace, and the hook throttled so a degraded pipeline (where
        EVERY message breaches) logs one causal chain per second
        instead of one per message."""
        now, n_over, worst, _keys = over
        ts = round(time.time(), 3)
        exs = [{"topic": msg.topic, "qos": msg.qos, "path": path,
                "latency_ms": round(seconds * 1000, 3),
                "trace_id": trace, "ts": ts} for msg, seconds in tail]
        self.exemplars.extend(exs)
        rec = self.recorder
        if rec is not None and trace:
            rec.event(trace, "slo_breach", track="latency",
                      meta={"latency_ms": round(worst * 1000, 3),
                            "path": path, "count": n_over})
        hooks = self.hooks
        if hooks is not None:
            if now - self._last_hook >= _HOOK_MIN_INTERVAL_S:
                self._last_hook = now
                self.hook_fires += 1
                self.hook_throttled += n_over - 1
                hooks.run("latency.breach", (exs[0],))
            else:
                self.hook_throttled += n_over

    def reset(self) -> None:
        """Zero every recorded distribution, slot and exemplar (the
        registry histogram objects are kept and zeroed in place, so
        exporters and cached references stay valid). Bench-phase
        tooling only — tools/overload_bench.py resets at the
        ramp→steady-state boundary so the graded p99 measures the
        governed steady state, not the untimed ramp."""
        for h in self._hist.values():
            h.counts = [0] * len(h.counts)
            h.count = 0
            h.sum = 0.0
        self._slots.clear()
        self.samples = 0
        self.breaches = 0
        self.windows = 0
        self.groups = 0
        self.exemplars.clear()
        # clamp/hook bookkeeping resets with the distributions: the
        # post-reset section's clamp.skipped must describe the
        # post-reset distribution, not the discarded ramp
        self._clamp_tick = 0
        self._clamp_tick_d = 0
        self.clamped = 0
        self.hook_fires = 0
        self.hook_throttled = 0

    # ---- read side -------------------------------------------------------
    def burn_rates(self) -> dict:
        """Rolling error-budget burn per window: (breach fraction) /
        (allowed fraction). 1.0 = breaching exactly 1% of messages —
        the budget a p99 objective grants; >1 over-burning (alert
        thresholds: the classic multi-window pairs, e.g. 1m>14 AND
        5m>14 for a page, 30m>1 for a ticket)."""
        slots = list(self._slots)
        now_sid = int(time.monotonic() / _SLOT_S)
        out = {}
        for label, n in _BURN_WINDOWS:
            tot = br = 0
            for sid, t, b in slots:
                if sid > now_sid - n:
                    tot += t
                    br += b
            out[label] = round((br / tot) / _P99_BUDGET, 3) if tot \
                else 0.0
        return out

    def _merged_percentile(self, leg: str, p: float):
        """Percentile across every (qos, path) series of one leg: the
        histograms share one bucket ladder, so summed counts walk the
        same bounds (the aggregate p99 the SLO verdict grades)."""
        hs = [h for (lg, _q, _pa), h in self._hist.items()
              if lg == leg and h.count]
        if not hs:
            return None
        bounds = hs[0].bounds
        counts = [0] * (len(bounds) + 1)
        total = 0
        for h in hs:
            total += h.count
            for i, c in enumerate(h.counts):
                counts[i] += c
        want = p * total
        acc = 0
        for b, c in zip(bounds, counts):
            acc += c
            if acc >= want:
                return b
        return 2 * bounds[-1]

    def section(self) -> dict:
        """The ``latency`` snapshot section — the one schema shared by
        telemetry.snapshot(), $SYS ``pipeline/latency``,
        ``GET /api/v5/pipeline/latency``, the bench phase rows and
        ``tools/latency_report.py``."""
        routed: dict = {}
        delivered: dict = {}
        for (leg, qos, path), h in sorted(self._hist.items()):
            if not h.count:
                continue
            row = {
                "count": h.count,
                "p50_ms": round(h.percentile(0.50) * 1000, 4),
                "p99_ms": round(h.percentile(0.99) * 1000, 4),
                "p999_ms": round(h.percentile(0.999) * 1000, 4),
            }
            (routed if leg == "routed" else
             delivered)[f"q{qos}.{path}"] = row
        p99 = self._merged_percentile("routed", 0.99)
        slo = {
            "objective_p99_ms": self.objective_ms,
            "samples": self.samples,
            "record": {"windows": self.windows, "groups": self.groups},
            "breaches": self.breaches,
            "burn": self.burn_rates(),
        }
        if p99 is None:
            slo["verdict"] = "no_data"
        else:
            slo["routed_p99_ms"] = round(p99 * 1000, 4)
            slo["verdict"] = "met" if p99 * 1000 <= self.objective_ms \
                else "breached"
        dp99 = self._merged_percentile("delivered", 0.99)
        if dp99 is not None:
            slo["delivered_p99_ms"] = round(dp99 * 1000, 4)
        out = {
            "schema": SCHEMA,
            "objective_p99_ms": self.objective_ms,
            "routed": routed,
            "delivered": delivered,
            "slo": slo,
        }
        if self.clamp > 1 or self.clamped:
            out["clamp"] = {"factor": self.clamp,
                            "skipped": self.clamped}
        if self.exemplars:
            out["exemplars"] = list(self.exemplars)
        if self.hook_fires or self.hook_throttled:
            out["breach_hook"] = {"fired": self.hook_fires,
                                  "throttled": self.hook_throttled}
        return out
