"""Broker node: the composition root bundling all subsystems.

Parity: the emqx application + emqx_sup supervision tree
(apps/emqx/src/emqx_sup.erl:64-79) — here a plain object graph assembled at
boot, since asyncio tasks replace the supervised process tree. Also carries
the facade API the reference exports from emqx.erl:25-52
(subscribe/publish/topics/hook/...).
"""

from __future__ import annotations

import asyncio
from typing import Any, Optional

from emqx_tpu.broker.alarm import AlarmManager
from emqx_tpu.broker.banned import Banned
from emqx_tpu.broker.cm import ConnectionManager
from emqx_tpu.broker.monitor import OsMon
from emqx_tpu.broker.hooks import Hooks
from emqx_tpu.broker.message import Message
from emqx_tpu.broker.metrics import Metrics, Stats
from emqx_tpu.broker.pubsub import Broker
from emqx_tpu.broker.router import Router


def _bind_device() -> dict:
    """Name the JAX device the route programs will run on."""
    import logging

    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "count": len(devs)}
    log = logging.getLogger("emqx.device")
    if info["platform"] == "cpu":
        log.warning("device route path bound to the CPU backend "
                    "(JAX found no accelerator): %s", info)
    else:
        log.info("device route path bound to %s", info)
    return info


class Node:
    def __init__(self, config: Optional[dict] = None, *,
                 use_device: Optional[bool] = None,
                 name: str = "emqx_tpu@127.0.0.1"):
        from emqx_tpu.broker.config import Config
        self.name = name
        self.config = config if hasattr(config, "get_zone") else Config(config)
        from emqx_tpu.utils.logger import setup_from_config
        setup_from_config(self.config.get("log") or {})
        self.hooks = Hooks()
        self.metrics = Metrics()
        self.stats = Stats()
        perf = self.config.get("broker") or {}
        if use_device is None:
            # default-on: the fused device route step IS the serving path
            use_device = bool(perf.get("device_route", True))
        from emqx_tpu.broker.telemetry import PipelineTelemetry
        slow_ms = perf.get("slow_batch_threshold_ms", 250)
        self.pipeline_telemetry = PipelineTelemetry(
            self.metrics, hooks=self.hooks,
            slow_batch_s=(slow_ms / 1000.0) if slow_ms else None,
            track_compiles=use_device)
        # rebuild threshold: config beats EMQX_TPU_REBUILD_THRESHOLD
        # beats the built-in default (one resolution shared by the host
        # router and both device engines)
        from emqx_tpu.broker.device_engine import resolve_rebuild_threshold
        rebuild_threshold = resolve_rebuild_threshold(
            perf.get("rebuild_threshold"))
        # double-buffered window pipeline depth (ISSUE 9): one
        # resolution shared by the batcher's settle ring and both
        # engines' async-readback gates. broker.dispatch_depth
        # / EMQX_TPU_DISPATCH_DEPTH, config beats env beats default 2;
        # =1 restores the synchronous pre-ISSUE-9 loop exactly.
        from emqx_tpu.broker.batcher import resolve_dispatch_depth
        dispatch_depth = resolve_dispatch_depth(
            perf.get("dispatch_depth"))
        # columnar zero-copy PUBLISH ingress (ISSUE 11): one resolution
        # for the whole layer — the native burst decode in the codec,
        # the channel's burst hand-off, the batcher's submit_burst and
        # the sharded acceptor lanes all read these two node attributes.
        # broker.columnar_ingress / EMQX_TPU_COLUMNAR_INGRESS, config
        # beats env beats default-on; =0 restores the per-packet ingress
        # path EXACTLY (single accept loop, parser.feed, per-packet
        # handle_in, no `ingress` telemetry section).
        from emqx_tpu.broker.connection import (resolve_columnar_ingress,
                                                resolve_ingress_lanes)
        self.columnar_ingress = resolve_columnar_ingress(
            perf.get("columnar_ingress"))
        self.ingress_lanes = resolve_ingress_lanes(
            perf.get("ingress_lanes")) if self.columnar_ingress else 1
        self.router = Router(
            use_device=use_device,
            rebuild_threshold=rebuild_threshold,
            device_min_batch=perf.get("device_min_batch", 4))
        self.broker = Broker(
            router=self.router, hooks=self.hooks, metrics=self.metrics,
            shared_strategy=perf.get("shared_subscription_strategy",
                                     "round_robin"),
            shared_dispatch_ack=perf.get("shared_dispatch_ack_enabled",
                                         False))
        self.device_engine = None
        self.publish_batcher = None
        # the platform the device route path is bound to. JAX falls back
        # to its CPU backend when it finds no accelerator, so the node
        # names what it got: logged here, exported in the telemetry
        # snapshot and the engine stats, asserted by chip_smoke.py.
        self.device_info = None
        if use_device or (perf.get("multichip") or {}).get("enable"):
            self.device_info = _bind_device()
            self.pipeline_telemetry.device_info = self.device_info
        # window-causal flight recorder (ISSUE 7): trace ids minted at
        # batcher admit ride the whole pipeline (dispatch/materialize/
        # replay/lanes/settle) into a bounded span ring — always on at
        # window granularity, dumpable post-mortem (GET /api/v5/
        # pipeline/trace?format=perfetto). broker.trace /
        # EMQX_TPU_TRACE =0 restores the pre-ISSUE-7 behavior exactly
        # (self.flight_recorder stays None everywhere).
        self.flight_recorder = None
        mc = perf.get("multichip") or {}
        from emqx_tpu.broker.trace import (FlightRecorder, GcWatch,
                                           LoopWatch, Spans, resolve_trace)
        if resolve_trace(perf.get("trace")) \
                and (use_device or mc.get("enable")):
            self.flight_recorder = FlightRecorder(
                self.metrics, cap=perf.get("trace_ring", 4096),
                sample=perf.get("trace_sample"))
            self.pipeline_telemetry.recorder = self.flight_recorder
        # the one span call of every pipeline stage: stage histogram,
        # ring (when there is one) and the profiler's host timeline
        self.spans = Spans(self.pipeline_telemetry, self.flight_recorder)
        # the interpreter's collections as counters (runtime.gc.*) and,
        # for generation 2, as spans; installed while a listener or the
        # housekeeping timer runs. Meanwhile a generation-2 collection
        # that spent long on what survived it freezes those survivors
        # (trace.HeapFreeze, one a process)
        self.gc_watch = GcWatch(self.metrics, self.spans)
        self.stats.register_stats_fun(self.gc_watch.stats_fun)
        # the loop's own clock (runtime.loop.*): waits, work and CPU of
        # the one asyncio loop, timed at its selector; started and
        # stopped with the collections' watch
        self.loop_watch = LoopWatch(self.metrics, self.spans)
        self.stats.register_stats_fun(self.loop_watch.stats_fun)
        self.pipeline_telemetry.runtime_state_fn = \
            lambda: {"loop": self.loop_watch.state()}
        # fault-domain supervision (ISSUE 6): the per-node supervision
        # tree every pipeline stage plugs into — fault injection points,
        # per-stage circuit breakers driving the degradation ladder
        # (device+cache+delta → device-plain → host-trie), the window
        # journal and the stage watchdogs. broker.supervise /
        # EMQX_TPU_SUPERVISE =0 restores the pre-ISSUE-6 ad-hoc unwind
        # behavior exactly (self.supervisor stays None everywhere).
        self.supervisor = None
        from emqx_tpu.broker.supervise import (PipelineSupervisor,
                                               resolve_supervise)
        if resolve_supervise(perf.get("supervise")) \
                and (use_device or mc.get("enable")):
            self.supervisor = PipelineSupervisor(
                self.metrics, telemetry=self.pipeline_telemetry,
                threshold=perf.get("supervise_threshold"))
            self.pipeline_telemetry.supervise_state_fn = \
                self.supervisor.state
            # rung changes / trips / restarts land in the flight
            # recorder as node-scope events (trace id 0) — the causal
            # timeline shows WHEN the ladder moved relative to the
            # windows that tripped it
            self.supervisor.recorder = self.flight_recorder
        # HBM ledger (ISSUE 8): per-category accounting of persistent
        # device allocations (snapshot tables/cursors, delta-overlay
        # versions, mesh shard tables) + the stale-pin sentinel. Both
        # engines register their device_put sites through it;
        # telemetry.snapshot() gains the `memory` section all four
        # exporters publish. broker.hbm_ledger / EMQX_TPU_HBM_LEDGER
        # =0 restores the untracked behavior exactly (self.hbm_ledger
        # stays None everywhere).
        self.hbm_ledger = None
        from emqx_tpu.broker.hbm_ledger import (HbmLedger,
                                                resolve_hbm_ledger)
        if resolve_hbm_ledger(perf.get("hbm_ledger")) \
                and (use_device or mc.get("enable")):
            self.hbm_ledger = HbmLedger(
                self.metrics,
                pin_warn_windows=perf.get("pin_warn_windows"),
                hooks=self.hooks, recorder=self.flight_recorder)
            self.pipeline_telemetry.ledger = self.hbm_ledger
            self.stats.register_stats_fun(self.hbm_ledger.stats_fun)
        # end-to-end latency SLO observatory (ISSUE 13): per-message
        # ingress→routed / ingress→delivered percentiles keyed by
        # (qos, path), the SLO burn engine and breach exemplars.
        # Stamps start at frame decode (mqtt/frame), ride Message
        # through the batcher/host paths, and are recorded at settle.
        # broker.latency_observatory / EMQX_TPU_LATENCY =0 restores the
        # pre-ISSUE-13 observable behavior (self.latency_observatory
        # stays None everywhere: no `latency` snapshot section, REST
        # 404; the frame-decode stamp itself stays on — see the
        # resolver docstring).
        # Deliberately NOT gated on use_device: the host-only twin
        # measures the same e2e legs (path `host`).
        self.latency_observatory = None
        from emqx_tpu.broker.latency import (LatencyObservatory,
                                             resolve_latency_observatory)
        if resolve_latency_observatory(perf.get("latency_observatory")):
            self.latency_observatory = LatencyObservatory(
                self.metrics, hooks=self.hooks,
                recorder=self.flight_recorder,
                objective_ms=perf.get("slo_route_p99_ms"))
            self.pipeline_telemetry.observatory = self.latency_observatory
            self.broker.latency_obs = self.latency_observatory
        # adaptive overload protection (ISSUE 14): the graded load-shed
        # ladder (normal → elevated → overload → critical) polled on
        # the housekeeping tick, fed by signals that already exist —
        # batcher queue/journal depth, lane backpressure, SLO burn,
        # HBM pressure, event-loop lag — arming ordered shed actions
        # per grade (sampling clamp → dispatch-depth shrink + retained
        # defer + CONNECT 0x97 → QoS0 shed + top-offender disconnect).
        # broker.overload / EMQX_TPU_OVERLOAD =0 restores the
        # pre-ISSUE-14 behavior exactly (self.overload_governor stays
        # None everywhere: no `overload` snapshot section, REST 404).
        # Deliberately NOT gated on use_device: a host-only node
        # overloads the same way (its queue/burn signals still exist).
        self.overload_governor = None
        from emqx_tpu.broker.overload import (OverloadGovernor,
                                              resolve_overload)
        if resolve_overload(perf.get("overload")):
            self.overload_governor = OverloadGovernor(
                self, self.metrics, hooks=self.hooks,
                recorder=self.flight_recorder)
            self.pipeline_telemetry.overload_state_fn = \
                self.overload_governor.state
        # session-affine delivery lanes (ISSUE 5): the overlapped egress
        # stage both engines' consume hands plans to. 0 lanes (config
        # broker.deliver_lanes / env EMQX_TPU_DELIVER_LANES) restores
        # the inline delivery loop exactly — the A/B baseline.
        self.deliver_lanes = None
        from emqx_tpu.broker.deliver import (DeliveryLanePool,
                                             resolve_deliver_lanes)
        n_lanes = resolve_deliver_lanes(perf.get("deliver_lanes"))
        if n_lanes > 0 and (use_device or mc.get("enable")):
            self.deliver_lanes = DeliveryLanePool(
                self.broker, self.metrics, hooks=self.hooks,
                telemetry=self.pipeline_telemetry, n_lanes=n_lanes,
                depth=perf.get("deliver_lane_depth", 8),
                supervisor=self.supervisor, spans=self.spans)
            self.pipeline_telemetry.deliver_state_fn = \
                self.deliver_lanes.state
            self.stats.register_stats_fun(self.deliver_lanes.stats_fun)
        if mc.get("enable"):
            # multichip serving mode: route through a dp×route device
            # mesh (parallel.serving) instead of the single-chip engine;
            # same PublishBatcher protocol, so channels are none the wiser
            from emqx_tpu.broker.batcher import PublishBatcher
            from emqx_tpu.parallel.serving import ShardedRouteServer
            self.device_engine = ShardedRouteServer(
                self, n_devices=mc.get("devices"), dp=mc.get("dp"),
                fanout_cap=perf.get("device_fanout_cap", 128),
                slot_cap=perf.get("device_slot_cap", 16),
                max_batch=mc.get("max_batch", 256),
                compact_readback=perf.get("compact_readback"),
                # churn knob (ISSUE 4): the mesh's churn path is already
                # incremental (per-shard compaction) — the knob is
                # accepted for config parity and surfaced in stats
                delta_overlay=perf.get("delta_overlay"),
                supervisor=self.supervisor,
                dispatch_depth=dispatch_depth,
                # device-to-device exchange stage (ISSUE 15):
                # broker.device_exchange / EMQX_TPU_EXCHANGE =0
                # restores host gather/merge exactly
                device_exchange=perf.get("device_exchange"),
                # subscription covering A/B knob (ISSUE 18; None =
                # EMQX_TPU_COVERING / default-on)
                subscription_covering=perf.get("subscription_covering"))
            self.publish_batcher = PublishBatcher(
                self, self.device_engine,
                window_us=perf.get("batch_window_us", 200),
                max_batch=mc.get("max_batch", 256),
                device_min_batch=perf.get("device_min_batch", 4),
                dispatch_depth=dispatch_depth)
        elif use_device:
            from emqx_tpu.broker.batcher import PublishBatcher
            from emqx_tpu.broker.device_engine import DeviceRouteEngine
            self.device_engine = DeviceRouteEngine(
                self,
                rebuild_threshold=rebuild_threshold,
                fanout_cap=perf.get("device_fanout_cap", 128),
                slot_cap=perf.get("device_slot_cap", 16),
                # device-match reuse layers (None = env / built-in
                # default; see EMQX_TPU_MATCH_CACHE / EMQX_TPU_DEDUP)
                match_cache_size=perf.get("match_cache_size"),
                dedup=perf.get("topic_dedup"),
                # CSR readback compaction A/B knob (ISSUE 3; None =
                # EMQX_TPU_COMPACT_READBACK / default-on)
                compact_readback=perf.get("compact_readback"),
                # delta-overlay A/B knob (ISSUE 4; None =
                # EMQX_TPU_DELTA_OVERLAY / default-on)
                delta_overlay=perf.get("delta_overlay"),
                # subscription covering A/B knob (ISSUE 18; None =
                # EMQX_TPU_COVERING / default-on)
                subscription_covering=perf.get("subscription_covering"),
                supervisor=self.supervisor,
                dispatch_depth=dispatch_depth)
            self.publish_batcher = PublishBatcher(
                self, self.device_engine,
                window_us=perf.get("batch_window_us", 200),
                max_batch=perf.get("max_publish_batch", 1024),
                device_min_batch=perf.get("device_min_batch", 4),
                dispatch_depth=dispatch_depth)
        if self.publish_batcher is not None:
            self.pipeline_telemetry.chooser_state_fn = \
                self.publish_batcher.chooser_state
        self.cm = ConnectionManager()
        self.cm.broker = self.broker
        self.banned = Banned()
        aconf = self.config.get("alarm") or {}
        self.alarms = AlarmManager(
            self.hooks, size_limit=aconf.get("size_limit", 1000),
            validity_period=aconf.get("validity_period", 86400))
        self.os_mon = OsMon(self.alarms,
                            self.config.get("sysmon", "os") or {})
        self.stats.register_stats_fun(self.broker.stats_fun)
        self.stats.register_stats_fun(self.cm.stats_fun)
        self.listeners: list = []
        self._apps: list = []      # started feature apps (retainer, ...)
        self._timer_task: Optional[asyncio.Task] = None

    # ---- config-file boot (emqx_machine_app load_config_files +
    #      emqx_listeners:start) ----
    @classmethod
    def from_config_file(cls, path: str, **kw) -> "Node":
        from emqx_tpu.broker.config import Config
        return cls(Config.load_file(path), **kw)

    async def start_listeners(self) -> list:
        """Start every listener configured under `listeners`
        (emqx_listeners.erl:91,126-138: tcp/ssl esockd, ws/wss cowboy)."""
        from emqx_tpu.broker.connection import Listener
        from emqx_tpu.broker.ws import WsListener
        for name, lc in (self.config.get("listeners") or {}).items():
            if not lc.get("enabled", True):
                continue
            ltype = lc.get("type", "tcp")
            ssl_opts = lc.get("ssl") \
                if ltype in ("ssl", "wss") or "ssl" in lc else None
            if ltype in ("ssl", "wss") and not ssl_opts:
                # never silently downgrade a TLS listener to plaintext
                raise ValueError(
                    f"listener {name!r} is type {ltype} but has no ssl "
                    f"block")
            common = dict(bind=lc.get("bind", "0.0.0.0"),
                          port=int(lc.get("port", 0)),
                          zone=lc.get("zone"),
                          max_connections=int(
                              lc.get("max_connections", 1024000)),
                          ssl_opts=ssl_opts)
            if ltype in ("ws", "wss"):
                lst = WsListener(self, path=lc.get("path", "/mqtt"),
                                 **common)
            elif ltype in ("tcp", "ssl"):
                lst = Listener(self, name=f"{ltype}:{name}", **common)
            elif ltype == "quic":
                from emqx_tpu.quic import QuicListener
                ssl_opts = lc.get("ssl") or {}
                if not ssl_opts.get("certfile") or \
                        not ssl_opts.get("keyfile"):
                    raise ValueError(
                        f"quic listener {name!r} needs ssl.certfile and "
                        f"ssl.keyfile")
                common.pop("ssl_opts", None)
                lst = QuicListener(self, certfile=ssl_opts["certfile"],
                                   keyfile=ssl_opts["keyfile"], **common)
            else:
                raise ValueError(f"unknown listener type {ltype!r}")
            await lst.start()
            self.listeners.append(lst)
        return self.listeners

    async def start_dashboard(self):
        """Boot the mgmt REST API + web dashboard from config (the
        reference's emqx_dashboard http listener, default port 18083).
        Opt-in: requires a `dashboard` config section; disable with
        `dashboard.enable = false`. The full /api/v5 surface and the
        single-file UI share one server; everything except the UI page
        and /api/v5/login sits behind the admin token/basic auth."""
        dc = self.config.get("dashboard") or {}
        if not dc or dc.get("enable") is False:
            return None
        from emqx_tpu.apps.dashboard import DashboardAdmin, register_api
        from emqx_tpu.mgmt import Mgmt, make_api
        lc = (dc.get("listeners") or {}).get("http") or {}
        cluster = getattr(self.broker, "cluster", None)
        admin = DashboardAdmin(self)
        mgmt = Mgmt(self, cluster)
        srv = make_api(self, mgmt, cluster=cluster,
                       host=str(lc.get("bind", "127.0.0.1")),
                       port=int(lc.get("port", 18083)))
        srv.auth_check = admin.auth_check
        register_api(srv, self, admin, mgmt)
        await srv.start()
        self.dashboard_server = srv
        return srv

    async def start_apps(self) -> list:
        """Boot every feature app the config declares (retainer, delayed,
        rewrite, rule engine, authn/authz chains, exhook) — the release
        application-start analog. See apps/boot.py for the surface."""
        from emqx_tpu.apps.boot import start_apps
        return await start_apps(self)

    async def start_gateways(self) -> list:
        """Boot protocol gateways from the `gateway` config section
        (emqx_gateway.erl loads gateway.stomp/mqttsn/coap/lwm2m/exproto
        blocks the same way). Each block: enable (default true) + the
        gateway's own options (bind/port/...)."""
        from emqx_tpu.gateway.registry import GatewayRegistry
        reg = getattr(self, "gateway_registry", None)
        if reg is None:
            reg = GatewayRegistry.with_builtins(self)
        started = []
        for name, conf in (self.config.get("gateway") or {}).items():
            if not isinstance(conf, dict) or conf.get("enable") is False:
                continue
            started.append(await reg.load(name, conf))
        return started

    async def stop_gateways(self) -> None:
        reg = getattr(self, "gateway_registry", None)
        if reg is not None:
            for name in list(reg._instances):
                await reg.unload(name)

    async def stop_listeners(self) -> None:
        for lst in self.listeners:
            await lst.stop()
        self.listeners.clear()
        await self.stop_gateways()
        srv = getattr(self, "dashboard_server", None)
        if srv is not None:
            await srv.stop()
            self.dashboard_server = None
        # resources created by the config boot (DB-backed authn/authz):
        # close their pools + health loop or their sockets outlive the node
        mgr = getattr(self, "resources", None)
        if mgr is not None:
            mgr.stop_health_checks()
            for rid in list(mgr.instances):
                await mgr.remove(rid)

    # ---- periodic housekeeping (the reference's per-subsystem timers:
    #      session expiry, retained expiry scan, delayed fire, stats) ----
    def sweep(self) -> None:
        """One housekeeping pass; also callable directly from tests."""
        self.cm.sweep_expired_sessions()
        self.banned.tick()
        self.alarms.tick()
        self.os_mon.tick()
        if self.overload_governor is not None:
            # overload governor poll (ISSUE 14): grade transitions and
            # shed arming ride the housekeeping cadence — BEFORE the
            # app ticks, so the retainer's deferred-replay drain sees
            # the post-recovery flags on the same tick
            self.overload_governor.poll()
        self.stats.sample()
        # churn among what the collector no longer walks: past a share
        # of the frozen heap this pass pays one full collection for it
        self.gc_watch.housekeeping(
            self.metrics.val("client.disconnected"),
            self.stats.getstat("subscriptions.count"))
        for app in self._apps:
            tick = getattr(app, "tick", None)
            if tick is not None:
                tick()

    async def _housekeeping(self, interval: float) -> None:
        while True:
            await asyncio.sleep(interval)
            self.sweep()

    def start_timers(self, interval: float = 1.0) -> None:
        if self._timer_task is None:
            if self.overload_governor is not None:
                # the loop-lag probe measures cadence drift against
                # this interval (poll later than interval ⇒ the loop
                # was wedged in callbacks for the difference)
                self.overload_governor.poll_interval_s = interval
            from emqx_tpu.broker.supervise import guard_task
            self._timer_task = guard_task(
                asyncio.ensure_future(self._housekeeping(interval)),
                "node-housekeeping", self.metrics)
            self.gc_watch.start()
            self.loop_watch.start()

    def stop_timers(self) -> None:
        if self._timer_task is not None:
            self._timer_task.cancel()
            self._timer_task = None
            self.gc_watch.stop()
            self.loop_watch.stop()

    # ---- facade (emqx.erl) ----
    def publish(self, msg: Message) -> int:
        return self.broker.publish(msg)

    async def publish_async(self, msg: Message) -> int:
        """The channel PUBLISH entry: batched through the device route
        pipeline when enabled, else the host per-message path."""
        if self.publish_batcher is not None:
            return await self.publish_batcher.submit(msg)
        return await self.broker.publish_async(msg)

    def publish_nowait(self, msg: Message) -> bool:
        """Fire-and-forget PUBLISH (QoS0 path): pipelines into the batch
        window without serializing the caller's read loop. Returns False
        when not accepted (no batcher, or backpressure bound hit) — the
        caller must `await publish_async` instead, which both preserves
        per-publisher ordering and stalls an overloading read loop."""
        if self.publish_batcher is not None:
            return self.publish_batcher.enqueue(msg)
        return False

    def topics(self) -> list[str]:
        return self.router.topics()

    def hook(self, name: str, action, priority: int = 0) -> None:
        self.hooks.add(name, action, priority)

    def unhook(self, name: str, action_or_tag) -> None:
        self.hooks.delete(name, action_or_tag)

    def run_hook(self, name: str, args: tuple = ()) -> None:
        self.hooks.run(name, args)

    def register_app(self, app: Any) -> Any:
        """Attach a feature app (retainer, delayed, rule engine, ...)."""
        self._apps.append(app)
        return app

    def get_app(self, cls) -> Optional[Any]:
        for a in self._apps:
            if isinstance(a, cls):
                return a
        return None
