"""Per-client connection task + config-driven listeners.

Parity: emqx_connection.erl (per-client recvloop with {active,N}-style
read batching :318-345,404-516, keepalive + idle timeout, force-shutdown
policy) and emqx_listeners.erl (listener lifecycle :126-138). One asyncio
task per socket replaces the reference's per-connection BEAM process; the
read loop drains whatever bytes are available and feeds the streaming frame
parser, so a burst of packets is handled as one batch (the P10 batching
window).
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import os
import socket
import time
from typing import Optional

from emqx_tpu.broker.channel import Channel, ProtocolError
from emqx_tpu.broker.limiter import (ConnectionLimiter, ForceShutdownPolicy,
                                     TokenBucket)
from emqx_tpu.mqtt import constants as C
from emqx_tpu.mqtt import packet as P
from emqx_tpu.mqtt.frame import (FrameError, FrameParser, PublishBurst,
                                 serialize)

log = logging.getLogger("emqx_tpu.connection")

READ_CHUNK = 65536
_NOT_OPEN = contextlib.nullcontext()    # this read has no control span yet


def resolve_columnar_ingress(configured=None) -> bool:
    """The one columnar-ingress resolution (ISSUE 11): config
    (``broker.columnar_ingress``) beats ``EMQX_TPU_COLUMNAR_INGRESS``
    beats default-on. ``=0`` restores the per-packet ingress path
    EXACTLY — parser.feed, per-packet handle_in, one accept loop, no
    ``ingress`` telemetry section — the A/B baseline the twin test
    compares."""
    if configured is not None:
        return bool(configured)
    return os.environ.get("EMQX_TPU_COLUMNAR_INGRESS", "1") \
        not in ("0", "false", "off")


def resolve_ingress_lanes(configured=None) -> int:
    """Sharded-acceptor lane count: config (``broker.ingress_lanes``)
    beats ``EMQX_TPU_INGRESS_LANES`` beats the built-in min(4, cpus).
    1 = the single accept loop; the whole layer additionally rides the
    columnar_ingress knob (=0 forces 1 lane). Must be a positive
    integer — anything else is a deployment error worth failing loudly
    on."""
    if configured is not None:
        val = int(configured)
    else:
        env = os.environ.get("EMQX_TPU_INGRESS_LANES")
        if env is None:
            return min(4, os.cpu_count() or 1)
        try:
            val = int(env)
        except ValueError:
            raise ValueError(
                f"EMQX_TPU_INGRESS_LANES={env!r} is not an integer")
    if val < 1:
        raise ValueError(f"ingress_lanes must be >= 1, got {val}")
    return val


class Connection:
    def __init__(self, node, reader: asyncio.StreamReader,
                 writer: asyncio.StreamWriter, zone: Optional[str] = None):
        self.node = node
        self.reader = reader
        self.writer = writer
        peer = writer.get_extra_info("peername") or ("?", 0)
        sock = writer.get_extra_info("sockname") or ("?", 0)
        from emqx_tpu.utils.tls import peer_cert_info
        peercert = peer_cert_info(writer)
        self.parser = FrameParser(
            max_size=node.config.mqtt(zone).get("max_packet_size"),
            strict=node.config.mqtt(zone).get("strict_mode", False))
        # columnar ingress (ISSUE 11): resolved once per node; off means
        # this connection's read loop is byte-for-byte the per-packet
        # path (parser.feed + handle_in, no ingress counters)
        self._columnar = bool(getattr(node, "columnar_ingress", False))
        from emqx_tpu.broker.trace import spans_of
        self._spans = spans_of(node)
        self.channel = Channel(
            node, {"peername": peer, "sockname": sock, "zone": zone,
                   "peercert": peercert},
            send=self._send_packets, close=self._request_close)
        self.channel.send_frames = self._send_frames
        self.last_rx = time.monotonic()
        self._closing: Optional[str] = None
        self._timer_task: Optional[asyncio.Task] = None
        rl = node.config.get_zone(zone, "rate_limit") or {}
        self.limiter = ConnectionLimiter(
            rl.get("conn_messages_in") or None,
            rl.get("conn_bytes_in") or None)
        fs = node.config.get_zone(zone, "force_shutdown") or {}
        self.force_shutdown = ForceShutdownPolicy(
            fs.get("max_mqueue_len", 0), fs.get("max_awaiting_rel", 0))
        from emqx_tpu.broker.congestion import Congestion
        cc = node.config.get_zone(zone, "conn_congestion") or {}
        self.congestion = Congestion(
            node, self.channel, writer,
            enable_alarm=cc.get("enable_alarm", False),
            min_alarm_sustain_duration=cc.get(
                "min_alarm_sustain_duration", 60))
        # overload governor (ISSUE 14): registered (weakly) so the
        # critical-grade top-offender shed can rank live connections by
        # limiter debt; shed_rows is the ingress-volume fallback score
        # when no rate limit is configured (decayed by the governor)
        self.shed_rows = 0.0
        gov = getattr(node, "overload_governor", None)
        if gov is not None:
            gov.register_conn(self)

    # ---- outbound ----
    def _send_packets(self, pkts: list[P.Packet]) -> None:
        if not self.writer.is_closing():
            self._send_frames(b"".join(
                serialize(p, self.channel.proto_ver) for p in pkts))

    def _send_frames(self, data: bytes) -> None:
        """Frames serialized already (`Channel._send_shared`). The one
        place this connection writes, so the one clocked: asyncio tries
        the `send()` inline (`pipeline.egress.write_us` / `.writes`)."""
        if self.writer.is_closing():
            return
        m = self.node.metrics
        m.inc("bytes.sent", len(data))
        t0 = time.perf_counter_ns()
        self.writer.write(data)
        m.inc("pipeline.egress.write_us",
              (time.perf_counter_ns() - t0 + 500) // 1000)
        m.inc("pipeline.egress.writes")

    def _end_ack(self, sp) -> None:
        """Leave a run of PUBACKs' emqx:ack span and count what it
        covered, as the lanes count `pipeline.deliver.lane_us`."""
        sp.__exit__(None, None, None)
        self.node.metrics.inc("session.ack_us",
                              round((sp.dur - sp.away) * 1e6))

    def _request_close(self, reason: str) -> None:
        if self._closing is None:
            self._closing = reason
            if not self.writer.is_closing():
                self.writer.close()

    # overload top-offender volume floor (ISSUE 14): without a
    # configured rate limit, a connection only qualifies for the
    # critical-grade disconnect when its DECAYED recent row count
    # reads as a genuine flood — a subscriber's ack stream or a
    # moderate publisher must never rank
    _SHED_VOLUME_FLOOR = 1000.0

    # ---- overload shed (ISSUE 14: force_shutdown parity) ----
    def shed_score(self) -> float:
        """How much this connection is over-driving ingress: limiter
        debt (seconds-to-repay) when a rate limit is configured — the
        primary ranking, offset so ANY debt outranks plain volume —
        else the decayed recent-rows count, floored so only a genuine
        flooder qualifies. 0.0 = not a shed candidate."""
        debt = self.limiter.debt()
        if debt > 0:
            return 1e6 + debt
        if self.shed_rows >= self._SHED_VOLUME_FLOOR:
            return self.shed_rows
        return 0.0

    def overload_disconnect(self) -> None:
        """The governor's critical-grade disconnect: v5 clients get a
        DISCONNECT with reason 0x97 (quota exceeded), everyone gets the
        close — exactly the force_shutdown lifecycle, so the session
        parks or terminates per its expiry config."""
        self.node.metrics.inc("connection.force_shutdown")
        if self.channel.proto_ver == C.MQTT_V5 \
                and self.channel.conn_state == "connected":
            self._send_packets([P.Disconnect(
                reason_code=C.RC_QUOTA_EXCEEDED)])
        self._request_close("overload_shed")

    # ---- main loop (emqx_connection:recvloop) ----
    async def run(self) -> None:
        from emqx_tpu.utils.logger import set_metadata_peername
        peer = self.channel.conninfo.get("peername")
        if peer:
            set_metadata_peername(f"{peer[0]}:{peer[1]}")
        from emqx_tpu.broker.supervise import guard_task
        self._timer_task = guard_task(
            asyncio.ensure_future(self._timers()), "conn-timers",
            self.node.metrics)
        reason = "closed"
        try:
            idle_timeout = self.node.config.mqtt(
                self.channel.zone).get("idle_timeout", 15)
            while self._closing is None:
                timeout = (idle_timeout
                           if self.channel.conn_state == "idle" else None)
                try:
                    data = await asyncio.wait_for(
                        self.reader.read(READ_CHUNK), timeout)
                except asyncio.TimeoutError:
                    reason = "idle_timeout"
                    break
                if not data:
                    reason = "closed"
                    break
                self.last_rx = time.monotonic()
                m = self.node.metrics
                m.inc("bytes.received", len(data))
                columnar = self._columnar
                try:
                    with self._spans.span("ingress",
                                          meta={"bytes": len(data)}):
                        if columnar:
                            # columnar ingress (ISSUE 11): PUBLISH runs
                            # decode as PublishBurst items, everything
                            # else (and small reads) stays per-packet,
                            # in order
                            items = self.parser.feed_columnar(data)
                        else:
                            items = self.parser.feed(data)
                except FrameError as e:
                    reason = f"frame_error:{e.code}"
                    self._frame_error_out(e)
                    break
                n_rows = 0
                n_pub = 0
                n_ctl = 0
                for it in items:
                    if type(it) is PublishBurst:
                        n_rows += len(it)
                        n_pub += len(it)
                    else:
                        n_rows += 1
                        if type(it) is P.Publish:
                            n_pub += 1
                        else:
                            n_ctl += 1
                if columnar and items:
                    m.inc("pipeline.ingress.bytes", len(data))
                    m.inc("pipeline.ingress.control_packets", n_ctl)
                n_done = 0
                # one emqx:control span a read, opened at its first
                # packet that is no burst and released wherever this
                # task waits: PUBACKs, PINGREQ fences, SUBSCRIBEs and
                # the PUBLISHes of a read too small to decode by column
                # and inside it one emqx:ack span a run of PUBACKs (a
                # subscriber's read is little else): from the first
                # one's `handle_in` to the write of what the run let
                # out of the session's mqueue, `session.ack_us`
                ctl = ack = None
                try:
                    for item in items:
                        if ack is not None and type(item) is not P.Puback:
                            self._end_ack(ack)
                            ack = None
                        if type(item) is PublishBurst:
                            m.inc("pipeline.ingress.bursts")
                            m.inc("pipeline.ingress.rows", len(item))
                            tele = self.node.pipeline_telemetry
                            if tele is not None:
                                tele.record_ingress_burst(len(item))
                            away = _NOT_OPEN if ctl is None \
                                else ctl.released()
                            try:
                                with away:
                                    await self.channel.handle_publish_burst(
                                        item)
                            except ProtocolError as e:
                                reason = f"protocol_error:0x{e.rc:02x}"
                                self._protocol_error_out(e)
                                break
                            n_done += len(item)
                            continue
                        if columnar:
                            m.inc("pipeline.ingress.fallback_frames")
                        if ctl is None:
                            ctl = self._spans.span("control").__enter__()
                        if ack is None and type(item) is P.Puback:
                            ack = self._spans.span("ack").__enter__()
                        try:
                            await ctl.run(self.channel.handle_in(item))
                        except ProtocolError as e:
                            reason = f"protocol_error:0x{e.rc:02x}"
                            self._protocol_error_out(e)
                            break
                        n_done += 1
                        if n_done % 64 == 0:
                            # one read can carry hundreds of frames;
                            # without a scheduling point the whole burst
                            # handles back-to-back and stalls every
                            # other task for tens of ms (handle_in's
                            # awaits don't yield unless they actually
                            # block)
                            if ack is not None:
                                self._end_ack(ack)
                                ack = None
                            with ctl.released():
                                await asyncio.sleep(0)
                finally:
                    if ack is not None:
                        self._end_ack(ack)
                    if ctl is not None:
                        ctl.__exit__(None, None, None)
                if items:
                    # offender score counts PUBLISH rows ONLY: a
                    # subscriber's PUBACK stream (or SUBSCRIBE/PING
                    # chatter) must never rank it for the overload
                    # disconnect — only publish pressure does
                    self.shed_rows += n_pub
                    await self._drain()
                    # ingress rate limit: a depleted bucket pauses reading
                    # (the {active,N}-off backpressure, emqx_connection
                    # ensure_rate_limit)
                    pause = self.limiter.check(n_rows, len(data))
                    if pause > 0:
                        self.node.metrics.inc("connection.rate_limited")
                        await asyncio.sleep(pause)
            reason = self._closing or reason
        except (ConnectionResetError, BrokenPipeError):
            reason = "closed"
        except asyncio.CancelledError:
            reason = "shutdown"
        except Exception:
            log.exception("connection crashed")
            reason = "internal_error"
        finally:
            if self._timer_task:
                self._timer_task.cancel()
            self.congestion.cancel()
            self.channel.terminate(self._closing or reason)
            try:
                # graceful close first (flushes the DISCONNECT we may have
                # just written); a stuck peer that can never drain falls
                # into the timeout and gets hard-aborted
                if not self.writer.is_closing():
                    self.writer.close()
                await asyncio.wait_for(self.writer.wait_closed(), 5)
            except (asyncio.CancelledError, KeyboardInterrupt, SystemExit):
                try:
                    self.writer.transport.abort()
                except Exception:  # noqa: BLE001 — transport already gone
                    pass
                raise               # preserve the cancellation contract
            except Exception:       # TimeoutError, reset mid-flush, ...
                try:
                    self.writer.transport.abort()
                except Exception:  # noqa: BLE001 — transport already gone
                    pass

    def _frame_error_out(self, e: FrameError) -> None:
        if self.channel.proto_ver == C.MQTT_V5 and \
                self.channel.conn_state == "connected":
            self._send_packets([P.Disconnect(
                reason_code=C.RC_MALFORMED_PACKET)])

    def _protocol_error_out(self, e: ProtocolError) -> None:
        if self.channel.proto_ver == C.MQTT_V5 and \
                self.channel.conn_state == "connected":
            self._send_packets([P.Disconnect(reason_code=e.rc)])
        self._request_close(f"protocol_error_0x{e.rc:02x}")

    async def _drain(self) -> None:
        try:
            await self.writer.drain()
        except (ConnectionResetError, BrokenPipeError):
            self._request_close("closed")

    # ---- keepalive + retry timers (emqx_channel timer table) ----
    async def _timers(self) -> None:
        backoff = self.node.config.mqtt(
            self.channel.zone).get("keepalive_backoff", 0.75)
        retry_iv = self.node.config.mqtt(
            self.channel.zone).get("retry_interval", 30)
        last_retry = time.monotonic()
        while True:
            await asyncio.sleep(1.0)
            now = time.monotonic()
            self.congestion.check()
            ka = self.channel.keepalive
            if (ka and self.channel.conn_state == "connected"
                    and now - self.last_rx > ka * 2 * backoff):
                if self.channel.proto_ver == C.MQTT_V5:
                    self._send_packets([P.Disconnect(
                        reason_code=C.RC_KEEP_ALIVE_TIMEOUT)])
                self._request_close("keepalive_timeout")
                return
            if retry_iv and now - last_retry >= retry_iv:
                last_retry = now
                self.channel.retry_deliveries()
            why = self.force_shutdown.violated(self.channel.session)
            if why is not None:
                self.node.metrics.inc("connection.force_shutdown")
                self._request_close(f"force_shutdown:{why}")
                return


class Listener:
    """One TCP/TLS listener (emqx_listeners:start_listener/3; ssl opts per
    emqx_listeners.erl:126-129 + emqx_schema ssl block via utils.tls)."""

    def __init__(self, node, *, bind: str = "0.0.0.0", port: int = 1883,
                 zone: Optional[str] = None, max_connections: int = 1024000,
                 name: str = "tcp:default", ssl_opts: Optional[dict] = None):
        self.node = node
        self.bind = bind
        self.port = port
        self.zone = zone
        self.name = name
        self.ssl_opts = ssl_opts
        if ssl_opts and name == "tcp:default":
            self.name = "ssl:default"
        self.max_connections = max_connections
        self._server: Optional[asyncio.AbstractServer] = None
        self._lane_servers: list[asyncio.AbstractServer] = []
        self.lane_conns: list[int] = []    # live conns per accept lane
        self._conns: set[asyncio.Task] = set()
        self.current_conns = 0
        rate = (node.config.get_zone(zone, "rate_limit") or {}) \
            .get("max_conn_rate", 0)
        self._accept_bucket = TokenBucket(rate) if rate else None
        self._gc_watched = False

    async def _on_client(self, reader: asyncio.StreamReader,
                         writer: asyncio.StreamWriter) -> None:
        if self.current_conns >= self.max_connections:
            writer.close()
            return
        if self._accept_bucket is not None \
                and self._accept_bucket.consume() > 0:
            # accept-rate limit: drop the connection (esockd max_conn_rate)
            self.node.metrics.inc("connection.accept_limited")
            writer.close()
            return
        self.current_conns += 1
        conn = Connection(self.node, reader, writer, self.zone)
        task = asyncio.current_task()
        self._conns.add(task)
        try:
            await conn.run()
        finally:
            self.current_conns -= 1
            self._conns.discard(task)

    def _ingress_lanes(self) -> int:
        """Acceptor-lane count for this listener (ISSUE 11): N
        SO_REUSEPORT listening sockets on the same port, each with its
        own accept loop, so the kernel spreads incoming connections —
        the ingress mirror of PR 5's egress lanes. Engages only for
        plain IPv4 TCP with columnar ingress on; TLS/IPv6 keep the
        single accept loop."""
        if not getattr(self.node, "columnar_ingress", False):
            return 1
        if self.ssl_opts or ":" in self.bind \
                or not hasattr(socket, "SO_REUSEPORT"):
            return 1
        return getattr(self.node, "ingress_lanes", 1)

    async def start(self) -> None:
        await self._listen()
        watch = getattr(self.node, "gc_watch", None)
        if watch is not None and not self._gc_watched:
            # the node serves from its first listener on: collections
            # are counted (runtime.gc.*) and the loop is timed
            # (runtime.loop.*) until the last one stops
            watch.start()
            self.node.loop_watch.start()
            self._gc_watched = True

    async def _listen(self) -> None:
        ssl_ctx = None
        if self.ssl_opts:
            from emqx_tpu.utils.tls import make_server_context
            ssl_ctx = make_server_context(self.ssl_opts)
        lanes = self._ingress_lanes()
        if lanes > 1:
            port = self.port
            self.lane_conns = [0] * lanes
            for i in range(lanes):
                sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
                try:
                    sock.setsockopt(socket.SOL_SOCKET,
                                    socket.SO_REUSEADDR, 1)
                    sock.setsockopt(socket.SOL_SOCKET,
                                    socket.SO_REUSEPORT, 1)
                    sock.bind((self.bind, port))
                except OSError:
                    sock.close()
                    if i == 0:
                        raise
                    break   # partial lane set still serves
                if port == 0:   # ephemeral port: later lanes join it
                    port = sock.getsockname()[1]
                srv = await asyncio.start_server(
                    self._lane_handler(i), sock=sock)
                self._lane_servers.append(srv)
            self.port = port
            self._server = self._lane_servers[0]
            log.info("listener %s started on %s:%d (%d ingress lanes)",
                     self.name, self.bind, self.port,
                     len(self._lane_servers))
            return
        self._server = await asyncio.start_server(
            self._on_client, self.bind, self.port, ssl=ssl_ctx)
        if self.port == 0:   # ephemeral port for tests
            self.port = self._server.sockets[0].getsockname()[1]
        log.info("listener %s started on %s:%d", self.name, self.bind,
                 self.port)

    def _lane_handler(self, lane: int):
        async def _on_lane_client(reader, writer):
            gov = getattr(self.node, "overload_governor", None)
            if lane > 0 and gov is not None and gov.connects_paused:
                # overload pause_connects (ISSUE 14): the extra
                # acceptor lanes stop taking connections — lane 0
                # keeps accepting so the CONNECT still gets its v5
                # 0x97 CONNACK (the channel-side half of this action)
                gov.count_accept_paused()
                writer.close()
                return
            self.node.metrics.inc(
                f"pipeline.ingress.lane{lane}.accepted")
            self.lane_conns[lane] += 1
            try:
                await self._on_client(reader, writer)
            finally:
                self.lane_conns[lane] -= 1
        return _on_lane_client

    async def stop(self) -> None:
        # stop accepting first so no connection slips in during the cancel
        # window; then cancel handlers (py3.12 wait_closed blocks until
        # every handler coroutine finishes, so cancel before waiting)
        if self._gc_watched:
            self._gc_watched = False
            self.node.gc_watch.stop()
            self.node.loop_watch.stop()
        servers = self._lane_servers or \
            ([self._server] if self._server else [])
        for srv in servers:
            srv.close()
        for t in list(self._conns):
            t.cancel()
        if self._conns:
            await asyncio.gather(*self._conns, return_exceptions=True)
        for srv in servers:
            try:
                await asyncio.wait_for(srv.wait_closed(), 2)
            except asyncio.TimeoutError:
                pass
        self._lane_servers = []
