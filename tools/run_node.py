#!/usr/bin/env python
"""Boot one broker node as an OS process (the two-node deployment shape
the reference exercises with scripts/start-two-nodes-in-docker.sh).

Usage:
    python tools/run_node.py --name a@127.0.0.1 [--config etc/emqx.conf]
        [--mqtt-port 0] [--rpc-port 0] [--join host:port] [--no-device]

Prints one `READY <mqtt_port> <rpc_port>` line on stdout once serving,
then runs until SIGTERM/SIGINT. A test harness (or an operator) parses
that line to wire clients and cluster joins.
"""

import argparse
import asyncio
import faulthandler
import os
import signal
import sys

# SIGUSR1 dumps every thread's stack to stderr — the first tool to reach
# for when a node stops answering (a wedged loop can't be introspected
# any other way from outside)
faulthandler.register(signal.SIGUSR1)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


async def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--name", default="emqx_tpu@127.0.0.1")
    ap.add_argument("--config", default=None)
    ap.add_argument("--mqtt-port", type=int, default=0)
    ap.add_argument("--rpc-port", type=int, default=0)
    ap.add_argument("--join", default=None, help="seed node host:port")
    ap.add_argument("--no-device", action="store_true")
    args = ap.parse_args()

    from emqx_tpu.broker.connection import Listener
    from emqx_tpu.broker.node import Node
    from emqx_tpu.cluster import ClusterNode
    from emqx_tpu.utils.compile_cache import configure_compile_cache

    if not args.no_device:
        configure_compile_cache()

    join_addr = None
    if args.join:
        host, sep, port = args.join.rpartition(":")
        if not sep or not host or not port.isdigit():
            ap.error(f"--join expects host:port, got {args.join!r}")
        join_addr = (host, int(port))

    kw = {"use_device": False} if args.no_device else {}
    if args.config:
        if args.mqtt_port:
            ap.error("--mqtt-port has no effect with --config "
                     "(set the port in the config's listeners block)")
        node = Node.from_config_file(args.config, name=args.name, **kw)
        listeners = await node.start_listeners()
        # advertise the first plain MQTT TCP listener (a ws/quic port
        # would mislead a TCP harness)
        tcp = [lst for lst in listeners if isinstance(lst, Listener)]
        mqtt_port = tcp[0].port if tcp else 0
    else:
        node = Node(name=args.name, **kw)
        lst = Listener(node, bind="127.0.0.1", port=args.mqtt_port)
        await lst.start()
        node.listeners.append(lst)
        mqtt_port = lst.port

    rpc_conf = node.config.get("rpc") or {}
    cluster_conf = node.config.get("cluster") or {}
    cn = ClusterNode(node, port=args.rpc_port,
                     cookie=cluster_conf.get("cookie",
                                             "emqxsecretcookie"),
                     rpc_mode=rpc_conf.get("mode", "async"))
    if rpc_conf.get("tcp_client_num"):
        cn.rpc.n_channels = int(rpc_conf["tcp_client_num"])
    await cn.start()
    if join_addr:
        await cn.join(*join_addr)
    elif cluster_conf.get("discovery", "manual") != "manual":
        # config-driven autocluster (static/dns/etcd/k8s/mcast seeds)
        from emqx_tpu.cluster.discovery import autocluster
        await autocluster(cn)

    node.start_timers()
    if args.config:
        # config-driven feature apps + mgmt REST + dashboard + gateways
        # (after cluster start so the API sees the cluster view)
        await node.start_apps()
        await node.start_dashboard()
        await node.start_gateways()
    print(f"READY {mqtt_port} {cn.address[1]}", flush=True)

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, stop.set)

    def dump_tasks():
        print(f"=== {len(asyncio.all_tasks(loop))} tasks ===",
              file=sys.stderr)
        for t in asyncio.all_tasks(loop):
            print(f"--- {t.get_name()}", file=sys.stderr)
            # walk the await chain (get_stack only shows the outer frame)
            obj = t.get_coro()
            depth = 0
            while obj is not None and depth < 40:
                fr = getattr(obj, "cr_frame", None) or \
                    getattr(obj, "gi_frame", None)
                if fr is not None:
                    print(f"    {fr.f_code.co_filename}:{fr.f_lineno} "
                          f"{fr.f_code.co_name}", file=sys.stderr)
                nxt = getattr(obj, "cr_await", None) or \
                    getattr(obj, "gi_yieldfrom", None)
                if nxt is None:
                    print(f"    -> awaiting {obj!r}"
                          if fr is None else f"    -> leaf {obj!r}",
                          file=sys.stderr)
                obj = nxt
                depth += 1
        sys.stderr.flush()

    # SIGUSR2 dumps every asyncio task's await stack (faulthandler's
    # SIGUSR1 shows threads, but a PARKED coroutine is invisible there)
    loop.add_signal_handler(signal.SIGUSR2, dump_tasks)
    await stop.wait()
    await cn.stop()
    await node.stop_listeners()


if __name__ == "__main__":
    asyncio.run(main())
