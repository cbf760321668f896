#!/usr/bin/env python
"""Sharded (multichip) serving bench at realistic scale — VERDICT r4 #6.

Boots a node in multichip SERVING mode over an 8-device virtual CPU mesh
(dp×route — correctness/scale proof; the chip bench measures raw speed)
and drives it through:

  1. full build of a >=100k-filter table (per-shard compile + stack +
     mesh placement), timed;
  2. a route_batch flood through the mesh step, with a host-router
     oracle spot-check on every batch's counts;
  3. churn WHILE serving: subscribe/unsubscribe bursts between batches —
     each burst dirties shards, the per-shard update path
     (parallel.sharded.update_shard) applies synchronously-before-serve;
  4. a shard OUTGROWING its capacity class mid-flood: a fan-out burst
     onto one filter blows the 'subs' class, kicking the background
     full rebuild; serving continues (host-side) during the rebuild and
     returns to the mesh after the swap — delivery counts stay correct
     throughout.

Prints ONE JSON line. This is a CPU tool: it forces a virtual CPU mesh,
run standalone or as bench.py's `cpu_sharded` child. It is a correctness
and scale proof, not evidence for a mesh of real chips (that is
`chip_smoke.py --mesh`). Reference analog: route replication + dispatch at scale,
emqx_router.erl:77-86, emqx_broker.erl:199-308.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# force the virtual CPU mesh BEFORE jax loads (same dance as
# __graft_entry__.dryrun_multichip)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
N_DEV = int(os.environ.get("BENCH_SHARDED_DEVICES", 8))
flag = "--xla_force_host_platform_device_count"
if flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = \
        f"{os.environ.get('XLA_FLAGS', '')} {flag}={N_DEV}".strip()


def log(*a):
    print(*a, file=sys.stderr, flush=True)


class Cap:
    __slots__ = ("n",)

    def __init__(self):
        self.n = 0

    def deliver(self, tf, msg):
        self.n += 1
        return True


class _Flat:
    """Flatten a future-of-future (dispatch stage returning the
    materialize future) into one result() — the flood's settle point."""

    __slots__ = ("fut",)

    def __init__(self, fut):
        self.fut = fut

    def result(self):
        return self.fut.result().result()


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")
    t_start = time.time()
    n_filters = int(os.environ.get("BENCH_SHARDED_FILTERS", 100_000))
    B = int(os.environ.get("BENCH_SHARDED_BATCH", 128))

    from emqx_tpu.broker.message import make
    from emqx_tpu.broker.node import Node

    node = Node({"broker": {"multichip": {
        "enable": True, "devices": N_DEV, "dp": 2,
        "max_batch": B}}})
    broker = node.broker
    eng = node.device_engine
    out = {"devices": N_DEV, "mesh": {"dp": eng.n_dp,
                                      "route": eng.n_route},
           "filters": n_filters, "batch": B}

    # ---- 1. population + full build ---------------------------------
    ids = max(8, int(n_filters ** 0.5))
    nums = max(1, n_filters // ids)
    caps = []
    t0 = time.time()
    for i in range(ids):
        for n in range(nums):
            c = Cap()
            caps.append(c)
            broker.subscribe(broker.register(c, f"s{i}-{n}"),
                             f"dev/d{i}/+/n{n}/#")
    out["subscribe_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    eng.rebuild()
    out["build_s"] = round(time.time() - t0, 2)
    st = eng.stats()
    out["built_filters"] = st["filters"]
    out["caps"] = st["caps"]
    log(f"built {st['filters']} filters over {eng.n_route} shards "
        f"in {out['build_s']}s (caps {st['caps']})")

    # ---- 2. flood with oracle spot-checks ----------------------------
    # ISSUE 9: the flood runs the PIPELINED dispatch loop the serving
    # path now uses — dispatch runs on its own thread and materialize
    # on another (the batcher's dispatch-pool/read-pool split), with up
    # to EMQX_TPU_DISPATCH_DEPTH windows in flight; settle order stays
    # FIFO and every batch's counts are still oracle-checked.
    # EMQX_TPU_DISPATCH_DEPTH=1 restores the synchronous
    # prepare→dispatch→materialize→finish round-trip exactly.
    import numpy as np
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    from emqx_tpu.broker.batcher import resolve_dispatch_depth
    depth = resolve_dispatch_depth(None)
    n_batches = int(os.environ.get("BENCH_SHARDED_BATCHES", 40))
    # mesh warm/ready before the timed window (route_batch wait=True
    # used to do this implicitly on the first flood batch)
    eng.route_batch([make("p", 0, "dev/d0/x/n0/t", b"x")] * B,
                    wait=True)
    # exchange stage (ISSUE 15): warm the segment-capacity class BEFORE
    # any timed window, letting the EWMA ladder adapt (a cold or
    # undersized class gathers — that would be the OLD path wearing the
    # new name). Adaptation routes use FLOOD-SHAPED traffic: a
    # degenerate warm batch (one hot topic) would teach the EWMA an
    # everything-to-one-dest peak and oversize the landed plans.
    metrics = node.metrics
    if eng.device_exchange:
        wrng = np.random.RandomState(7)
        for _ in range(5):
            before = metrics.val("pipeline.exchange.windows")
            eng.warm_exchange(B)
            wm = [make("p", 0, f"dev/d{i}/x/n{n}/t", b"x")
                  for i, n in zip(wrng.randint(0, ids, B),
                                  wrng.randint(0, nums, B))]
            eng.route_batch(wm, wait=True)
            if metrics.val("pipeline.exchange.windows") > before:
                break
        log(f"exchange warm: classes {sorted(eng._exch_warm)} "
            f"ewma {eng._exch_ewma}")

    def run_flood(n_batches, seed=11):
        """Pipelined oracle-checked flood; returns (msgs, wall_s)."""
        rng = np.random.RandomState(seed)
        disp_pool = ThreadPoolExecutor(1,
                                       thread_name_prefix="bench-disp")
        read_pool = ThreadPoolExecutor(1,
                                       thread_name_prefix="bench-read")
        t0 = time.time()
        routed = 0
        inflight: deque = deque()

        def settle(rec):
            nonlocal routed
            bi, h, mat_fut = rec
            mat_fut.result()
            counts = eng.finish(h)
            assert counts == [1] * B, f"batch {bi}: {counts[:8]}..."
            routed += B

        try:
            for bi in range(n_batches):
                i_ = rng.randint(0, ids, B)
                n_ = rng.randint(0, nums, B)
                msgs = [make("p", 0, f"dev/d{i}/x/n{n}/t", b"x")
                        for i, n in zip(i_, n_)]
                while len(inflight) >= depth:
                    settle(inflight.popleft())
                h = eng.prepare(msgs)
                assert h is not None, f"mesh stood down at batch {bi}"

                def stages(h=h):
                    eng.dispatch(h)
                    return read_pool.submit(eng.materialize, h)

                # dispatch(W+1) launches while materialize(W)/finish(W)
                # run
                dfut = disp_pool.submit(stages)
                inflight.append((bi, h, _Flat(dfut)))
            while inflight:
                settle(inflight.popleft())
            dt = time.time() - t0
        finally:
            disp_pool.shutdown(wait=True)
            read_pool.shutdown(wait=True)
        return routed, dt

    def _landed_snapshot():
        return {k: metrics.val(k) for k in (
            "pipeline.exchange.windows",
            "pipeline.exchange.host_landed_bytes",
            "pipeline.readback.windows.compact",
            "pipeline.readback.bytes.compact",
            "pipeline.readback.windows.dense",
            "pipeline.readback.bytes.dense")}

    def _landed_per_window(before, after):
        d = {k: after[k] - before[k] for k in before}
        xw = d["pipeline.exchange.windows"]
        gw = d["pipeline.readback.windows.compact"] \
            + d["pipeline.readback.windows.dense"]
        gb = d["pipeline.readback.bytes.compact"] \
            + d["pipeline.readback.bytes.dense"]
        xb = d["pipeline.exchange.host_landed_bytes"]
        total_w = xw + gw
        return {
            "windows_exchange": xw, "windows_gather": gw,
            "host_landed_bytes_per_window":
                round((xb + gb) / total_w) if total_w else None,
        }

    routed, dt = run_flood(n_batches)
    out["flood"] = {"msgs": routed, "per_s": round(routed / dt),
                    "wall_s": round(dt, 2),
                    "dispatch_depth": depth}
    log(f"flood: {routed} msgs in {dt:.1f}s = {routed / dt:.0f}/s "
        f"(depth {depth})")

    # ---- 2b. exchange twin row (ISSUE 15 satellite) ------------------
    # host-landed bytes/window + flood msgs/s, exchange on vs off, on
    # the SAME node/state. The flood above ran with the resolved knob
    # (default on); the twin re-floods with the stage forced off — the
    # host gather/merge baseline. EXCHANGE_BATCHES sizes the twin
    # floods (resume-signature relevant, like every EXCHANGE_* knob).
    if eng.device_exchange and \
            os.environ.get("BENCH_SHARDED_EXCHANGE", "1") != "0":
        n_tw = int(os.environ.get("EXCHANGE_BATCHES", n_batches))
        # the twin MUST compare identical traffic: both rows re-flood
        # with the same seed (the main flood above used seed 11 and
        # serves as the headline row, not the A/B)
        s0 = _landed_snapshot()
        r_on, dt_on = run_flood(n_tw, seed=13)
        on_row = dict(_landed_per_window(s0, _landed_snapshot()),
                      per_s=round(r_on / dt_on))
        eng.device_exchange = False      # twin: host gather/merge
        # warm the CSR compact payload class so the baseline is the
        # established SHARDED_r05 gather path, not cold dense windows
        wrng = np.random.RandomState(5)
        for _ in range(100):
            before = metrics.val("pipeline.readback.windows.compact")
            wm = [make("p", 0, f"dev/d{i}/x/n{n}/t", b"x")
                  for i, n in zip(wrng.randint(0, ids, B),
                                  wrng.randint(0, nums, B))]
            eng.route_batch(wm, wait=True)
            if metrics.val("pipeline.readback.windows.compact") \
                    > before:
                break
            time.sleep(0.05)
        s0 = _landed_snapshot()
        r_off, dt_off = run_flood(n_tw, seed=13)
        off_row = dict(_landed_per_window(s0, _landed_snapshot()),
                       per_s=round(r_off / dt_off))
        eng.device_exchange = True
        row = {"on": on_row, "off": off_row}
        lb_on = on_row["host_landed_bytes_per_window"]
        lb_off = off_row["host_landed_bytes_per_window"]
        if lb_on and lb_off:
            row["landed_reduction"] = round(lb_off / lb_on, 2)
        if off_row["per_s"]:
            row["flood_speedup"] = round(on_row["per_s"]
                                         / off_row["per_s"], 2)
        out["exchange"] = row
        log(f"exchange twin: landed/window on={lb_on} off={lb_off} "
            f"reduction={row.get('landed_reduction')} "
            f"speedup={row.get('flood_speedup')}")

    # ---- 3. churn while serving --------------------------------------
    t0 = time.time()
    churn_caps = []
    updates = 0
    for round_i in range(10):
        # subscribe burst (dirties shards)
        for k in range(32):
            c = Cap()
            churn_caps.append(c)
            broker.subscribe(
                broker.register(c, f"ch{round_i}-{k}"),
                f"churn/r{round_i}/k{k}/+")
        assert eng.dirty_shards
        updates += len(eng.dirty_shards)
        # serve: the dirty shards update synchronously-before-serve
        msgs = [make("p", 0, f"churn/r{round_i}/k{k}/z", b"y")
                for k in range(min(32, B))]
        counts = eng.route_batch(msgs, wait=True)
        assert counts == [1] * len(msgs), counts[:8]
        assert not eng.dirty_shards
        # unsubscribe burst
        if round_i % 2:
            for k, c in enumerate(churn_caps[-32:]):
                pass   # keep them; deletes covered by device tests
    out["churn"] = {"rounds": 10, "shard_updates": updates,
                    "wall_s": round(time.time() - t0, 2)}
    log(f"churn: {updates} shard updates while serving, "
        f"{out['churn']['wall_s']}s")

    # ---- 4. capacity overflow mid-flood ------------------------------
    # blow ONE shard's 'slots' class with shared groups on a hot filter:
    # poll_rebuild sees the shard no longer fits, kicks the BACKGROUND
    # full rebuild, and serving continues host-side until the swap
    t0 = time.time()
    caps_before = dict(eng._caps)
    n_groups = int(caps_before["slots"]) + 2
    grow = []
    for k in range(n_groups):
        c = Cap()
        grow.append(c)
        broker.subscribe(broker.register(c, f"g{k}"),
                         f"$share/g{k}/grow/hot/topic")
    per_msg = n_groups          # one pick per group
    host_served = 0
    mesh_served = 0
    deadline = time.time() + 120
    while time.time() < deadline:
        msgs = [make("p", 0, "grow/hot/topic", b"z")]
        counts = eng.route_batch(msgs)
        if counts is None:
            # mesh rebuilding: the production path routes host-side
            broker._route(msgs[0], broker.router.match(msgs[0].topic))
            host_served += 1
            time.sleep(0.01)
        else:
            assert counts == [per_msg], counts
            mesh_served += 1
            if eng._caps["slots"] > caps_before["slots"] \
                    and mesh_served >= 3:
                break
    assert eng._caps["slots"] > caps_before["slots"], \
        (caps_before, eng._caps)
    got = sum(c.n for c in grow)
    want = (host_served + mesh_served) * per_msg
    assert got == want, \
        f"deliveries lost across the capacity rebuild: {got} != {want}"
    out["overflow"] = {
        "slots_cap": [caps_before["slots"], eng._caps["slots"]],
        "host_served_during_rebuild": host_served,
        "mesh_served_after": mesh_served,
        "wall_s": round(time.time() - t0, 2),
    }
    log(f"overflow: slots cap {caps_before['slots']} -> "
        f"{eng._caps['slots']}, {host_served} host-served during "
        f"rebuild, mesh resumed ({mesh_served})")

    out["total_wall_s"] = round(time.time() - t_start)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
