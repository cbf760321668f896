#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: a `Node` with the cell's configuration, a
`Listener` on 127.0.0.1:0 (loopback TCP) and this harness. Two child
processes (`loadgen.py`, no JAX, no `emqx_tpu`) hold every subscriber
and every publisher connection. Set-up installs the subscriptions over
the wire and warms the cell's own classes; then the cell's traffic runs
for a lead-in and `--seconds` measured by the generators' stamps; then
the stream drains and every message sent is checked against the
configuration's guarantees. The last line of stdout is the result.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()          # process start, for `setup_s`

import argparse          # noqa: E402
import asyncio           # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402

import numpy as np       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (check as checker, controls, e2e,        # noqa: E402
                       manifest, populations, traffic_gen)
from benchmark.readers import read_metric, xplane               # noqa: E402

# counters that mean "served by the host instead, because something
# broke": a run that moves one of them is not a measurement
HIDDEN_FAULTS = ("routing.device.rebuild_failed",
                 "routing.mesh.rebuild_failed",
                 "routing.device.warm_failed",
                 "routing.device.supervised_bypass",
                 "routing.device.dispatch_failed",
                 "supervise.replays", "supervise.faults",
                 "pipeline.exchange.fallback.error")
TRACE_S = 3.0
WARM_TIMEOUT_S = 900.0   # for the background warm and its compiles
WARM_PUB = 0xFFFF        # publisher id of the set-up's direct device warm
# What set-up takes from the engine besides the served path. A name that
# is gone is a refusal, never a silent "idle" or a skipped warm.
ENGINE_SURFACE = ("_STD_CLASSES", "_fuse_warm_task", "stats",
                  "batch_class_warm", "max_fuse", "route_batch",
                  "prepare_window", "dispatch", "materialize", "finish_sub",
                  "abandon")


def say(*a) -> None:
    print(f"[{time.monotonic() - _T0:7.1f}s]", *a, flush=True)


class Refused(Exception):
    """The run cannot be a measurement; exit non-zero, no result line."""


class Child:
    """A generator process, spoken to in JSON lines."""

    def __init__(self, role: str):
        self.role = role
        self.proc = None

    async def start(self, port: int, cell, seed: int, out: str) -> dict:
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        self.proc = await asyncio.create_subprocess_exec(
            sys.executable, os.path.join(HERE, "loadgen.py"), self.role,
            "--port", str(port), "--config", cell.config_path,
            "--traffic", cell.traffic_path, "--seed", str(seed),
            "--out", out, stdin=asyncio.subprocess.PIPE,
            stdout=asyncio.subprocess.PIPE, env=env, limit=1 << 20)
        return await self.read(900)

    async def read(self, timeout: float) -> dict:
        line = await asyncio.wait_for(self.proc.stdout.readline(), timeout)
        if not line:
            rc = await self.proc.wait()
            raise Refused(f"generator {self.role} ended (exit {rc})")
        return json.loads(line)

    async def ask(self, timeout: float = 120, **cmd) -> dict:
        self.proc.stdin.write((json.dumps(cmd) + "\n").encode())
        await self.proc.stdin.drain()
        return await self.read(timeout)

    async def stop(self) -> None:
        p = self.proc
        if p is None or p.returncode is not None:
            return
        try:
            p.stdin.write(b'{"cmd": "quit"}\n')
            await p.stdin.drain()
            p.stdin.close()
            await asyncio.wait_for(p.wait(), 10)
        except (asyncio.TimeoutError, ConnectionError, OSError):
            try:
                p.kill()
            except ProcessLookupError:
                pass
            await p.wait()


def load_npz(path: str) -> dict:
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


async def wait_until(pred, timeout: float, step: float = 0.05) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        await asyncio.sleep(step)
    return pred()


async def sleep_until_ns(t_ns: int) -> None:
    while True:
        left = (t_ns - time.monotonic_ns()) / 1e9
        if left <= 0:
            return
        await asyncio.sleep(min(left, 0.5))


def engine_idle(eng) -> bool:
    """No background class warm or rebuild in flight."""
    return eng._fuse_warm_task is None and not eng.stats()["building"]


def annotate_engine(eng) -> None:
    """Traced runs only: the harness's own spans around its calls into
    the engine's stages, so idle gaps on the device can be named."""
    from jax.profiler import TraceAnnotation

    def wrap(name):
        fn = getattr(eng, name)

        def spanned(*a, **kw):
            with TraceAnnotation(f"{xplane.SPAN}{name}"):
                return fn(*a, **kw)
        setattr(eng, name, spanned)

    for name in ("prepare_window", "dispatch", "materialize", "finish_sub"):
        wrap(name)


class Run:
    def __init__(self, cell, args, node, device: dict):
        self.cell, self.args, self.node, self.device = cell, args, node, device
        self.eng = node.device_engine
        self.sub, self.pub = Child("sub"), Child("pub")
        self.split: dict = {"setup": {}}
        self.tmp = tempfile.mkdtemp(prefix="bench-")
        self.pop = populations.load(cell.config)
        self.ctx: dict = {"pop": self.pop}
        self.warm_expected = 0       # deliveries of the direct device warm
        self.gc2: list = []          # (end ns, ms) of gen-2 collections
        self._gc_t0 = 0

    def watch_gc(self) -> None:
        """Note the broker process's gen-2 collections: each stops the
        loop, and a tail reads differently with one in the window."""
        import gc

        def on_gc(phase, info):
            if phase == "start":
                self._gc_t0 = time.monotonic_ns()
            elif info.get("generation") == 2:
                now = time.monotonic_ns()
                self.gc2.append((now, (now - self._gc_t0) / 1e6))
        gc.callbacks.append(on_gc)

    def counters(self) -> dict:
        return dict(self.node.metrics.all())

    def telemetry(self) -> dict:
        return self.node.pipeline_telemetry.snapshot()

    async def settle(self, what: str, timeout: float = 120.0) -> None:
        """Everything sent so far has arrived and been acknowledged."""
        deadline = time.monotonic() + timeout
        while True:
            got = await self.sub.ask(cmd="count")
            st = await self.pub.ask(cmd="status")
            if got["received"] >= st["expected"] + self.warm_expected \
                    and st["acked"] >= st["qos1_sent"]:
                return
            if time.monotonic() > deadline:
                say(f"{what}: did not settle in {timeout}s: received "
                    f"{got['received']} of {st['expected']}, acked "
                    f"{st['acked']} of {st['qos1_sent']}")
                return
            await asyncio.sleep(0.05)

    async def set_up(self, port: int) -> None:
        cell, eng, setup = self.cell, self.eng, self.split["setup"]
        missing = [n for n in ENGINE_SURFACE if not hasattr(eng, n)]
        if not missing and "building" not in eng.stats():
            missing = ["stats()['building']"]
        if missing:
            raise Refused(f"the engine no longer has {missing}: set-up "
                          f"cannot tell warm from cold")
        t = time.monotonic()
        sub_ready = asyncio.ensure_future(
            self.sub.start(port, cell, self.args.seed, self.tmp))
        pub_ready = asyncio.ensure_future(
            self.pub.start(port, cell, self.args.seed, self.tmp))
        subscribed = await sub_ready
        await pub_ready
        setup["subscribe"] = time.monotonic() - t
        say(f"subscribed: {subscribed['subscriptions']} subscriptions over "
            f"{cell.config['connections']['subscribers']} connections in "
            f"{setup['subscribe']:.1f}s; {cell.traffic['connections']} "
            f"publisher connections up")

        # bursts over the wire reach the batcher, whose rebuild policy
        # captures, builds, uploads and warm-compiles in the background;
        # the host routes meanwhile (and those deliveries are checked)
        t = time.monotonic()
        std = tuple(eng._STD_CLASSES)
        top = max(bp for _w, bp in std)

        def std_warm() -> bool:
            return eng.batch_class_warm(top) and engine_idle(eng) and \
                (max(w for w, _b in std) == 1 or eng.max_fuse() > 1)

        while not std_warm():
            if time.monotonic() - t > WARM_TIMEOUT_S:
                raise Refused(f"standard classes not warm after "
                              f"{WARM_TIMEOUT_S}s")
            await self.pub.ask(cmd="burst", messages=64)
            await asyncio.sleep(0.5)
        setup["build_and_warm"] = time.monotonic() - t
        say(f"snapshot built and standard classes warm in "
            f"{setup['build_and_warm']:.1f}s")

        t = time.monotonic()
        self.device_warm(std)
        setup["device_warm"] = time.monotonic() - t

        # demand warm-up: rounds of the cell's own traffic register the
        # cached / compact classes it wants; wait out their compiles
        t = time.monotonic()
        warm = cell.traffic["warm"]

        def warm_state():
            """What a round may still change: executables built, and
            windows turned away from a class that was not warm yet."""
            m = self.node.metrics.all()
            by = self.telemetry()["compiles"]["by_shape"]
            return (sum(v["executables"] for v in by.values()),
                    sum(v for k, v in m.items()
                        if k.startswith("routing.device.cold_")))

        rounds = 0
        setup["demand_warm_round_s"] = took = []
        for rounds in range(1, int(warm["max_rounds"]) + 1):
            before = warm_state()
            t_round = time.monotonic()
            await self.pub.ask(cmd="run", t0_ns=time.monotonic_ns(),
                               seconds=float(warm["seconds"]), timeout=300)
            await self.settle("warm-up")
            t_settled = time.monotonic()
            if not await wait_until(lambda: engine_idle(eng),
                                    WARM_TIMEOUT_S):
                raise Refused(f"background warm still running after "
                              f"{WARM_TIMEOUT_S}s")
            # [traffic + drain, waiting out background compiles]
            took.append([round(t_settled - t_round, 1),
                         round(time.monotonic() - t_settled, 1)])
            # at least `rounds`, then until one passes with nothing
            # compiled and no window turned away cold
            if rounds >= int(warm["rounds"]) and warm_state() == before:
                break
        setup["demand_warm_rounds"] = rounds
        setup["demand_warm"] = time.monotonic() - t
        tele = self.telemetry()
        setup["rebuild_stages_ms"] = {
            k: round(v["mean_ms"] * v["count"], 1)
            for k, v in tele.get("rebuild", {}).get("stages", {}).items()}
        setup["compile_s"] = tele["compiles"]["total_s"]
        setup["executables"] = sum(
            v["executables"] for v in tele["compiles"]["by_shape"].values())

    def device_warm(self, std, reps: int = 2) -> None:
        """Drive each standard class through the engine's own calls
        (`route_batch`; `prepare_window` .. `finish_sub` as the batcher
        makes them) with the cell's topics, letting a cold class compile
        in the call (`gate_cold=False`). This is the one place set-up
        goes round the batcher, and it stands for a broker that has been
        up for a while: through the batcher alone the compact and fused
        variants come warm one chooser probe at a time (a probe every 64
        host windows), minutes that no run can pay, and a checkout's
        first run ended in another regime than its later ones (PERF.md,
        section 6). Nothing is pinned: in the window the chooser decides
        every window by its own measurements, and the split is printed.
        The messages are real and are delivered; the check leaves their
        publisher id out."""
        from emqx_tpu.broker.message import make
        eng, cfg = self.eng, self.cell.config
        pop = self.pop
        pub = cfg["publish"]
        every = int(pub.get("qos1_every", 0))
        pad = bytes(int(pub["payload_bytes"]) - 14)
        rng = traffic_gen.rng_for(self.args.seed, 2)
        seq = 0

        def batch(n):
            nonlocal seq
            keys = traffic_gen.draw_keys(rng, n, pop.dims, pub["keys"])
            self.warm_expected += populations.expected_count(pop, keys)
            out = []
            for k in keys:
                qos = 1 if every and seq % every == 0 else 0
                out.append(make("bench-warm", qos, pop.topic(int(k)),
                                WARM_PUB.to_bytes(2, "little")
                                + seq.to_bytes(4, "little") + bytes(8) + pad))
                seq += 1
            return out

        for w, bp in std:
            for _rep in range(reps):
                lives = [batch(bp) for _k in range(w)]
                if w == 1:
                    if eng.route_batch(lives[0]) is None:
                        raise Refused("the engine declined a direct batch")
                    continue
                h = eng.prepare_window(lives, gate_cold=False)
                if h is None:
                    raise Refused("the engine declined a fused window")
                try:
                    eng.dispatch(h)
                    eng.materialize(h)
                except Exception:
                    eng.abandon(h)
                    raise
                for k in range(w):
                    eng.finish_sub(h, k, defer=False)

    async def window(self) -> None:
        cell, args, ctx = self.cell, self.args, self.ctx
        lead = float(cell.traffic["lead_in_s"])
        t_go = time.monotonic_ns() + int(0.3e9)
        w0 = t_go + int(lead * 1e9)
        w1 = w0 + int(args.seconds * 1e9)
        running = asyncio.ensure_future(self.pub.ask(
            cmd="run", t0_ns=t_go, seconds=lead + args.seconds + 0.5,
            timeout=lead + args.seconds + 180))
        self.watch_gc()
        await sleep_until_ns(w0)
        self.setup_s = time.monotonic() - _T0
        ctx["m0"], ctx["tele0"] = self.counters(), self.telemetry()
        if args.trace:
            await self.trace(w0 + int(max(0.0, args.seconds - TRACE_S) / 2
                                      * 1e9))
        await sleep_until_ns(w1)
        ctx["m1"], ctx["tele1"] = self.counters(), self.telemetry()
        ctx["window"] = {"t0_ns": w0, "t1_ns": w1, "seconds": args.seconds}
        await running

    async def trace(self, start_ns: int) -> None:
        import jax
        loop = asyncio.get_running_loop()
        ctx = self.ctx
        tdir = os.path.join(self.tmp, "trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        await sleep_until_ns(start_ns)
        await loop.run_in_executor(
            None, lambda: jax.profiler.start_trace(tdir, profiler_options=opts))
        ctx["trace_m0"] = self.counters()
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            await asyncio.sleep(min(TRACE_S, self.args.seconds))
        ctx["trace_m1"] = self.counters()
        await loop.run_in_executor(None, jax.profiler.stop_trace)
        ctx["trace_dir"] = tdir

    async def reset_logs(self) -> None:
        """Between the segments of a control run: forget what was sent."""
        await asyncio.sleep(1.0)
        await self.pub.ask(cmd="reset")
        await self.sub.ask(cmd="reset")

    async def drain_and_check(self, settle_s: float = 180.0) -> dict:
        ctx = self.ctx
        await self.settle("drain", settle_s)
        await asyncio.sleep(0.3)          # a duplicate would arrive late
        pub_info = await self.pub.ask(cmd="dump", timeout=300)
        sub_info = await self.sub.ask(cmd="dump", timeout=600)
        pub, sub = load_npz(pub_info["path"]), load_npz(sub_info["path"])
        ctx["pub"], ctx["sub"] = pub, sub
        w = ctx["window"]
        w["publishes"] = e2e.publishes_in(pub, w["t0_ns"], w["t1_ns"])
        live = ~sub["dup"]
        w["deliveries"] = int(((sub["recv_ns"][live] >= w["t0_ns"])
                               & (sub["recv_ns"][live] < w["t1_ns"])).sum())
        # a change of regime inside the window shows here (PERF.md,
        # section 5); the last bin is what is left of the window
        per_5s, _ = np.histogram(sub["recv_ns"][live], bins=np.append(
            np.arange(w["t0_ns"], w["t1_ns"], int(5e9)), w["t1_ns"]))
        say(f"deliveries per 5 s of the window: {per_5s.tolist()}")
        t = time.monotonic()
        verdict = checker.check(self.pop, pub, sub, self.args.seed,
                                limits=self.cell.config.get("limits"))
        say(f"checked {verdict['attempted']} PUBLISHes, "
            f"{verdict['info']['deliveries']} deliveries in "
            f"{time.monotonic() - t:.1f}s: {json.dumps(verdict['info'])}")
        for name, (value, limit) in verdict["numbers"].items():
            say(f"compared {name} = {value} (limit {limit})")
        return verdict

    def hidden_faults(self) -> list:
        m = self.counters()
        names = list(HIDDEN_FAULTS) + [k for k in m
                                       if k.startswith("supervise.faults.")]
        bad = [f"{k} = {m[k]}" for k in names if m.get(k, 0)]
        sup = self.node.supervisor
        if sup is not None:
            bad += [f"breaker {s} is {b.state}"
                    for s, b in sup.breakers.items() if b.state != "closed"]
        return bad

    def print_split(self) -> None:
        c, w = self.ctx, self.ctx["window"]

        def d(name):
            return c["m1"].get(name, 0) - c["m0"].get(name, 0)

        split = {
            "publishes": w["publishes"], "deliveries": w["deliveries"],
            "node_deliveries": d("messages.delivered"),
            "device_routed_deliveries": d("messages.routed.device"),
            "device_windows": d("routing.device.batches"),
            # the cell's per-layer metrics that need the counters alone,
            # each through its own file: an untraced run then shows, for
            # one, which regime `share50-250k.flood` drew
            # (`fuse_depth.flood`; PERF.md, section 5)
            "by_counter": {m["name"]: round(read_metric(
                c, m["reader"], m["args"]), 4) for m in self.cell.per_layer
                if m["reader"] == "counter"},
            "bypassed": d("routing.device.bypassed"),
            "cold_class": d("routing.device.cold_class"),
            "batches": {k.rsplit(".", 1)[1]: d(k) for k in c["m1"]
                        if k.startswith("pipeline.batches.")},
            "overload": {k: d(k) for k in c["m1"]
                         if k.startswith("pipeline.overload.") and d(k)},
        }
        self.split["window"] = split
        say(f"chooser split of the window: {json.dumps(split)}")
        t0, t1 = c["tele0"].get("stages", {}), c["tele1"].get("stages", {})
        stages = {k: [v["count"] - t0.get(k, {}).get("count", 0),
                      round(v["sum_ms"] - t0.get(k, {}).get("sum_ms", 0), 1)]
                  for k, v in t1.items()}
        say(f"stage spans of the window [count, sum ms]: "
            f"{json.dumps(stages)}")
        moved = {k: d(k) for k in sorted(c["m1"]) if d(k) and k.startswith(
            ("routing.", "match_cache.", "pipeline.readback.",
             "pipeline.deliver.", "pipeline.slow", "delivery.dropped",
             "connection.", "messages.dropped"))}
        say(f"counters that moved in the window: {json.dumps(moved)}")
        say(f"gen-2 collections of the broker process, [s into the window, "
            f"ms]: {[[round((t - w['t0_ns']) / 1e9, 1), round(ms)] for t, ms in self.gc2]}")
        st = self.eng.stats()
        say(f"engine at the end: match_cache={json.dumps(st.get('match_cache'))} "
            f"payload_ewma={json.dumps(st.get('payload_ewma'))}")
        say(f"set-up split (s): {json.dumps(self.split['setup'])}")


async def drive(cell, args, node, device: dict) -> dict:
    from emqx_tpu.broker.connection import Listener
    run = Run(cell, args, node, device)
    lst = Listener(node, bind="127.0.0.1", port=0)
    await lst.start()
    try:
        await run.set_up(lst.port)
        if args.trace:
            annotate_engine(node.device_engine)
        if not args.control:
            await run.window()
            verdict = await run.drain_and_check()
        for k, name in enumerate(args.control):
            # a control run: one segment per control, none a measurement
            if k:
                await run.reset_logs()
            undo = controls.apply(name, node)
            try:
                await run.window()
                verdict = await run.drain_and_check(settle_s=10.0)
            finally:
                undo()
            say(f"control {name}: correct={verdict['correct']} "
                f"failed={verdict['failed']} of {verdict['attempted']}")
        run.print_split()
        bad = run.hidden_faults()
        if bad:
            raise Refused(f"served by the host instead: {bad}")
        return finish(run, verdict)
    finally:
        # the generators are stopped and waited for; the broker is not
        # unwound (closing 16 connections unsubscribes every filter on
        # the loop, seconds that every run would pay): `leave()` ends the
        # process once the result is out
        await run.sub.stop()
        await run.pub.stop()
        shutil.rmtree(run.tmp, ignore_errors=True)


def finish(run: Run, verdict: dict) -> dict:
    """The result line's object."""
    import jax
    cell, ctx, args = run.cell, run.ctx, run.args
    w = ctx["window"]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())
    device = dict(run.device, memory_peak_bytes=peak)
    metrics: dict = {}
    breakdown = None
    if not args.trace:
        for m in cell.end_to_end:
            value = run.setup_s if m["name"] == "setup_s" else \
                e2e.METRICS[m["name"]](ctx["pub"], ctx["sub"],
                                       w["t0_ns"], w["t1_ns"])
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        ctx["trace"] = xplane.from_file(xplane.find_xplane(ctx["trace_dir"]))
        red = ctx["trace_reduced"] = xplane.reduce(
            ctx["trace"], any_device=args.rehearse)
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)
        if run.device["kind"] not in peaks and not args.rehearse:
            raise Refused(f"no peaks for device kind "
                          f"{run.device['kind']!r} in peaks.json")
        ctx["peaks"] = peaks.get(run.device["kind"]) \
            or next(iter(peaks.values()))       # a rehearsal: any row
        ctx["engine_stats"] = run.eng.stats()
        ctx["setup_s"] = run.setup_s
        for m in cell.per_layer:
            value = read_metric(ctx, m["reader"], m["args"])
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
    out = {"correct": verdict["correct"], "attempted": verdict["attempted"],
           "failed": verdict["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if args.control:
        out["control"] = args.control
    out["split"] = run.split
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in verdict["numbers"].items()}
    return out


def build_native() -> None:
    """`native/libemqx_native.so` is git-ignored: build it if absent."""
    so = os.path.join(ROOT, "native", "libemqx_native.so")
    if not os.path.exists(so):
        r = subprocess.run(["make", "-C", os.path.join(ROOT, "native")],
                           capture_output=True, text=True, timeout=300)
        if r.returncode != 0:
            raise Refused(f"cannot build libemqx_native.so:\n"
                          f"{r.stdout}{r.stderr}")
    from emqx_tpu import native
    if not native.available():
        raise Refused("libemqx_native.so did not load")


def gate(args) -> None:
    """What must hold before JAX is touched."""
    knobs = sorted(k for k in os.environ if k.startswith("EMQX_TPU_"))
    if knobs:
        raise Refused(f"refusing to run with {knobs} set: the benchmark "
                      f"measures the configuration its files state")
    if not os.path.isdir(os.path.join(ROOT, "emqx_tpu")):
        raise Refused("emqx_tpu/ is not next to benchmark/: nothing to "
                      "measure here")
    plats = os.environ.get("JAX_PLATFORMS", "").lower()
    if not args.rehearse and plats and all(
            p.strip() in ("cpu", "") for p in plats.split(",")):
        raise Refused(f"JAX_PLATFORMS={plats!r} names no accelerator")


def apply_rehearsal(cell) -> None:
    """The CPU rehearsal's tiny sizes, from the files' own `rehearse`
    keys; for the benchmark's tests only."""
    r = cell.config.get("rehearse", {})
    cell.config["population"]["params"].update(r.get("population", {}))
    cell.traffic.update(cell.traffic.get("rehearse", {}))
    for name, obj in (("config", cell.config), ("traffic", cell.traffic)):
        path = os.path.join(cell.tmp_dir, name + ".json")
        with open(path, "w") as f:
            json.dump(obj, f)
        setattr(cell, name + "_path", path)


def open_node(args):
    """Gates, the cell's files, JAX on the chip, the native codec and a
    `Node` with the cell's configuration. Returns (cell, node, device,
    a function that counts the compile cache's entries)."""
    gate(args)
    cell = manifest.Cell(args.workload)
    if args.rehearse:
        cell.tmp_dir = tempfile.mkdtemp(prefix="bench-cfg-")
        apply_rehearsal(cell)
    from emqx_tpu.utils.compile_cache import (compile_cache_entries,
                                              configure_compile_cache)
    cache_dir = configure_compile_cache()
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and (device["platform"] != "tpu"
                              or len(devs) < cell.chips):
        raise Refused(f"cell {cell.name} needs {cell.chips} TPU "
                      f"chip(s); JAX found {device}")
    say(f"device: {json.dumps(device)} jax={jax.__version__}; compile "
        f"cache {cache_dir} ({compile_cache_entries(cache_dir)} entries "
        f"before)")
    build_native()
    from emqx_tpu.broker.node import Node
    return cell, Node(cell.config.get("node") or None), device, \
        lambda: compile_cache_entries(cache_dir)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU backend at a tiny size, for the benchmark's "
                         "own tests; prints no metric")
    ap.add_argument("--control", default="",
                    type=lambda x: [c for c in x.split(",") if c],
                    help="never part of a measurement: run with these "
                         "guarantees broken (benchmark/controls.py), one "
                         "segment each, to show `correct` come out false")
    args = ap.parse_args(argv)
    tmp_dir = None
    try:
        cell, node, device, cache = open_node(args)
        tmp_dir = getattr(cell, "tmp_dir", None)
        out = asyncio.new_event_loop().run_until_complete(
            drive(cell, args, node, device))
        say(f"compile cache: {cache()} entries after")
    except (Refused, manifest.ManifestError) as e:
        print(f"benchmark.run: {e}", file=sys.stderr)
        return 2
    finally:
        if tmp_dir:
            shutil.rmtree(tmp_dir, ignore_errors=True)
    if out["device"]["platform"] != "tpu":
        # a CPU rehearsal: nothing under a metric's name
        out["rehearsal_values"] = out.pop("metrics")
        out["metrics"] = {}
    print(json.dumps(out))
    sys.stdout.flush()
    for name, c in out["compared"].items():
        print(f"compared {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    return 0


def leave(rc: int) -> None:
    """End the process without unwinding the broker's connections (see
    `drive`); every child has been stopped and waited for by now."""
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)


if __name__ == "__main__":
    leave(main())
