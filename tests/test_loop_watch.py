"""The loop's own clock (ISSUE 40): `trace.LoopWatch` times the one
asyncio loop's waits, work and CPU at the loop's own `select()`, and
`Spans` reads the CPU clock beside the wall clock on the two stages that
run off the loop.

The arithmetic is held with a clock the test sets; the cases on a real
selector loop hold what a clock cannot fake: that the loop calls the
stand-in once a turn, that wait and work add up to the wall clock, and
that the loop gets its own selector back.
"""

import asyncio
import selectors
import socket
import threading
import time

import pytest

from emqx_tpu.broker import trace as T
from emqx_tpu.broker.metrics import Metrics
from emqx_tpu.broker.node import Node
from emqx_tpu.broker.telemetry import PipelineTelemetry


def run(coro, timeout=60):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()


def _watch():
    m = Metrics()
    return T.LoopWatch(m, T.Spans(None, None)), m


def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _burn(cpu_seconds: float) -> None:
    """Spin until this thread has had that much of a core."""
    end = time.thread_time() + cpu_seconds
    while time.thread_time() < end:
        pass


def _loop_counters(m) -> dict:
    return {k.rsplit(".", 1)[1]: v for k, v in m.all().items()
            if k.startswith("runtime.loop.")}


# ------------------------------------------------- on a real selector loop

def test_wait_and_work_add_up_to_the_wall_clock():
    """A sleep is wait, a spinning callback is work (the polls between
    two of them too), and between them they are the wall clock within
    2 %; the work's CPU never passes its wall time, and a turn over
    10 ms is a long one."""
    w, m = _watch()

    async def go():
        w.start()
        t0 = time.perf_counter()
        await asyncio.sleep(0.15)               # the loop waits
        _spin(0.06)                             # one long turn
        for _ in range(20):
            await asyncio.sleep(0)              # short turns, polls
            _spin(0.001)
        await asyncio.sleep(0.05)
        wall = time.perf_counter() - t0
        st = w.state()
        w.stop()
        return wall, st
    wall, st = run(go())
    assert st["on"] is True and "why" not in st
    assert (st["wait_us"] + st["busy_us"]) / 1e6 == \
        pytest.approx(wall, rel=0.02)
    # (lower bounds: a loaded machine stretches both, never shrinks them)
    assert 0.195 <= st["wait_us"] / 1e6 and 0.078 <= st["busy_us"] / 1e6
    assert 0 < st["cpu_us"] <= st["busy_us"]
    assert st["long_turns"] >= 1 and st["longest_turn_us"] >= 60_000
    assert st["turns"] >= 22 and st["polls"] >= 15
    # the node's counters hold what the state says
    assert _loop_counters(m) == {k: st[k] for k in (
        "turns", "wait_us", "busy_us", "cpu_us", "long_turns")}


def test_start_and_stop_are_counted_and_the_selector_comes_back():
    w, m = _watch()

    async def go():
        loop = asyncio.get_running_loop()
        own = loop._selector
        w.start()                       # a listener
        proxy = loop._selector
        assert isinstance(proxy, T._TimedSelector) and proxy._sel is own
        w.start()                       # the housekeeping timer
        assert loop._selector is proxy  # wrapped once
        await asyncio.sleep(0.01)
        w.stop()
        assert loop._selector is proxy and w.state()["on"]
        w.stop()
        assert loop._selector is own
        turns = w.state()["turns"]
        await asyncio.sleep(0.01)       # nothing is timed any more
        assert w.state()["turns"] == turns and turns >= 1
        w.stop()                        # one stop too many is nothing
        assert w.state() == dict(w.state(), on=False, users=0,
                                 why="stopped")
        # the loop still reads through its own selector
        a, b = socket.socketpair()
        got = loop.create_future()
        loop.add_reader(a, got.set_result, True)
        b.send(b"x")
        assert await got
        loop.remove_reader(a)
        a.close()
        b.close()
    run(go())
    assert m.val("runtime.loop.turns") >= 1


def test_two_nodes_watch_one_loop_and_leave_in_any_order():
    (a, ma), (b, mb) = _watch(), _watch()

    async def go():
        loop = asyncio.get_running_loop()
        own = loop._selector
        a.start()
        b.start()                       # b's stand-in holds a's
        await asyncio.sleep(0.01)
        a.stop()                        # the inner one leaves first
        assert loop._selector._sel is own
        await asyncio.sleep(0.01)
        assert b.state()["on"]
        b.stop()
        assert loop._selector is own
    run(go())
    assert 1 <= ma.val("runtime.loop.turns") < mb.val("runtime.loop.turns")


def test_the_watch_moves_to_the_loop_that_runs():
    """Tests run several loops against one node: a start on another
    loop than the watched one (which may be closed) wraps that one."""
    w, m = _watch()

    async def first():
        w.start()
        await asyncio.sleep(0.01)
    run(first())                        # its loop is closed by now

    async def second():
        loop = asyncio.get_running_loop()
        own = loop._selector
        w.start()
        assert loop._selector._sel is own and w.state()["on"]
        await asyncio.sleep(0.01)
        w.stop()
        w.stop()
        assert loop._selector is own
    run(second())
    assert m.val("runtime.loop.turns") >= 2


def test_a_loop_without_a_selector_leaves_the_watch_off(monkeypatch):
    """uvloop and the proactor loop have no `_selector`: the watch
    stays off, counts nothing, raises nothing and says why."""
    w, m = _watch()

    class Uvloopish:
        pass

    monkeypatch.setattr(T.asyncio, "get_running_loop", Uvloopish)
    w.start()
    st = w.state()
    assert st["on"] is False and st["users"] == 1
    assert st["why"] == "Uvloopish has no selector to wrap"
    assert st["turns"] == 0 and _loop_counters(m) == {}
    w.stop()
    assert w.state()["why"] == "stopped"

    def no_loop():
        raise RuntimeError("no running event loop")
    monkeypatch.setattr(T.asyncio, "get_running_loop", no_loop)
    w.start()
    assert w.state()["why"] == "no running loop"
    w.stop()


# --------------------------------------------------- with a clock we set

class FakeClock:
    """`time` as `trace.py` uses it: a wall clock and a thread CPU clock
    that move only when the test says so."""

    def __init__(self):
        self.wall = self.cpu = 1_000_000_000

    def perf_counter_ns(self):
        return self.wall

    def thread_time_ns(self):
        return self.cpu

    def perf_counter(self):
        return self.wall / 1e9

    def work(self, wall_us, cpu_us=None):
        self.wall += wall_us * 1000
        self.cpu += (wall_us if cpu_us is None else cpu_us) * 1000


class FakeSelector(selectors.DefaultSelector):
    """A selector whose `select` takes the time the test gives it."""

    def __init__(self, clock):
        super().__init__()
        self.clock, self.takes_us, self.timeouts = clock, 0, []

    def select(self, timeout=None):
        self.timeouts.append(timeout)
        self.clock.work(self.takes_us, 0)
        return []


@pytest.fixture
def faked(monkeypatch):
    clock = FakeClock()
    monkeypatch.setattr(T, "time", clock)
    w, m = _watch()
    sel = FakeSelector(clock)

    class Loop:
        _selector = sel
    loop = Loop()
    monkeypatch.setattr(T.asyncio, "get_running_loop", lambda: loop)
    w.start()
    return clock, w, m, sel, loop


def test_a_thread_that_takes_the_core_shows_as_busy_less_cpu(faked):
    clock, w, m, sel, loop = faked
    select = loop._selector.select
    clock.work(400)                     # 400 us of callbacks, all on core
    sel.takes_us = 1_000
    select(0.5)                         # 1 ms asleep in select
    clock.work(2_000, cpu_us=800)       # 2 ms runnable, 0.8 ms running
    sel.takes_us = 3
    select(0)                           # a poll: the loop's own work
    clock.work(12_000, cpu_us=12_000)   # a long turn
    select(None)
    st = w.state()
    assert st["turns"] == 3 and st["polls"] == 1
    assert st["busy_us"] == 400 + 2_000 + 3 + 12_000
    assert st["cpu_us"] == 400 + 800 + 12_000
    assert st["busy_us"] - st["cpu_us"] == 1_203    # the GIL's other holders
    assert st["wait_us"] == 1_000 + 3               # the poll is not in it
    assert st["long_turns"] == 1 and st["longest_turn_us"] == 12_003
    assert sel.timeouts == [0.5, 0, None]
    assert _loop_counters(m) == {"turns": 3, "wait_us": 1_003,
                                 "busy_us": 14_403, "cpu_us": 13_200,
                                 "long_turns": 1}


def test_the_sums_reach_the_counters_within_a_flush(faked):
    """The watch adds up on itself and flushes every 50 ms of its own
    clock and on every read; whole microseconds, the rest kept."""
    clock, w, m, sel, loop = faked
    select = loop._selector.select
    sel.takes_us = 0
    for _ in range(10):
        clock.wall += 1_500             # 1.5 us a turn
        clock.cpu += 1_500
        select(0)
    assert m.val("runtime.loop.turns") == 0         # not flushed yet
    clock.work(60_000)
    select(0)                                       # past the 50 ms
    assert m.val("runtime.loop.turns") == 11
    assert m.val("runtime.loop.busy_us") == 60_015  # 10 x 1.5 us whole
    select(0)
    assert m.val("runtime.loop.turns") == 11
    w.flush()
    assert m.val("runtime.loop.turns") == 12


def test_only_a_select_that_may_block_is_a_span(faked, monkeypatch):
    clock, w, m, sel, loop = faked
    seen = []

    class Ann:
        def __exit__(self, *a):
            seen.append("left")

    def annotate(label, **kw):
        seen.append(label)
        return Ann()
    monkeypatch.setattr(w.spans, "annotate", annotate)
    select = loop._selector.select
    select(0)
    select(-1)                          # a timer already due
    assert seen == []
    select(None)
    select(0.25)
    assert seen == ["emqx:loop_wait", "left"] * 2
    # and the first select after a flush, so that a loop that only
    # polls still marks its line twenty times a second
    w.flush()
    select(0)
    select(0)
    assert seen == ["emqx:loop_wait", "left"] * 3
    assert w.state()["polls"] == 3


def test_the_gauge_is_the_longest_turn_since_the_last_sample(faked):
    clock, w, m, sel, loop = faked
    select = loop._selector.select

    class Stats:
        def setstat(self, name, val):
            self.got = (name, val)
    stats = Stats()
    clock.work(700)
    select(0)
    clock.work(300)
    select(0)
    w.stats_fun(stats)
    assert stats.got == ("runtime.loop.longest_turn_us", 700)
    clock.work(200)
    select(0)
    w.stats_fun(stats)
    assert stats.got == ("runtime.loop.longest_turn_us", 200)
    assert w.state()["longest_turn_us"] == 700      # since the start


def test_everything_else_is_the_selectors_own(faked):
    clock, w, m, sel, loop = faked
    a, b = socket.socketpair()
    try:
        key = loop._selector.register(a, selectors.EVENT_READ, "data")
        assert sel.get_key(a) is key and loop._selector.get_key(a) is key
        assert loop._selector.get_map() is sel.get_map()
        loop._selector.unregister(a)
        assert loop._selector.clock is clock        # through __getattr__
    finally:
        a.close()
        b.close()
    w.stop()
    assert loop._selector is sel
    sel.close()


# ------------------------------------------------------------- on a node

def test_a_node_times_its_loop_from_first_listener_to_last():
    from emqx_tpu.broker.connection import Listener
    node = Node({"broker": {"deliver_lanes": 2}})

    async def go():
        loop = asyncio.get_running_loop()
        own = loop._selector
        lst = Listener(node, bind="127.0.0.1", port=0)
        await lst.start()
        node.start_timers(30.0)
        assert isinstance(loop._selector, T._TimedSelector)
        await asyncio.sleep(0.05)
        snap = node.pipeline_telemetry.snapshot()
        node.stats.sample()
        node.stop_timers()
        assert isinstance(loop._selector, T._TimedSelector)
        await lst.stop()
        assert loop._selector is own
        return snap
    snap = run(go())
    lp = snap["runtime"]["loop"]
    assert lp["on"] and lp["users"] == 2 and lp["turns"] >= 1
    assert lp["wait_us"] >= 40_000
    assert node.metrics.val("runtime.loop.wait_us") >= 40_000
    assert node.stats.getstat("runtime.loop.longest_turn_us") > 0
    assert node.loop_watch.state()["why"] == "stopped"


# ------------------------------------- CPU beside wall on the two stages

def test_dispatch_and_materialize_read_their_threads_cpu():
    tele = PipelineTelemetry(track_compiles=False)
    spans = T.Spans(tele, None)
    m = tele.metrics

    def stage(name, spin_s, sleep_s):
        with spans.span(name, track=name):
            _burn(spin_s)
            time.sleep(sleep_s)         # as a thread waiting for the chip
    t = threading.Thread(target=stage, args=("dispatch", 0.03, 0.05))
    t.start()
    t.join()
    stage("materialize", 0.02, 0.0)
    with spans.span("finish_sub", stage="deliver"):
        _burn(0.01)
    with spans.span("dispatch", stage="dispatch_cached"):
        pass
    d, r = m.val("runtime.dispatch.cpu_us"), m.val("runtime.readback.cpu_us")
    assert 30_000 <= d <= 40_000        # the spin, not the 50 ms asleep
    assert 20_000 <= r <= 30_000
    # no other stage reads the CPU clock
    assert {k for k in m.all() if k.endswith(".cpu_us")} == {
        "runtime.dispatch.cpu_us", "runtime.readback.cpu_us"}
    wall = tele.snapshot()["stages"]["dispatch"]["sum_ms"]
    assert wall >= 75                   # the histogram still has the wall


def test_a_span_without_metrics_reads_no_cpu_clock():
    spans = T.Spans(None, None)
    with spans.span("dispatch") as sp:
        pass
    assert sp.dur >= 0 and spans.metrics is None


def test_selectors_module_is_what_asyncio_wraps():
    """The stand-in leans on one private name of asyncio's selector
    loop; this holds it to the interpreter the tests run on."""
    loop = asyncio.new_event_loop()
    try:
        assert isinstance(loop._selector, selectors.BaseSelector)
    finally:
        loop.close()
