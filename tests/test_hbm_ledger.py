"""Device-resource observatory (ISSUE 8).

Coverage, per the issue's tentpole + satellite list:

- knob resolution (`broker.hbm_ledger` / `EMQX_TPU_HBM_LEDGER` config
  beats env beats default-on; `EMQX_TPU_PIN_WARN_WINDOWS` validation)
- ledger unit lifecycle: hold/weakref-release, aliased-leaf dedup,
  peak watermarks, owner accounting, non-weakrefable leaf skip
- reconciliation: ledger-accounted bytes == summed `.nbytes` of the
  live held pytrees within 1% (live engine AND tools/hbm_report.py
  measure points)
- snapshot swap + overlay lifecycle: bytes return to baseline after a
  rebuild, no weakref leaks (live_leaves returns to the live set)
- the pin sentinel: counter + `pipeline.pin_stale` hook + `stale_pin`
  flight-recorder event after EMQX_TPU_PIN_WARN_WINDOWS windows,
  fired once per handle
- A/B: `EMQX_TPU_HBM_LEDGER=0` yields no ledger objects anywhere, an
  identical snapshot schema minus `memory`, and bit-identical
  delivery counts
- exporter exposition of the `memory` section: $SYS
  `pipeline/memory`, Prometheus gauge families, StatsD lines, REST
  `GET /api/v5/pipeline/memory`
- the jit-program cost registry: per-class compile rows recorded by
  the wrapped route programs, `snapshot()["program_costs"]`, lazy
  `cost_stats(analyze=True)` flop/byte fill, external rows via
  `record_program_cost`
- the untracked-allocation gate (tools/check_hbm_hygiene.py) as a
  tier-1 test over emqx_tpu/
- tools/hbm_report.py: the capacity forecast fits per-sub bytes and
  reports a >=10M-subscription ceiling for the 16GB budget
- the ledger-overhead guard: per-window ledger cost (<1% of a window)
"""

import gc
import json
import os
import sys
import time
import weakref

import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))

from emqx_tpu.broker import hbm_ledger as H      # noqa: E402
from emqx_tpu.broker.message import make         # noqa: E402
from emqx_tpu.broker.metrics import Metrics      # noqa: E402
from emqx_tpu.broker.node import Node            # noqa: E402


class Sink:
    def __init__(self):
        self.got = []

    def deliver(self, topic_filter, msg):
        self.got.append((topic_filter, msg.topic))
        return True


def _mk_node(**over):
    conf = {"device_fanout_cap": 16, "device_slot_cap": 4,
            "device_min_batch": 1, "deliver_lanes": 0}
    conf.update(over)
    return Node({"broker": conf})


def _subscribe(node, n=8):
    sinks = []
    for i in range(n):
        s = Sink()
        sid = node.broker.register(s, f"c{i}")
        node.broker.subscribe(sid, f"t/{i}/+", {"qos": 1})
        sinks.append(s)
    return sinks


def _route(node, windows=3, n=8):
    """Synchronous route_batch windows (no loop needed)."""
    out = []
    for w in range(windows):
        out.append(node.device_engine.route_batch(
            [make("p", 0, f"t/{i}/x", b"m%d" % w) for i in range(n)]))
    return out


def _tree_nbytes(tree) -> int:
    return sum(int(x.nbytes) for x in H._leaves(tree))


@pytest.fixture(scope="module")
def ledger_run():
    """One routed node with the ledger on (default), shared by the
    read-only tests: (node, delivery counts)."""
    node = _mk_node()
    _subscribe(node)
    counts = _route(node, windows=4)
    return node, counts


# ---------- knob resolution ----------

class TestKnobs:
    def test_config_beats_env_beats_default(self, monkeypatch):
        assert H.resolve_hbm_ledger(None) is True
        monkeypatch.setenv("EMQX_TPU_HBM_LEDGER", "0")
        assert H.resolve_hbm_ledger(None) is False
        assert H.resolve_hbm_ledger(True) is True     # config wins
        monkeypatch.setenv("EMQX_TPU_HBM_LEDGER", "off")
        assert H.resolve_hbm_ledger(None) is False

    def test_pin_warn_windows_resolution(self, monkeypatch):
        assert H.resolve_pin_warn_windows(None) == 64
        monkeypatch.setenv("EMQX_TPU_PIN_WARN_WINDOWS", "7")
        assert H.resolve_pin_warn_windows(None) == 7
        assert H.resolve_pin_warn_windows(3) == 3     # config wins
        with pytest.raises(ValueError):
            H.resolve_pin_warn_windows(0)
        with pytest.raises(ValueError):
            H.resolve_pin_warn_windows(-4)
        monkeypatch.setenv("EMQX_TPU_PIN_WARN_WINDOWS", "banana")
        with pytest.raises(ValueError):
            H.resolve_pin_warn_windows(None)

    def test_host_only_node_has_no_ledger(self):
        node = Node(use_device=False)
        assert node.hbm_ledger is None

    def test_env_knob_off(self, monkeypatch):
        monkeypatch.setenv("EMQX_TPU_HBM_LEDGER", "0")
        node = _mk_node()
        assert node.hbm_ledger is None
        assert node.pipeline_telemetry.ledger is None


# ---------- ledger unit lifecycle ----------

class TestLedgerUnit:
    def test_hold_release_and_alias_dedup(self):
        m = Metrics()
        led = H.HbmLedger(m)
        a = np.zeros(1000, np.int32)          # 4000 B
        b = np.ones(10, np.float64)           # 80 B
        tree = {"x": a, "y": [a, b]}          # a aliased twice
        out = led.hold("snapshot_tables", tree, owner="sid1")
        assert out is tree                    # identity passthrough
        assert led.live_bytes() == 4080       # alias counted once
        assert led.live_bytes("snapshot_tables") == 4080
        assert led.live_leaves() == 2
        sec = led.section()
        cat = sec["categories"]["snapshot_tables"]
        assert cat["live_bytes"] == 4080 and cat["holds"] == 1
        assert cat["owners"] == {"sid1": 4080}
        # metric counts LEAVES (2), symmetric with per-finalizer
        # releases; the category row counts hold() calls (1)
        assert m.val("pipeline.memory.holds") == 2
        assert m.val("pipeline.memory.hold_bytes") == 4080
        # release is AUTOMATIC: drop the arrays, GC returns the bytes
        del tree, out, a, b
        gc.collect()
        assert led.live_bytes() == 0
        assert led.live_leaves() == 0
        assert m.val("pipeline.memory.releases") == 2
        assert m.val("pipeline.memory.release_bytes") == 4080
        # peak watermark + release count survive the release
        cat = led.section()["categories"]["snapshot_tables"]
        assert cat["peak_bytes"] == 4080
        assert cat["releases"] == 2

    def test_owner_accounting_clears_on_release(self):
        led = H.HbmLedger()
        a = np.zeros(100, np.int8)
        led.hold("delta_overlay", a, owner="v3")
        assert led.section()["categories"]["delta_overlay"][
            "owners"] == {"v3": 100}
        del a
        gc.collect()
        assert "owners" not in led.section()[
            "categories"]["delta_overlay"]

    def test_non_weakrefable_leaf_skipped(self):
        led = H.HbmLedger()
        # numpy scalars expose .nbytes but reject weakrefs — the
        # ledger must skip them rather than leak an unreleasable entry
        with pytest.raises(TypeError):
            weakref.finalize(np.int32(5), lambda: None)
        tree = [np.int32(5), np.zeros(4, np.int8)]
        led.hold("snapshot_tables", tree)
        assert led.live_bytes() == 4
        assert led.live_leaves() == 1
        del tree

    def test_rehold_same_leaf_is_idempotent(self):
        led = H.HbmLedger()
        a = np.zeros(64, np.int8)
        led.hold("snapshot_cursors", a)
        led.hold("snapshot_cursors", a)     # cursor re-adopt idiom
        assert led.live_bytes() == 64
        assert led.section()["categories"]["snapshot_cursors"][
            "holds"] == 2

    def test_global_peak_is_true_high_water_mark(self):
        """Top-level peak_bytes is the high-water mark of SUMMED live
        bytes — not the sum of per-category peaks, which can report a
        total that never occurred when categories peak at different
        times."""
        led = H.HbmLedger()
        a = led.hold("snapshot_tables", np.zeros(1000, np.int8))
        del a
        gc.collect()                       # tables gone: live back to 0
        b = led.hold("delta_overlay", np.zeros(600, np.int8))
        sec = led.section()
        assert sec["live_bytes"] == 600
        assert sec["peak_bytes"] == 1000   # not 1600 (sum of cat peaks)
        assert sec["categories"]["snapshot_tables"]["peak_bytes"] == 1000
        assert sec["categories"]["delta_overlay"]["peak_bytes"] == 600
        assert b is not None               # keep the hold live

    def test_section_is_json_clean(self):
        led = H.HbmLedger()
        held = led.hold("mesh_tables", np.zeros(8, np.int8), owner="s0")
        doc = json.loads(json.dumps(led.section()))
        assert held is not None     # keep the hold live for the read
        assert doc["schema"] == H.SCHEMA
        assert doc["live_bytes"] == 8
        assert doc["pins"]["outstanding"] == 0


# ---------- pin sentinel ----------

class TestPinSentinel:
    def test_warning_fires_once_past_threshold(self):
        from emqx_tpu.broker.hooks import Hooks
        from emqx_tpu.broker.trace import FlightRecorder
        m = Metrics()
        hooks = Hooks()
        fired = []
        hooks.add("pipeline.pin_stale", lambda info: fired.append(info))
        rec = FlightRecorder(cap=64, sample=0)
        led = H.HbmLedger(m, pin_warn_windows=3, hooks=hooks,
                          recorder=rec)

        class Handle:
            trace = 42
        h = Handle()     # alive-but-leaked: something still holds it
        led.pin(1, h)
        for _ in range(3):
            led.note_window()
        assert led.pin_warnings == 0          # age == threshold: OK
        led.note_window()                     # age 4 > 3: fires
        assert led.pin_warnings == 1
        assert m.val("pipeline.memory.pin_warnings") == 1
        assert fired and fired[0]["age_windows"] == 4
        assert fired[0]["warn_windows"] == 3
        assert fired[0]["trace"] == 42
        evs = [s for s in rec.spans() if s.name == "stale_pin"]
        assert evs and evs[0].trace_id == 42
        assert evs[0].meta["age_windows"] == 4
        # fires ONCE per handle, not once per window
        led.note_window()
        assert led.pin_warnings == 1
        st = led.pin_state()
        assert st["outstanding"] == 1 and st["warnings"] == 1
        assert st["max_age_windows"] == 5
        led.unpin(1)
        assert led.pin_state()["outstanding"] == 0

    def test_pin_holds_handle_by_weakref_only(self):
        # the ledger must never retain the handle it is instrumenting:
        # a truly dropped handle stays collectable (its snapshot HBM
        # frees) and the sentinel still fires, trace falling back to 0
        import gc
        led = H.HbmLedger(None, pin_warn_windows=1)

        class Handle:
            trace = 7
        led.pin(1, Handle())          # no other reference anywhere
        gc.collect()
        assert led._pins[1][1]() is None
        led.note_window()
        led.note_window()             # age 2 > 1: fires, trace=0
        assert led.pin_warnings == 1

    def test_live_engine_pins_ride_the_clock(self):
        node = _mk_node(pin_warn_windows=2)
        _subscribe(node)
        _route(node)                          # snapshot built + warm
        eng = node.device_engine
        led = node.hbm_ledger
        h = eng.prepare([make("p", 0, "t/0/z", b"")], gate_cold=False)
        assert h is not None
        assert led.pin_state()["outstanding"] == 1
        for _ in range(4):
            led.note_window()
        assert node.metrics.val("pipeline.memory.pin_warnings") >= 1
        eng.abandon(h)
        assert led.pin_state()["outstanding"] == 0


# ---------- reconciliation + swap/overlay lifecycle ----------

class TestLifecycle:
    def test_live_bytes_reconcile_with_held_trees(self, ledger_run):
        """The acceptance criterion: ledger-accounted bytes == summed
        .nbytes of the LIVE held pytrees, within 1%."""
        node, _counts = ledger_run
        eng = node.device_engine
        gc.collect()                 # superseded cursor chains release
        led = node.hbm_ledger
        expected = _tree_nbytes(eng._tables) + _tree_nbytes(
            eng._cursors)
        ov = getattr(eng, "_overlay", None)
        if ov is not None:
            expected += _tree_nbytes(ov.dev)
        live = led.live_bytes()
        assert expected > 0
        assert abs(live - expected) / expected < 0.01, (live, expected)

    def test_swap_returns_bytes_to_baseline(self):
        """A snapshot rebuild swaps new tables in; the old snapshot's
        bytes must come back through the weakref finalizers — the
        leak class the ledger exists to catch."""
        node = _mk_node()
        _subscribe(node)
        _route(node)
        led = node.hbm_ledger
        eng = node.device_engine
        gc.collect()
        base_bytes = led.live_bytes()
        base_leaves = led.live_leaves()
        holds0 = led.section()["categories"]["snapshot_tables"]["holds"]
        for i in range(3):
            eng.rebuild()            # full swap, same route set
            _route(node, windows=1)
        gc.collect()
        assert led.section()["categories"]["snapshot_tables"][
            "holds"] > holds0       # the swaps really re-held
        # same route set -> same table sizes: bytes return to baseline
        assert led.live_bytes() == pytest.approx(base_bytes, rel=0.01)
        # no weakref leaks: the live set tracks the live snapshot only
        assert led.live_leaves() <= base_leaves + 2

    def test_overlay_versions_release_on_compaction(self):
        """Delta-overlay versions are per-version ledger owners; a
        rebuild (compaction) folds them into the snapshot and their
        bytes must return."""
        node = _mk_node(delta_overlay=True)
        s = Sink()
        sid = node.broker.register(s, "seed")
        for i in range(8):
            node.broker.subscribe(sid, f"dev/{i}/+", {"qos": 1})
        node.device_engine.route_batch(
            [make("p", 0, f"dev/{i}/t", b"") for i in range(8)])
        # post-build churn -> overlay versions
        node.broker.subscribe(sid, "fresh/+/x", {"qos": 0})
        node.broker.subscribe(sid, "deep/#", {"qos": 1})
        node.device_engine.route_batch(
            [make("p", 0, "fresh/1/x", b""), make("p", 0, "deep/a/b", b"")])
        led = node.hbm_ledger
        if led.section()["categories"].get("delta_overlay") is None:
            pytest.skip("overlay did not engage on this backend")
        assert led.live_bytes("delta_overlay") > 0
        node.device_engine.rebuild()     # compaction folds the overlay
        node.device_engine.route_batch(
            [make("p", 0, "fresh/1/x", b"")])
        gc.collect()
        assert led.live_bytes("delta_overlay") == 0
        # ... but the category's history (peak/holds) remains readable
        assert led.section()["categories"]["delta_overlay"][
            "peak_bytes"] > 0


# ---------- A/B: EMQX_TPU_HBM_LEDGER=0 restores current behavior ----

class TestLedgerOffAB:
    def test_off_means_no_ledger_and_same_results(self):
        node_off = _mk_node(hbm_ledger=False)
        assert node_off.hbm_ledger is None
        assert node_off.pipeline_telemetry.ledger is None
        assert node_off.device_engine.ledger is None
        _subscribe(node_off)
        counts_off = _route(node_off, windows=4)
        node_on = _mk_node(hbm_ledger=True)
        assert node_on.hbm_ledger is not None
        _subscribe(node_on)
        counts_on = _route(node_on, windows=4)
        # delivery counts are bit-identical either way
        assert counts_off == counts_on
        # snapshot schema identical minus the memory section
        snap_off = node_off.pipeline_telemetry.snapshot()
        snap_on = node_on.pipeline_telemetry.snapshot()
        assert "memory" not in snap_off
        assert set(snap_off) == set(snap_on) - {"memory"}
        # no memory counters leak into the off registry
        assert node_off.metrics.val("pipeline.memory.holds") == 0
        assert "pipeline.memory.live_bytes" not in \
            node_off.stats.sample()


# ---------- exporter exposition of the memory section ----------

class TestExporters:
    def test_snapshot_memory_section(self, ledger_run):
        node, _counts = ledger_run
        snap = node.pipeline_telemetry.snapshot()
        mem = snap["memory"]
        assert mem["schema"] == H.SCHEMA
        assert mem["live_bytes"] > 0
        assert mem["categories"]["snapshot_tables"]["live_bytes"] > 0
        assert "pins" in mem
        json.dumps(snap)        # the whole document stays JSON-clean

    def test_sys_publishes_memory_topic(self, ledger_run):
        node, _counts = ledger_run
        from emqx_tpu.apps.sys import SysBroker
        seen = {}

        class Spy(SysBroker):
            def _pub(self, suffix, payload):
                seen[suffix] = payload
        Spy(node).publish_pipeline()
        assert "pipeline/memory" in seen
        doc = json.loads(seen["pipeline/memory"])
        assert doc["live_bytes"] > 0
        # the cost registry rides the same cadence
        assert "pipeline/program_costs" in seen
        assert json.loads(seen["pipeline/program_costs"])

    def test_prometheus_carries_memory_gauges(self, ledger_run):
        node, _counts = ledger_run
        from emqx_tpu.apps.prometheus import collect
        text = collect(node)
        assert "emqx_pipeline_memory_live_bytes" in text
        assert "emqx_pipeline_memory_holds" in text
        for line in text.splitlines():
            if line.startswith("emqx_pipeline_memory_live_bytes "):
                assert int(line.split()[1]) > 0
                break
        else:
            raise AssertionError("live_bytes gauge sample missing")
        # well-formedness: exactly one TYPE declaration per family
        fams = [ln for ln in text.splitlines()
                if ln.startswith("# TYPE emqx_pipeline_memory_")]
        assert len(fams) == len(set(fams)) and fams

    def test_statsd_renders_memory_lines(self, ledger_run):
        node, _counts = ledger_run
        from emqx_tpu.apps.statsd import StatsdApp
        app = StatsdApp(node)
        lines = app.render()
        gauges = [ln for ln in lines
                  if ln.startswith("emqx.pipeline.memory.live_bytes:")]
        assert gauges and gauges[0].endswith("|g")

    def test_api_endpoint(self, ledger_run):
        import asyncio
        node, _counts = ledger_run
        from emqx_tpu.mgmt import make_api

        async def _get(port, path, expect=b"200"):
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port)
            writer.write(f"GET {path} HTTP/1.1\r\nhost: x\r\n"
                         "connection: close\r\n\r\n".encode())
            await writer.drain()
            raw = await asyncio.wait_for(reader.read(-1), 10)
            writer.close()
            head, _, body = raw.partition(b"\r\n\r\n")
            assert expect in head.split(b"\r\n")[0], head
            return json.loads(body) if expect == b"200" else None

        async def go():
            srv = make_api(node, port=0)
            await srv.start()
            try:
                doc = await _get(srv.port, "/api/v5/pipeline/memory")
                assert doc["schema"] == H.SCHEMA
                assert doc["live_bytes"] > 0
                assert doc["categories"]["snapshot_tables"][
                    "live_bytes"] > 0
            finally:
                await srv.stop()
        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(asyncio.wait_for(go(), 60))
        finally:
            loop.close()

    def test_api_endpoint_404_when_off(self):
        import asyncio
        node = _mk_node(hbm_ledger=False)
        from emqx_tpu.mgmt import make_api

        async def go():
            srv = make_api(node, port=0)
            await srv.start()
            try:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", srv.port)
                writer.write(b"GET /api/v5/pipeline/memory HTTP/1.1"
                             b"\r\nhost: x\r\nconnection: close\r\n\r\n")
                await writer.drain()
                raw = await asyncio.wait_for(reader.read(-1), 10)
                writer.close()
                assert b"404" in raw.split(b"\r\n")[0]
            finally:
                await srv.stop()
        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(asyncio.wait_for(go(), 60))
        finally:
            loop.close()


# ---------- the jit-program cost registry ----------

class TestCostRegistry:
    def test_route_programs_record_compile_rows(self, ledger_run):
        import emqx_tpu.models.router_engine as R
        node, _counts = ledger_run
        cs = R.cost_stats()
        assert cs, "no cost rows after a routed run"
        prog, rows = next(iter(cs.items()))
        assert prog.startswith("route_")
        label, row = next(iter(rows.items()))
        assert row["compiles"] >= 1
        assert row["compile_ms"] > 0
        # keyed like compiles.by_shape ("dispatch W1xB64" / "warm ...")
        assert " W" in label or label.startswith("adhoc")
        # no private keys leak into the exported table
        assert not any(k.startswith("_")
                       for r in rows.values() for k in r)

    def test_snapshot_embeds_program_costs(self, ledger_run):
        node, _counts = ledger_run
        snap = node.pipeline_telemetry.snapshot()
        assert snap["program_costs"]
        json.dumps(snap["program_costs"])

    def test_analyze_fills_flops_and_drops_avals(self, ledger_run):
        import emqx_tpu.models.router_engine as R
        _node, _counts = ledger_run
        cs = R.cost_stats(analyze=True)
        rows = [row for prog in cs.values() for row in prog.values()]
        assert rows
        # the CPU backend provides cost_analysis: flops/bytes land
        assert any("flops" in r for r in rows)
        for r in rows:
            if "flops" in r:
                assert r["flops"] > 0
            if "bytes_accessed" in r:
                assert r["bytes_accessed"] > 0
        # analysis is idempotent and cheap the second time
        assert R.cost_stats(analyze=True) == R.cost_stats()

    def test_external_harness_rows(self):
        import emqx_tpu.models.router_engine as R
        R.record_program_cost("bench_kernel", "profile match_only",
                              compile_ms=12.5, flops=1e6,
                              bytes_accessed=2e6)
        row = R.cost_stats()["bench_kernel"]["profile match_only"]
        assert row == {"compiles": 1, "compile_ms": 12.5,
                       "flops": 1e6, "bytes_accessed": 2e6}

    def test_wrapper_is_transparent(self):
        import emqx_tpu.models.router_engine as R
        for fn in (R.route_step, R.route_window, R.route_window_shapes):
            assert callable(fn.lower)
            assert isinstance(fn._cache_size(), int)
            assert fn.__name__.startswith("route_")

    def test_env_off_leaves_programs_unwrapped(self):
        """EMQX_TPU_HBM_LEDGER=0 restores pre-ISSUE-8 behavior for
        the registry leg too: programs bind unwrapped (zero per-call
        introspection) and snapshot(full=True) has no program_costs
        section. Subprocess: the binding happens at module import."""
        import subprocess
        env = dict(os.environ)
        env["EMQX_TPU_HBM_LEDGER"] = "0"
        env["JAX_PLATFORMS"] = "cpu"
        code = (
            "import types\n"
            "import emqx_tpu.models.router_engine as R\n"
            "assert not R.cost_registry_enabled()\n"
            "# unwrapped: the raw jit object, not a plain function\n"
            "assert not isinstance(R.route_step, types.FunctionType)\n"
            "assert not R._cost_programs, 'programs registered'\n"
            "from emqx_tpu.broker.telemetry import PipelineTelemetry\n"
            "snap = PipelineTelemetry().snapshot(full=True)\n"
            "assert 'program_costs' not in snap, sorted(snap)\n"
            "print('OFF_OK')\n")
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=120,
                           env=env, cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))))
        assert r.returncode == 0, r.stderr
        assert "OFF_OK" in r.stdout

    def test_foreign_thread_compile_not_attributed(self, ledger_run):
        """The per-thread jax.monitoring compile-seq confirmation: a
        compile on ANOTHER thread bumps that thread's seq, not ours —
        the signal the wrapper uses to reject cache growth it did not
        cause (cross-thread misattribution guard)."""
        import threading as T
        import jax
        import jax.numpy as jnp
        from emqx_tpu.broker import telemetry as tele
        node, _counts = ledger_run      # listener installed + warm
        seq_here = tele.thread_compile_seq()
        assert seq_here is not None     # listener is installed
        done = T.Event()
        other_seq = []

        @jax.jit
        def _fresh(x):
            return x * 2 + 1

        def compile_elsewhere():
            _fresh(jnp.arange(7))       # fresh program: compiles there
            other_seq.append(tele.thread_compile_seq())
            done.set()

        t = T.Thread(target=compile_elsewhere)
        t.start()
        assert done.wait(60)
        t.join()
        assert other_seq[0] >= 1        # the compiling thread saw it
        # our thread's seq did not move: the confirmation signal is
        # exactly per-thread
        assert tele.thread_compile_seq() == seq_here


# ---------- untracked-allocation gate (tier-1 satellite) ----------

class TestHygieneGate:
    def test_no_device_put_bypasses_the_ledger(self):
        import check_hbm_hygiene as hygiene
        root = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "emqx_tpu")
        findings = hygiene.check(root)
        assert not findings, "\n".join(map(repr, findings))

    def test_gate_catches_a_bypass(self):
        import check_hbm_hygiene as hygiene
        bad = "import jax\nx = jax.device_put(tables)\n"
        assert len(hygiene.check_source("x.py", bad)) == 1
        wrapped = "x = ledger.hold('c', jax.device_put(t))\n"
        assert not hygiene.check_source("x.py", wrapped)
        noted = "# hbm: transient — consumed by this dispatch\n" \
                "x = jax.device_put(t)\n"
        assert not hygiene.check_source("x.py", noted)


# ---------- the capacity forecaster ----------

class TestHbmReport:
    def test_forecast_fits_and_extrapolates(self):
        import hbm_report
        doc = hbm_report.report(sizes=(5_000, 10_000, 20_000),
                                budgets_gb=(16,))
        assert doc["schema"] == hbm_report.SCHEMA
        assert len(doc["points"]) == 3
        for p in doc["points"]:
            # the acceptance reconciliation: ledger vs .nbytes < 1%
            assert p["reconcile_err"] < 0.01
            assert p["released"], "ledger leaked a measure point"
        fit = doc["fit"]
        assert fit["per_sub_bytes"] > 0
        assert fit["r2"] is None or fit["r2"] > 0.9
        head = doc["headline"]
        # the 16GB v5e-1 budget holds the 10M-subscription target
        assert head["budget"] == "16GB"
        assert head["ceiling_subs"] >= 10_000_000
        assert head["target_10m_fits"] is True
        json.dumps(doc)

    def test_no_default_budget_on_a_device_without_memory_stats(self):
        """The CPU backend reports no bytes_limit: the forecaster must
        refuse to assume a chip, not fall back to 16 GiB."""
        import hbm_report
        with pytest.raises(SystemExit, match="--budget-gb"):
            hbm_report.device_budget_gb()

    def test_cli_writes_report(self, tmp_path):
        import hbm_report
        out = tmp_path / "hbm.json"
        rc = hbm_report.main(["5000", "8000", "--budget-gb", "16",
                              "--out", str(out)])
        assert rc == 0
        doc = json.loads(out.read_text())
        assert doc["budgets"]["16GB"]["ceiling_subs"] > 0


# ---------- ledger-overhead guard ----------

class TestOverheadGuard:
    def test_per_window_ledger_cost_under_1pct(self, ledger_run):
        """Deterministic bound, like the PR-7 tracing guard: the
        per-window ledger work is note_window + pin + unpin. Measure
        the primitive cost tight-loop and bound it against 1% of the
        mean dispatch stage span of the live run — a hot-path
        regression (section() leaking into note_window, a lock on the
        pin path) fails immediately; scheduler noise cannot."""
        node, _counts = ledger_run
        led = H.HbmLedger(pin_warn_windows=64)

        class Handle:
            trace = 1
        h = Handle()
        for i in range(4):              # realistic outstanding depth
            led.pin(1000 + i, h)
        n = 20_000
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for i in range(n):
                led.note_window()
                led.pin(i, h)
                led.unpin(i)
            best = min(best, (time.perf_counter() - t0) / n)
        hist = node.metrics.histograms().get("pipeline.stage.dispatch"
                                             ".seconds")
        if hist is None or not hist.count:
            pytest.skip("no dispatch spans in the shared run")
        mean_window = hist.sum / hist.count
        assert best < 0.01 * mean_window, (
            f"ledger per-window cost {best * 1e6:.2f}us vs mean "
            f"dispatch {mean_window * 1e3:.2f}ms — over the 1% budget")
