"""chip_smoke.py, the chip-proof script, exercised off the chip.

- the drive-and-check function runs end to end on the CPU backend at a
  tiny size (socket stream, mid-stream churn, direct leg) and passes;
- with faults injected, the same function reports the "nothing hidden"
  counters that moved (a class warm-compile that raised, windows the
  supervisor kept off the device) instead of passing;
- the script itself refuses to run without a TPU, or with any
  `EMQX_TPU_*` variable set;
- the compile-cache helper leaves `JAX_COMPILATION_CACHE_DIR` alone and
  otherwise names one absolute path whatever the working directory;
- the bring-up's counters: the bound platform through every exporter,
  executables counted apart from trace events, and bench.py's own gates
  (no TPU -> exit 2, a failed CPU child row raises).
"""

import asyncio
import importlib.util
import os
import subprocess
import sys

import pytest

from emqx_tpu.broker import supervise as S
from emqx_tpu.broker.node import Node

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _drive(smoke, node):
    return asyncio.run(asyncio.wait_for(smoke.drive(
        node, subs=2000, msgs=1024, seed=3, direct_batches=1,
        warm_timeout_s=120), 300))


class TestDrive:
    def test_tiny_drive_passes_on_cpu(self, smoke):
        node = Node()
        report = _drive(smoke, node)
        assert report["failures"] == []
        assert report["device"]["platform"] == "cpu"
        # the churn landed and the direct leg ran every standard class
        assert report["stats"]["delta_filters"] == smoke.CHURN
        assert report["direct"]["device_batches"] >= 4
        assert report["stream"]["in_path_compiles"] == {}
        assert report["messages_checked"] > 1024
        # platform rides the node's own exporters too
        assert node.pipeline_telemetry.snapshot()["device"] == \
            node.device_info

    def test_injected_faults_fail_the_drive(self, smoke):
        """A warm-compile that raises and dispatches the supervisor
        had to replay must each surface as a failed check."""
        node = Node()
        eng = node.device_engine
        node.supervisor.injector = S.FaultInjector(
            S.parse_faults("dispatch:exception:count=3"))
        real = eng._warm_class
        calls = {"n": 0}

        def flaky_warm_class(*a):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected warm-compile failure")
            return real(*a)

        eng._warm_class = flaky_warm_class
        failures = "\n".join(_drive(smoke, node)["failures"])
        assert "routing.device.warm_failed == 0" in failures
        assert "routing.device.supervised_bypass == 0" in failures
        assert "supervise.faults.dispatch == 0" in failures
        assert "supervise.replays == 0" in failures


class TestRefusals:
    def _run(self, env):
        return subprocess.run([sys.executable, SMOKE], env=env,
                              capture_output=True, text=True, timeout=120)

    def test_no_tpu_exits_nonzero_and_says_so(self):
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("EMQX_TPU_")}
        env["JAX_PLATFORMS"] = "cpu"
        r = self._run(env)
        assert r.returncode != 0
        assert "TPU" in r.stderr and "JAX_PLATFORMS" in r.stderr
        assert '"ok"' not in r.stdout

    def test_any_knob_set_is_refused(self):
        env = dict(os.environ, EMQX_TPU_DEDUP="0")
        r = self._run(env)
        assert r.returncode != 0
        assert "EMQX_TPU_DEDUP" in r.stderr


class TestCompileCacheHelper:
    CODE = ("import os, jax\n"
            "from emqx_tpu.utils.compile_cache import "
            "configure_compile_cache\n"
            "print(configure_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")

    def _run(self, cwd, extra_env):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env.update(extra_env)
        r = subprocess.run([sys.executable, "-c", self.CODE], env=env,
                           cwd=cwd, capture_output=True, text=True,
                           timeout=120)
        assert r.returncode == 0, r.stderr
        return r.stdout.split()

    def test_env_unset_same_absolute_path_from_any_cwd(self, tmp_path):
        want = os.path.join(REPO, ".jax_cache")
        assert self._run(REPO, {}) == [want, want]
        assert self._run(str(tmp_path), {}) == [want, want]

    def test_env_set_is_left_alone(self, tmp_path):
        outside = str(tmp_path / "elsewhere")
        helper, jax_dir = self._run(
            REPO, {"JAX_COMPILATION_CACHE_DIR": outside})
        # the helper reports the env's directory and sets nothing in
        # code: what JAX holds is what JAX itself read from the env
        assert helper == outside and jax_dir == outside


class TestBoundDeviceIsExported:
    """The platform the route path is bound to, through the node's own
    exporters (counting only — nothing reads it to decide anything)."""

    def test_snapshot_sys_and_prometheus_carry_the_device(self):
        import json

        from emqx_tpu.apps.prometheus import collect
        from emqx_tpu.apps.sys import SysBroker
        node = Node()
        assert node.device_info["platform"] == "cpu"
        assert node.device_engine.stats()["platform"] == "cpu"
        published = {}
        app = SysBroker(node)
        app._pub = lambda topic, payload: published.update(
            {topic: payload})
        app.publish_pipeline()
        assert json.loads(published["pipeline/device"]) == \
            node.device_info
        assert 'emqx_pipeline_device_info{platform="cpu"' in collect(node)

    def test_host_only_node_binds_no_device(self):
        node = Node(use_device=False)
        assert node.device_info is None
        assert "device" not in node.pipeline_telemetry.snapshot()

    def test_executables_counted_apart_from_trace_events(self):
        """A jit fast-path miss re-uses a finished trace: one trace
        event, no executable. Only `executables` means XLA compiled."""
        from emqx_tpu.broker import telemetry as T
        tele = T.PipelineTelemetry(track_compiles=False)
        with tele.compile_context("dispatch W1xB64"):
            T._on_jax_event(T._TRACE_EVENT, 0.0001)
        row = tele.snapshot()["compiles"]["by_shape"]["dispatch W1xB64"]
        assert (row["count"], row["executables"]) == (1, 0)
        with tele.compile_context("dispatch W1xB64"):
            T._on_jax_event(T._TRACE_EVENT, 0.2)
            T._on_jax_event(T._BACKEND_EVENT, 1.5)
        row = tele.snapshot()["compiles"]["by_shape"]["dispatch W1xB64"]
        assert (row["count"], row["executables"]) == (2, 1)


class TestWarmedClassesHitTheJitFastPath:
    """Real JAX, no synthetic events: numpy and device-resident
    arguments do not share a jit fast-path entry, so a warm pass has to
    hand a program what the live dispatch will hand it."""

    def test_numpy_then_device_argument_is_a_trace_event_only(self):
        import jax
        import numpy as np

        from emqx_tpu.broker import telemetry as T
        tele = T.PipelineTelemetry()

        @jax.jit
        def lookup(table, idx):
            return table["rows"][idx] + 1

        table = {"rows": np.arange(16, dtype=np.int32)}
        idx = np.zeros(4, np.int32)
        with tele.compile_context("warm"):
            lookup(table, idx)
        with tele.compile_context("live numpy"):
            lookup(table, idx)
        with tele.compile_context("live device"):
            lookup(jax.device_put(table), idx)
        by = tele.snapshot()["compiles"]["by_shape"]
        assert by["warm"]["executables"] == 1
        assert "live numpy" not in by            # the fast path: silent
        assert by["live device"]["count"] >= 1   # a miss on the live call
        assert by["live device"]["executables"] == 0

    def test_first_live_delta_dispatch_after_warm_is_silent(self):
        """Serving-path gates on: a post-build SUBSCRIBE puts an overlay
        in play, the delta class warms in the background, and the first
        live dispatch that fuses the overlay causes no jit event under
        any `dispatch` label — no compile and no re-trace."""
        from emqx_tpu.broker.message import make

        class Sink:
            def deliver(self, topic_filter, msg):
                return True

        async def go():
            node = Node()
            eng, broker = node.device_engine, node.broker
            sid = broker.register(Sink(), "s")
            # a population no other test builds: the jit cache is
            # process-wide, and tests that assert their own cold compile
            # must not find this one's programs already there
            for i in range(96):
                broker.subscribe(sid, f"fp/{i}/+/s/#", {"qos": 0})
            eng.rebuild()

            async def warm_idle():
                eng._kick_class_warm()
                while eng._fuse_warm_task is not None:
                    await asyncio.sleep(0.01)

            await warm_idle()
            broker.subscribe(sid, "fresh/+/x", {"qos": 0})
            fused = 0
            for w in range(6):
                msgs = [make("pub", 0, t, b"x") for t in
                        [f"fp/{k}/w{w}/s/t" for k in range(8)]
                        + [f"fresh/{w}/x"] * 4]
                h = eng.prepare(msgs, gate_cold=True)
                eng.dispatch(h)
                eng.materialize(h)
                fused += h.delta is not None
                assert eng.finish(h) == [1] * len(msgs)
                await warm_idle()
            return node, fused

        node, fused = asyncio.run(asyncio.wait_for(go(), 300))
        assert fused >= 1
        assert node.metrics.val("routing.device.warm_failed") == 0
        by = node.pipeline_telemetry.snapshot()["compiles"]["by_shape"]
        assert any(k.startswith("warm") and "d" in k.split("B")[-1]
                   for k in by), by
        assert {k: v for k, v in by.items()
                if k.startswith("dispatch")} == {}


class TestBenchGates:
    @pytest.fixture(scope="class")
    def bench(self):
        spec = importlib.util.spec_from_file_location(
            "bench_mod", os.path.join(REPO, "bench.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_require_tpu_refuses_the_cpu_backend(self, bench, capsys):
        with pytest.raises(SystemExit) as e:
            bench.require_tpu()
        assert e.value.code == 2
        assert "no TPU" in capsys.readouterr().out

    def test_cpu_row_fails_loudly(self, bench, tmp_path):
        """A CPU child row that exits non-zero (or prints no JSON)
        raises — bench.py's main records it and fails the run."""
        ok = tmp_path / "ok.py"
        ok.write_text("import os, json\n"
                      "print(json.dumps({'p': os.environ['JAX_PLATFORMS'],"
                      " 'telemetry': 1}))\n")
        assert bench._cpu_row("cpu_skew", str(ok), (), 60) == {"p": "cpu"}
        bad = tmp_path / "bad.py"
        bad.write_text("import sys\nprint('{\"x\": 1}')\nsys.exit(2)\n")
        with pytest.raises(RuntimeError, match="rc=2"):
            bench._cpu_row("cpu_x", str(bad), (), 60)
