"""Whole runs of the harness on the CPU backend, at the rehearsal size.

Each run is a child process with its own time limit, so a hung
generator cannot stall the suite. `--rehearse` skips only the harness's
look for a chip: the generators, the wire, the broker, the window, the
drain and the comparison are the ones a chip run drives.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def run_cell(*args, env=None, cwd=ROOT, timeout=420):
    e = {k: v for k, v in os.environ.items() if not k.startswith("EMQX_TPU_")}
    e["JAX_PLATFORMS"] = "cpu"
    e.update(env or {})
    r = subprocess.run([sys.executable, "-m", "benchmark.run", *args],
                       cwd=cwd, env=e, capture_output=True, text=True,
                       timeout=timeout)
    lines = r.stdout.strip().splitlines()
    last = None
    if lines and lines[-1].startswith("{"):
        last = json.loads(lines[-1])
    return r, last


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_each_cell_end_to_end(cell):
    r, out = run_cell("--workload", cell, "--seed", str(2**31 + 17),
                      "--seconds", "3", "--trace", "1", "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 100
    # a CPU run prints nothing under a metric's name
    assert out["metrics"] == {} and out["device"]["platform"] == "cpu"
    values = out["rehearsal_values"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    mine = {m["name"] for m in bench["per_layer"]
            if cell in m.get("workloads", [
                w["name"] for w in bench["workloads"]
                if w["name"] in e2e[m["moves"]].get(
                    "workloads", [w["name"]])])}
    assert set(values) == mine           # every per-layer metric reported
    # the CPU backend has no device plane (the rehearsal reduces the
    # host's XLA threads), so only the shape of these is held here
    assert out["device"]["busy_s"] >= 0 and out["device"]["window_s"] > 2.5
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["split"]["window"]["publishes"] > 0
    # the metrics that need the counters alone are in the split too,
    # each read through its own file, so that an untraced run shows them
    # (`fuse_depth.flood`: which regime `share50-250k.flood` drew)
    by_counter = out["split"]["window"]["by_counter"]
    for name, value in by_counter.items():
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            assert json.load(f)["reader"] == "counter"
        assert value == pytest.approx(values[name]["value"], abs=1e-3)
    assert ("fuse_depth.flood" in by_counter) == \
        (cell == "share50-250k.flood")


@pytest.mark.parametrize("control,number,cell", [
    ("lose", "wrong_delivery_sets", "plus-100k.flood"),
    ("duplicate", "wrong_delivery_sets", "plus-100k.flood"),
    ("reorder", "order_breaks", "plus-100k.flood"),
    ("lose", "wrong_delivery_sets", "share50-250k.flood"),
    ("random_pick", "rr_excess_vs_random", "share50-250k.flood"),
])
def test_a_run_with_the_timed_path_broken_is_not_correct(control, number,
                                                         cell):
    r, out = run_cell("--workload", cell, "--seed", "29", "--seconds", "1",
                      "--trace", "0", "--rehearse", "--control", control)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is False and out["failed"] > 0
    assert out["compared"][number]["value"] > out["compared"][number]["limit"]


def test_no_chip_no_result():
    r, out = run_cell("--workload", CELLS[0], "--seed", "1",
                      "--seconds", "1", "--trace", "0", timeout=120)
    assert r.returncode != 0 and out is None
    assert "accelerator" in r.stderr
    r, out = run_cell("--workload", CELLS[0], "--seed", "1", "--seconds",
                      "1", "--trace", "0", "--rehearse",
                      env={"EMQX_TPU_DELIVER_LANES": "0"}, timeout=120)
    assert r.returncode != 0 and out is None and "EMQX_TPU" in r.stderr


def test_nothing_to_measure_in_a_bare_directory(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r, out = run_cell("--workload", CELLS[0], "--seed", "1", "--seconds",
                      "1", "--trace", "0", "--rehearse", cwd=str(tmp_path),
                      timeout=120)
    assert r.returncode != 0 and out is None
