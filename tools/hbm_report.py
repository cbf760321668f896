#!/usr/bin/env python
"""HBM capacity forecaster (ISSUE 8): what fits on the chip?

The 10M-subscription north star is ultimately an HBM-budget question:
the snapshot tables the broker `device_put`s grow linearly with the
subscription count, and nothing before the ISSUE-8 ledger measured the
slope. This tool measures it directly — it builds the SAME
ShapeRouterTables the serving path uploads (bench.py's canonical
`device/{id}/+/{num}/#` workload generator, so the fitted bytes are
the bytes a real broker pays) at several table sizes, registers each
upload with a fresh `broker.hbm_ledger.HbmLedger`, and fits

    table_bytes = intercept + per_sub_bytes * subscriptions

by least squares, then inverts the fit per HBM budget:

    ceiling_subs = (budget * (1 - headroom) - intercept) / per_sub_bytes

The budget is the device's own `memory_stats()["bytes_limit"]`; a
backend that reports none (XLA CPU) has no budget, so a what-if budget
must be named with `--budget-gb` (bench.py's CPU row asks for 16, a
v5e-1). Each point also carries
the reconciliation the ISSUE-8 acceptance demands: ledger-accounted
bytes vs the summed `.nbytes` of the held pytree (must agree within
1%), and a release check (weakref finalizers return the bytes when the
point's tables are dropped — a leak here is a ledger bug, caught
before it lies in production).

Usage: python tools/hbm_report.py [size ...] [--budget-gb G]...
                                  [--out FILE]

Defaults: sizes 50_000 100_000 200_000 (CPU-friendly; on a chip pass
1_000_000 10_000_000). The JSON document goes to stdout (and --out
FILE); bench.py embeds the same document as its `cpu_hbm` row. `report()` is importable — the
tier-1 test (tests/test_hbm_ledger.py) runs the full fit at small
sizes and asserts the ceiling forecast.

Env knobs: BENCH_HBM_SIZES (comma-separated, overrides argv sizes),
BENCH_HBM_HEADROOM (fraction of the budget reserved for working
buffers / jit programs / runtime, default 0.25 — the ceiling is a
TABLE budget, not a whole-chip budget).
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

SCHEMA = "emqx_tpu.hbm_report/v1"
GIB = 1 << 30


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _tree_nbytes(tree) -> int:
    """Summed `.nbytes` of a pytree's array leaves — the ground truth
    the ledger's accounting is reconciled against."""
    from emqx_tpu.broker.hbm_ledger import _leaves
    return sum(int(x.nbytes) for x in _leaves(tree))


def measure_point(subs: int, shared_pct: int = 50) -> dict:
    """Build + device_put one snapshot-table set at `subs`
    subscriptions through a fresh ledger; return the accounting row.

    The row records the ledger's live bytes, the pytree's summed
    nbytes, their relative error, and whether dropping the tables
    returned the ledger to zero (the weakref-release proof)."""
    import jax

    from bench import bench_subtable, device_filter_set
    from emqx_tpu.broker.hbm_ledger import HbmLedger
    from emqx_tpu.models.router_engine import ShapeRouterTables
    from emqx_tpu.ops.shapes import build_shape_tables

    t0 = time.time()
    fs = device_filter_set(subs)
    F = fs["ids"] * fs["nums"]
    shapes = build_shape_tables(fs["rows"], fs["lens"])
    subs_tbl, n_groups = bench_subtable(F, shared_pct)
    ledger = HbmLedger()
    # hbm: the whole point of this put IS the ledger hold below
    tables = ledger.hold(
        "snapshot_tables",
        jax.device_put(ShapeRouterTables(shapes=shapes, subs=subs_tbl)))
    cursors = ledger.hold(
        "snapshot_cursors",
        jax.device_put(np.zeros(n_groups, np.int32)))
    jax.block_until_ready(jax.tree.leaves(tables))
    ledger_bytes = ledger.live_bytes()
    tree_bytes = _tree_nbytes(tables) + _tree_nbytes(cursors)
    err = abs(ledger_bytes - tree_bytes) / max(1, tree_bytes)
    row = {
        "subs": int(F),
        "requested_subs": int(subs),
        "ledger_bytes": int(ledger_bytes),
        "tree_bytes": int(tree_bytes),
        "reconcile_err": round(err, 6),
        "categories": {k: v["live_bytes"]
                       for k, v in ledger.section()["categories"].items()},
        "build_s": round(time.time() - t0, 2),
    }
    # release proof: dropping the point's tables must return every
    # byte through the weakref finalizers (no explicit release API
    # exists — automatic release is the design)
    del tables, cursors, shapes, subs_tbl, fs
    gc.collect()
    row["released"] = ledger.live_bytes() == 0 \
        and ledger.live_leaves() == 0
    log(f"point subs={row['subs']}: "
        f"{row['ledger_bytes'] / 1e6:.1f}MB ledgered "
        f"(err {err * 100:.3f}%, released={row['released']}, "
        f"{row['build_s']}s)")
    return row


def fit_points(points: list[dict]) -> dict:
    """Least-squares line through (subs, ledger_bytes): the
    per-subscription byte slope + fixed intercept, with r² so a
    non-linear regime (bucket-table quantization steps) is visible."""
    xs = np.array([p["subs"] for p in points], np.float64)
    ys = np.array([p["ledger_bytes"] for p in points], np.float64)
    if len(xs) == 1:
        # one point fixes only the slope-through-origin
        return {"per_sub_bytes": round(float(ys[0] / xs[0]), 3),
                "intercept_bytes": 0, "r2": None, "points": 1}
    slope, intercept = np.polyfit(xs, ys, 1)
    pred = slope * xs + intercept
    ss_res = float(((ys - pred) ** 2).sum())
    ss_tot = float(((ys - ys.mean()) ** 2).sum())
    return {"per_sub_bytes": round(float(slope), 3),
            "intercept_bytes": int(intercept),
            "r2": round(1.0 - ss_res / ss_tot, 6) if ss_tot else 1.0,
            "points": len(points)}


def ceiling(fit: dict, budget_bytes: int, headroom: float) -> dict:
    """Invert the fit for one HBM budget: how many subscriptions fit
    once `headroom` of the budget is reserved for working buffers,
    compiled programs and the runtime."""
    usable = budget_bytes * (1.0 - headroom)
    per_sub = fit["per_sub_bytes"]
    subs = int((usable - fit["intercept_bytes"]) / per_sub) \
        if per_sub > 0 else 0
    return {"budget_bytes": int(budget_bytes),
            "headroom": headroom,
            "table_budget_bytes": int(usable),
            "ceiling_subs": max(0, subs)}


def device_budget_gb() -> float:
    """The HBM budget of the device this process is bound to, from its
    own `memory_stats()`. A device that reports none is an error: there
    is no default chip to assume."""
    import jax

    from emqx_tpu.broker.hbm_ledger import device_memory_stats
    jax.devices()       # bind the backend; memory_stats never forces it
    dev = device_memory_stats()
    if not dev or "bytes_limit" not in dev:
        raise SystemExit(
            f"hbm_report: {jax.devices()[0].device_kind!r} reports no "
            f"memory_stats()['bytes_limit']; name a what-if budget "
            f"with --budget-gb")
    return dev["bytes_limit"] / GIB


def report(sizes=(50_000, 100_000, 200_000), budgets_gb=None,
           shared_pct: int = 50, headroom: float = None) -> dict:
    """The full forecast document (importable: the tier-1 test calls
    this). `budgets_gb=None` takes the bound device's own limit."""
    if headroom is None:
        headroom = float(os.environ.get("BENCH_HBM_HEADROOM", 0.25))
    if not budgets_gb:
        budgets_gb = (device_budget_gb(),)
    t0 = time.time()
    points = [measure_point(s, shared_pct) for s in sorted(sizes)]
    fit = fit_points(points)
    budgets = {f"{g:g}GB": ceiling(fit, g * GIB, headroom)
               for g in budgets_gb}
    head_g = f"{budgets_gb[0]:g}"
    doc = {
        "schema": SCHEMA,
        "workload": f"device/{{id}}/+/{{num}}/# {shared_pct}% shared",
        "points": points,
        "fit": fit,
        "budgets": budgets,
        "headline": {
            "budget": f"{head_g}GB",
            "per_sub_bytes": fit["per_sub_bytes"],
            "ceiling_subs": budgets[f"{head_g}GB"]["ceiling_subs"],
            "target_10m_fits":
                budgets[f"{head_g}GB"]["ceiling_subs"] >= 10_000_000,
        },
        "elapsed_s": round(time.time() - t0, 1),
    }
    from emqx_tpu.broker.hbm_ledger import device_memory_stats
    dev = device_memory_stats()
    if dev is not None:
        doc["device"] = dev
    log(f"forecast: {fit['per_sub_bytes']:.1f} B/sub -> "
        f"{doc['headline']['ceiling_subs'] / 1e6:.1f}M subs in "
        f"{head_g}GB (10M fits: {doc['headline']['target_10m_fits']})")
    return doc


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    sizes, budgets, out = [], [], None
    it = iter(argv)
    for a in it:
        if a == "--budget-gb":
            v = next(it, None)
            if v is None:
                print("hbm_report: --budget-gb requires a value",
                      file=sys.stderr)
                return 2
            budgets.append(float(v))
        elif a.startswith("--budget-gb="):
            budgets.append(float(a.split("=", 1)[1]))
        elif a == "--out":
            out = next(it, None)
        elif a.startswith("--out="):
            out = a.split("=", 1)[1]
        else:
            sizes.append(int(a))
    env_sizes = os.environ.get("BENCH_HBM_SIZES")
    if env_sizes:
        sizes = [int(s) for s in env_sizes.split(",") if s.strip()]
    doc = report(sizes or (50_000, 100_000, 200_000), budgets)
    text = json.dumps(doc)
    print(text, flush=True)
    if out:
        with open(out, "w") as f:
            f.write(text)
    # exit 2 when the release proof failed — CI catches ledger leaks
    return 0 if all(p["released"] for p in doc["points"]) else 2


if __name__ == "__main__":
    sys.exit(main())
