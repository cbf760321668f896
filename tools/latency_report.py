#!/usr/bin/env python
"""Offline per-path latency percentile / SLO report (ISSUE 13).

Renders the latency observatory's schema (``emqx_tpu.latency/v1``) from
a bench artifact — bench.py's JSON line or a single phase row —
without importing jax or the broker:

    python tools/latency_report.py bench.json
    python tools/latency_report.py --require e2e_device bench.json

Exit codes (the CI gate):

    0  every required row carries a latency section; report printed
    1  usage / unreadable / unparseable input
    2  a required bench row carries NO latency section — the run is
       about to commit a p99-less headline. The offending rows are
       named on stderr.

By default the required rows are every phase row PRESENT in the
artifact from {cpu_latency0, e2e_host, e2e_device} — a row that
ran but lost its latency section fails; a phase that never ran (e.g.
BENCH_E2E=0) is not invented. ``--require a,b`` pins an explicit list
instead (a named row that is absent then also fails: the gate is "this
run MUST carry these measured tails"). The microbench row
``cpu_sharded`` is also requirable: it carries matches/s + speedup
headlines instead of a latency section, so for it the gate is row
presence and the report prints its scalar summary.
"""

from __future__ import annotations

import json
import sys

# the phase rows that must carry a latency section when present
DEFAULT_ROWS = ("cpu_latency0", "e2e_host", "e2e_device")
# microbench phase rows --require can pin: they carry their own metric
# (matches/s, speedup, reduction) instead of a latency section, so the
# gate checks PRESENCE and renders the headline numbers
MICRO_ROWS = ("cpu_sharded",)


def _rows_of(doc: dict) -> dict:
    """Candidate phase rows from any supported artifact shape."""
    if not isinstance(doc, dict):
        return {}
    # a single phase row passed directly
    if "latency" in doc and not any(k in doc for k in DEFAULT_ROWS):
        return {"row": doc}
    # merged bench JSON: phase rows are top-level keys
    return {k: v for k, v in doc.items()
            if k in DEFAULT_ROWS + MICRO_ROWS and isinstance(v, dict)}


def _render_micro(name: str, row: dict) -> str:
    """Headline numbers of a latency-less microbench row (one line per
    nesting level — enough for a run log, not a full report)."""
    def scalars(d):
        return {k: v for k, v in d.items()
                if isinstance(v, (int, float, str, bool))}

    out = [f"== {name} =="]
    top = scalars(row)
    if top:
        out.append("  " + " ".join(f"{k}={v}"
                                   for k, v in sorted(top.items())))
    for k in sorted(row):
        v = row[k]
        if isinstance(v, dict):
            s = scalars(v)
            if s:
                out.append(f"  {k}: " + " ".join(
                    f"{kk}={vv}" for kk, vv in sorted(s.items())))
    return "\n".join(out)


def _latency_of(row: dict):
    """The latency section of one phase row (latency0 nests it)."""
    lat = row.get("latency")
    if isinstance(lat, dict) and (lat.get("routed")
                                  or lat.get("delivered")
                                  or lat.get("slo")):
        return lat
    return None


def _fmt_leg(name: str, series: dict, out: list) -> None:
    if not series:
        return
    out.append(f"  {name} (ms):")
    out.append(f"    {'series':<22}{'count':>9}{'p50':>10}"
               f"{'p99':>10}{'p999':>10}")
    for key in sorted(series):
        row = series[key]
        out.append(f"    {key:<22}{row.get('count', 0):>9}"
                   f"{row.get('p50_ms', 0):>10}"
                   f"{row.get('p99_ms', 0):>10}"
                   f"{row.get('p999_ms', 0):>10}")


def _overload_of(row: dict):
    """The overload section riding a phase row (ISSUE 14): either
    embedded directly (overload_bench rows) or inside the row's full
    telemetry snapshot (e2e phase rows)."""
    if not isinstance(row, dict):
        return None
    ov = row.get("overload")
    if not isinstance(ov, dict):
        ov = (row.get("telemetry") or {}).get("overload") \
            if isinstance(row.get("telemetry"), dict) else None
    return ov if isinstance(ov, dict) else None


def render(name: str, lat: dict, overload=None) -> str:
    out = [f"== {name} =="]
    _fmt_leg("ingress→routed", lat.get("routed") or {}, out)
    _fmt_leg("ingress→delivered", lat.get("delivered") or {}, out)
    slo = lat.get("slo") or {}
    if slo:
        out.append(
            f"  SLO: routed p99 {slo.get('routed_p99_ms')}ms vs "
            f"objective {slo.get('objective_p99_ms')}ms -> "
            f"{str(slo.get('verdict', '?')).upper()}"
            f"  (samples {slo.get('samples')}, breaches "
            f"{slo.get('breaches')}, burn {slo.get('burn')})")
    if overload:
        # the governor's sheds NEXT TO the p99 (ISSUE 14): a tail
        # measured while load was being shed must say so — a p99 with
        # qos0_shed > 0 measures the governed broker, not raw capacity
        state = overload.get("state") or {}
        parts = [f"grade={state.get('grade', '?')}"]
        for k in ("qos0_shed", "connects_rejected", "disconnects",
                  "retained_deferred", "sheds", "grade_changes"):
            v = overload.get(k)
            if v:
                parts.append(f"{k}={v}")
        out.append("  overload: " + " ".join(parts))
    for ex in (lat.get("exemplars") or [])[-3:]:
        out.append(f"  exemplar: {ex.get('latency_ms')}ms "
                   f"path={ex.get('path')} qos={ex.get('qos')} "
                   f"topic={ex.get('topic')} "
                   f"trace={ex.get('trace_id')}")
    return "\n".join(out)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    require = None
    if "--require" in argv:
        i = argv.index("--require")
        if i + 1 >= len(argv):
            print("latency_report: --require needs a comma-separated "
                  "row list", file=sys.stderr)
            return 1
        require = [r for r in argv[i + 1].split(",") if r]
        del argv[i:i + 2]
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 1
    try:
        with open(argv[0]) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        print(f"latency_report: cannot read {argv[0]}: {e}",
              file=sys.stderr)
        return 1
    rows = _rows_of(doc)
    wanted = require if require is not None else \
        [n for n in rows if n in DEFAULT_ROWS or n == "row"]
    missing = []
    printed = 0
    for name in wanted:
        row = rows.get(name)
        if name in MICRO_ROWS:
            # a microbench row (sharded) carries its own metric,
            # not a latency section: the gate is row PRESENCE
            if row is None:
                missing.append(name)
                continue
            print(_render_micro(name, row))
            printed += 1
            continue
        lat = _latency_of(row) if row else None
        if lat is None:
            missing.append(name)
            continue
        print(render(name, lat, overload=_overload_of(row)))
        printed += 1
    if missing:
        print(f"latency_report: required bench rows missing or carry "
              f"NO latency section: {missing} — this run would "
              f"commit a p99-less headline (run with "
              f"EMQX_TPU_LATENCY=1 / BENCH_LATENCY0=1)",
              file=sys.stderr)
        return 2
    if not printed:
        print("latency_report: artifact contains no latency-bearing "
              "phase rows at all", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
