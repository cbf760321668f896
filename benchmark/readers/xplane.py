"""Reduction of a `jax.profiler` trace (`.xplane.pb`) to what the
benchmark reports: device busy seconds, the device operations that took
most time, the longest idle gaps named by what the host was doing, and
the device time of programs by name.

The trace is first brought to a plain form,
`{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns], ...]}]}]}`, so that the arithmetic below can be checked on a
small recorded trace kept as JSON (`tests/data/trace_small.json`).
"""

from __future__ import annotations

import glob
import os

# one line of a TPU plane holds every executed HLO operation; the other
# lines (steps, modules, TraceMes, framework ops) cover the same time
# again and must not be added to it
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
CHIP_PREFIX = "#Chip"           # the profiler's own planes of a chip it watched
SPAN = "bench:"                 # prefix of the harness's own annotations
WINDOW_SPAN = "bench:trace_window"


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def from_file(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list:
    """The TPU planes that hold an ops line. Where there is none but
    the profiler watched a chip, nothing ran on it while the trace was
    on, and one plane without an operation stands for it: an idle trace
    is a reading, not a fault. (A v5e on which nothing was dispatched
    leaves `#Chip0 Host Interface` and `#Chip0 Misc` and no
    `/device:TPU:0` at all: my chip run, PR 26.)"""
    busy = [p for p in trace["planes"]
            if p["name"].startswith(DEVICE_PREFIX)
            and any(ln["name"] == OPS_LINE for ln in p["lines"])]
    if busy or not any(p["name"].startswith((DEVICE_PREFIX, CHIP_PREFIX))
                       for p in trace["planes"]):
        return busy
    return [{"name": "idle chip", "lines": []}]


def _rehearsal_plane(trace: dict) -> list:
    """The CPU backend has no device plane: a rehearsal reduces the
    host's XLA threads instead, to exercise the arithmetic only."""
    lines = [{"name": OPS_LINE, "events": [
        ev for p in trace["planes"] if p["name"].startswith("/host:")
        for ln in p["lines"] if "XLA" in ln["name"] or "xla" in ln["name"]
        for ev in ln["events"]]}]
    return [{"name": "rehearsal", "lines": lines}]


def _line(plane: dict, name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def host_spans(trace: dict) -> list:
    """The harness's own annotations, [(name, start, end)], any thread."""
    out = []
    for p in trace["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            out += [(n, s, s + d) for n, s, d in ln["events"]
                    if n.startswith(SPAN)]
    return out


def window(trace: dict):
    """(start_ns, end_ns) of the traced window: the harness's window
    annotation, or else everything the trace holds."""
    for n, s, e in host_spans(trace):
        if n == WINDOW_SPAN:
            return s, e
    starts, ends = [], []
    for p in trace["planes"]:
        for ln in p["lines"]:
            for _n, s, d in ln["events"]:
                starts.append(s)
                ends.append(s + d)
    if not starts:
        raise ValueError("empty trace")
    return min(starts), max(ends)


def union(intervals: list) -> list:
    """Merged, sorted [(start, end)]."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(events, t0, t1):
    return [(n, max(s, t0), min(s + d, t1)) for n, s, d in events
            if s + d > t0 and s < t1]


def reduce(trace: dict, top: int = 10, any_device: bool = False) -> dict:
    """busy_s (mean over the device planes), window_s, `device_ops` and
    `idle_gaps` as the result line's `breakdown` wants them."""
    t0, t1 = window(trace)
    planes = device_planes(trace)
    if not planes and any_device:
        planes = _rehearsal_plane(trace)
    if not planes:
        raise ValueError("the trace holds no plane of a TPU")
    # innermost (shortest) first: a gap's time goes to the harness span
    # that covers it most closely, and the rest to "host:other"
    spans = sorted(((n, max(s, t0), min(e, t1))
                    for n, s, e in host_spans(trace)
                    if n != WINDOW_SPAN and e > t0 and s < t1),
                   key=lambda x: x[2] - x[1])
    busy = []
    by_op: dict = {}
    gaps: dict = {}
    for p in planes:
        ops = _clip(_line(p, OPS_LINE), t0, t1)
        merged = union([(s, e) for _n, s, e in ops])
        busy.append(sum(e - s for s, e in merged))
        for n, s, e in ops:
            n = n.split(" = ")[0].lstrip("%")       # the HLO's own name
            by_op[n] = by_op.get(n, 0.0) + (e - s)
        edges = [t0] + [x for se in merged for x in se] + [t1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            left = [(g0, g1)]
            for n, s, e in spans:
                nxt = []
                for a, b in left:
                    lo, hi = max(a, s), min(b, e)
                    if hi <= lo:
                        nxt.append((a, b))
                        continue
                    gaps[n] = gaps.get(n, 0.0) + (hi - lo)
                    if a < lo:
                        nxt.append((a, lo))
                    if hi < b:
                        nxt.append((hi, b))
                left = nxt
            rest = sum(b - a for a, b in left)
            if rest:
                gaps["host:other"] = gaps.get("host:other", 0.0) + rest
    k = len(planes)

    def rank(d):
        return [[n, v / k / 1e9] for n, v in
                sorted(d.items(), key=lambda x: -x[1])[:top]]

    return {"busy_s": sum(busy) / k / 1e9, "window_s": (t1 - t0) / 1e9,
            "device_ops": rank(by_op), "idle_gaps": rank(gaps),
            "chips": k}


def program_seconds(trace: dict, match: list) -> tuple:
    """(device seconds, executions) of the XLA modules whose name holds
    any of `match`, inside the window, summed over the device planes."""
    t0, t1 = window(trace)
    total, n = 0.0, 0
    for p in device_planes(trace):
        for name, s, e in _clip(_line(p, MODULES_LINE), t0, t1):
            if any(x in name for x in match):
                total += e - s
                n += 1
    return total / 1e9, n
