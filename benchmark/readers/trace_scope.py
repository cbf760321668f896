"""Device self time of the route programs' operations, by the
`jax.named_scope` they were traced under.

The route programs wrap each phase in a named scope
(`emqx_tpu/models/router_engine.py`: `match`, `fanout`, `shared`,
`compact`, `delta`, `exchange`, and `scan` around a window program's
`lax.scan`), and XLA keeps the scope path of every operation as its
`op_name` metadata, which the profiler writes as a stat of the
operation's event on the device plane's "XLA Ops" line.
`xplane.from_file` keeps names only, so the `.xplane.pb` under
`ctx["trace_dir"]` is read again here, with the stats this reader and
`trace_join` need (`load`).

That line nests: a `while` is one event from its first iteration to
its last, and the operations of its body are events inside its
interval (so `xplane.reduce`'s `device_ops` counts a `while` together
with its body). **Self time** of an operation is its duration minus
the operations nested in its interval; self times of one line add up
to the union of its intervals, with nothing counted twice.

The value: milliseconds of self time, inside the traced window, of
operations of the programs whose module name holds any of `match`
that ran under scope `scope` (the innermost of the known scopes in
the operation's path; "" for none), per device window formed while
the trace ran (`routing.device.batches`, as `trace_program` counts
them), mean over the device planes. 0 when none was formed, when the
trace carries no scope path (a CPU rehearsal; a program without the
scopes), or when no such operation ran.
"""

from __future__ import annotations

import math

from benchmark.readers import xplane

SCOPES = ("match", "fanout", "shared", "compact", "delta", "exchange",
          "scan")
# where the profiler puts an operation's `op_name`, first that is there
PATH_STATS = ("tf_op", "op_name", "name_scope")
KEEP_STATS = ("trace_id", "run_id")
HOST_EVENTS = ("emqx:", xplane.SPAN)


def _fields(buf: bytes, pos: int, end: int):
    """(field number, wire type, value) of one protobuf message: the
    int of a varint, the (start, end) of a length-delimited field."""
    while pos < end:
        tag = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            tag |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        num, wire = tag >> 3, tag & 7
        if wire == 0:
            val = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield num, wire, val
        elif wire == 2:
            n = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                n |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield num, wire, (pos, pos + n)
            pos += n
        elif wire in (1, 5):
            pos += 8 if wire == 1 else 4
        else:
            raise ValueError(f"xplane: wire type {wire} at {pos}")


def op_paths(path: str) -> dict:
    """{plane name: {event name: scope path}} from the event metadata
    of an `.xplane.pb` (`XSpace.planes[].event_metadata[].stats`, where
    the profiler keeps what is the same for every execution of an
    operation; `jax.profiler.ProfileData` shows an event's own stats
    only). The lines, which hold nearly all the bytes, are skipped."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for num, _w, v in _fields(buf, 0, len(buf)):
        if num != 1:                                    # XSpace.planes
            continue
        name, stat_names, metas = "", {}, []
        for pn, _pw, pv in _fields(buf, *v):
            if pn == 2:                                 # XPlane.name
                name = buf[pv[0]:pv[1]].decode()
            elif pn in (4, 5):          # event_metadata, stat_metadata
                for en, _ew, ev in _fields(buf, *pv):
                    if en == 2:                         # the map's value
                        if pn == 5:
                            sid, sname = 0, ""
                            for mn, _mw, mv in _fields(buf, *ev):
                                if mn == 1:
                                    sid = mv
                                elif mn == 2:
                                    sname = buf[mv[0]:mv[1]].decode()
                            stat_names[sid] = sname
                        else:
                            metas.append(ev)
        wanted = {sid for sid, n in stat_names.items() if n in PATH_STATS}
        paths = {}
        for m in metas:
            ev_name, found = "", None
            for mn, _mw, mv in _fields(buf, *m):
                if mn == 2:                     # XEventMetadata.name
                    ev_name = buf[mv[0]:mv[1]].decode(errors="replace")
                elif mn == 5:                   # XEventMetadata.stats
                    sid, val = 0, None
                    for sn, _sw, sv in _fields(buf, *mv):
                        if sn == 1:
                            sid = sv
                        elif sn == 5:                   # str_value
                            val = buf[sv[0]:sv[1]].decode(
                                errors="replace")
                        elif sn == 7:       # ref_value: a stat's name
                            val = stat_names.get(sv, "")
                    if sid in wanted and val:
                        found = val
            if found:
                paths[ev_name] = found
        if paths:
            out[name] = paths
    return out


def load(path: str) -> dict:
    """The plain form of `xplane.from_file`, cut to what the readers of
    stats use, with each event's kept stats as a 4th element: the
    device planes' operation and module lines (an operation's scope
    path under `PATH_STATS[0]`), and the host's `emqx:*` spans and the
    harness's own."""
    from jax.profiler import ProfileData
    paths = op_paths(path)
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        by_name = paths.get(plane.name, {})
        lines = []
        for line in plane.lines:
            if device and line.name not in (xplane.OPS_LINE,
                                            xplane.MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(HOST_EVENTS):
                    continue
                stats = {k: v for k, v in ev.stats if k in KEEP_STATS}
                if name in by_name:
                    stats[PATH_STATS[0]] = by_name[name]
                events.append([name, float(ev.start_ns),
                               float(ev.duration_ns), stats])
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def loaded(ctx) -> dict | None:
    """The trace with stats: `ctx["trace_stats"]` (a test hands one in),
    or read once from the file under `ctx["trace_dir"]`."""
    t = ctx.get("trace_stats")
    if t is None and ctx.get("trace_dir"):
        t = ctx["trace_stats"] = load(xplane.find_xplane(ctx["trace_dir"]))
    return t


def scope_of(stats: dict) -> str:
    """The innermost known scope in an operation's path, "" for none."""
    for key in PATH_STATS:
        path = stats.get(key)
        if path:
            # "<scope path>/<primitive>:<type>"
            for part in reversed(str(path).rsplit(":", 1)[0].split("/")):
                if part in SCOPES:
                    return part
            return ""
    return ""


def self_times(events: list) -> list:
    """[(event, self_ns)] for the events [(name, start, end, ...)] of
    one line: duration minus the events nested in the interval. An
    event that ends after the one it starts in is cut to it."""
    out, stack = [], []           # stack of [event, start, end, child ns]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            ev, s, e, inner = stack.pop()
            out.append((ev, (e - s) - inner))
            if stack:
                stack[-1][3] += e - s

    for ev in sorted(events, key=lambda x: (x[1], -x[2])):
        s, e = ev[1], ev[2]
        close(s)
        if stack:
            e = min(e, stack[-1][2])
        stack.append([ev, s, e, 0.0])
    close(math.inf)
    return out


def window(trace: dict):
    """`xplane.window` for the form with stats: the harness's window
    annotation, or else everything the trace holds."""
    events = [ev for p in trace["planes"] for ln in p["lines"]
              for ev in ln["events"]]
    for ev in events:
        if ev[0] == xplane.WINDOW_SPAN:
            return ev[1], ev[1] + ev[2]
    if not events:
        raise ValueError("empty trace")
    return (min(ev[1] for ev in events),
            max(ev[1] + ev[2] for ev in events))


def scope_seconds(trace: dict, match: list) -> dict:
    """{scope: seconds of self time} of the operations that ran inside
    a module whose name holds any of `match`, in the traced window,
    mean over the device planes; "" is the time under no known scope.
    "device_s" is those modules' own time (`xplane.program_seconds`)."""
    t0, t1 = window(trace)
    planes = xplane.device_planes(trace)
    total: dict = {}
    for p in planes:
        mods = xplane.union([
            (s, e) for n, s, e in xplane._clip(
                [ev[:3] for ev in xplane._line(p, xplane.MODULES_LINE)],
                t0, t1) if any(x in n for x in match)])
        ops = [(ev[0], max(ev[1], t0), min(ev[1] + ev[2], t1), ev[3])
               for ev in xplane._line(p, xplane.OPS_LINE)
               if ev[1] + ev[2] > t0 and ev[1] < t1]
        k = 0
        for ev, self_ns in sorted(self_times(ops), key=lambda x: x[0][1]):
            while k < len(mods) and mods[k][1] <= ev[1]:
                k += 1
            if k < len(mods) and mods[k][0] <= ev[1]:
                sc = scope_of(ev[3])
                total[sc] = total.get(sc, 0.0) + self_ns
    n = max(1, len(planes))
    return {sc: v / n / 1e9 for sc, v in total.items()}


def read(ctx, scope, match):
    trace = loaded(ctx)
    if not trace or "trace_m1" not in ctx:
        return None
    key = "_scope_seconds:" + ",".join(match)
    by_scope = ctx.get(key)
    if by_scope is None:
        by_scope = ctx[key] = scope_seconds(trace, match) \
            if xplane.device_planes(trace) else {}
    windows = ctx["trace_m1"].get("routing.device.batches", 0) \
        - ctx["trace_m0"].get("routing.device.batches", 0)
    return 1000.0 * by_scope.get(scope, 0.0) / windows if windows else 0.0
