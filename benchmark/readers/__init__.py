"""Per-layer metric readers, found by the name a metric's file gives.

A reader is a module here with `read(ctx, **args) -> float | None`; it
takes its number from counters, telemetry spans, the generators' logs or
the reduced profiler trace in `ctx`, and returns None when it finds
nothing to read (the harness then leaves the metric out of the line).
"""

from __future__ import annotations

import importlib


def read_metric(ctx: dict, reader: str, args: dict):
    mod = importlib.import_module(f"benchmark.readers.{reader}")
    return mod.read(ctx, **args)
