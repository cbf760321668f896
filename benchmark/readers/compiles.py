"""Executables built inside the window under a label the background
warm passes did not issue: a compile on the serving path. 0 expected."""

from __future__ import annotations


def read(ctx):
    b0 = ctx["tele0"]["compiles"]["by_shape"]
    b1 = ctx["tele1"]["compiles"]["by_shape"]
    return float(sum(
        v["executables"] - b0.get(k, {"executables": 0})["executables"]
        for k, v in b1.items() if not k.startswith("warm")))
