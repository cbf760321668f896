"""Find a cell's files by the names `BENCHMARK.json` gives, and refuse
a manifest whose pieces do not fit together.

A configuration is `configs/<config>.json` (the manifest's `file`), a
traffic mix `traffic/<traffic>.json`, a per-layer metric
`layer_metrics/<name>.json`; nothing here names a cell, so a later PR
adds one by adding files and manifest entries.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class ManifestError(ValueError):
    pass


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str, cells_reporting: dict) -> bool:
    """Does `metric` belong to `cell`? With a `workloads` key: where it
    is listed. Without: a per-layer metric belongs to every cell that
    reports the end-to-end metric it moves; an end-to-end metric to
    every cell."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return cell in cells_reporting[metric["moves"]]
    return True


class Cell:
    """One entry of `workloads` with everything it points at loaded."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench = b = _load(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in b["workloads"]}
        if name not in cells:
            raise ManifestError(
                f"no workload {name!r}; there are {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.run_seconds = int(b["run_seconds"])
        configs = {c["name"]: c for c in b["configs"]}
        self.config_path = os.path.join(
            root, configs[self.entry["config"]]["file"])
        self.config = _load(self.config_path)
        self.traffic_path = os.path.join(
            HERE, "traffic", self.entry["traffic"] + ".json")
        self.traffic = _load(self.traffic_path)

        e2e = {m["name"]: m for m in b["end_to_end"]}
        # which cells report which end-to-end metric
        self.cells_reporting = {
            n: [c for c in cells if applies(m, c, {})]
            for n, m in e2e.items()}
        self.end_to_end = [m for n, m in e2e.items()
                           if name in self.cells_reporting[n]]
        can = set(self.traffic["reports"]) | {"setup_s"}
        for m in self.end_to_end:
            if m["name"] not in can:
                raise ManifestError(
                    f"{name}: the manifest wants {m['name']} but the "
                    f"traffic mix {self.traffic['name']!r} reports "
                    f"{sorted(can)}")
        if len(self.end_to_end) < 2:
            raise ManifestError(f"{name}: reports no end-to-end metric "
                                f"besides setup_s")
        self.per_layer = []
        for m in b["per_layer"]:
            if m["moves"] not in e2e:
                raise ManifestError(f"{m['name']} moves {m['moves']!r}, "
                                    f"which is no end-to-end metric")
            for c in m.get("workloads", ()):
                if c in cells and c not in self.cells_reporting[m["moves"]]:
                    raise ManifestError(
                        f"per-layer metric {m['name']} is listed for "
                        f"{c}, which does not report {m['moves']}")
            if not applies(m, name, self.cells_reporting):
                continue
            spec = _load(os.path.join(HERE, "layer_metrics",
                                      m["name"] + ".json"))
            for k in ("unit", "moves"):
                if spec.get(k) != m[k]:
                    raise ManifestError(
                        f"{m['name']}: {k} is {spec.get(k)!r} in its file "
                        f"and {m[k]!r} in BENCHMARK.json")
            self.per_layer.append({**m, "reader": spec["reader"],
                                   "args": spec.get("args", {})})
        if not self.per_layer:
            raise ManifestError(f"{name}: reports no per-layer metric")
