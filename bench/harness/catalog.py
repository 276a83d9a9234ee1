"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell lives in a file of its own, found by name:

    bench/configs/<config>.json       sizes of the configuration as run
    bench/configs/<reference>.py      its plain reference (named in the json)
    bench/traffic/<traffic>.json      parameters of the traffic mix
    bench/metrics/<metric>.py         reader of one per-layer metric
    bench/limits/<workload>.json      the limits that decide ``correct``
    bench/peaks.json                  the chip's peaks, by device kind

so a new cell, configuration, mix or metric is added as files and entries,
with no edit to a file that is already here.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class CatalogError(RuntimeError):
    pass


def _json(path: str) -> dict:
    if not os.path.exists(path):
        raise CatalogError(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def _module(path: str, tag: str):
    if not os.path.exists(path):
        raise CatalogError(f"missing {path}")
    name = "bench_" + tag + "_" + "".join(
        c if c.isalnum() else "_" for c in os.path.basename(path)[:-3])
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Catalog:
    def __init__(self, root: str = ROOT, bench_dir: str = BENCH_DIR):
        self.root = root
        self.dir = bench_dir
        self.benchmark = _json(os.path.join(root, "BENCHMARK.json"))

    def workload(self, name: str) -> dict:
        for w in self.benchmark["workloads"]:
            if w["name"] == name:
                return w
        raise CatalogError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.benchmark["configs"]:
            if c["name"] == name:
                return _json(os.path.join(self.root, c["file"]))
        raise CatalogError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _json(os.path.join(self.dir, "traffic", name + ".json"))

    def limits(self, workload: str) -> dict:
        return _json(os.path.join(self.dir, "limits", workload + ".json"))

    def reference(self, name: str):
        return _module(os.path.join(self.dir, "configs", name + ".py"), "ref")

    def reader(self, metric: str):
        return _module(os.path.join(self.dir, "metrics", metric + ".py"),
                       "metric")

    def peaks(self, device_kind: str) -> dict:
        table = _json(os.path.join(self.dir, "peaks.json"))
        if device_kind not in table:
            raise CatalogError(f"device kind {device_kind!r} is not in "
                               f"bench/peaks.json ({sorted(table)})")
        return table[device_kind]

    def metrics(self, workload: str, kind: str) -> list:
        """The cell's ``end_to_end`` or ``per_layer`` entries."""
        return [m for m in self.benchmark[kind]
                if workload in m.get("workloads", (workload,))]
