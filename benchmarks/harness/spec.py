"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names its ``config`` and ``traffic``; those are
``configs/<config>.json`` and ``traffic/<traffic>.json``; its limits are
``limits/<cell>.json``; the per-layer metrics are every ``*.json`` in
``metrics/``. Nothing here knows a name: a later PR adds a cell, a
configuration, a mix or a metric by adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything its files say."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "benchmarks")
        self.benchmark = _load(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in cells:
            raise SystemExit(
                f"benchmark: no workload {name!r} in BENCHMARK.json "
                f"(have {sorted(cells)})")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.benchmark["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = _load(os.path.join(root, self.config_entry["file"]))
        self.traffic = _load(os.path.join(
            self.bench_dir, "traffic", self.entry["traffic"] + ".json"))
        self.limits = _load(os.path.join(
            self.bench_dir, "limits", name + ".json"))

    def _reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> list:
        return [m for m in self.benchmark["end_to_end"] if self._reports(m)]

    def per_layer(self) -> list:
        """The cell's per-layer metrics, each with its file's reducer."""
        out = []
        for m in self.benchmark["per_layer"]:
            if not self._reports(m):
                continue
            path = os.path.join(self.bench_dir, "metrics", m["name"] + ".json")
            out.append({**_load(path), **m})
        return out

    def metric_reader(self, name: str):
        """``read(ctx)`` of ``metrics/<name>.py`` where the file exists."""
        path = os.path.join(self.bench_dir, "metrics", name + ".py")
        if not os.path.exists(path):
            return None
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + name.replace("-", "_").replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def load_peaks(device_kind: str) -> dict:
    peaks = _load(os.path.join(BENCH_DIR, "harness", "peaks.json"))
    if device_kind not in peaks:
        raise SystemExit(
            f"benchmark: device kind {device_kind!r} has no entry in "
            "benchmarks/harness/peaks.json — an unknown chip is an error, "
            "not a default")
    return peaks[device_kind]
