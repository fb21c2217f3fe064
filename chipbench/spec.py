"""Find a cell's files by the names in BENCHMARK.json.  No model size,
traffic number or cell name lives in code: this module only joins files."""

from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path):
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` joined with its files."""

    def __init__(self, name: str, bench: dict | None = None):
        bench = bench or benchmark()
        rows = [w for w in bench["workloads"] if w["name"] == name]
        if not rows:
            raise SystemExit(f"chipbench: no workload {name!r} in "
                             f"BENCHMARK.json")
        self.bench = bench
        self.workload = rows[0]
        self.name = name
        self.chips = int(self.workload["chips"])
        cfg_row = next(c for c in bench["configs"]
                       if c["name"] == self.workload["config"])
        self.config = _load(os.path.join(ROOT, cfg_row["file"]))
        self.traffic = _load(os.path.join(
            HERE, "traffic", self.workload["traffic"] + ".json"))
        self.mode = self.traffic["mode"]
        key = f"{self.mode}.{self.chips}"
        if key not in self.config["depth"]:
            raise SystemExit(
                f"chipbench: configuration {self.config['name']!r} has no "
                f"written depth for {key!r}")
        self.depth = int(self.config["depth"][key])
        self.published = self.config["published"]

    def reports(self, metric: dict) -> bool:
        """Whether this cell reports ``metric`` (an entry of
        ``end_to_end`` or ``per_layer``).  A per-layer metric without a
        ``workloads`` key is reported wherever the end-to-end metric it
        moves is."""
        if "workloads" in metric:
            return self.name in metric["workloads"]
        if "moves" in metric:
            return self.reports(next(m for m in self.bench["end_to_end"]
                                     if m["name"] == metric["moves"]))
        return True

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self.reports(m)]

    def per_layer(self):
        return [m for m in self.bench["per_layer"] if self.reports(m)]

    def driver(self):
        return importlib.import_module(
            f"chipbench.drivers.{self.traffic['driver']}")

    def _of_family(self, package):
        return importlib.import_module(
            f"chipbench.{package}.{self.config['family']}")

    def reference(self):
        """The plain reference of the configuration's family."""
        return self._of_family("reference")

    def weights(self):
        """The family's seeded weights (handed to both sides)."""
        return self._of_family("weights")

    def layout(self):
        """Where the family's weights sit in the program's tree."""
        return self._of_family("layouts")


def layer_metric(name: str) -> dict:
    """``chipbench/layer_metrics/<name>.json``: reader and parameters."""
    return _load(os.path.join(HERE, "layer_metrics", name + ".json"))


def reader(name: str):
    return importlib.import_module(f"chipbench.readers.{name}")


def roofline(name: str):
    return importlib.import_module(f"chipbench.rooflines.{name}")


def peaks(device_kind: str) -> dict:
    table = _load(os.path.join(HERE, "peaks.json"))["by_device_kind"]
    if device_kind not in table:
        raise SystemExit(f"chipbench: no peaks for device kind "
                         f"{device_kind!r} in chipbench/peaks.json")
    return table[device_kind]
