"""``BENCHMARK.json`` and the files it names, found by name.

configs/<config>.json       a configuration, its sizes as run
reference/<reference>.py    the plain model the configuration's
                            ``reference`` key names (reference/__init__.py)
traffic/<traffic>.json      a traffic mix: the parameters the feed and the
                            step are built from
limits/<cell>.json          the limits of the numbers that decide
                            ``correct`` in that cell
layer_metrics/<metric>.py   the reader of one per-layer metric
kernels/<kernel>.py         operations and bytes of one kernel's operation
"""
from __future__ import annotations

import importlib.util
import json
import os

from reference import reference_for

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
        self.workload = cells[name]
        self.name = name
        self.chips = self.workload["chips"]
        conf = {c["name"]: c for c in self.bench["configs"]}[
            self.workload["config"]]
        self.config_name = conf["name"]
        self.config = load_json(os.path.join(root, conf["file"]))
        reference_for(self.config, conf["file"])     # fails early, by name
        self.traffic_name = self.workload["traffic"]
        self.traffic = load_json(self.path("traffic", self.traffic_name
                                           + ".json"))
        self.limits = load_json(self.path("limits", name + ".json"))
        if self.traffic["data_chips"] != self.chips:
            raise ValueError(f"{name}: traffic runs on "
                             f"{self.traffic['data_chips']} chips, the cell "
                             f"asks for {self.chips}")

    def path(self, *parts) -> str:
        return os.path.join(self.root, "bench", *parts)

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list:
        """The per-layer metrics this cell reports: those that list it, and
        those without a list whose end-to-end metric it reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def roofline_kernels(self) -> list:
        """Kernels whose ``<kernel>_roofline`` this cell reports."""
        return [m["name"][:-len("_roofline")] for m in self.per_layer()
                if m["name"].endswith("_roofline")]

    def kernel_cost(self, kernel: str):
        return load_module(self.path("kernels", kernel + ".py"),
                           "kernel_cost_" + kernel)

    def reader(self, metric: str):
        return load_module(self.path("layer_metrics", metric + ".py"),
                           "layer_metric_" + metric.replace(".", "_"))
