"""Finds everything by name: the cell in ``BENCHMARK.json``, its
configuration's file, and — under the benchmark's own directory — the
traffic mix ``traffic/<name>.json``, each statement
``statements/<name>.py`` and each per-layer metric's reader
``layer_metrics/<name>.py``. Nothing here lists names: a later PR adds a
cell, a mix, a statement or a metric as files and one entry."""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class SpecError(Exception):
    pass


def load_benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise SpecError(f"no BENCHMARK.json at {root}")
    with open(path) as f:
        return json.load(f)


def _module(kind: str, name: str, here: str):
    path = os.path.join(here, kind, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no {kind}/{name}.py under {here}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.here = os.path.join(root, os.path.relpath(HERE, ROOT))
        self.bench = load_benchmark(root)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                            f"(has {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = {c["name"]: c for c in self.bench["configs"]}[self.entry["config"]]
        with open(os.path.join(root, cfg["file"])) as f:
            self.config = json.load(f)
        if int(self.config["chips"]) != self.chips:
            raise SpecError(f"{name}: the cell asks for {self.chips} chips, "
                            f"its configuration for {self.config['chips']}")
        tpath = os.path.join(self.here, "traffic", self.entry["traffic"] + ".json")
        if not os.path.isfile(tpath):
            raise SpecError(f"no traffic/{self.entry['traffic']}.json")
        with open(tpath) as f:
            self.traffic = json.load(f)
        self.statements = {}
        for item in self.traffic["menu"] + self.traffic.get("writers", []):
            s = item["statement"]
            if s not in self.statements:
                self.statements[s] = _module("statements", s, self.here)

    def _metrics(self, group: str) -> list:
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def end_to_end(self) -> list:
        return self._metrics("end_to_end")

    def per_layer(self) -> list:
        reports = {m["name"] for m in self.end_to_end()}
        return [m for m in self._metrics("per_layer") if m["moves"] in reports]

    def reader(self, metric: str):
        return _module("layer_metrics", metric, self.here).read
