"""Find a cell's files by the names in BENCHMARK.json.

Nothing here knows a cell, a configuration, a mix or a metric by name:
a later PR adds files and `BENCHMARK.json` entries and edits nothing.

    configs/<config>.json     sizes as run, `reference` names the plain
                              reference beside it
    traffic/<traffic>.json    parameters of the mix; `driver` names the
                              module under drivers/ that runs it
    cells/<cell>.json         what belongs to one cell alone (slots, batch
                              rows) with its `why`.  A cell BENCHMARK.json
                              lacks (a rehearsal) names its `config` and
                              `traffic` here, and under `metrics_as` the
                              listed cell whose metrics it prints
    metrics/<metric>.py       METRIC (name, unit, layer, moves, source,
                              why) and read(run) -> number | None
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def tmp_dir() -> str:
    """This run's scratch directory inside the checkout (the generator's
    spec and records, the trace); its driver's `after` removes it."""
    return os.path.join(ROOT, ".bench_tmp", str(os.getpid()))


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_json() -> dict:
    path = os.path.join(ROOT, "BENCHMARK.json")
    return _json(path) if os.path.isfile(path) else {}


class Cell:
    """One workload: its configuration, mix, own sizes and metrics."""

    def __init__(self, name: str):
        self.name = name
        bench = benchmark_json()
        entry = next((w for w in bench.get("workloads", ())
                      if w["name"] == name), None)
        cell_path = os.path.join(BENCH_DIR, "cells", name + ".json")
        self.own = _json(cell_path) if os.path.isfile(cell_path) else {}
        if entry is None and not self.own:
            raise SystemExit(
                f"unknown workload {name!r}: neither in BENCHMARK.json nor "
                f"in {os.path.relpath(cell_path, ROOT)}")
        self.listed = entry is not None
        src = entry or self.own
        # The one selection of metrics is BENCHMARK.json's: a rehearsal
        # borrows a listed cell's, so it proves the same lookup.
        self.metrics_as = name if entry else self.own.get("metrics_as")
        self.config_name, self.traffic_name = src["config"], src["traffic"]
        self.chips = int(src.get("chips", 1))
        cfg_entry = next((c for c in bench.get("configs", ())
                          if c["name"] == self.config_name), None)
        cfg_path = (os.path.join(ROOT, cfg_entry["file"]) if cfg_entry else
                    os.path.join(BENCH_DIR, "configs",
                                 self.config_name + ".json"))
        self.config = _json(cfg_path)
        self.reference = load_module(
            os.path.join(os.path.dirname(cfg_path), self.config["reference"]),
            "bench_reference_" + self.config_name.replace("-", "_"))
        self.traffic = _json(os.path.join(
            BENCH_DIR, "traffic", self.traffic_name + ".json"))
        # A cell's own sizes override the mix's defaults, key by key.
        self.params = {**self.traffic, **self.own.get("params", {})}
        self._bench = bench

    def driver(self):
        name = self.traffic["driver"]
        return load_module(
            os.path.join(BENCH_DIR, "drivers", name + ".py"),
            "bench_driver_" + name)

    def _applies(self, metric: dict, reported: set) -> bool:
        if "workloads" in metric:
            return self.metrics_as in metric["workloads"]
        return "moves" not in metric or metric["moves"] in reported

    def metric_names(self, trace: bool) -> list[str]:
        """The metrics this run prints: the cell's `end_to_end` ones
        without a trace, its `per_layer` ones with it."""
        if not any(w["name"] == self.metrics_as
                   for w in self._bench.get("workloads", ())):
            raise SystemExit(
                f"{self.name!r} is not in BENCHMARK.json and its file names "
                "no listed cell under `metrics_as`")
        e2e = [m["name"] for m in self._bench["end_to_end"]
               if self._applies(m, set())]
        if not trace:
            return e2e
        return [m["name"] for m in self._bench["per_layer"]
                if self._applies(m, set(e2e))]


def metric_reader(name: str):
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"metric {name!r} has no reader at "
                         f"{os.path.relpath(path, ROOT)}")
    return load_module(path, "bench_metric_" + name.replace(".", "_")
                       .replace("-", "_"))
