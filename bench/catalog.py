"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root names each cell (workload), its
configuration and its traffic mix, and the metrics.  Every other piece is
a file of its own, found by name:

* ``bench/configs/<config>.json``: the deployment (the file named by the
  configuration's entry in ``BENCHMARK.json``);
* ``bench/traffic/<traffic>.json``: policy, engine, execution path, jobs
  and replications of one call;
* ``bench/paths/<path>.py``: how one call enters the program;
* ``bench/reference/<policy>.py``: the plain reference of the policy;
* ``bench/metrics/<metric>.py``: ``read(record, trace)`` of one metric;
* ``bench/limits/<cell>.json``: the limit of each number compared.

A new cell, mix, path, reference or metric is a new file and an entry in
``BENCHMARK.json``; no existing file changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType

BENCH = "bench"


class CatalogError(ValueError):
    """A cell or one of its pieces is missing or malformed."""


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CatalogError(f"missing file {path}") from None


_LOADED: dict[str, ModuleType] = {}


def load_module(path: str) -> ModuleType:
    """Import a benchmark file by its path (names may hold '-'), once."""
    path = os.path.realpath(path)
    if path in _LOADED:
        return _LOADED[path]
    if not os.path.isfile(path):
        raise CatalogError(f"missing file {path}")
    parts = path.split(os.sep)[-2:]
    name = "bench_" + "_".join(parts).replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _LOADED[path] = mod
    return mod


@dataclasses.dataclass
class Cell:
    """Everything one workload of ``BENCHMARK.json`` needs to run."""

    root: str
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    limits: dict

    def file(self, *parts: str) -> str:
        return os.path.join(self.root, BENCH, *parts)

    def path_module(self) -> ModuleType:
        return load_module(self.file("paths", self.traffic["path"] + ".py"))

    def reference_module(self) -> ModuleType:
        return load_module(self.file("reference",
                                     self.traffic["policy"] + ".py"))

    def metric_module(self, name: str) -> ModuleType:
        return load_module(self.file("metrics", name + ".py"))

    @property
    def jobs_per_call(self) -> int:
        return int(self.traffic["jobs"]) * int(self.traffic["reps"])


def _applies(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def benchmark(root: str) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def cell(root: str, name: str) -> Cell:
    """The cell ``name`` of the benchmark at ``root``, with its pieces."""
    bm = benchmark(root)
    work = {w["name"]: w for w in bm["workloads"]}
    if name not in work:
        raise CatalogError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in bm["configs"]}
    if w["config"] not in configs:
        raise CatalogError(f"workload {name!r} names unknown configuration "
                           f"{w['config']!r}")
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(root, BENCH, "traffic",
                                      w["traffic"] + ".json"))
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"] if _applies(m, name, e2e_names)]
    limits = _read_json(os.path.join(root, BENCH, "limits", name + ".json"))
    return Cell(root=root, name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                limits=limits)
