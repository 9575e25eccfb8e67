"""Find a cell's files by the names in ``BENCHMARK.json``.

Each configuration, traffic mix, set of limits and per-layer metric is a
file of its own, looked up by name:

    <file of the configuration entry>      sizes, source, deployment
    bench/traffic/<traffic>.json           parameters of the traffic mix
    bench/limits/<workload>.json           the correctness limits of a cell
    bench/metrics/<metric>.py              the reader of a per-layer metric
    bench/families/<family>.py             a model family's weights,
                                           reference and counts

Lookups search the data root first (the directory of the BENCHMARK.json
in use) and then this checkout, so a cell defined in another directory can
reuse the files here.  Adding a cell, a mix, a metric or a model family
means adding files and entries, never editing code.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]


class Spec:
    def __init__(self, path=None):
        self.path = pathlib.Path(path or ROOT / "BENCHMARK.json").resolve()
        self.root = self.path.parent
        self.data = json.loads(self.path.read_text())

    def _find(self, rel: str) -> pathlib.Path:
        for base in (self.root, ROOT):
            p = base / rel
            if p.is_file():
                return p
        raise FileNotFoundError(f"{rel} not found under {self.root} or {ROOT}")

    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.path}")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads(self._find(c["file"]).read_text())
        raise KeyError(f"no config {name!r} in {self.path}")

    def traffic(self, name: str) -> dict:
        return json.loads(self._find(f"bench/traffic/{name}.json").read_text())

    def limits(self, workload: str) -> dict:
        return json.loads(self._find(f"bench/limits/{workload}.json").read_text())

    def end_to_end(self, workload: str) -> list:
        return [m for m in self.data["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> list:
        e2e = {m["name"] for m in self.end_to_end(workload)}
        return [m for m in self.data["per_layer"]
                if workload in m.get("workloads", [workload])
                and m["moves"] in e2e]

    def _module(self, rel: str, prefix: str, name: str):
        path = self._find(rel)
        spec = importlib.util.spec_from_file_location(
            prefix + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def reader(self, metric: str):
        return self._module(f"bench/metrics/{metric}.py", "bench_metric_",
                            metric).read

    def family(self, model: dict):
        """The module of ``model["family"]`` (``bench/families/dense.py``
        says what it holds)."""
        name = model["family"]
        return self._module(f"bench/families/{name}.py", "bench_family_",
                            name)
