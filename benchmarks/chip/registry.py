"""Finds the benchmark's parts by name, each in a file of its own.

    configs/<config>.json     one archive deployment
    data/<kind>.py            ``generate(rng, n) -> bytes``
    encoders/<format>.py      ``encode(data, **options)``, ``decode(archive)``
    traffic/<mix>.json        parameters, naming ``drivers/<driver>.py``
    drivers/<driver>.py       ``drive(cell, traffic, deadline) -> dict``
    metrics/<metric>.py       ``read(run) -> float | None``

Adding a configuration, a mix or a metric is adding a file and an entry in
``BENCHMARK.json``; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class Registry:
    def __init__(self, bench_dir: str = HERE, spec_path: str = os.path.join(ROOT, "BENCHMARK.json")):
        self.dir = bench_dir
        with open(spec_path) as f:
            self.spec = json.load(f)

    def _json(self, kind: str, name: str) -> Dict[str, Any]:
        with open(os.path.join(self.dir, kind, name + ".json")) as f:
            return json.load(f)

    def _module(self, kind: str, name: str):
        path = os.path.join(self.dir, kind, name + ".py")
        spec = importlib.util.spec_from_file_location("chipbench_%s_%s" % (kind, name.replace(".", "_")), path)
        if spec is None or not os.path.exists(path):
            raise KeyError("no %s named %r (%s)" % (kind, name, path))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def workload(self, name: str) -> Dict[str, Any]:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError("no workload named %r in BENCHMARK.json" % name)

    def config(self, name: str) -> Dict[str, Any]:
        return self._json("configs", name)

    def traffic(self, name: str) -> Dict[str, Any]:
        return self._json("traffic", name)

    def generator(self, kind: str):
        return self._module("data", kind).generate

    def encoder(self, fmt: str):
        return self._module("encoders", fmt)

    def driver(self, name: str):
        return self._module("drivers", name).drive

    def metric(self, name: str):
        return self._module("metrics", name).read

    def metrics_for(self, workload: str, section: str):
        """The ``end_to_end`` or ``per_layer`` entries that ``workload`` reports."""
        return [m for m in self.spec[section]
                if "workloads" not in m or workload in m["workloads"]]
