"""Finds a cell's configuration, traffic mix and per-layer metrics by the
names ``BENCHMARK.json`` gives them, each in a file of its own:

* a configuration: the ``file`` its entry names (``bench/configs/``);
* a traffic mix: ``bench/mixes/<traffic>.json``, whose ``driver`` key
  names the general driver that reads it, ``bench/harness/<driver>.py``
  (``harness.main.driver_for``);
* a per-layer metric: ``bench/metrics/<name>.py``, a small reader with
  ``read(run) -> float | None``.

A later cell, configuration, driver or metric is a new file and a new
entry; no file that is here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


class Catalog:
    def __init__(self, checkout: str = CHECKOUT,
                 bench_dir: str = BENCH_DIR):
        self.checkout = checkout
        self.bench_dir = bench_dir
        with open(os.path.join(checkout, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.spec["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: {known}")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.checkout, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def mix(self, traffic: str) -> dict:
        with open(os.path.join(self.bench_dir, "mixes",
                               f"{traffic}.json")) as f:
            return json.load(f)

    def _applies(self, metric: dict, workload: str) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    def end_to_end(self, workload: str) -> "list[dict]":
        return [m for m in self.spec["end_to_end"]
                if self._applies(m, workload)]

    def per_layer(self, workload: str) -> "list[dict]":
        return [m for m in self.spec["per_layer"]
                if self._applies(m, workload)]

    def reader(self, metric: str):
        """The ``read`` function of ``bench/metrics/<metric>.py``."""
        path = os.path.join(self.bench_dir, "metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_').replace('-', '_')}",
            path,
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read
