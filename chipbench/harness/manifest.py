"""``BENCHMARK.json`` and the files it names.

The harness holds no list of configurations, traffic mixes, job kinds or
layer metrics: a cell names its configuration and its traffic mix, the
traffic file names its job kind, a metric is its own name, and each is looked
up as a file under the benchmark's directory (``paths[0]`` of the manifest):

    configs/<configuration>.json      (the manifest entry's ``file``)
    traffic/<traffic>.json
    jobs/<job kind>.py
    layer_metrics/<metric>.py
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _json(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def _module(path: str):
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    name = "chipbench_file_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.data = _json(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, self.data["paths"][0])

    def _named(self, group: str, name: str) -> dict:
        found = [e for e in self.data[group] if e["name"] == name]
        if len(found) != 1:
            known = ", ".join(e["name"] for e in self.data[group])
            raise KeyError(f"{name!r} is not one of the manifest's {group}: {known}")
        return found[0]

    def cell(self, name: str) -> dict:
        return self._named("workloads", name)

    def config(self, cell: dict) -> dict:
        return _json(os.path.join(self.root, self._named("configs", cell["config"])["file"]))

    def traffic(self, cell: dict) -> dict:
        return _json(os.path.join(self.bench_dir, "traffic", cell["traffic"] + ".json"))

    def job(self, kind: str):
        return _module(os.path.join(self.bench_dir, "jobs", kind + ".py"))

    def reader(self, metric: str):
        return _module(os.path.join(self.bench_dir, "layer_metrics", metric + ".py")).read

    def metrics(self, group: str, cell_name: str) -> list:
        """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports."""
        return [m for m in self.data[group] if cell_name in m.get("workloads", [cell_name])]
