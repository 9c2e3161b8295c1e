"""Finds what a cell is made of, by the names in ``BENCHMARK.json``.

    configs[*].file                         a configuration's sizes
    <bench>/traffic/<traffic>.json          a traffic mix's parameters
    <bench>/cells/<workload>.json           what belongs to one cell
                                            (its rate); optional
    <bench>/end_to_end/<metric>.py          one reader a quantity,
    <bench>/layer_metrics/<metric>.py       ``read(run) -> number|None``;
                                            ``<quantity>.<suffix>``, a
                                            quantity split by the
                                            end-to-end metric it moves,
                                            is read by ``<quantity>.py``
                                            where it has no file of its
                                            own
    <bench>/peaks.json                      peaks by ``device_kind``

``<bench>`` is the first of ``paths``.  A later PR adds a cell, a mix
or a metric by adding files and entries; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os


class Manifest:
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.data = json.load(f)
        self.bench = os.path.join(self.root, self.data["paths"][0])

    def _json(self, *parts):
        with open(os.path.join(self.bench, *parts)) as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        for cell in self.data["workloads"]:
            if cell["name"] == name:
                return cell
        raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json")

    def config_path(self, name: str) -> str:
        for config in self.data["configs"]:
            if config["name"] == name:
                return os.path.join(self.root, config["file"])
        raise SystemExit(f"perfbench: no config {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        with open(self.config_path(name)) as f:
            return json.load(f)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name + ".json")

    def cell_params(self, name: str) -> dict:
        try:
            return self._json("cells", name + ".json")
        except FileNotFoundError:
            return {}

    def peaks(self) -> dict:
        return self._json("peaks.json")

    def metrics(self, kind: str, cell: str) -> list:
        """The metrics of ``kind`` this cell reports: those that list it
        under ``workloads``, and those that list nothing."""
        return [
            m for m in self.data[kind]
            if cell in m.get("workloads", [cell])
        ]

    def reader(self, kind: str, name: str):
        folder = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}[kind]
        path = os.path.join(self.bench, folder, name + ".py")
        if not os.path.exists(path) and "." in name:
            path = os.path.join(
                self.bench, folder, name.rsplit(".", 1)[0] + ".py"
            )
        spec = importlib.util.spec_from_file_location(
            "perfbench_reader_" + name.replace(".", "_").replace("-", "_"), path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read
