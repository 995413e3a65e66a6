"""Finds everything a cell names, by name, from BENCHMARK.json.

A cell names a configuration and a traffic mix; the configuration names its
driver, its architecture and its plain reference; a per-layer metric names
its reader. Each is a file of its own under one of the directories in
``paths``, so a later PR adds a cell, a configuration, a mix, a metric, a
reader, a driver or an architecture by adding files and entries and edits
none.
"""
from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class SpecError(ValueError):
    """BENCHMARK.json or a file it names does not say what is needed."""


class Spec:
    def __init__(self, root=ROOT):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        self.paths = list(self.doc["paths"])

    # -- lookup by name ----------------------------------------------------
    def _entry(self, group, name):
        for e in self.doc[group]:
            if e["name"] == name:
                return e
        raise SpecError(f"BENCHMARK.json has no entry {name!r} in {group!r}: "
                        f"{[e['name'] for e in self.doc[group]]}")

    def cell(self, name):
        return self._entry("workloads", name)

    def find(self, kind, name, ext=".json"):
        """``<path>/<kind>/<name><ext>`` in the first of ``paths`` that has it."""
        for p in self.paths:
            cand = os.path.join(self.root, p, kind, name + ext)
            if os.path.isfile(cand):
                return cand
        raise SpecError(f"no {kind}/{name}{ext} under any of {self.paths}")

    def load_json(self, kind, name):
        with open(self.find(kind, name)) as f:
            return json.load(f)

    def config(self, name):
        entry = self._entry("configs", name)
        with open(os.path.join(self.root, entry["file"])) as f:
            doc = json.load(f)
        doc["name"] = name
        return doc

    def traffic(self, cell):
        return self.load_json("traffic", self.cell(cell)["traffic"])

    def module(self, kind, name):
        """The Python file ``<path>/<kind>/<name>.py`` as a module."""
        path = self.find(kind, name, ".py")
        modname = f"_bench_{kind}_{name}".replace("-", "_").replace(".", "_")
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    # -- which metrics a cell reports --------------------------------------
    @staticmethod
    def _applies(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    def end_to_end(self, cell):
        return [m for m in self.doc["end_to_end"] if self._applies(m, cell)]

    def per_layer(self, cell):
        """Per-layer metrics of a cell: those that list it, or that list no
        cell and move an end-to-end metric this cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.doc["per_layer"]
                if self._applies(m, cell) and m["moves"] in e2e]
