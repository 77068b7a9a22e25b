"""``BENCHMARK.json`` and the data files it names, found by name.

A cell is ``{name, config, traffic, chips, why}``.  Its configuration is
the file the manifest gives; its traffic mix is
``benchmark/traffic/<traffic>.json``; a per-layer metric ``m`` is
described by ``benchmark/metrics/<m>.json`` (layer, unit, moves, the
reader's name and its arguments) and read by
``benchmark/readers/<reader>.py``; a configuration's architecture is
``benchmark/archs/<arch>.py``.  Every lookup tries the manifest's
own directory first and this checkout second, so a later PR — or a test
in a temporary directory — adds cells, configurations, mixes, metrics
and readers as new files and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class ManifestError(ValueError):
    pass


def load_file(path: str, name: str):
    """A module loaded from its file, under ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch_names(root: str) -> list:
    """The architecture modules under one root's ``benchmark/archs``."""
    try:
        names = os.listdir(os.path.join(root, "benchmark", "archs"))
    except OSError:
        return []
    return sorted(n[:-3] for n in names
                  if n.endswith(".py") and not n.startswith("_"))


class Manifest:
    def __init__(self, path: str = None):
        self.path = os.path.abspath(path or os.path.join(REPO,
                                                         "BENCHMARK.json"))
        self.root = os.path.dirname(self.path)
        with open(self.path) as f:
            self.data = json.load(f)
        self.roots = [self.root] + ([REPO] if self.root != REPO else [])
        self._specs, self._readers = {}, {}

    # ------------------------------------------------------------- lookups

    def find(self, *parts: str) -> str:
        for root in self.roots:
            p = os.path.join(root, *parts)
            if os.path.exists(p):
                return p
        raise ManifestError(f"no {os.path.join(*parts)} under {self.roots}")

    def load(self, *parts: str) -> dict:
        with open(self.find(*parts)) as f:
            return json.load(f)

    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(
            f"unknown workload {name!r}; known: "
            f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name: str) -> tuple:
        """``(manifest entry, the configuration file's contents)``, with
        the path of its architecture module (``benchmark/archs/<arch>.py``,
        ``arch`` a key of the file, ``llama`` where it has none) added
        under ``arch_file``: the configuration is all that the seats, the
        readers and ``kernels.py`` are handed."""
        for c in self.data["configs"]:
            if c["name"] == name:
                cfg = self.load(c["file"])
                arch = cfg.get("arch", "llama")
                try:
                    cfg["arch_file"] = self.find("benchmark", "archs",
                                                 arch + ".py")
                except ManifestError:
                    raise ManifestError(
                        f"config {name!r} names the architecture {arch!r}; "
                        f"known: {self.archs()}") from None
                return c, cfg
        raise ManifestError(f"unknown config {name!r}")

    def archs(self) -> list:
        """The architecture modules a configuration here may name."""
        return sorted({a for root in self.roots for a in arch_names(root)})

    def traffic(self, name: str) -> dict:
        return self.load("benchmark", "traffic", name + ".json")

    def metrics_for(self, workload: str, group: str) -> list:
        """Manifest entries of ``end_to_end`` / ``per_layer`` that this
        cell reports (no ``workloads`` key = every cell)."""
        return [m for m in self.data[group]
                if "workloads" not in m or workload in m["workloads"]]

    def metric_spec(self, name: str) -> dict:
        if name not in self._specs:
            self._specs[name] = self.load("benchmark", "metrics",
                                          name + ".json")
        return self._specs[name]

    def reader(self, name: str):
        """The ``read(ctx, **args)`` function of a reader, by file."""
        if name not in self._readers:
            self._readers[name] = self.module("readers", name).read
        return self._readers[name]

    def module(self, kind: str, name: str):
        """``benchmark/<kind>/<name>.py`` (a reader, a driver), found like
        every other piece and loaded from its file."""
        return load_file(self.find("benchmark", kind, name + ".py"),
                         f"benchmark_{kind}_{name}")
