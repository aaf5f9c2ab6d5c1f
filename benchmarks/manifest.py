"""Finds everything a cell needs by NAME: the cell in ``BENCHMARK.json``,
its configuration file, its traffic file, the feed kind that reads the
traffic file, its reference module and the reader of each of its
per-layer metrics. A later PR adds a cell, a configuration, a traffic
mix, a metric or a feed kind by adding files and an entry in
``BENCHMARK.json``; nothing here lists them.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or inconsistent."""


def _load_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def _module(path):
    """A module loaded from its file, once: a reference, a metric reader
    or a feed kind."""
    if not os.path.isfile(path):
        raise ManifestError(f"no such file: {os.path.relpath(path, ROOT)}")
    name = "benchmarks._by_name." + os.path.relpath(
        path, HERE)[:-3].replace(os.sep, ".")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def feed_kind(kind: str, root: str = ROOT):
    """The module of the feed kind a traffic file names:
    ``benchmarks/feeds/<kind>.py`` (``generator.py`` has the contract)."""
    there = os.path.join(root, "benchmarks", "feeds")
    path = os.path.join(there, f"{kind}.py")
    if not os.path.isfile(path):
        have = sorted(f[:-3] for f in os.listdir(there)
                      if f.endswith(".py") and not f.startswith("_"))
        raise ManifestError(
            f"no feed kind {kind!r}: there is no "
            f"{os.path.relpath(path, root)}; benchmarks/feeds has {have}")
    return _module(path)


class Cell:
    """One entry of ``workloads`` with its files loaded."""

    def __init__(self, workload: str, root: str = ROOT):
        self.root = root
        bench = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.bench = bench
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise ManifestError(
                f"no workload {workload!r} in BENCHMARK.json; it has "
                f"{sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        here = os.path.join(root, "benchmarks")
        self.config = _load_json(
            os.path.join(root, configs[self.entry["config"]]["file"]))
        self.traffic = _load_json(os.path.join(
            here, "traffic", self.entry["traffic"] + ".json"))
        self.family = _module(os.path.join(
            here, "references", self.config["family"] + ".py"))
        # a missing kind fails here, where a missing reference does, before
        # JAX is imported; ``generator.make_feed`` is the one that uses it
        feed_kind(self.traffic["kind"], root)

    def sized(self, rehearsal: bool):
        """(sizes, traffic) as run: the two files' own, or with their
        ``rehearsal`` entries laid over them (a CPU rehearsal; never a
        measurement). ``batch_rows`` is a size too."""
        sizes, traffic = dict(self.config["sizes"]), dict(self.traffic)
        if rehearsal:
            sizes.update(self.config["rehearsal"])
            traffic.update(traffic["rehearsal"])
        sizes["batch_rows"] = traffic["batch_rows"]
        return sizes, traffic

    def _reports(self, metric) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    def per_layer(self):
        """(entry, reader module) of each per-layer metric of this cell."""
        here = os.path.join(self.root, "benchmarks", "metrics")
        return [(m, _module(os.path.join(here, m["name"] + ".py")))
                for m in self.bench["per_layer"] if self._reports(m)]
