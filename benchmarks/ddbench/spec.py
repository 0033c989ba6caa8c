"""Resolves a ``workloads`` entry of BENCHMARK.json to its files, by name.

A cell names a configuration and a traffic mix; the configuration's file names
its family. Every file is found from those names alone, so a later PR adds a
cell, a configuration, a traffic mix or a metric with new files and appended
entries, and edits nothing here.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module. A dotted metric name
    (``data_wait_share.tokens``) whose own file is absent takes the reader of
    its stem (``data_wait_share``): the split exists only because the variants
    move different end-to-end metrics."""
    for cand in (name, name.split(".", 1)[0]):
        path = os.path.join(BENCH_DIR, kind, cand + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"ddbench_{kind}_{cand.replace('.', '_').replace('-', '_')}",
                path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no benchmarks/{kind}/{name}.py")


class Cell:
    """One workloads entry with its configuration, traffic mix and metrics."""

    def __init__(self, bench: dict, name: str, dry_run: bool = False):
        entries = {w["name"]: w for w in bench["workloads"]}
        if name not in entries:
            raise KeyError(f"workload {name!r} is not in BENCHMARK.json "
                           f"(has {sorted(entries)})")
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[
            self.entry["config"]]
        self.config_path = os.path.join(ROOT, cfg_entry["file"])
        self.config = _load_json(self.config_path)
        self.traffic_path = os.path.join(
            BENCH_DIR, "traffic", self.entry["traffic"] + ".json")
        self.traffic = _load_json(self.traffic_path)
        self.dry_run = dry_run
        if dry_run:
            # Toy sizes for the CPU, kept beside the real ones; a dry run
            # never reports a metric.
            self.config = {**self.config, **self.config.get("dry_run", {})}
            self.traffic = {**self.traffic, **self.traffic.get("dry_run", {})}
        self.family_name = self.config["family"]
        self._bench = bench

    def family(self):
        return load_module("families", self.family_name)

    def metric_names(self, group: str) -> list:
        """Names of the ``end_to_end`` or ``per_layer`` metrics this cell
        reports: those with no ``workloads`` key, or with this cell in it."""
        return [m["name"] for m in self._bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def metric_unit(self, name: str) -> str:
        for group in ("end_to_end", "per_layer"):
            for m in self._bench[group]:
                if m["name"] == name:
                    return m["unit"]
        raise KeyError(name)
