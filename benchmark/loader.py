"""Finds everything that belongs to one cell by the names in BENCHMARK.json.

A cell is one entry of ``workloads``. Its traffic mix is
``benchmark/workloads/<cell>.json``, its configuration the ``file`` of the
entry of ``configs`` it names, each per-layer metric
``benchmark/layer_metrics/<metric>.json``, and a driver, reader or plain
reference the module of that name under ``benchmark/drivers``,
``benchmark/readers`` or ``benchmark/references``. Nothing here lists
names: a later PR adds a file and an entry, and edits no file.
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark_json(root: str = ROOT) -> dict:
    return read_json(os.path.join(root, "BENCHMARK.json"))


def named(entries, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json "
                     f"(it has {[e['name'] for e in entries]})")


class Cell:
    """One workload of BENCHMARK.json with the files its names lead to."""

    def __init__(self, name: str, root: str = ROOT):
        spec = benchmark_json(root)
        self.name = name
        self.entry = named(spec["workloads"], name, "workload")
        self.chips = int(self.entry["chips"])
        config_entry = named(spec["configs"], self.entry["config"], "config")
        self.config = read_json(os.path.join(root, config_entry["file"]))
        self.workload = read_json(
            os.path.join(root, "benchmark", "workloads", name + ".json"))
        if self.workload["config"] != self.entry["config"]:
            raise SystemExit(
                f"benchmark: {name}.json is written for config "
                f"{self.workload['config']!r}, BENCHMARK.json gives the cell "
                f"{self.entry['config']!r}")
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            dict(m, **read_json(os.path.join(
                root, "benchmark", "layer_metrics", m["name"] + ".json")))
            for m in spec["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]

    def driver(self):
        return plugin("drivers", self.workload["driver"])


def plugin(kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py``."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; a device not in the table is an error."""
    table = read_json(os.path.join(HERE, "peaks.json"))["chips"]
    if device_kind not in table:
        raise SystemExit(f"benchmark: no published peaks for device kind "
                         f"{device_kind!r} in benchmark/peaks.json")
    return table[device_kind]
