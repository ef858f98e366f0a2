"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix, one driver,
one reference or one per-layer metric is a file of its own, found by the
name in the manifest — so a later PR adds a cell with new files and new
entries, and edits nothing that is here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from typing import Callable, Dict, List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class ManifestError(ValueError):
    pass


def _name(value, what: str) -> str:
    if not isinstance(value, str) or not NAME.match(value):
        raise ManifestError(
            f"{what} {value!r}: a name is 1-64 letters, digits, '_', '.', "
            f"'-' and does not start with '.' or '-'")
    return value


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    check_manifest(manifest)
    return manifest


def check_manifest(m: dict) -> None:
    """Names, units and cross-references; raises :class:`ManifestError`."""
    configs = {_name(c["name"], "config") for c in m["configs"]}
    cells = set()
    for w in m["workloads"]:
        _name(w["name"], "workload")
        _name(w["traffic"], "traffic")
        if w["config"] not in configs:
            raise ManifestError(f"workload {w['name']}: no config {w['config']!r}")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"workload {w['name']}: chips must be 1 or 4")
        cells.add(w["name"])
    e2e = set()
    for metric in m["end_to_end"] + m["per_layer"]:
        _name(metric["name"], "metric")
        if not UNIT.match(metric.get("unit", "")):
            raise ManifestError(f"metric {metric['name']}: bad unit "
                                f"{metric.get('unit')!r}")
        if metric["better"] not in ("lower", "higher"):
            raise ManifestError(f"metric {metric['name']}: better?")
        if metric["source"] not in SOURCES:
            raise ManifestError(f"metric {metric['name']}: source?")
        for w in metric.get("workloads", ()):
            if w not in cells:
                raise ManifestError(f"metric {metric['name']}: no cell {w!r}")
    for metric in m["end_to_end"]:
        e2e.add(metric["name"])
    if "setup_s" not in e2e:
        raise ManifestError("end_to_end lacks setup_s")
    for metric in m["per_layer"]:
        if metric["moves"] not in e2e:
            raise ManifestError(f"metric {metric['name']}: moves "
                                f"{metric['moves']!r} is no end-to-end metric")
        moved = next(x for x in m["end_to_end"] if x["name"] == metric["moves"])
        for w in metric.get("workloads", cells):
            if w not in moved.get("workloads", cells):
                raise ManifestError(
                    f"metric {metric['name']} is reported in {w} but "
                    f"{metric['moves']} is not")


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise ManifestError(f"no file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    """``<root>/benchmark/<kind>/<name>.py`` as a module, by file."""
    _name(name, kind)
    path = os.path.join(root, "benchmark", kind, name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict                    # the configuration file
    traffic: dict                   # the traffic file
    end_to_end: List[dict]          # this cell's end-to-end metric entries
    per_layer: List[dict]           # this cell's per-layer metric entries
    root: str

    def reader(self, metric_name: str) -> Callable:
        """The reader of a per-layer metric: the file named by the part of
        the metric's name before the first '.'."""
        return load_module(self.root, "layer_metrics",
                           metric_name.split(".")[0]).read

    def driver(self):
        return load_module(self.root, "drivers", self.config["driver"])

    def reference(self):
        return load_module(self.root, "reference", self.config["reference"])


def load_cell(manifest: dict, name: str, root: str = ROOT) -> Cell:
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise ManifestError(f"no workload {name!r}; there are "
                            f"{sorted(by_name)}")
    w = by_name[name]
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    traffic = _read_json(os.path.join(root, "benchmark", "traffic",
                                      w["traffic"] + ".json"))

    def mine(metrics):
        return [x for x in metrics if name in x.get("workloads", [name])]

    cell = Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                end_to_end=mine(manifest["end_to_end"]),
                per_layer=mine(manifest["per_layer"]), root=root)
    for metric in cell.per_layer:
        cell.reader(metric["name"])         # every named reader has a file
    cell.driver()
    cell.reference()
    return cell


def load_peaks(root: str = ROOT) -> Dict[str, dict]:
    return _read_json(os.path.join(root, "benchmark", "peaks.json"))["chips"]
