"""Resolve a workload name to its data files. A cell is data: BENCHMARK.json
names a configuration and a traffic mix, and each is one file found by name."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # benchmark/configs/<config>.json
    traffic_name: str
    traffic: dict         # benchmark/traffic/<traffic>.json
    end_to_end: tuple     # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         "BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    bench_dir = os.path.join(root, bench["paths"][0])
    e2e = tuple(m for m in bench["end_to_end"] if _applies(m, workload))
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config_name=entry["config"],
        config=load_json(os.path.join(root, cfg_entry["file"])),
        traffic_name=entry["traffic"],
        traffic=load_json(os.path.join(
            bench_dir, "traffic", entry["traffic"] + ".json")),
        end_to_end=e2e,
        # A per-layer metric belongs to a cell when it lists the cell, or
        # lists nothing and the cell reports the end-to-end metric it moves.
        per_layer=tuple(
            m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else any(e["name"] == m["moves"] for e in e2e))))


def load_module(path: str, name: str):
    """A reader, kernel model or reference found by file name (names may
    hold dots, so no import statement can reach them)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernel(name: str):
    """benchmark/kernels/<name>.py: operations and bytes from shapes."""
    return load_module(os.path.join(BENCH_DIR, "kernels", name + ".py"),
                       "kernel_" + name)


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip, by device_kind. Unknown kind: error."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    kind = device_kind.lower()
    for key, row in table["chips"].items():
        if key in kind:
            return row
    raise SystemExit(f"benchmark: device_kind {device_kind!r} is not in "
                     "benchmark/peaks.json")
