"""Find everything of a cell by the names in BENCHMARK.json.

  configuration   the file BENCHMARK.json gives for it (under configs/)
  traffic mix     traffic/<traffic>.json, read by traffic.py
  limits          checks/<workload>.json: each compared number's limit
                  and the readings it was set from
  metrics         metrics/<metric>.py, one reader per metric, end to end
                  or per layer: `read(run)` returns a number or None
  device peaks    peaks.json, keyed by JAX's device_kind

A cell, mix, metric or configuration is added as new files and entries;
nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(BENCH_DIR))


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    checks: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = CHECKOUT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; there are "
                   f"{sorted(e['name'] for e in entries)}")


def load_cell(name: str, root: str = CHECKOUT,
              bench_dir: str = BENCH_DIR) -> Cell:
    bench = load_benchmark(root)
    work = _by_name(bench["workloads"], name, "workload")
    conf = _by_name(bench["configs"], work["config"], "configuration")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(
        name=name, chips=int(work["chips"]),
        config=_json(os.path.join(root, conf["file"])),
        traffic=_json(os.path.join(bench_dir, "traffic",
                                   work["traffic"] + ".json")),
        checks=_json(os.path.join(bench_dir, "checks", name + ".json")),
        end_to_end=e2e, per_layer=per_layer)


def reader(metric: str, bench_dir: str = BENCH_DIR):
    """The module of metrics/<metric>.py."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_of(kind: str, bench_dir: str = BENCH_DIR) -> Optional[dict]:
    """The chip's published peaks, or None where the table lacks it."""
    table = _json(os.path.join(bench_dir, "peaks.json"))
    return table["devices"].get(kind)
