"""Resolve a cell of `BENCHMARK.json` into its parts, by name alone.

A cell names a configuration and a traffic mix; the configuration's entry
names its file, the mix is ``bench/traffic/<mix>.json``, and each metric is
read by ``bench/metrics/<metric>.py``. Adding a cell, a mix or a metric is
therefore adding files and entries; nothing here knows any of them.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import NamedTuple

ROOT = pathlib.Path(__file__).resolve().parents[1]


class Cell(NamedTuple):
    name: str
    chips: int
    config: str
    dep: dict                   # the configuration's file
    mix: dict                   # the traffic mix's file
    end_to_end: list            # metric entries this cell reports, trace off
    per_layer: list             # metric entries this cell reports, trace on


def load(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str, reported_e2e: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported_e2e


def cell(bench: dict, name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics."""
    try:
        w = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{[w['name'] for w in bench['workloads']]}") from None
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    dep = json.loads((root / cfg["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, int(w["chips"]), w["config"], dep, mix, e2e, per_layer)


def reader(metric: str, root: pathlib.Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location("bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
