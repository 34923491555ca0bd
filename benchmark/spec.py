"""Finds a cell's files by the names in `BENCHMARK.json`.

A configuration is `configs/<name>.json`, a cell `workloads/<name>.json`, a
per-layer metric `metrics/<name>.json` naming its reader, a reader
`readers/<module>.py` with a `read(run, **args)` function.  Adding one is
adding a file and an entry in `BENCHMARK.json`; nothing here changes.
"""
from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path):
    with open(path) as f:
        return json.load(f)


def benchmark():
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def workload(name):
    """The cell's own file, with its `BENCHMARK.json` entry merged in."""
    bench = benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError("BENCHMARK.json has no workload %r (it has %s)"
                       % (name, [w["name"] for w in bench["workloads"]]))
    cell = _load(os.path.join(HERE, "workloads", name + ".json"))
    for key in ("config", "traffic", "chips"):
        if cell.get(key, entry[key]) != entry[key]:
            raise ValueError("workloads/%s.json says %s=%r, BENCHMARK.json "
                             "%r" % (name, key, cell[key], entry[key]))
    cell.update(entry)
    return cell


def config(name, tiny=False):
    """The configuration as it is run.  ``tiny`` applies the file's own
    `tiny` overrides: the CPU rehearsal's sizes, never a cell's."""
    bench = benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == name)
    cfg = _load(os.path.join(ROOT, entry["file"]))
    if tiny:
        cfg.update(cfg.get("tiny", {}))
    return cfg


def metrics_for(cell_name, kind):
    """The entries of ``kind`` ("end_to_end" or "per_layer") that this cell
    reports."""
    return [m for m in benchmark()[kind]
            if cell_name in m.get("workloads", [cell_name])]


def reader(metric_name):
    """(read function, args) of a per-layer metric."""
    meta = _load(os.path.join(HERE, "metrics", metric_name + ".json"))
    mod = importlib.import_module("benchmark.readers." + meta["reader"])
    return mod.read, meta.get("args", {})
