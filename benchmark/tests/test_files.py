"""Every file under configs/, workloads/ and metrics/ loads, and every name
in BENCHMARK.json resolves to its files."""
import glob
import json
import os

import pytest

from benchmark import spec

HERE = spec.HERE
BENCH = spec.benchmark()


def _names(kind):
    return [os.path.basename(p)[:-5]
            for p in sorted(glob.glob(os.path.join(HERE, kind, "*.json")))]


@pytest.mark.parametrize("kind", ["configs", "workloads", "metrics"])
def test_every_file_loads(kind):
    assert _names(kind)
    for name in _names(kind):
        with open(os.path.join(HERE, kind, name + ".json")) as f:
            assert json.load(f)["name"] == name


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    c = spec.workload(cell)
    cfg = spec.config(c["config"])
    assert cfg["reduced"] == [] and cfg["assumed"]
    assert os.path.exists(os.path.join(HERE, c["kind"] + ".py"))
    assert c["name"] == c["config"] + "." + c["traffic"]
    e2e = [m["name"] for m in spec.metrics_for(cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.metrics_for(cell, "per_layer")


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_resolves(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    with open(os.path.join(HERE, "metrics", metric + ".json")) as f:
        meta = json.load(f)
    for key in ("unit", "layer", "source", "moves", "workloads"):
        assert meta[key] == entry[key], key
    assert meta.get("better", entry["better"]) == entry["better"]
    read, args = spec.reader(metric)
    assert callable(read) and isinstance(args, dict)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for cell in entry["workloads"]:
        moved = e2e[entry["moves"]]
        assert cell in moved.get("workloads", [cell])


def _workloads_named(where):
    """{where it is named: the workloads it names}."""
    if where in ("end_to_end", "per_layer"):
        return {"BENCHMARK.json %s %s" % (where, m["name"]):
                m.get("workloads", []) for m in BENCH[where]}
    out = {}
    for name in _names("metrics"):
        with open(os.path.join(HERE, "metrics", name + ".json")) as f:
            out["metrics/%s.json" % name] = json.load(f).get("workloads", [])
    return out


@pytest.mark.parametrize("where", ["end_to_end", "per_layer", "metrics"])
def test_every_workload_named_is_a_cell(where):
    """A metric that names a cell `workloads` does not have (a retired
    one, a misspelt one) would have nothing to read."""
    cells = {w["name"] for w in BENCH["workloads"]}
    for by, named in _workloads_named(where).items():
        assert set(named) <= cells, (by, named)


def test_config_files_are_each_configs_own():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
