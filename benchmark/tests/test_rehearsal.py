"""The command itself: the `--tiny` CPU rehearsal prints a well-formed last
line that names the platform it ran on, and without `--tiny` a machine with
no TPU gets a non-zero exit and no result."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def _metrics(cell, kind):
    return {m["name"] for m in BENCH[kind]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_tiny_rehearsal_last_line(cell, trace):
    p = _run("--workload", cell, "--seed", str(2 ** 31 + 12345),
             "--seconds", "2", "--trace", trace, "--tiny")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    assert line["device"]["memory_peak_bytes"] > 0
    names = set(line["metrics"])
    if trace == "0":
        assert names == _metrics(cell, "end_to_end")
        assert line["metrics"]["setup_s"]["value"] > 0
    else:
        # a CPU trace has no device plane: the readers of the device trace
        # find nothing and their metrics are left out, never reported as 0
        assert names and names <= _metrics(cell, "per_layer")
        assert not any(n.startswith(("device_idle", "flash_roofline"))
                       for n in names)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # each number compared is printed beside its limit, last on stderr
    tail = p.stderr.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("[check] ") for t in tail)


def test_sweep_prints_a_line_for_every_rate():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "sweep.py"),
         "--workload", "gpt2-large.chat-near-knee", "--rates", "4", "8",
         "--seconds", "2", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(line) for line in p.stdout.strip().splitlines()]
    assert [r["rate_rps"] for r in rows] == [4.0, 8.0]
    assert all(r["lost"] == 0 and r["compiles_in_window"] == 0
               and r["serve_tok_s"] > 0 for r in rows)
    assert rows[1]["due"] > rows[0]["due"]


def test_no_chip_no_result():
    p = _run("--workload", BENCH["workloads"][0]["name"], "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bare_directory_no_result(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: no program."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0", "--tiny"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
