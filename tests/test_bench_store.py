"""bench_results persistence, and what `bench.py` does without a chip.

`tools/bench_store.py` persists every measurement as a JSON artifact.  An
artifact is a record, never a substitute for a run: `bench.py` that finds no
chip fails and prints nothing that could be read as a result.
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import bench_store  # noqa: E402


def test_record_latest_roundtrip(tmp_path):
    d = str(tmp_path)
    assert bench_store.latest(results_dir=d) is None
    p = bench_store.record({"metric": "m", "value": 1.5, "unit": "u",
                            "vs_baseline": 2.0}, results_dir=d)
    assert os.path.exists(p)
    got = bench_store.latest(results_dir=d)
    assert got["value"] == 1.5
    assert got["measured_at"]  # stamped
    assert got["replayed_from"] == os.path.basename(p)


def test_latest_returns_newest_and_respects_kind(tmp_path):
    d = str(tmp_path)
    bench_store.record({"value": 1}, results_dir=d)
    p2 = bench_store.record({"value": 2}, results_dir=d)
    bench_store.record({"value": 99}, kind="io", results_dir=d)
    got = bench_store.latest(results_dir=d)
    assert got["value"] == 2
    assert got["replayed_from"] == os.path.basename(p2)
    assert bench_store.latest(kind="io", results_dir=d)["value"] == 99


def test_caller_supplied_measured_at_is_kept(tmp_path):
    d = str(tmp_path)
    bench_store.record({"value": 3, "measured_at": "20260730T000000Z"},
                       results_dir=d)
    assert bench_store.latest(results_dir=d)["measured_at"] == \
        "20260730T000000Z"


def test_latest_skips_torn_artifact(tmp_path):
    d = str(tmp_path)
    bench_store.record({"value": 7}, results_dir=d)
    # a torn/truncated file sorting newest must not crash or win
    with open(os.path.join(d, "bench_99999999T999999Z_zz.json"), "w") as f:
        f.write('{"value": ')
    assert bench_store.latest(results_dir=d)["value"] == 7


def test_bench_without_a_chip_fails_and_prints_no_value(tmp_path):
    """bench.py under an unloadable device platform exits non-zero and its
    standard output holds no value — not even with a stored artifact in
    reach."""
    d = str(tmp_path)
    bench_store.record(
        {"metric": "resnet50_train_images_per_sec_per_chip",
         "value": 2361.8, "unit": "images/sec/chip (mfu=0.294, ...)",
         "vs_baseline": 55.57}, results_dir=d)
    env = dict(os.environ)
    env.update({"MXNET_BENCH_RESULTS_DIR": d,
                "JAX_PLATFORMS": "no_such_platform"})
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=110, env=env)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "2361.8" not in proc.stdout + proc.stderr
    # the CPU backend is no chip either
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=110, env=env)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
