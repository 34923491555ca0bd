"""`chip_smoke.py` rehearsed on the CPU mesh, and what it must refuse.

The script itself has no off-chip mode: run as it is without a TPU it fails
in its first phase.  These tests import its phase functions and drive the
same control flow at a tiny geometry, steering the three things only a chip
satisfies — the device check, the placement checks and the `tpu_custom_call`
count — from here, never through an option of the script.  The one-chip
phases run with the Pallas kernels in interpret mode; the four-chip phases
run the `jax.numpy` bodies over four virtual devices (the interpreter cannot
run under `shard_map`; the kernels' side of that path is compiled for four
described devices in test_aot_compile.py).
"""
import json
import os
import subprocess
import sys

import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

import chip_env  # noqa: E402
import chip_smoke  # noqa: E402

# every kernel's gates hold here: d=128 heads, S*S >= 512*512, V >= 1024
TINY = chip_smoke.Geometry(vocab=1024, seq_len=512, layers=1, heads=1,
                           embed=128, batch=2)
TINY_REQUESTS = ((24, 8), (47, 6), (96, 8), (180, 8), (300, 6), (37, 4),
                 (75, 8), (260, 6), (130, 8), (33, 8))


@pytest.fixture
def steered(monkeypatch):
    """The checks a CPU cannot pass, recorded instead of enforced."""
    from mxnet_tpu import telemetry

    telemetry.reset()
    seen = {"devices": [], "kernel_checks": 0}
    monkeypatch.setattr(chip_env, "require_tpu", lambda: jax.devices())

    def placed(device, what):
        assert device.platform == "cpu"
        seen["devices"].append(what)

    def counted(text):
        seen["kernel_checks"] += 1
        return 1

    monkeypatch.setattr(chip_smoke, "require_tpu_device", placed)
    monkeypatch.setattr(chip_smoke, "kernel_calls", counted)
    yield seen
    telemetry.reset()


@pytest.fixture
def interpreted(monkeypatch):
    from mxnet_tpu.ops.pallas_kernels import flash_attention_mod as fa
    from mxnet_tpu.ops.pallas_kernels import fused_ce_mod as fc
    from mxnet_tpu.ops.pallas_kernels import layer_norm as ln

    for mod in (fa, fc, ln):
        monkeypatch.setattr(mod, "_INTERPRET", True)


def test_one_chip_phases_rehearsed_on_cpu(steered, interpreted, monkeypatch,
                                          capsys):
    monkeypatch.setattr(chip_smoke, "PREFILL_BUCKETS", (128, 512))
    devices = chip_smoke.phase_device(1)
    assert devices == jax.devices()
    chip_smoke.phase_legacy()
    params = chip_smoke.phase_train(TINY)
    assert set(params) == set(chip_smoke._kv_model(TINY).param_shapes())
    assert all(str(v.dtype) == "bfloat16" for v in params.values())
    chip_smoke.phase_serve(TINY, params, n_blocks=256,
                           requests=TINY_REQUESTS)
    out = capsys.readouterr().out
    assert "for all 10 requests; 0 leaked blocks" in out
    assert "warmup compiled 4 programs in" in out
    # every placement was checked, and the train step's kernels counted
    assert steered["kernel_checks"] == 1
    assert {"mx.tpu(0)", "the trainer's mesh",
            "a bound array of the predictor"} <= set(steered["devices"])


def test_train_phase_fails_when_the_step_holds_no_kernel(steered,
                                                         monkeypatch):
    """On the CPU backend the gates choose the `jax.numpy` bodies: with the
    real count in place that is a failure, before any step runs."""
    monkeypatch.setattr(chip_smoke, "kernel_calls",
                        lambda text: text.count("tpu_custom_call"))
    with pytest.raises(chip_smoke.SmokeFailure, match="no tpu_custom_call"):
        chip_smoke.phase_train(TINY)


def test_placement_check_refuses_a_cpu_device():
    import mxnet_tpu as mx

    with pytest.raises(chip_smoke.SmokeFailure, match="not a TPU"):
        chip_smoke.require_tpu_device(mx.tpu(0).jax_device(), "mx.tpu(0)")


def test_four_chip_phases_rehearsed_on_virtual_devices(steered, capsys):
    geom = TINY._replace(batch=4)
    chip_smoke.phase_multichip(geom, chip_smoke.phase_device(4))
    out = capsys.readouterr().out
    assert "head weight on 4 device(s) in row blocks [512]" in out
    assert "equal the one-device engine's for all 8 requests" in out
    assert "requests completed per replica [2, 2, 2, 2]" in out
    assert steered["kernel_checks"] == 2  # the 2x2 step and the 1x1 step


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_script_as_it_is_fails_without_a_tpu(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT,
                                                        "chip_smoke.py")]
                          + args, capture_output=True, text=True,
                          timeout=120, env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_last_line_contract(steered, monkeypatch, capsys):
    """The result line is the last one and holds exactly the contract's
    keys, with the device as jax reports it."""
    for phase in ("phase_legacy", "phase_train", "phase_serve"):
        monkeypatch.setattr(chip_smoke, phase, lambda *a, **k: None)
    monkeypatch.setattr(chip_env, "enable_compile_cache",
                        lambda: "/nonexistent")
    # another test file of this worker may have loaded the native library
    monkeypatch.delitem(sys.modules, "mxnet_tpu._native", raising=False)
    chip_smoke.main([])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    dev = jax.devices()[0]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}


# -- tools/chip_env.py ------------------------------------------------------


def test_compile_cache_dir_from_outside_or_fixed_in_checkout(monkeypatch,
                                                             tmp_path):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    outside = str(tmp_path / "given")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    assert chip_env.enable_compile_cache() == outside
    assert "jax_compilation_cache_dir" not in dict(updates)  # set nowhere

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    del updates[:]
    first = chip_env.enable_compile_cache()
    monkeypatch.chdir(tmp_path)
    second = chip_env.enable_compile_cache()
    assert first == second == os.path.join(ROOT, ".jax_compile_cache")
    assert updates.count(("jax_compilation_cache_dir", first)) == 2
    # the serving warmup's small programs are cached too
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) in updates
    assert ("jax_persistent_cache_min_entry_size_bytes", 0) in updates
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()


def test_peak_flops_knows_the_v5e_and_refuses_the_rest():
    class Device:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    assert chip_env.peak_flops(Device("TPU v5 lite")) == 197e12
    with pytest.raises(KeyError, match="no published peak"):
        chip_env.peak_flops(Device("TPU v9 imaginary"))
    with pytest.raises(KeyError, match="no published peak"):
        chip_env.peak_flops(jax.devices()[0])  # the CPU test mesh


def test_require_tpu_names_what_it_found():
    with pytest.raises(RuntimeError, match=r"no TPU: .* cpu device"):
        chip_env.require_tpu()
