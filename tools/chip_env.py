"""What the on-chip scripts share: the device check, the published peaks and
the persistent compile cache.

Used by `chip_smoke.py`, `bench.py` and the `tools/benchmark_*.py` scripts —
programs that only mean something on the chip.  The package itself
(`mxnet_tpu`) and the tests never call any of this.
"""
from __future__ import annotations

import os

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: where compiled programs persist when nothing says otherwise: one fixed
#: path inside the checkout (git-ignored).  The path is part of JAX's cache
#: key, so it must never carry a pid, a timestamp or a temporary name.
CACHE_DIR = os.path.join(_REPO, ".jax_compile_cache")

#: dense bf16 peak FLOP/s per chip, keyed by `jax.Device.device_kind`
#: (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s).  A device that is not here is an error, not a default.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
}


def require_tpu():
    """`jax.devices()`, or RuntimeError naming what was found instead of
    a TPU.  No child process, no retry: the caller holds the chip from
    here on."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            "no TPU: jax.devices() found %d %s device(s) (%s)"
            % (len(devices), devices[0].platform, devices[0].device_kind))
    return devices


def peak_flops(device):
    """Published bf16 peak FLOP/s of ``device``; KeyError for a device
    kind the table does not know."""
    kind = device.device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            "no published peak for device_kind %r (platform %s): add it to "
            "tools/chip_env.py PEAK_BF16_FLOPS with its source"
            % (kind, device.platform))
    return PEAK_BF16_FLOPS[kind]


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache; call before the first
    compile.  Returns the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and no
    directory is set here; otherwise the cache goes to `CACHE_DIR`.  The
    thresholds drop to zero so the serving warmup's small programs are
    cached as well as the minute-long train step."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def cache_entries(path):
    """Number of compiled programs stored under ``path`` (0 if absent)."""
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0
