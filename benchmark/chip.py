"""The device side of the yardstick: which chip, its published peaks, where
compiled programs persist, how much memory a run took, and how many programs
compiled inside a window.

A copy of what `tools/chip_env.py` does (later PRs may edit `tools/`, not the
yardstick), plus the HBM peak from the same source.
"""
from __future__ import annotations

import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: one fixed path inside the checkout: the path is part of JAX's cache key
CACHE_DIR = os.path.join(ROOT, ".jax_compile_cache")

#: published peaks per chip, keyed by `jax.Device.device_kind` (Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).  A
#: device that is not here is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
}


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def devices(chips, tiny=False):
    """The first ``chips`` devices.  Anything but TPUs raises `NoChip`,
    unless this is the ``--tiny`` rehearsal, which takes what there is."""
    import jax

    found = jax.devices()
    if not tiny and found[0].platform != "tpu":
        raise NoChip("no TPU: jax.devices() found %d %s device(s) (%s)"
                     % (len(found), found[0].platform, found[0].device_kind))
    if len(found) < chips:
        raise NoChip("the cell asks for %d chip(s), jax.devices() has %d"
                     % (chips, len(found)))
    return found[:chips]


def peaks(device, tiny=False):
    """Published peaks of ``device``.  The rehearsal on a CPU gets the v5e's
    row so that the arithmetic runs; its numbers are never device numbers."""
    kind = device.device_kind
    if kind not in PEAKS:
        if tiny:
            return PEAKS["TPU v5e"]
        raise KeyError("no published peaks for device_kind %r: add a row to "
                       "benchmark/chip.py PEAKS with its source" % kind)
    return PEAKS[kind]


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache before the first compile:
    `JAX_COMPILATION_CACHE_DIR` where it is set, else `CACHE_DIR`."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def cache_entries(path):
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0


class CompileCounter:
    """Counts backend compilations (cache hits included: a program that is
    read back from the persistent cache inside a window is still a stall).
    `jax.monitoring` has no way to take a listener off, so one counter lives
    for the process and windows difference it."""

    def __init__(self):
        import jax.monitoring as mon

        self.n = 0
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def device_info(devs):
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def live_bytes(device):
    import jax

    n = 0
    for arr in jax.live_arrays():
        for shard in arr.addressable_shards:
            if shard.device == device:
                n += shard.data.nbytes
    return n


def memory_peak_bytes(devs, program_bytes=0):
    """Peak bytes on the fullest chip.  `memory_stats()["peak_bytes_in_use"]`
    leaves out program temporaries on this runtime (PERF.md, PR 21), so the
    figure is the larger of it and ``program_bytes``: what the caller counted
    as live arrays plus the largest compiled program's temporaries and
    outputs (`memory_analysis()`)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return max(peak, int(program_bytes))
