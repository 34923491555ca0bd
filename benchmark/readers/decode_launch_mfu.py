"""One decode launch's share of the chip's bf16 peak: the model FLOPs of the
window's mean launch over the median device time of a launch.

The mean launch holds `decode_rows` / `decode_steps` rows (the engine's
counters over the window); each goes once through the layers' matmuls and the
head (`flops.serve_flops` with ``head_rows`` = rows), and its attention
context is counted as 0: whatever implements attention, and however many
positions it reads, the FLOPs are the same on both sides of a comparison and
the share reads low rather than high.  The time is what
`decode_launch_device_ms` reads: the median "XLA Modules" event of
``programs`` (reader `module_ms`).  A decode launch is bound by memory (the
weights and each row's context are read once for one token a row), so this
share stays far from 100 %; it bounds the claim on the gap between tokens,
which `serve_step_mfu` cannot below the knee (there it follows the offered
load).

Nothing to read (None) where `module_ms` finds nothing, or where the window
launched no decode."""
from benchmark import flops
from benchmark.readers import module_ms


def read(run, programs):
    launch_ms = module_ms.read(run, programs)
    steps = run.counters.get("decode_steps")
    if launch_ms is None or not steps:
        return None
    rows = run.counters["decode_rows"] / float(steps)
    launch_flops = flops.serve_flops(run.cfg, 0, 0, rows, 0, rows)
    peak = run.peaks["bf16_flops"] * len(run.devices)
    return 100.0 * launch_flops / (launch_ms / 1e3) / peak
