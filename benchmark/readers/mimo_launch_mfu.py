"""One decode launch's share of the chip's bf16 peak, for the MiMo-V2 block:
`decode_launch_mfu` with this block's FLOPs.

The mean launch holds `decode_rows` / `decode_steps` rows (the engine's
counters over the window); each goes once through the layers' matmuls and
the head (`flops_mimo.serve_flops` with ``head_rows`` = rows).  Its attention
contexts and its routed experts are counted as 0, as in `latent_launch_mfu`:
the `iteration` records do not tell a launch's held pairs from those of the
chunks that rode with it, and an expected share is never counted, so the
share reads low rather than high.  The time is what `decode_launch_device_ms`
reads: the median "XLA Modules" event of ``programs`` (reader `module_ms`).

Nothing to read (None) where `module_ms` finds nothing, or where the window
launched no decode."""
from benchmark import flops_mimo
from benchmark.readers import module_ms


def read(run, programs):
    launch_ms = module_ms.read(run, programs)
    steps = run.counters.get("decode_steps")
    if launch_ms is None or not steps:
        return None
    rows = run.counters["decode_rows"] / float(steps)
    launch_flops = flops_mimo.serve_flops(run.cfg, rows, 0, 0, 0, rows)
    peak = run.peaks["bf16_flops"] * len(run.devices)
    return 100.0 * launch_flops / (launch_ms / 1e3) / peak
