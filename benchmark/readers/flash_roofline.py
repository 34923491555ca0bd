"""The flash-attention kernels' share of their roofline: the least time the
chip could take for the attention the traced steps needed (FLOPs and bytes
from shapes, `benchmark/flops.py`) over the device time of the kernels'
events in the trace.  ``passes`` says which passes the cell runs.

The kernels are found by what their instructions say (`benchmark/trace.py`):
Pallas custom calls over (batch, heads, seq, head_dim) operands.  A trace in
which the device ran and no such call is found is an error, not a silent
metric: the kernels have left the path or changed their operands' layout,
and this reader has to be told (a benchmark PR)."""
from benchmark import flops, trace

PALLAS = 'custom_call_target="tpu_custom_call"'
#: kernel launches per layer: the backward is a dq and a dk/dv kernel
LAUNCHES = {"fwd": 1, "bwd": 2}


def read(run, passes):
    r = run.reduced
    if r is None:
        return None
    cfg, cell = run.cfg, run.cell
    head_dim = cfg["n_embd"] // cfg["n_head"]
    shape = (cell["batch_rows"], cfg["n_head"], cell["seq_len"], head_dim)
    operand = "bf16[%d,%d,%d,%d]" % shape
    seconds, calls = trace.kernel_seconds(r["events"], (PALLAS, operand))
    if not calls or not seconds:
        raise RuntimeError(
            "flash_roofline: the trace holds %d device operations and no "
            "Pallas custom call over %s" % (r["n_ops"], operand))
    # how many layers' worth of attention the traced window ran
    layers_run = calls / float(sum(LAUNCHES[p] for p in passes))
    least = 0.0
    for p in passes:
        f, b = (flops.flash_fwd if p == "fwd" else flops.flash_bwd)(*shape)
        least += flops.roofline_seconds(f, b, run.peaks)[0]
    run.note("flash kernels: %d launches, %.4fs on the device, %.1f layer "
             "passes" % (calls, seconds, layers_run))
    return 100.0 * least * layers_run / seconds
