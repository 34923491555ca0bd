"""The whole step's share of the chip's peak: model FLOPs of all the work the
window did, over the window, over chips x the published bf16 peak."""


def read(run):
    flops = run.counters.get("model_flops")
    if flops is None and "tokens" in run.counters:
        flops = run.counters["tokens"] * run.counters["flops_per_token"]
    if not flops:
        return None
    peak = run.peaks["bf16_flops"] * len(run.devices)
    return 100.0 * flops / run.window_s / peak
