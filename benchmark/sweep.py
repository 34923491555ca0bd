#!/usr/bin/env python3
"""The knee of an open-loop serving cell: one engine, one window for every
rate, the cell's own mix at each.

    python3 benchmark/sweep.py --workload <name> --rates 0.8 1.0 1.2 ... \
        [--seed 600] [--seconds 40]

The knee is the highest rate at which no backlog grows: time to first token
reads alike in the two halves of the window and rows stay free.  The cell's
file then states 0.8 of it as `rate_rps`.  The benchmark's own runs never
call this; it needs the chip like they do.  One line of JSON for every rate.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def halves(run, window):
    """(p50, p95) of time to first token, in ms, of the requests due in the
    first and in the second half of the window."""
    import numpy as np

    mid = run.t_open + run.window_s / 2
    out = []
    for part in ([r for r in window if r.due < mid],
                 [r for r in window if r.due >= mid]):
        v = [1e3 * (r.times[0] - r.due) for r in part if r.times]
        out.append([float(np.percentile(v, q)) for q in (50, 95)]
                   if v else None)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=600)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import chip, harness, serve, spec

    cell = spec.workload(args.workload)
    cfg = spec.config(cell["config"], tiny=args.tiny)
    if args.tiny:
        cell.update(cell.get("tiny", {}))
    try:
        devices = chip.devices(cell["chips"], tiny=args.tiny)
    except chip.NoChip as e:
        print("[sweep] %s" % e, file=sys.stderr)
        return 3
    if not args.tiny:
        chip.enable_compile_cache()

    def new_run(rate, seed):
        c = copy.deepcopy(cell)
        c["traffic_mix"]["arrivals"]["rate_rps"] = rate
        return harness.Run(c, cfg, seed, args.seconds, False, devices,
                           chip.peaks(devices[0], tiny=args.tiny), T_PROCESS,
                           tiny=args.tiny)

    engine, drv = serve.set_up(new_run(args.rates[0], args.seed))
    try:
        for k, rate in enumerate(args.rates):
            run = new_run(rate, args.seed + k)
            window, lost = serve.measure(run, engine, drv)
            c = run.counters
            first, second = halves(run, window)
            print(json.dumps({
                "rate_rps": rate, "due": len(window), "lost": len(lost),
                "serve_tok_s": run.e2e.get("serve_tok_s"),
                "itl_p95_ms": run.e2e.get("itl_p95_ms"),
                "ttft_p50_p95_ms_first_half": first,
                "ttft_p50_p95_ms_second_half": second,
                "queue_depth_at_close": c["queue_depth_at_close"],
                "rows_per_launch": c["decode_rows"]
                / max(1, c["decode_steps"]),
                "decode_steps": c["decode_steps"],
                "prefill_chunks": c["prefill_chunks"],
                "host_s": c["host_s"], "fetch_wait_s": c["fetch_wait_s"],
                "preemptions": c["preemptions"],
                "compiles_in_window":
                    run.checks["compiles_in_window"]["value"]}), flush=True)
    finally:
        engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
