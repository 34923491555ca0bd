#!/usr/bin/env python3
"""Run one cell of the benchmark once, in one process, on the chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (build, make the weights on the device from the seed, warm this cell's
shapes), then a measured window of `--seconds`, then the comparison with the
plain reference.  The last line of standard output is the result.  Without a
TPU, or with fewer chips than the cell asks for, the exit code is not 0 and
no result is printed; `--tiny` is the CPU rehearsal, at the configuration's
`tiny` sizes, whose line names the platform it ran on.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at the configuration's tiny sizes")
    args = ap.parse_args(argv)

    from benchmark import chip, harness, spec

    cell = spec.workload(args.workload)
    try:
        devices = chip.devices(cell["chips"], tiny=args.tiny)
    except chip.NoChip as e:
        print("[bench] %s" % e, file=sys.stderr)
        return 3
    # the rehearsal keeps no cache: XLA:CPU warns at length when it reads one
    cache = "(none)" if args.tiny else chip.enable_compile_cache()
    print("[bench] %s seed %d on %d x %s; compile cache %s holds %d entries"
          % (args.workload, args.seed, len(devices), devices[0].device_kind,
             cache, chip.cache_entries(cache)), file=sys.stderr, flush=True)
    result = harness.execute(args.workload, args.seed, args.seconds,
                             bool(args.trace), devices, T_PROCESS,
                             tiny=args.tiny)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
