#!/usr/bin/env python3
"""The readings a cell's limits are set from, many seeds in one process.

    python3 benchmark/readings.py --workload <name> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3] [--fault-seeds 1 2 3] [--out file.jsonl]

For every seed: the numbers `correct` compares, program against reference
(the lower reading is their largest over the seeds).  For the control seeds:
the same numbers with the reference computed in the precision below the
configuration's in the program's place (the upper reading is their smallest).
For the fault seeds: the same numbers with a fault planted in the feed.  The
benchmark's own runs never call this; it needs the chip like they do.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    import importlib

    from benchmark import chip, spec

    cell = spec.workload(args.workload)
    cfg = spec.config(cell["config"], tiny=args.tiny)
    if args.tiny:
        cell.update(cell.get("tiny", {}))
    try:
        devices = chip.devices(cell["chips"], tiny=args.tiny)
    except chip.NoChip as e:
        print("[readings] %s" % e, file=sys.stderr)
        return 3
    if not args.tiny:
        chip.enable_compile_cache()
    driver = importlib.import_module("benchmark." + cell["kind"])
    out = open(args.out, "a") if args.out else None
    try:
        for row in driver.readings(cell, cfg, devices, args):
            line = json.dumps(row)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
