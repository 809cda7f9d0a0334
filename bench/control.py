#!/usr/bin/env python3
"""Read a cell's compared numbers for the program and for its control.

    python3 bench/control.py --workload <name> --seeds 21,22,23 --seconds 10

For each seed, one run of the cell as ``run.py`` makes it (weights from
the seed, warm-up, a window at the cell's own load, the timed path's
outputs), all seeds in one process; then the comparison reads the program
against the float32 reference over the block stack the configuration
names and, on the same sampled requests, the control one step below the
configuration's bfloat16: that reference computed at fp8 (e4m3, scaled
per output channel and per row), which gives each limit its upper
reading. One JSON line per seed on stdout. The limits of
``traffic/<mix>.json`` are set from these readings (``PERF.md`` gives
them); the benchmark's own runs never compute the control.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    import argparse

    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = run.prepare(args.workload, args.smoke)
        if ctx is None:
            return 2
        ctx.update(seed=seed, seconds=args.seconds, trace=False,
                   t_start=time.perf_counter(), control=True)
        out = run.execute(ctx)
        print(json.dumps({"seed": seed, "checks": out["checks"],
                          "attempted": out["attempted"],
                          "failed": out["failed"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    sys.exit(main())
