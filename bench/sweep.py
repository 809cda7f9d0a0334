#!/usr/bin/env python3
"""Offer a cell's traffic at several fixed rates, one window each, to find
the knee: the highest rate the system sustains without a growing backlog.

    python3 bench/sweep.py --workload <name> --rates 2,4,6,8 --seconds 20

All rates in one process (the programs compile once). One JSON line per
rate: the end-to-end metrics, and how many of the window's requests were
done by its close. A cell's rate is then fixed in its traffic file at
about four fifths of the knee; the benchmark itself never searches.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for rate in (float(r) for r in args.rates.split(",")):
        ctx = run.prepare(args.workload, smoke=False)
        if ctx is None:
            return 2
        ctx["mix"]["arrival"] = dict(ctx["mix"]["arrival"], rate_per_s=rate)
        ctx.update(seed=args.seed, seconds=args.seconds, trace=False,
                   t_start=time.perf_counter())
        out = run.execute(ctx)
        print(json.dumps({"rate_per_s": rate, "metrics": out["metrics"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "correct": out["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    sys.exit(main())
