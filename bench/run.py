#!/usr/bin/env python3
"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --workload <name> --smoke     # CPU rehearsal, tiny

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix. Everything is found by name: the configuration's file, the
block stack ``bench/blocks/<stack>.py`` that it names (the reference's
blocks, their work counts and the device kernels the trace reduction
credits), the mix ``bench/traffic/<traffic>.json`` (one budget or many,
guided or not), the plane ``bench/planes/<plane>.py`` that the mix names,
and one reader ``bench/metrics/<metric>.py`` per metric. Adding a cell, a
configuration, a block stack, a mix or a metric adds files and entries; it
edits none.

One process per run: the weights are made from the seed on the device,
the cell's programs are warmed up, the window is measured from the client
side, and the served outputs are compared with the plain reference. The
last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, in a traced run ``breakdown``, and
last ``checks``: each number compared, beside its limit). Without a TPU,
or with fewer chips than the cell asks for, it prints no result and exits
non-zero; ``--smoke`` runs on the CPU and reports no device metric.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, cell: str, traced: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with ``traced`` its per-layer
    ones: each that lists the cell, or lists no cells."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def prepare(workload: str, smoke: bool):
    """The cell, its mix, its configuration and the chip's peaks; None
    (after saying why on stderr) where this machine cannot run it."""
    from bench import harness, loadgen
    from bench.model import Model

    bench = load_json("BENCHMARK.json")
    cell = cell_of(bench, workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    mix = loadgen.load_mix(os.path.join(ROOT, "bench", "traffic",
                                        cell["traffic"] + ".json"))
    if smoke:
        mix = {**mix, **mix.get("smoke", {})}

    # the system under test: a directory without it fails here
    from repro.launch.compile_cache import enable_compile_cache

    if not smoke:              # a CPU rehearsal leaves the cache alone
        enable_compile_cache()
    import jax

    devs = jax.devices()
    peaks = None
    if not smoke:
        if devs[0].platform != "tpu":
            harness.log(f"bench: no TPU (found {devs[0].platform}); there "
                        "is no CPU fallback (--smoke rehearses on the CPU)")
            return None
        if len(devs) < cell["chips"]:
            harness.log(f"bench: cell needs {cell['chips']} chips, found "
                        f"{len(devs)}")
            return None
        table = load_json("bench/peaks.json")["kinds"]
        if devs[0].device_kind not in table:
            harness.log(f"bench: no peaks for device kind "
                        f"{devs[0].device_kind!r} in bench/peaks.json")
            return None
        peaks = table[devs[0].device_kind]
    return {"bench": bench, "cell": cell, "mix": mix, "peaks": peaks,
            "model": Model(load_json(entry["file"]), smoke=smoke),
            "smoke": smoke, "chips": cell["chips"]}


def execute(ctx: dict) -> dict:
    """One run of the cell's plane, then its metrics by their readers;
    returns the result object (``checks`` last)."""
    from bench import harness

    plane = importlib.import_module("bench.planes." + ctx["mix"]["plane"])
    run = dict(ctx)
    run.update(plane.run(ctx))
    metrics = {}
    if not ctx["smoke"]:
        for m in metrics_for(ctx["bench"], ctx["cell"]["name"], ctx["trace"]):
            value = reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = harness.device_facts(ctx["chips"])
    device.update(run["device"])
    checks = run["checks"]
    correct = bool(checks) and all(
        ch["value"] is not None and ch["value"] <= ch["limit"]
        for ch in checks.values())
    out = {"correct": correct, "attempted": run["attempted"],
           "failed": run["failed"], "metrics": metrics, "device": device}
    if run.get("breakdown") is not None:
        out["breakdown"] = run["breakdown"]
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CPU rehearsal at the configuration's smoke sizes; "
                         "prints no device metric")
    args = ap.parse_args(argv)

    from bench import harness

    ctx = prepare(args.workload, args.smoke)
    if ctx is None:
        return 2
    seconds = (args.seconds if args.seconds is not None
               else ctx["bench"]["run_seconds"])
    if args.smoke:
        seconds = min(seconds, ctx["mix"].get("smoke_seconds", seconds))
    ctx.update(seed=args.seed, seconds=seconds, trace=bool(args.trace),
               t_start=T_START)
    out = execute(ctx)
    for name, ch in out["checks"].items():
        harness.log(f"check {name}: {ch['value']} (limit {ch['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # the benchmark is the package ``bench`` beside the program's ``src``;
    # the script's own directory must not shadow standard modules
    # (``bench/trace.py``)
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    sys.exit(main())
