#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads and warms up the cell (set-up), measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints
one JSON object as the last line of standard output: the cell's
end-to-end metrics (``--trace 0``) or its per-layer metrics read from a
profiler trace of the window (``--trace 1``; a traced window lasts at
most ``harness.TRACE_SECONDS``, so that the trace is written and read
within a run's time).  The numbers compared for
``correct`` are printed last on standard error and last in the line.
Exits non-zero, printing no result, where JAX finds no TPU, fewer chips
than the cell asks for, or a device kind without peaks.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import argparse  # noqa: E402
import json  # noqa: E402
import types  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(cell: str, bench: dict, out: dict, ctx, devs) -> tuple:
    """Every per-layer metric that lists this cell, read from the trace
    reduction by its own reader; a reader that finds nothing returns
    None and the metric is left out.  Returns (metrics, reduction)."""
    from bench import harness, reduce_trace, work
    red = reduce_trace.reduce(out["trace"]["xplane"], devs=len(devs))
    kind = devs[0].device_kind
    r = {"trace": red, "work": work, "cfg": ctx.cfg, "mix": ctx.mix,
         "window": out["window"], "peaks": harness.peaks(kind),
         "chips": len(devs)}
    metrics = {}
    for m in bench["per_layer"]:
        if cell not in m.get("workloads", [cell]):
            continue
        v = harness.metric_reader(m["name"]).read(r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return metrics, red


def run_cell(args, devs, clock, root=ROOT) -> dict:
    """Everything after the chip check; returns the result object."""
    from bench import harness
    bench = harness.benchmark(root)
    cell = harness.workload(args.workload, root)
    mix_name = cell["traffic"]
    from bench import gen
    ctx = types.SimpleNamespace(
        cell=cell, cfg=harness.config(cell["config"], root),
        mix=gen.load(mix_name), seed=args.seed,
        seconds=min(args.seconds, harness.TRACE_SECONDS) if args.trace
        else args.seconds,
        trace=bool(args.trace), trace_out={}, devs=devs, clock=clock)
    out = harness.driver(ctx.mix["kind"]).run(ctx)
    harness.log(f"window {out['window']['seconds']:.3f} s, checked")
    out["trace"] = ctx.trace_out
    lim = harness.limits(cell["name"])
    checks = {k: {"value": v, "limit": lim[k]} for k, v in
              out["checks"].items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values()) and \
        set(lim) <= set(checks)
    if args.trace:
        try:
            metrics, red = per_layer(cell["name"], bench, out, ctx, devs)
        finally:
            harness.cleanup_trace(ctx.trace_out)
        harness.log("trace read")
    else:
        want = {m["name"]: m["unit"] for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])}
        vals = dict(out["metrics"], setup_s=clock.setup_s)
        metrics = {k: {"value": vals[k], "unit": u} for k, u in want.items()}
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs), "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = red["breakdown"]
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    from bench import harness
    args = parse(argv)
    clock = harness.Clock()
    cell = harness.workload(args.workload)
    try:
        devs = harness.check_devices(cell["chips"])
    except harness.NoChip as e:
        harness.log(str(e))
        return 3
    harness.enable_compile_cache()
    result = run_cell(args, devs, clock)
    print(json.dumps(result), flush=True)
    harness.checks_line(result["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
