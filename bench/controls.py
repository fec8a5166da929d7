#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.  Not run by the
benchmark's own runs.

For each seed, the numbers a cell compares are read three ways against
the f32 reference: for the program (the lower reading), for the
control (the reference itself in the next precision below the
configuration's, in the program's place) and for each fault that the
cell can have (the upper readings).

    python3 bench/controls.py --workload <cell> --seeds 1 2 3 [--program]

prints one JSON line per seed and reading, and writes them all to
``--out`` when given.  ``--program`` adds the program's readings (which
need the chip's compile of the cell).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CONTROL_PREC = {"float32": "bf16", "bfloat16": "fp8"}


def train_readings(cfg: dict, mix: dict, seed: int, program: bool) -> dict:
    """{"program"|"control"|"half_batch": the three gaps} for one seed."""
    import jax.numpy as jnp
    from bench import gen
    from bench.drivers import train as T
    batches = gen.train_batches(mix, cfg["vocab_size"], seed,
                                T.CHECK_STEPS)
    ref = T.reference_readings(cfg, seed, batches)
    out = {"control": T.compare(
        T.reference_readings(cfg, seed, batches,
                             CONTROL_PREC[cfg["dtype"]]), ref)}
    half = batches[:, : max(1, batches.shape[1] // 2)]
    out["half_batch"] = T.compare(T.reference_readings(cfg, seed, half), ref)
    if program:
        prog = T.Program(cfg, seed, batches.shape[1:])
        out["program"] = T.compare(
            prog.check_steps([jnp.asarray(b) for b in batches]), ref)
        del prog
    return out


def serve_readings(cfg: dict, mix: dict, seed: int, program: bool,
                   seconds: float = None) -> dict:
    """Serves the mix for a window as long as a run's (``run_seconds``)
    at its own load, so that as many tokens are compared, then reads,
    over the sample of sessions the benchmark checks, the program's gap
    and the control's: at each position of the same prompts and served
    tokens, the gap of the token that the lower precision puts first."""
    import types

    import jax
    import numpy as np
    from bench import harness
    from bench.drivers import serve as S
    if seconds is None:
        seconds = harness.benchmark()["run_seconds"]
    ctx = types.SimpleNamespace(cfg=cfg, mix=mix, seed=seed,
                                seconds=seconds, trace=False, trace_out={},
                                devs=jax.devices()[:1], clock=harness.Clock())
    out = S.run(ctx)
    seqs = out["seqs"]
    ref = S.reference_logits(cfg, seed, seqs, pad_to=mix["max_len"])
    low = S.reference_logits(cfg, seed, seqs, CONTROL_PREC[cfg["dtype"]],
                             pad_to=mix["max_len"])
    firsts = [(p, np.argmax(lg, -1)) for (p, _), lg in zip(seqs, low)]
    return {"program": {"served_gap": S.served_gap(ref, seqs)},
            "control": {"served_gap": S.served_gap(ref, firsts)}}


def main(argv=None) -> int:
    from bench import gen, harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = harness.workload(args.workload)
    cfg, mix = harness.config(cell["config"]), gen.load(cell["traffic"])
    harness.check_devices(cell["chips"])
    harness.enable_compile_cache()
    readers = {"train": train_readings, "serve": serve_readings}
    rows = []
    for seed in args.seeds:
        for kind, gaps in readers[mix["kind"]](cfg, mix, seed,
                                               args.program).items():
            row = {"cell": cell["name"], "seed": seed, "reading": kind,
                   **gaps}
            print(json.dumps(row), flush=True)
            rows.append(row)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
