"""Small shapes of the benchmark's cells, so that a whole run (set-up,
window, reference, comparison) can be driven on the CPU in a test.

A configuration's small shapes are ``small/configs/<config>.json`` and a
traffic mix's ``small/traffic/<mix>.json`` beside this file: each holds
the keys that replace the full file's.  A mix without one runs as it
is."""
import json
import os
import types

from bench import gen, harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def full_config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def widths(name):
    """The small shapes of configuration ``name``."""
    return harness.load_json(HERE, "small", "configs", f"{name}.json")


def config(name, root=ROOT):
    return dict(full_config(name), **widths(name))


def mix(name, _load=gen.load):
    path = os.path.join(HERE, "small", "traffic", f"{name}.json")
    small = harness.load_json(path) if os.path.exists(path) else {}
    return dict(_load(name), **small)


def patch(monkeypatch):
    """Make the harness load the small shapes and run on the CPU."""
    monkeypatch.setattr(harness, "config", config)
    monkeypatch.setattr(gen, "load", mix)
    monkeypatch.setattr(harness, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def run_cell(cell, seed=4_000_000_007, seconds=1.0, root=ROOT):
    import jax
    from bench import run
    args = types.SimpleNamespace(workload=cell, seed=seed, seconds=seconds,
                                 trace=0)
    return run.run_cell(args, jax.devices()[:1], harness.Clock(), root)
