"""Small shapes of the benchmark's cells, so that a whole run (set-up,
window, reference, comparison) can be driven on the CPU in a test."""
import json
import os
import types

from bench import gen, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
WIDTHS = {
    "h1d-lm-144m": dict(num_layers=2, d_model=64, num_heads=4,
                        num_kv_heads=4, head_dim=16, d_ff=128,
                        vocab_size=512, nr=8),
    "yi-6b": dict(num_layers=2, d_model=64, num_heads=8, num_kv_heads=2,
                  head_dim=8, d_ff=128, vocab_size=512, nr=8),
}
MIXES = {
    "packed-4k": dict(seq_len=128, batch=2, doc_median=32, doc_max=256),
    "decode-8k": dict(requests=2, slots=2, prompt_len=200, out_min=600,
                       out_max=600, max_len=1024),
}


def full_config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def config(name, root=ROOT):
    return dict(full_config(name), **WIDTHS[name])


def mix(name, _load=gen.load):
    return dict(_load(name), **MIXES.get(name, {}))


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
