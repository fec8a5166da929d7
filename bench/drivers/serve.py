"""Serving cells: the paged ``ServeEngine`` with greedy decoding.

Set-up makes the weights on the device from the seed and builds the
engine.  The batch is closed: every session is submitted and admitted
during set-up, one at a time, so that the prefill and the decode tick
compile there; the window then runs engine ticks back to back and
nothing is admitted in it.  Each tick ends in the
engine's own host read of the sampled tokens, so a token's time is the
host clock when its tick returned.

``correct``: once the window has closed and the engine is freed, a
sample of sessions drawn from the seed is run through the plain
reference in f32 at the highest matmul precision, over each prompt and
the tokens served for it.  ``served_gap`` is the widest gap, over every
served token of the sample, by which the reference's logit of the
served token lies below its best logit.  The first served token comes
from the prefill's logits, the others from decode ticks through the
paged cache.
"""
from __future__ import annotations

import gc
import time

import numpy as np


def model_config(cfg: dict):
    from bench.drivers.train import model_config as mc
    return mc(cfg)


def reference_logits(cfg: dict, seed: int, seqs, prec="f32", pad_to=None):
    """For each (prompt, served) pair, the reference's logits (n, V) at
    the positions that produced the served tokens, as numpy."""
    import jax
    import jax.numpy as jnp
    from bench import harness, weights
    w = jax.jit(lambda k: weights.canonical(k, cfg, jnp.dtype(cfg["dtype"])))(
        weights.key_from_seed(seed, 1))
    return harness.reference(cfg).served_logits(w, cfg, seqs, prec, pad_to)


def served_gap(ref_logits, seqs) -> float:
    gaps = [np.max(lg, -1) - lg[np.arange(len(s)), s]
            for lg, (_, s) in zip(ref_logits, seqs)]
    return float(max(g.max() for g in gaps))


class Engine:
    """The system under test, built as ``launch/serve.py`` builds it."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        import jax
        import jax.numpy as jnp
        from bench import harness, weights
        from repro.serve import ServeEngine
        lay = harness.layout(cfg)
        params = jax.jit(lambda k: lay.to_program(
            weights.canonical(k, cfg, jnp.dtype(cfg["dtype"])), cfg))(
            weights.key_from_seed(seed, 1))
        self.eng = ServeEngine(model_config(cfg), params,
                               slots=mix["slots"], max_len=mix["max_len"],
                               paged=True, greedy=True)

    def warm_copies(self):
        """Compile (or load) the program that makes the page copies of
        ticks whose sessions cross into new pages, so that it does not
        compile inside the window.  Its table has one shape for every
        flush, so one copy of the zero page into a free page, which
        allocation zeroes anyway, is enough."""
        from repro.serve import paged_cache as pc
        eng = self.eng
        level = next(l for l, f in enumerate(eng.pool.free) if f)
        eng.caches = pc.apply_copies(
            eng.caches, {level: [(pc.ZERO, eng.pool.free[level][0])]},
            eng.cfg.num_kv_heads, eng._stacked)

    def admit_all(self, reqs):
        """Submit and admit each request in turn (closed batch)."""
        from bench import harness
        for r in reqs:
            with harness.span("serve.submit"):
                self.eng.submit(r)
            while not r.out_tokens:
                self.eng.step()


def run(ctx) -> dict:
    import jax
    from bench import gen, harness
    from repro.serve import Request
    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    reqs = [Request(uid=r["uid"], prompt=r["prompt"],
                    max_new_tokens=r["max_new"])
            for r in gen.serve_requests(mix, cfg["vocab_size"], seed)]
    e = Engine(cfg, mix, seed)
    e.admit_all(reqs)
    e.warm_copies()
    eng = e.eng
    jax.block_until_ready(eng.caches)
    before = sum(len(r.out_tokens) for r in reqs)
    positions = []
    ticks = []
    with harness.traced(ctx.trace, ctx.trace_out):
        t0 = ctx.clock.start_window()
        while True:
            positions.append(eng.pos_host[eng.active].copy())
            with harness.span("serve.step"):
                eng.step()
            ticks.append(time.perf_counter())
            if ticks[-1] - t0 >= ctx.seconds:
                break
    window = ticks[-1] - t0
    tokens = sum(len(r.out_tokens) for r in reqs) - before
    gaps = np.diff([t0] + ticks)
    med = float(np.median(gaps))
    slow = gaps[gaps > 3 * med]
    harness.log(f"{len(gaps)} ticks: median {med * 1e3:.3f} ms, "
                f"max {gaps.max() * 1e3:.3f} ms; {len(slow)} over 3x the "
                f"median, {slow.sum():.3f} s in all")
    running = int(eng.active.sum())
    mem = harness.peak_bytes(ctx.devs)

    pick = gen.rng(seed, 30).choice(len(reqs), mix["check_sessions"],
                                    replace=False)
    seqs = [(np.asarray(reqs[i].prompt), np.asarray(reqs[i].out_tokens))
            for i in sorted(pick)]
    del e, eng
    gc.collect()
    ref = reference_logits(cfg, seed, seqs, pad_to=mix["max_len"])
    return {
        "metrics": {"decode_tokens_per_s": tokens / window,
                    "itl_p95_s": float(np.quantile(gaps, 0.95))},
        "attempted": len(reqs), "failed": len(reqs) - running,
        "checks": {"served_gap": served_gap(ref, seqs)},
        "memory_peak_bytes": mem,
        "window": {"seconds": window, "ticks": len(ticks),
                   "tokens": tokens, "positions": positions,
                   "checked_tokens": int(sum(len(s) for _, s in seqs))},
        "seqs": seqs,
    }
