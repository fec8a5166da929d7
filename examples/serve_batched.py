"""Batched serving demo: continuous batching with hierarchical KV caches.

    PYTHONPATH=src python examples/serve_batched.py --arch llama3.2-1b
    PYTHONPATH=src python examples/serve_batched.py --paged --pool-pages 24
    PYTHONPATH=src python examples/serve_batched.py --paged --cache-dtype int8

Uses the reduced smoke config (random weights) to demonstrate the engine:
8 requests over 4 slots, greedy decoding, O(nr log L) attention per step.

``--paged`` swaps the dense per-slot caches for the paged hierarchical
cache pool (serve/paged_cache.py): requests sharing the demo's common
prompt prefix map the same physical pages (fine blocks AND their coarse
ancestor rows), pages are copy-on-write, and an undersized pool preempts
and requeues the newest request instead of failing -- same greedy tokens,
a fraction of the cache HBM.
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.models import get_model
from repro.serve import ServeEngine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--decode-impl", default=None,
                    choices=["auto", "jnp", "pallas", "pallas_interpret"],
                    help="h1d decode tick backend (pallas = fused "
                         "single-launch kernels; 'auto' resolves per "
                         "backend)")
    ap.add_argument("--paged", action="store_true",
                    help="serve from the paged cache pool with prefix "
                         "sharing + copy-on-write")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="pool size in nr-row pages (small values "
                         "exercise eviction/preemption)")
    ap.add_argument("--cache-dtype", default=None,
                    choices=["fp32", "int8"],
                    help="paged page storage dtype (int8: per-row "
                         "scales, ~4x pages at fixed HBM)")
    ap.add_argument("--telemetry", action="store_true",
                    help="enable repro.obs metrics and print a summary "
                         "(implied by --prom-out)")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="record a jax.profiler trace of serving into DIR "
                         "(load its perfetto_trace.json.gz at "
                         "ui.perfetto.dev)")
    ap.add_argument("--prom-out", default=None, metavar="PATH",
                    help="write a Prometheus text exposition of the "
                         "final metrics")
    args = ap.parse_args()

    from repro import obs
    if args.telemetry or args.prom_out:
        obs.enable()

    cfg = get_smoke_config(args.arch)
    fns = get_model(cfg)
    params, _ = fns.init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, slots=args.slots, max_len=128,
                      decode_impl=args.decode_impl, paged=args.paged,
                      pool_pages=args.pool_pages,
                      cache_dtype=args.cache_dtype)

    rng = np.random.default_rng(0)
    # a shared system-prompt prefix makes the paged pool's prefix
    # sharing visible in the stats line
    prefix = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    reqs = []
    for i in range(args.requests):
        tail = rng.integers(0, cfg.vocab_size,
                            size=rng.integers(4, 16)).astype(np.int32)
        r = Request(uid=i, prompt=np.concatenate([prefix, tail]),
                    max_new_tokens=args.new_tokens)
        reqs.append(r)
        eng.submit(r)

    t0 = time.perf_counter()
    ticks = 0
    with obs.tracing.profile(args.trace_out):
        while eng.queue or eng.active.any():
            eng.step()
            ticks += 1
    dt = time.perf_counter() - t0
    total = sum(len(r.out_tokens) for r in reqs)
    print(f"served {len(reqs)} requests / {total} tokens in {dt:.2f}s "
          f"({ticks} engine ticks, {total / dt:.1f} tok/s on CPU)")
    if args.paged:
        st = eng.pool.stats
        print(f"paged pool: shared_maps={st.shared_maps} "
              f"cow={st.cow_copies} evictions={st.evictions} "
              f"preemptions={eng.preemptions} "
              f"fresh_pages={st.fresh_pages} "
              f"prefix_hit_rate={st.prefix_hit_rate():.2f}")
    for r in reqs[:3]:
        print(f"  req {r.uid}: prompt[:6]={r.prompt[:6].tolist()} "
              f"-> out={r.out_tokens[:8]}...")
    if obs.enabled():
        snap = obs.export.snapshot()
        c = snap["metrics"]["counters"]
        hbm = sum(v for k, v in c.items()
                  if k.startswith("kernel.hbm_"))
        print(f"telemetry: ticks={c.get('serve.ticks', 0)} "
              f"launches={sum(v for k, v in c.items() if k.startswith('kernel.launches'))} "
              f"analytic_hbm_bytes={hbm}")
        if args.prom_out:
            obs.export.write_prometheus(args.prom_out)
            print(f"telemetry: wrote Prometheus text -> {args.prom_out}")
    if args.trace_out:
        print(f"wrote a profiler trace -> {args.trace_out}")


if __name__ == "__main__":
    main()
