"""Serving driver: batched generation with the ServeEngine.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
        --requests 8 --slots 4 --new-tokens 16

On a production mesh the same engine runs under jax.set_mesh with the
decode-cache shardings from repro.parallel (the dry-run proves those
lower); this driver exercises the engine on local devices.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch import compile_cache
from repro.models import get_model
from repro.serve import ServeEngine, Request


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--sample", action="store_true")
    ap.add_argument("--decode-impl", default=None,
                    choices=["auto", "jnp", "pallas", "pallas_interpret"],
                    help="h1d decode tick backend (pallas = fused "
                         "single-launch kernels; 'auto' resolves per "
                         "backend; default: cfg.decode_impl)")
    ap.add_argument("--sp-data", type=int, default=1,
                    help="sequence-parallel degree: shard the "
                         "hierarchical KV cache over an N-way 'data' "
                         "axis and run the fused decode kernels per "
                         "shard (shard_map halo exchange)")
    ap.add_argument("--paged", action="store_true",
                    help="serve from the paged hierarchical cache pool "
                         "(prefix sharing + copy-on-write + preemption; "
                         "serve/paged_cache.py) instead of one dense "
                         "max-len cache per slot")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="paged pool size in nr-row level-0 pages "
                         "(default: dense-equivalent slots*Lmax/nr)")
    ap.add_argument("--cache-dtype", default=None,
                    choices=["fp32", "int8"],
                    help="paged KV-page storage dtype (int8: symmetric "
                         "per-row scales, ~4x pages at fixed HBM; "
                         "requires --paged)")
    ap.add_argument("--quant-levels", type=int, default=None,
                    help="with --cache-dtype int8: quantize hierarchy "
                         "levels [0, n) only (default -1 = all levels)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="continuous-batching per-tick token budget "
                         "(decode slots + admitted prefill chunks)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="admit long prompts on their first N tokens; "
                         "the tail streams through decode ticks")
    ap.add_argument("--lookahead", type=int, default=0,
                    help="admission skip-ahead window past a "
                         "head-of-queue that does not fit")
    ap.add_argument("--telemetry", action="store_true",
                    help="enable repro.obs metrics (implied by "
                         "--prom-out / --metrics-jsonl)")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="record a jax.profiler trace of serving into DIR "
                         "(serve.* spans beside the device's operations; "
                         "Perfetto-loadable)")
    ap.add_argument("--prom-out", default=None, metavar="PATH",
                    help="write a Prometheus text exposition at exit")
    ap.add_argument("--metrics-jsonl", default=None, metavar="PATH",
                    help="append periodic metrics snapshots as JSON "
                         "lines while serving")
    ap.add_argument("--metrics-period", type=float, default=10.0,
                    help="--metrics-jsonl emission period in seconds")
    args = ap.parse_args(argv)
    compile_cache.enable()

    from repro import obs
    telemetry = args.telemetry or args.prom_out or args.metrics_jsonl
    if telemetry:
        obs.enable()
    emitter = (obs.export.JsonlEmitter(args.metrics_jsonl,
                                       args.metrics_period)
               if args.metrics_jsonl else None)

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    fns = get_model(cfg)
    params, _ = fns.init(jax.random.PRNGKey(0), cfg)
    mesh = None
    if args.sp_data > 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((args.sp_data,), ("data",))
    eng = ServeEngine(cfg, params, slots=args.slots, max_len=args.max_len,
                      greedy=not args.sample, decode_impl=args.decode_impl,
                      mesh=mesh, paged=args.paged, pool_pages=args.pool_pages,
                      cache_dtype=args.cache_dtype,
                      quant_levels=args.quant_levels,
                      token_budget=args.token_budget,
                      prefill_chunk=args.prefill_chunk,
                      lookahead=args.lookahead)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        prompt = rng.integers(
            0, cfg.vocab_size, size=int(rng.integers(8, 32))
        ).astype(np.int32)
        r = Request(uid=i, prompt=prompt, max_new_tokens=args.new_tokens)
        reqs.append(r)
        eng.submit(r)
    t0 = time.perf_counter()
    with obs.tracing.profile(args.trace_out):
        if emitter is None:
            eng.run()
        else:
            while eng.queue or eng.active.any():
                eng.step()
                emitter.maybe_emit()
            emitter.emit()       # short runs still get >= 1 line
    dt = time.perf_counter() - t0
    total = sum(len(r.out_tokens) for r in reqs)
    print(f"[serve] {len(reqs)} requests, {total} tokens, {dt:.2f}s "
          f"({total/dt:.1f} tok/s)")
    if args.paged:
        st = eng.pool.stats
        print(f"[serve] paged: shared={st.shared_maps} cow={st.cow_copies} "
              f"evict={st.evictions} preempt={eng.preemptions} "
              f"hit_rate={st.prefix_hit_rate():.2f}")
    if args.trace_out:
        print(f"[serve] trace -> {args.trace_out}")
    if telemetry:
        if args.prom_out:
            obs.export.write_prometheus(args.prom_out)
            print(f"[serve] telemetry: prometheus -> {args.prom_out}")
        c = obs.export.snapshot()["metrics"]["counters"]
        print(f"[serve] telemetry: ticks={c.get('serve.ticks', 0)} "
              f"finished={c.get('serve.finished', 0)}")
    return reqs


if __name__ == "__main__":
    main()
