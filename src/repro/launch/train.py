"""Distributed training driver.

    PYTHONPATH=src python -m repro.launch.train --arch h1d-lm-53m \
        --steps 200 --batch 8 --seq 512 [--smoke] [--mesh 1x1]

On a real cluster this process runs per host under
``jax.distributed.initialize()``; here the same code drives whatever
devices exist.  Features: sharded state, checkpoint/restart (atomic +
resharding), gradient accumulation, optional cross-pod gradient
compression, watchdog straggler alarms.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.data import ZipfLM, HierarchicalLM, Prefetcher
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh
from repro.models import set_mesh_axes
from repro.parallel import param_shardings, sp_scope
from repro.train import (TrainConfig, TrainState, init_state,
                         make_train_step, Watchdog, checkpoint as ckpt)


def build(cfg, tc: TrainConfig, mesh, sp: bool = False):
    """The sharded initial state (seeded by ``tc.seed``) and the train
    step, as :func:`main` runs them; call inside ``jax.set_mesh(mesh)``.
    With ``sp`` the step traces under ``sp_scope(mesh, 'data')``, so
    every kernel-path attention call shards its sequence axis over
    'data'."""
    set_mesh_axes(mesh.shape.get("model"))
    state, specs = init_state(jax.random.PRNGKey(tc.seed), cfg, tc)
    psh = param_shardings(mesh, specs)
    state = TrainState(
        state.step,
        jax.tree.map(jax.device_put, state.params, psh),
        state.opt_state, state.ef_state)
    step = make_train_step(cfg, tc)
    if sp:
        inner = step

        def step(state, batch):
            with sp_scope(mesh, "data"):
                return inner(state, batch)
    return state, step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h1d-lm-53m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 4x2")
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress", default="none", choices=["none", "int8"])
    ap.add_argument("--data", default="zipf", choices=["zipf", "hier"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--attn-impl", default=None,
                    choices=["auto", "jnp", "pallas", "pallas_interpret"],
                    help="banded-attention backend override (both passes "
                         "run on the fused kernels for 'pallas'; 'auto' "
                         "resolves per backend via the KernelPolicy)")
    ap.add_argument("--attn-tq", type=int, default=None,
                    help="Pallas query-tile rows override (multiple of "
                         "nr; default: the KernelPolicy tuning table)")
    ap.add_argument("--sp", action="store_true",
                    help="sequence-parallel attention: shard L over the "
                         "'data' axis and run the fused band kernels per "
                         "shard (shard_map halo exchange); pairs with "
                         "--attn-impl pallas for long-sequence training")
    ap.add_argument("--telemetry", action="store_true",
                    help="enable repro.obs metrics")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="record a jax.profiler trace of the run into DIR "
                         "(train.step spans beside the device's "
                         "operations; Perfetto-loadable)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    from repro import obs
    if args.telemetry:
        obs.enable()

    dshape = tuple(int(x) for x in args.mesh.split("x"))
    mesh = make_mesh(dshape, ("data", "model")[:len(dshape)] if
                     len(dshape) == 2 else ("data",))

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    tc = TrainConfig(peak_lr=args.lr, total_steps=args.steps,
                     warmup=max(10, args.steps // 20),
                     ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                     grad_accum=args.grad_accum,
                     compress_grads=args.compress, seed=args.seed,
                     attn_impl=args.attn_impl, attn_tq=args.attn_tq)

    src_cls = ZipfLM if args.data == "zipf" else HierarchicalLM
    data = src_cls(vocab_size=cfg.vocab_size, seq_len=args.seq,
                   batch_per_host=args.batch, seed=args.seed)

    with jax.set_mesh(mesh), obs.tracing.profile(args.trace_out):
        state, raw_step = build(cfg, tc, mesh, sp=args.sp)
        start = ckpt.latest_step(tc.ckpt_dir) if args.ckpt_every else None
        if start is not None:
            state = ckpt.restore(tc.ckpt_dir, start, state)
            print(f"[restart] resumed from step {start}")
        step_fn = jax.jit(raw_step, donate_argnums=(0,))
        saver = ckpt.AsyncCheckpointer(tc.ckpt_dir)
        wd = Watchdog()
        pre = Prefetcher(data, start_step=int(state.step))
        try:
            for step in range(int(state.step), args.steps):
                batch = jax.tree.map(jnp.asarray, pre.next())
                t0 = time.perf_counter()
                with obs.span("train.step"):
                    state, metrics = step_fn(state, batch)
                    loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                if obs.enabled():
                    obs.counter("train.steps").inc()
                    obs.histogram("train.step_s").observe(dt)
                    obs.gauge("train.loss").set(loss)
                if wd.observe(dt):
                    obs.counter("train.watchdog_alarms").inc()
                    print(f"[watchdog] slow step {step}: {dt:.2f}s")
                if step % 10 == 0 or step == args.steps - 1:
                    print(f"step {step}: loss={loss:.4f} ({dt*1e3:.0f} ms)")
                if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                    saver.save(step + 1, state)
        finally:
            pre.close()
        saver.wait()
    if args.trace_out:
        print(f"[train] trace -> {args.trace_out}")
    print("[train] done")
    return state


if __name__ == "__main__":
    main()
