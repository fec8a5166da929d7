"""Training loop: jit'd train step factory, gradient accumulation,
cross-pod gradient compression hook, checkpoint/restart, watchdog.

``make_train_step`` builds a single pjit-able function
``(state, batch) -> (state, metrics)`` -- this is also exactly what the
multi-pod dry-run lowers.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import ModelConfig, get_model
from repro.optim import (
    adamw, adafactor, apply_updates, cosine_schedule, init_error_feedback,
    int8_compress, Optimizer)


class TrainState(NamedTuple):
    step: jnp.ndarray
    params: Any
    opt_state: Any
    ef_state: Optional[Any]   # error-feedback residual (grad compression)


@dataclasses.dataclass
class TrainConfig:
    peak_lr: float = 3e-4
    warmup: int = 200
    total_steps: int = 10_000
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    optimizer: str = "adamw"          # adamw | adafactor
    grad_accum: int = 1
    compress_grads: str = "none"      # none | int8 | topk
    ckpt_dir: str = "checkpoints"
    ckpt_every: int = 500
    log_every: int = 10
    seed: int = 0
    watchdog_factor: float = 3.0      # straggler alarm threshold
    # attention backend overrides (None = keep the ModelConfig value);
    # setting attn_impl='pallas' runs BOTH passes of EVERY banded level
    # on the fused kernels (forward + hand-written backward) -- including
    # the causal_mode='fine-q' coarse levels, which lower to the 'sub'
    # kernel, so a default-config causal train step is kernel-complete.
    attn_impl: Optional[str] = None   # auto | jnp | pallas | pallas_interpret
    attn_tq: Optional[int] = None     # Pallas query-tile rows override
                                      # (None = KernelPolicy tuning table)
    attn_causal_mode: Optional[str] = None  # fine-q | coarse-q


def resolve_model_config(cfg: ModelConfig, tc: "TrainConfig") -> ModelConfig:
    """Apply the TrainConfig attention-backend overrides to ``cfg``."""
    updates = {}
    if tc.attn_impl is not None:
        updates["attn_impl"] = tc.attn_impl
    if tc.attn_tq is not None:
        updates["attn_tq"] = tc.attn_tq
    if tc.attn_causal_mode is not None:
        updates["causal_mode"] = tc.attn_causal_mode
    return dataclasses.replace(cfg, **updates) if updates else cfg


def make_optimizer(tc: TrainConfig) -> Optimizer:
    sched = cosine_schedule(tc.peak_lr, tc.warmup, tc.total_steps)
    if tc.optimizer == "adafactor":
        return adafactor(sched)
    return adamw(sched, weight_decay=tc.weight_decay,
                 clip_norm=tc.clip_norm)


def init_state(key, cfg: ModelConfig, tc: TrainConfig):
    cfg = resolve_model_config(cfg, tc)
    fns = get_model(cfg)
    params, specs = fns.init(key, cfg)
    opt = make_optimizer(tc)
    ef = (init_error_feedback(params)
          if tc.compress_grads != "none" else None)
    return TrainState(jnp.zeros((), jnp.int32), params, opt.init(params),
                      ef), specs


def make_train_step(cfg: ModelConfig, tc: TrainConfig) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    Gradient accumulation is a lax.scan over microbatches (the leading
    batch dim is split); compute/comm overlap between the microbatch
    gradient psums is XLA's latency-hiding scheduler's job, enabled via
    mesh flags in launch/mesh.py.
    """
    cfg = resolve_model_config(cfg, tc)
    fns = get_model(cfg)
    opt = make_optimizer(tc)

    def loss_fn(params, batch):
        loss, metrics = fns.loss(params, cfg, batch)
        return loss, metrics

    def train_step(state: TrainState, batch):
        if tc.grad_accum > 1:
            micro = jax.tree.map(
                lambda x: x.reshape((tc.grad_accum,
                                     x.shape[0] // tc.grad_accum)
                                    + x.shape[1:]), batch)

            def acc(carry, mb):
                gsum, lsum = carry
                (loss, metrics), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(state.params, mb)
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (gsum, lsum + loss), metrics

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (gsum, lsum), metrics = jax.lax.scan(acc, (zeros, 0.0), micro)
            grads = jax.tree.map(lambda g: g / tc.grad_accum, gsum)
            loss = lsum / tc.grad_accum
            metrics = jax.tree.map(lambda m: m[-1], metrics)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, batch)

        ef = state.ef_state
        if tc.compress_grads == "int8":
            grads, ef = int8_compress(grads, ef)

        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = apply_updates(state.params, updates)
        new_state = TrainState(state.step + 1, params, opt_state, ef)
        metrics = dict(metrics)
        metrics["loss"] = loss
        return new_state, metrics

    return train_step


class Watchdog:
    """Step-time straggler detector: EMA of step latency; flags (and
    counts) steps slower than ``factor`` x the EMA.  On a real cluster the
    callback would trigger hot-spare swap / re-scheduling; here it logs."""

    def __init__(self, factor: float = 3.0):
        self.factor = factor
        self.ema: Optional[float] = None
        self.alarms = 0

    def observe(self, dt: float) -> bool:
        slow = self.ema is not None and dt > self.factor * self.ema
        self.alarms += int(slow)
        self.ema = dt if self.ema is None else 0.9 * self.ema + 0.1 * dt
        return slow


def train(cfg: ModelConfig, tc: TrainConfig, data_source, num_steps: int,
          *, state=None, log=print):
    """Single-host driver with checkpoint/restart; the multi-pod driver in
    launch/train.py wraps this with mesh + sharded batches."""
    from . import checkpoint as ckpt

    key = jax.random.PRNGKey(tc.seed)
    if state is None:
        state, _ = init_state(key, cfg, tc)
        start = ckpt.latest_step(tc.ckpt_dir)
        if start is not None:
            state = ckpt.restore(tc.ckpt_dir, start, state)
            log(f"[restart] resumed from step {start}")
    step0 = int(state.step)
    train_step = jax.jit(make_train_step(cfg, tc), donate_argnums=(0,))
    saver = ckpt.AsyncCheckpointer(tc.ckpt_dir)
    wd = Watchdog(tc.watchdog_factor)
    metrics = {}
    for step in range(step0, num_steps):
        batch = jax.tree.map(jnp.asarray, data_source.batch(step))
        t0 = time.perf_counter()
        with obs.span("train.step"):
            state, metrics = train_step(state, batch)
            jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        if obs.enabled():
            obs.counter("train.steps").inc()
            obs.histogram("train.step_s").observe(dt)
            obs.gauge("train.loss").set(float(metrics["loss"]))
        if wd.observe(dt):
            obs.counter("train.watchdog_alarms").inc()
            log(f"[watchdog] step {step} took {dt:.3f}s "
                f"(ema {wd.ema:.3f}s) -- straggler suspected")
        if step % tc.log_every == 0:
            log(f"step {step}: loss={float(metrics['loss']):.4f} "
                f"({dt*1e3:.1f} ms)")
        if tc.ckpt_every and (step + 1) % tc.ckpt_every == 0:
            saver.save(step + 1, state)
    saver.wait()
    return state, metrics
