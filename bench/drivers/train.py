"""Training cells: the repo's AdamW train step, jitted with its state
donated, driven over packed-document batches.

Set-up builds the state from the benchmark's seeded weights, compiles
the step, and drives it through its first three steps on the window's
own call and feed (these are the steps the reference follows).  The
window then dispatches steps back to back: the host waits only for the
loss of the step about ``AHEAD_S`` seconds back (by the step time seen
in set-up), so a stall of the host shorter than that leaves the chip
busy.  When the time is up nothing more is sent, and the window ends
when the last step's state is ready: every step sent counts.

``correct`` compares, against the plain reference's three AdamW steps
in f32 at the highest matmul precision:

* ``loss_gap``: each step's loss, relative;
* ``grad_gap``: the norm of the first gradient as the optimizer got it
  (its first moment after one step over ``1 - b1``), worst leaf;
* ``change_gap``: the norm of each leaf's change over the three steps,
  worst leaf, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's.

A leaf's gap is ``| |prog| - |ref| |`` over the larger of the reference's
norm of that leaf and of the median leaf.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

CHECK_STEPS = 3
REF_ROWS = 2          # rows of a batch the reference takes at a time
BATCHES = 16          # distinct batches cycled through by the window
AHEAD_S = 6.0         # seconds of steps queued beyond the one waited for
MAX_AHEAD = 64        # at most this many: short steps would queue thousands


# Keys of a configuration file that describe it to the reader and the
# harness; every other key is the model's and has to reach the program.
DESCRIPTIVE = {"source", "deployment", "reference", "reduced", "published",
               "assumed", "precision", "train"}


def model_config(cfg: dict):
    """The program's ``ModelConfig`` of a configuration file.  A model key
    that the program cannot honour raises: one it has no field for,
    unless the program's code fixes it at the same value."""
    import inspect

    from repro.models import ModelConfig
    from repro.models.common import rmsnorm_apply
    names = {f.name for f in dataclasses.fields(ModelConfig)}
    fixed = {"rms_norm_eps":
             inspect.signature(rmsnorm_apply).parameters["eps"].default}
    for k, v in cfg.items():
        if k in DESCRIPTIVE or k in names:
            continue
        if k not in fixed or v != fixed[k]:
            raise ValueError(f"the program cannot honour {k}={v!r}"
                             + (f" (it fixes {fixed[k]!r})" if k in fixed
                                else ""))
    return ModelConfig(**{k: v for k, v in cfg.items() if k in names})


def train_config(cfg: dict):
    from repro.train import TrainConfig
    t = cfg["train"]
    return TrainConfig(peak_lr=t["peak_lr"], warmup=t["warmup"],
                       total_steps=t["total_steps"],
                       weight_decay=t["weight_decay"],
                       clip_norm=t["clip_norm"], ckpt_every=0)


def _median(d: dict) -> float:
    return float(np.median(list(d.values())))


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """{leaf: gap} as the module docstring defines it."""
    med = _median(ref)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in ref if keep is None or k in keep}


def reference_readings(cfg: dict, seed: int, batches, prec="f32") -> dict:
    """Loss per step, first clipped gradient norms and three-step
    change norms of the plain reference.  ``prec="bf16"`` is the
    control: products, activations and the stored weights in
    bfloat16."""
    import jax
    import jax.numpy as jnp
    from bench import harness, weights
    ref = harness.reference(cfg)
    tc = cfg["train"]
    key = weights.key_from_seed(seed, 1)
    w0 = jax.jit(lambda k: weights.canonical(k, cfg, jnp.dtype(cfg["dtype"])))(
        key)
    w0 = jax.tree.map(lambda x: x.astype(jnp.float32), w0)

    def store(w):
        # the bf16 control keeps its weights in bf16 between steps too
        if prec != "bf16":
            return w
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(
            jnp.float32), w)

    def one(w, m, v, tokens, step):
        # the mean loss and its gradient over blocks of REF_ROWS rows in
        # turn, so that the f32 reference fits beside nothing else
        blocks = tokens.reshape(-1, min(REF_ROWS, tokens.shape[0]),
                                tokens.shape[1])

        def add(acc, rows):
            loss, g = jax.value_and_grad(ref.loss)(w, cfg, rows, prec)
            return jax.tree.map(jnp.add, acc, (loss, g)), None
        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, w))
        (loss, g), _ = jax.lax.scan(add, zero, blocks)
        n = blocks.shape[0]
        loss, g = loss / n, jax.tree.map(lambda x: x / n, g)
        w, m, v, g = ref.adamw_step(w, m, v, g, step, tc)
        return store(w), m, v, loss, weights.leaf_norms(g)

    step = jax.jit(one)
    w0 = store(w0)
    m = jax.tree.map(jnp.zeros_like, w0)
    v = jax.tree.map(jnp.zeros_like, w0)
    w = w0
    losses = []
    with jax.default_matmul_precision("highest"):
        for i in range(CHECK_STEPS):
            w, m, v, loss, gn = step(w, m, v, jnp.asarray(batches[i]),
                                     jnp.float32(i + 1))
            losses.append(float(loss))
            if i == 0:
                grad = {k: float(x) for k, x in gn.items()}
        change = jax.jit(lambda a, b: weights.leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))(w, w0)
    return {"loss": losses, "grad": grad,
            "change": {k: float(x) for k, x in change.items()}}


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers ``correct`` is decided on."""
    med = _median(ref["grad"])
    keep = {k for k, g in ref["grad"].items() if g >= 1e-3 * med}
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"],
                                                   ref["loss"]))
    return {"loss_gap": loss,
            "grad_gap": max(leaf_gaps(prog["grad"], ref["grad"]).values()),
            "change_gap": max(leaf_gaps(prog["change"], ref["change"],
                                        keep).values())}


class Program:
    """The system under test: the compiled step and its state."""

    def __init__(self, cfg: dict, seed: int, batch_shape):
        import jax
        import jax.numpy as jnp
        from bench import weights
        from repro.models import set_mesh_axes
        from repro.train import TrainState
        from repro.train.loop import make_optimizer, make_train_step
        self.cfg = cfg
        mc, tc = model_config(cfg), train_config(cfg)
        set_mesh_axes(1)
        opt = make_optimizer(tc)

        def init(key):
            w = weights.canonical(key, cfg, jnp.dtype(cfg["dtype"]))
            params = weights.to_program(w, cfg)
            return TrainState(jnp.zeros((), jnp.int32), params,
                              opt.init(params), None)

        self.state = jax.jit(init)(weights.key_from_seed(seed, 1))
        step = make_train_step(mc, tc)
        tokens = jax.ShapeDtypeStruct(batch_shape, jnp.int32)
        self.step = jax.jit(step, donate_argnums=(0,)).lower(
            self.state, {"tokens": tokens}).compile()
        self._norms = jax.jit(lambda t: weights.leaf_norms(
            weights.from_program(t, cfg)))
        self._diff = jax.jit(lambda a, b: weights.leaf_norms(
            weights.from_program(jax.tree.map(jnp.subtract, a, b), cfg)))

    def __call__(self, batch):
        self.state, metrics = self.step(self.state, {"tokens": batch})
        return metrics["loss"]

    def check_steps(self, batches) -> dict:
        """The first three steps, with what the comparison reads."""
        import jax
        import jax.numpy as jnp
        b1 = self.cfg["train"]["b1"]
        p0 = jax.tree.map(jnp.copy, self.state.params)
        losses, grad = [], None
        for i in range(CHECK_STEPS):
            losses.append(self(batches[i]))
            if i == 0:
                grad = {k: float(x) / (1 - b1) for k, x in
                        self._norms(self.state.opt_state.mu).items()}
                t1 = time.perf_counter()
        jax.block_until_ready(self.state)
        # the time of a step after the first, which compiled
        self.step_s = (time.perf_counter() - t1) / (CHECK_STEPS - 1)
        change = {k: float(x) for k, x in
                  self._diff(self.state.params, p0).items()}
        return {"loss": [float(x) for x in losses], "grad": grad,
                "change": change}


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from bench import gen, harness
    cfg, mix, seed = ctx.cfg, ctx.mix, ctx.seed
    host_batches = gen.train_batches(mix, cfg["vocab_size"], seed, BATCHES)
    batches = [jnp.asarray(b) for b in host_batches]
    prog = Program(cfg, seed, host_batches.shape[1:])
    readings = prog.check_steps(batches)
    jax.block_until_ready(prog.state)

    tokens_per_step = mix["batch"] * mix["seq_len"]
    ahead = min(MAX_AHEAD, max(2, round(AHEAD_S / prog.step_s)))
    harness.log(f"step {prog.step_s:.4f} s in set-up, {ahead} queued ahead")
    losses = []
    i = CHECK_STEPS
    with harness.traced(ctx.trace, ctx.trace_out):
        t0 = ctx.clock.start_window()
        while True:
            with harness.span("train.dispatch"):
                losses.append(prog(batches[i % BATCHES]))
            i += 1
            if len(losses) > ahead:
                with harness.span("train.wait"):
                    losses[-ahead - 1].block_until_ready()
            if time.perf_counter() - t0 >= ctx.seconds:
                break
        with harness.span("train.wait"):
            jax.block_until_ready(prog.state)
        window = time.perf_counter() - t0
    steps = len(losses)
    failed = int(sum(not np.isfinite(float(x)) for x in losses))
    mem = harness.peak_bytes(ctx.devs)
    del prog, batches, losses

    ref = reference_readings(cfg, seed, host_batches)
    checks = compare(readings, ref)
    harness.log(f"program {readings['loss']} reference {ref['loss']}")
    return {
        "metrics": {"train_tokens_per_s": steps * tokens_per_step / window},
        "attempted": steps, "failed": failed, "checks": checks,
        "memory_peak_bytes": mem,
        "window": {"seconds": window, "steps": steps,
                   "tokens": steps * tokens_per_step},
    }
