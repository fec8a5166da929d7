#!/usr/bin/env python3
"""Bring-up smoke: the system's main paths on TPU, through the normal
entry points, at published widths, with random weights from a seed.

    python chip_smoke.py               # one chip: serve, parity, train
    python chip_smoke.py --four-chips  # four chips: sequence-parallel
                                       # serve and train vs one device

One chip:
  * serve  -- llama3.2-1b (bf16, published widths) through the paged
    ``ServeEngine`` as ``launch/serve.py`` builds it; prompts of
    256-2048 tokens, 16-32 new tokens each, every request must finish.
  * parity -- on the same params, the paged engine's own jitted prefill
    and one decode tick, kernel path vs the blocked-XLA (``jnp``) path,
    compared at the logits they return: max |dlogit| / max |logit| <=
    1e-4 with the fp32 page pool, <= 1e-3 with the int8 one.  Both run in
    f32 at the highest matmul precision: at the default precision TPU
    matmuls round f32 operands to bf16, and that rounding alone moves
    this random-weight 16-layer model's logits by about 1e-2 between any
    two implementations, the jnp path against itself included -- so a
    kernel that ignored the precision would fail these bounds.
  * train  -- h1d-lm-144m (the paper's LM) through ``train/loop.py``'s
    step as ``launch/train.py`` builds it, seq 4096, 3 steps with a
    finite loss; the compiled step must contain ``tpu_custom_call``.

Every kernel family traced must have resolved to ``pallas`` in the
launch policy's decision log.  Earlier lines print set-up data (phase
wall times, compile times, decisions, peak device memory); none of it
is a benchmark metric.  The last line is the JSON verdict
``{"ok": true, "device": {...}}``; any failed phase exits non-zero
without it, and so does a run that finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

PARITY_BOUND = 1e-4
# int8 pages: a K/V value within ~1e-7 of a rounding boundary lands on
# the other int8 step on one path, moving that element by 1/127 of its
# row's absmax
INT8_PARITY_BOUND = 1e-3


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# launch-policy bookkeeping
# ---------------------------------------------------------------------------

class LaunchLog:
    """Kernel families traced in a phase, plus the policy decisions
    made meanwhile (both recorded at trace time)."""

    def __init__(self):
        from repro.analysis import contracts
        from repro.kernels.tuning import get_policy
        self._contracts = contracts
        self._policy = get_policy()
        self.families = set()
        self.decisions = []

    def _hook(self, contract):
        self.families.add(contract.family)

    def __enter__(self):
        self._policy.decisions.clear()
        self._contracts.add_launch_hook(self._hook)
        return self

    def __exit__(self, *exc):
        self._contracts.remove_launch_hook(self._hook)
        self.decisions = list(self._policy.decisions)
        return False


def check_resolved(launches: LaunchLog, impl: str, expect) -> dict:
    """Every decision that names an impl chose ``impl``; every family
    in ``expect`` was traced, and each traced family has a decision
    that resolved it to ``impl``.  Returns {family: impl}."""
    bad = [d for d in launches.decisions
           if "impl" in d["config"] and d["config"]["impl"] != impl]
    if bad:
        raise AssertionError(f"launches resolved away from {impl}: {bad}")
    missing = set(expect) - launches.families
    if missing:
        raise AssertionError(f"kernel families never traced: {missing}")
    resolved = {}
    for fam in sorted(launches.families):
        # the backward kernels run under their forward's custom VJP and
        # share its resolution; SP '_partial' launches resolve with
        # their single-chip family
        keys = {fam, fam.replace("_bwd", "_fwd"),
                fam.replace("_partial", ""), "band"}
        hits = [d for d in launches.decisions if d["family"] in keys
                and d["config"].get("impl") == impl]
        if not hits:
            raise AssertionError(f"{fam}: no decision resolved it to {impl}")
        resolved[fam] = impl
    return resolved


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def rel_err(got, ref) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        return float("inf")
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def make_requests(cfg, n, seed, lo=256, hi=2048, new=(16, 32)):
    import numpy as np
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        S = int(rng.integers(lo, hi + 1))
        prompt = rng.integers(0, cfg.vocab_size, size=S).astype(np.int32)
        reqs.append(Request(uid=i, prompt=prompt, max_new_tokens=int(
            rng.integers(new[0], new[1] + 1))))
    return reqs


def serve_phase(cfg, params, *, slots, max_len, n_requests, seed,
                mesh=None, paged=True, prompt_range=(256, 2048)):
    """Serve ``n_requests`` through the engine as launch/serve.py builds
    it; every request must finish with its token count."""
    from repro.serve import ServeEngine
    eng = ServeEngine(cfg, params, slots=slots, max_len=max_len,
                      mesh=mesh, paged=paged)
    reqs = make_requests(cfg, n_requests, seed, *prompt_range)
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    for r in reqs:
        if len(r.out_tokens) != r.max_new_tokens:
            raise AssertionError(
                f"request {r.uid}: {len(r.out_tokens)} tokens, expected "
                f"{r.max_new_tokens}")
    n_tok = sum(len(r.out_tokens) for r in reqs)
    return eng, reqs, {"requests": len(reqs), "prompt_tokens": int(sum(
        len(r.prompt) for r in reqs)), "new_tokens": n_tok,
        "wall_s_incl_compile": round(wall, 3)}


def engine_logits(cfg, params, prompts, *, max_len, cache_dtype):
    """Serve each prompt for two tokens (its prefill, then one decode
    tick) through the paged engine; return the logits that the engine's
    jitted prefill and decode return (decode rows of active slots only)
    and the tokens served."""
    import numpy as np
    from repro.serve import Request, ServeEngine
    eng = ServeEngine(cfg, params, slots=len(prompts), max_len=max_len,
                      paged=True, cache_dtype=cache_dtype)
    got = {"prefill": [], "decode": []}

    def tap(kind, fn):
        def run(*args):
            out = fn(*args)
            logits = np.asarray(out[0])
            got[kind].append(logits[eng.active] if kind == "decode"
                             else logits)
            return out
        return run
    eng._prefill1 = tap("prefill", eng._prefill1)
    eng._decode = tap("decode", eng._decode)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=2)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return ({k: np.concatenate(v) for k, v in got.items()},
            [r.out_tokens for r in reqs])


def parity_phase(cfg, params, *, max_len, prompt_lens, seed):
    """Paged engine, kernel path (``cfg``'s impls, which the policy
    resolves) vs the jnp path, with the fp32 and with the int8 page
    pool, in f32 at the highest matmul precision (see the module
    docstring).  Returns {pool: {"prefill"|"decode": error}}."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    cfg = dataclasses.replace(cfg, dtype="float32")
    ref_cfg = dataclasses.replace(cfg, attn_impl="jnp", decode_impl="jnp")
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in prompt_lens]
    errs, wall = {}, {}
    for cache_dtype in ("fp32", "int8"):
        got = {}
        for name, c in (("jnp", ref_cfg), ("kernel", cfg)):
            t0 = time.perf_counter()
            with jax.default_matmul_precision("highest"):
                got[name] = engine_logits(c, params, prompts,
                                          max_len=max_len,
                                          cache_dtype=cache_dtype)
            wall[f"{cache_dtype}_{name}"] = round(
                time.perf_counter() - t0, 3)
        (ref, ref_toks), (ker, ker_toks) = got["jnp"], got["kernel"]
        if ker_toks != ref_toks:
            raise AssertionError(f"{cache_dtype} pool: greedy tokens "
                                 f"differ: {ker_toks} vs {ref_toks}")
        errs[cache_dtype] = {k: rel_err(ker[k], ref[k]) for k in ref}
    return errs, {"wall_s_incl_compile": wall}


def train_phase(cfg, *, seq, batch, steps, seed, mesh_shape=(1, 1),
                sp=False):
    """``steps`` train steps on the state and step that launch/train.py
    builds."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data import ZipfLM
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build
    from repro.train import TrainConfig

    mesh = make_mesh(mesh_shape, ("data", "model")[:len(mesh_shape)])
    tc = TrainConfig(total_steps=steps, warmup=1, seed=seed)
    data = ZipfLM(vocab_size=cfg.vocab_size, seq_len=seq,
                  batch_per_host=batch, seed=seed)
    with jax.set_mesh(mesh):
        state, step = build(cfg, tc, mesh, sp=sp)
        batch0 = jax.tree.map(jnp.asarray, data.batch(0))
        t0 = time.perf_counter()
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            state, batch0).compile()
        compile_s = time.perf_counter() - t0
        log(f"train step {cfg.name} compiled in {compile_s:.3f} s")
        hlo = compiled.as_text()
        losses, step_s = [], []
        for i in range(steps):
            b = jax.tree.map(jnp.asarray, data.batch(i))
            t0 = time.perf_counter()
            state, metrics = compiled(state, b)
            losses.append(float(metrics["loss"]))
            step_s.append(round(time.perf_counter() - t0, 4))
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    return state, hlo, {"losses": losses, "compile_s": round(compile_s, 3),
                        "step_s": step_s}


# ---------------------------------------------------------------------------
# runs
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Plan:
    """What one_chip and four_chips run: the configurations and the
    sizes.  The defaults are the chip run's; ``impl`` is what the
    policy must resolve every kernel family to."""
    serve_cfg: object
    train_cfg: object
    seed: int = 0
    impl: str = "pallas"
    slots: int = 4
    max_len: int = 4096
    requests: int = 8
    prompt_range: tuple = (256, 2048)
    parity_prompts: tuple = (1024, 700)
    seq: int = 4096
    batch: int = 2
    steps: int = 3
    sp_max_len: int = 8192
    sp_prompt_range: tuple = (2049, 4096)
    sp_parity_len: int = 2048


def one_chip(plan: Plan) -> None:
    import jax
    from repro.models import get_model

    cfg = plan.serve_cfg
    params, _ = get_model(cfg).init(jax.random.PRNGKey(plan.seed), cfg)

    t0 = time.perf_counter()
    with LaunchLog() as launches:
        _, _, stats = serve_phase(cfg, params, slots=plan.slots,
                                  max_len=plan.max_len,
                                  n_requests=plan.requests, seed=plan.seed,
                                  prompt_range=plan.prompt_range)
    resolved = check_resolved(launches, plan.impl, (
        "band_fwd", "sub_fwd", "decode_attend_paged", "decode_update_paged"))
    log(f"serve {cfg.name} paged: {stats} resolved={resolved} "
        f"phase_s={time.perf_counter() - t0:.3f} "
        f"peak_bytes_in_use={peak_bytes()}")

    t0 = time.perf_counter()
    with LaunchLog() as launches:
        errs, stats = parity_phase(cfg, params, max_len=plan.max_len,
                                   prompt_lens=plan.parity_prompts,
                                   seed=plan.seed)
    resolved = check_resolved(launches, plan.impl, (
        "band_fwd", "sub_fwd", "decode_attend_paged", "decode_update_paged",
        "decode_attend_paged_quant", "decode_update_paged_quant"))
    bounds = {"fp32": PARITY_BOUND, "int8": INT8_PARITY_BOUND}
    log(f"parity {cfg.name} paged engine (f32, highest precision) kernel "
        f"vs jnp: max|dlogit|/max|logit| " + " ".join(
            f"{pool}_pool prefill={e['prefill']:.3e} "
            f"decode={e['decode']:.3e} (bound {bounds[pool]})"
            for pool, e in errs.items())
        + f" {stats} resolved={resolved} "
        f"phase_s={time.perf_counter() - t0:.3f}")
    for pool, e in errs.items():
        if max(e.values()) > bounds[pool]:
            raise AssertionError(f"{pool} pool: kernel path disagrees "
                                 f"with jnp: {e}")
    del params

    t0 = time.perf_counter()
    tcfg = plan.train_cfg
    with LaunchLog() as launches:
        _, hlo, stats = train_phase(tcfg, seq=plan.seq, batch=plan.batch,
                                    steps=plan.steps, seed=plan.seed)
    resolved = check_resolved(launches, plan.impl, (
        "band_fwd", "sub_fwd", "band_bwd", "sub_bwd"))
    if plan.impl == "pallas" and "tpu_custom_call" not in hlo:
        raise AssertionError("compiled train step has no tpu_custom_call")
    log(f"train {tcfg.name} seq={plan.seq} batch={plan.batch}: {stats} "
        f"tpu_custom_calls={hlo.count('tpu_custom_call')} "
        f"resolved={resolved} phase_s={time.perf_counter() - t0:.3f} "
        f"peak_bytes_in_use={peak_bytes()}")


def four_chips(plan: Plan) -> None:
    """Sequence-parallel serve (dense engine, cache sharded over a
    4-way 'data' axis) and one SP train step, each against the same
    work on a single device.  The engines serve the requests in the
    published dtype; the logits are compared in f32 at the highest
    matmul precision, for the reason the parity phase gives, through
    the functions the engine jits (prefill and decode tick under the
    engine's ``sp_scope``).  The train step is compared at its loss and
    gradient, in f32 at the highest precision too."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import make_mesh
    from repro.models import get_model
    from repro.parallel import sp_scope

    cfg = plan.serve_cfg
    fns = get_model(cfg)
    params, _ = fns.init(jax.random.PRNGKey(plan.seed), cfg)
    mesh = make_mesh((4,), ("data",))
    meshes = (("sp4", mesh), ("one", None))
    rng = np.random.default_rng(plan.seed)

    t0 = time.perf_counter()
    with LaunchLog() as launches:
        served = {}
        for name, m in meshes:
            eng, reqs, stats = serve_phase(
                cfg, params, slots=2, max_len=plan.sp_max_len,
                n_requests=3, seed=plan.seed, mesh=m, paged=False,
                prompt_range=plan.sp_prompt_range)
            served[name] = (reqs, stats)
            if m is not None:
                for leaf in jax.tree.leaves(eng.caches):
                    if len(leaf.sharding.device_set) != 4:
                        raise AssertionError(
                            f"SP cache array {leaf.shape} lives on "
                            f"{len(leaf.sharding.device_set)} device(s), "
                            f"not 4")
            del eng
        match = np.mean([a == b for ra, rb in zip(served["sp4"][0],
                                                   served["one"][0])
                         for a, b in zip(ra.out_tokens, rb.out_tokens)])
        log(f"sp serve {cfg.name} data=4 max_len={plan.sp_max_len}: sp4 "
            f"{served['sp4'][1]} one {served['one'][1]} "
            f"greedy_token_match={match:.3f} "
            f"phase_s={time.perf_counter() - t0:.3f}")
        cfg32 = dataclasses.replace(cfg, dtype="float32")
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        plen = plan.sp_parity_len
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, plen)),
                           jnp.int32)
        n = jnp.asarray([plen, plen - plen // 4], jnp.int32)
        got = {}
        for name, m in meshes:
            def prefill(p, b, n, m=m):
                with sp_scope(m, "data"):
                    return fns.prefill(p, cfg32, b, plan.sp_max_len,
                                       true_len=n)

            def decode(p, c, k, t, m=m):
                with sp_scope(m, "data"):
                    return fns.decode_step(p, cfg32, c, k, t)
            with jax.default_matmul_precision("highest"):
                lg0, caches, pos = jax.jit(prefill)(params, {"tokens": toks},
                                                    n)
                # the next token goes to both paths as a host array: one
                # left on the 4-chip mesh would make the one-device decode
                # a 4-device program, which Mosaic kernels cannot join
                tok = got["sp4"][2] if got else \
                    np.asarray(jnp.argmax(lg0, -1), np.int32)
                lg1, caches = jax.jit(decode)(params, caches, tok, pos)
            if m is not None:
                for leaf in jax.tree.leaves(caches):
                    if len(leaf.sharding.device_set) != 4:
                        raise AssertionError(
                            f"SP decode cache {leaf.shape} lives on "
                            f"{len(leaf.sharding.device_set)} device(s)")
            got[name] = (np.asarray(lg0), np.asarray(lg1), tok)
            del caches
    errs = {"prefill": rel_err(got["sp4"][0], got["one"][0]),
            "decode": rel_err(got["sp4"][1], got["one"][1])}
    resolved = check_resolved(launches, plan.impl, (
        "band_fwd", "sub_fwd", "decode_attend_partial",
        "decode_update_partial"))
    log(f"sp serve parity {cfg.name} data=4 (f32, highest precision) sp4 "
        f"vs one: max|dlogit|/max|logit| prefill={errs['prefill']:.3e} "
        f"decode={errs['decode']:.3e} (bound {PARITY_BOUND}) "
        f"resolved={resolved} phase_s={time.perf_counter() - t0:.3f} "
        f"peak_bytes_in_use={peak_bytes()}")
    if max(errs.values()) > PARITY_BOUND:
        raise AssertionError(f"SP serve disagrees with one device: {errs}")
    del got, params

    # One train step per side, in f32 at the highest matmul precision.
    # The first step's learning rate is zero (warmup from 0), so the
    # parameters do not move: the step's loss compares the SP forward,
    # and Adam's first moment -- a tenth of the step's gradient --
    # compares the SP backward, leaf by leaf.
    t0 = time.perf_counter()
    tcfg = dataclasses.replace(plan.train_cfg, dtype="float32")
    with LaunchLog() as launches:
        stats, grads = {}, {}
        for name, m in meshes:
            with jax.default_matmul_precision("highest"):
                state, _, stats[name] = train_phase(
                    tcfg, seq=plan.seq, batch=plan.batch, steps=1,
                    seed=plan.seed, mesh_shape=(4,) if m else (1,),
                    sp=m is not None)
            grads[name] = jax.device_get(state.opt_state.mu)
            del state
            log(f"sp train step {name}: {stats[name]} "
                f"phase_s={time.perf_counter() - t0:.3f}")
    resolved = check_resolved(launches, plan.impl, (
        "band_fwd", "sub_fwd", "band_bwd", "sub_bwd"))
    loss_err = rel_err(stats["sp4"]["losses"], stats["one"]["losses"])
    grad_err = max(jax.tree.leaves(jax.tree.map(
        rel_err, grads["sp4"], grads["one"])))
    log(f"sp train {tcfg.name} data=4 seq={plan.seq} (f32, highest "
        f"precision) sp4 vs one: loss {loss_err:.3e} gradient (worst "
        f"leaf, max|d|/max|ref|) {grad_err:.3e} (bound {PARITY_BOUND}) "
        f"resolved={resolved} phase_s={time.perf_counter() - t0:.3f} "
        f"peak_bytes_in_use={peak_bytes()}")
    if max(loss_err, grad_err) > PARITY_BOUND:
        raise AssertionError(f"SP train disagrees with one device: "
                             f"loss {loss_err}, gradient {grad_err}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sequence-parallel serve and train "
                         "paths on four chips, each against one device")
    args = ap.parse_args(argv)

    import jax
    from repro.launch import compile_cache
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        log(f"no TPU: JAX found {platform} ({len(devices)} device(s))")
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        log(f"{want} chip(s) needed, found {len(devices)}")
        return 2
    cache_dir = compile_cache.enable()
    log(f"devices={[d.device_kind for d in devices]} jax={jax.__version__} "
        f"compile_cache={cache_dir}")
    from repro.configs import get_config
    plan = Plan(serve_cfg=get_config("llama3.2-1b"),
                train_cfg=get_config("h1d-lm-144m"))
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(plan)
    log(f"total_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
