"""Jit'd differentiable wrapper around the banded block attention kernel.

``band_attention(q, k, v, w, nr=..., mode=..., impl=...)``:

* ``impl='pallas'``            -- Pallas TPU kernel forward.
* ``impl='pallas_interpret'``  -- Pallas kernel in interpret mode (CPU
  validation path; executes the kernel body in Python).
* ``impl='jnp'``               -- blocked XLA implementation (used for the
  multi-pod dry-run on host-platform devices and as the backward body).

The custom VJP runs hand-written fused Pallas kernels in BOTH passes
(EXPERIMENTS.md P23): forward saves only its inputs plus its ``(y, dn,
m)`` outputs, and the backward in ``h1d_block_bwd`` recomputes the
banded scores per tile in VMEM -- no per-level band tensor is ever
re-materialized in HBM.  The ``impl='jnp'`` path stays a plain
differentiable XLA program (``jax.vjp`` of :func:`_blocked_jnp` /
:func:`_blocked_sub_jnp`) and is the gradient oracle the kernel backward
is tested against.

``mode='sub'`` (with ``ratio=2**l``) is the fine-q causal coarse level:
queries keep the fine length L while k/v/w are the level-l coarsened
sequence of length ``L / ratio`` -- see ``h1d_block`` for the fused
kernel and DESIGN.md section 2 for the tiling.

Tile-size policy: every launch resolves through the process
:class:`repro.kernels.tuning.KernelPolicy` (DESIGN.md section 10).
``impl`` is validated against the canonical enum (``'auto'`` resolves
per backend); ``tq=None`` (the default) asks the policy for the tuned /
default tile, while an explicit ``tq`` is an override that bypasses
tuning.  Either way the hint is legalized by ``resolve_tq`` -- shrunk
to the largest tile compatible with (L, nr, mode) instead of silently
falling back to XLA, so kernel benchmarks and parity tests always
measure what they claim to.  A truly incompatible shape (L not a
multiple of nr) raises.

Mesh-aware dispatch: inside an ``sp_scope(mesh)`` region
(``repro.parallel.sp_attention``), kernel-path calls whose sequence
length shards over the ``data`` axis route through
``sp_band_attention`` -- each shard runs this module's unmodified
kernels on its local rows and the boundary blocks arrive via one
packed ``ppermute`` halo exchange per direction.  Shapes too short to
keep an ``nr``-row block per shard stay on the single-launch kernel
(still ``pallas``, never a silent ``jnp`` downgrade).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import h1d_block
from . import h1d_block_bwd
from . import tuning
from .tuning import resolve_tq  # re-exported; historically lived here


def _blocked_jnp(q, k, v, w, *, nr: int, mode: str):
    """O(L * nr) blocked XLA implementation (linear-memory reference).

    Mirrors the kernel tiling but with plain jnp ops; this is what the
    distributed dry-run lowers (Pallas TPU kernels cannot compile for the
    host platform).

    ``k``/``v`` may be (B, L, d) (shared across the G query groups) or
    (B, G, L, d) (per-head KV, GSPMD-friendly: the head axis flows
    through every einsum, so the partitioner never sees size-1 dims or
    sharded-axis splits).
    """
    from repro.core import hierarchy as hc

    B, G, L, d = q.shape
    kv_g = k.ndim == 4
    f32 = jnp.float32
    causal = mode.endswith("causal")
    qb = hc.block(q.astype(f32), nr)                    # (B,G,NB,nr,d)
    kb = hc.block(k.astype(f32), nr)
    vb = hc.block(v.astype(f32), nr)
    wb = hc.block(w.astype(f32), nr, axis=-1)
    nb = qb.shape[-3]
    s_eq = "bgnqd,bgnkd->bgnqk" if kv_g else "bgnqd,bnkd->bgnqk"
    y_eq = "bgnqk,bgnkv->bgnqv" if kv_g else "bgnqk,bnkv->bgnqv"
    w_allow = (lambda wt: (wt > 0)[:, None, :, None, :])

    terms = []

    def add(offset):
        kt = hc.shift_blocks(kb, offset)
        vt = hc.shift_blocks(vb, offset)
        wt = hc.shift_blocks(wb, offset, block_axis=-2)
        qi = jnp.arange(nr)[:, None] + jnp.arange(nb)[:, None, None] * nr
        ki = qi.transpose(0, 2, 1) + offset * nr
        allow = h1d_block.band_mask(qi, ki, nr, mode, L)      # (nb, nr, nr)
        s = jnp.einsum(s_eq, qb, kt, preferred_element_type=f32)
        allow = allow[None, None] & w_allow(wt)
        terms.append((jnp.where(allow, s, h1d_block.NEG_INF), vt, wt))

    add(0)
    add(-1)
    if not causal:
        add(1)

    m = jnp.maximum(
        functools.reduce(jnp.maximum, [t[0].max(-1) for t in terms]),
        h1d_block._MIN_M)
    y = dn = None
    for s, vt, wt in terms:
        a = jnp.exp(s - m[..., None])
        yt = jnp.einsum(y_eq, a, vt, preferred_element_type=f32)
        dt = jnp.einsum("bgnqk,bnk->bgnq", a, wt,
                        preferred_element_type=f32)
        y = yt if y is None else y + yt
        dn = dt if dn is None else dn + dt
    return (hc.unblock(y, axis=-3), hc.unblock(dn, axis=-2),
            hc.unblock(m, axis=-2))


def _blocked_sub_jnp(q, k, v, w, *, nr: int, ratio: int):
    """Blocked XLA implementation of ``mode='sub'`` (fine-q causal coarse
    level): fine query blocks of ``nq = nr * ratio`` rows against the
    previous coarse key block, masked by ``band_mask`` -- the same
    partition as the Pallas sub kernel, kept as its gradient oracle.
    """
    from repro.core import hierarchy as hc

    B, G, Lq, d = q.shape
    Lk = k.shape[1]
    kv_g = k.ndim == 4
    f32 = jnp.float32
    nq = nr * ratio
    qb = hc.block(q.astype(f32), nq)                    # (B,G,NB,nq,d)
    kt = hc.shift_blocks(hc.block(k.astype(f32), nr), -1)
    vt = hc.shift_blocks(hc.block(v.astype(f32), nr), -1)
    wt = hc.shift_blocks(hc.block(w.astype(f32), nr, axis=-1), -1,
                         block_axis=-2)
    nb = qb.shape[-3]
    qi = jnp.arange(nq)[:, None] + jnp.arange(nb)[:, None, None] * nq
    ki = (jnp.arange(nr)[None, :] + (jnp.arange(nb)[:, None, None] - 1) * nr)
    allow = h1d_block.band_mask(qi, ki, nr, "sub", Lk, ratio)  # (nb, nq, nr)
    s_eq = "bgnqd,bgnkd->bgnqk" if kv_g else "bgnqd,bnkd->bgnqk"
    y_eq = "bgnqk,bgnkv->bgnqv" if kv_g else "bgnqk,bnkv->bgnqv"
    s = jnp.einsum(s_eq, qb, kt, preferred_element_type=f32)
    allow = allow[None, None] & (wt > 0)[:, None, :, None, :]
    s = jnp.where(allow, s, h1d_block.NEG_INF)
    m = jnp.maximum(s.max(-1), h1d_block._MIN_M)
    a = jnp.exp(s - m[..., None])
    y = jnp.einsum(y_eq, a, vt, preferred_element_type=f32)
    dn = jnp.einsum("bgnqk,bnk->bgnq", a, wt, preferred_element_type=f32)
    return (hc.unblock(y, axis=-3), hc.unblock(dn, axis=-2),
            hc.unblock(m, axis=-2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _band_attention_kernel(q, k, v, w, nr, mode, tq, ratio, interpret):
    return h1d_block.band_attention_fwd(
        q, k, v, w, nr=nr, mode=mode, tq=tq, ratio=ratio,
        interpret=interpret)


def _fwd(q, k, v, w, nr, mode, tq, ratio, interpret):
    out = h1d_block.band_attention_fwd(
        q, k, v, w, nr=nr, mode=mode, tq=tq, ratio=ratio,
        interpret=interpret)
    y, dn, m = out
    # (y, dn, m) are the whole softmax residual: the backward recomputes
    # scores from (q, k, w, m) and needs y/dn only for the row-wise
    # delta term -- nothing tile-shaped is saved.
    return out, (q, k, v, w, y, dn, m)


def _bwd(nr, mode, tq, ratio, interpret, res, cts):
    q, k, v, w, y, dn, m = res
    gy, gdn, gm = cts
    return h1d_block_bwd.band_attention_bwd(
        q, k, v, w, y, dn, m, gy, gdn, gm,
        nr=nr, mode=mode, tq=tq, ratio=ratio, interpret=interpret)


_band_attention_kernel.defvjp(_fwd, _bwd)


def band_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
    *, nr: int, mode: str, impl: str = "jnp", tq: Optional[int] = None,
    ratio: int = 1,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Banded block attention for one hierarchy level.  See module doc."""
    policy = tuning.get_policy()
    family = "sub_fwd" if mode == h1d_block.SUB_MODE else "band_fwd"
    impl = policy.resolve_impl(impl, family)
    L = q.shape[-2]
    if impl == "jnp":
        if mode == h1d_block.SUB_MODE:
            return _blocked_sub_jnp(q, k, v, w, nr=nr, ratio=ratio)
        return _blocked_jnp(q, k, v, w, nr=nr, mode=mode)
    # impl is 'pallas' or 'pallas_interpret' (the enum admits nothing
    # else); the log entry covers the custom VJP's backward kernels too
    policy.note_launch(family, impl=impl, grid="tiles")
    ctx = _sp_ctx()
    if ctx is not None and _sp_shardable(L, ctx, nr, mode, ratio):
        from repro.parallel.sp_attention import sp_band_attention
        return sp_band_attention(q, k, v, w, nr=nr, mode=mode,
                                 ratio=ratio, impl=impl, tq=tq,
                                 mesh=ctx[0], axis=ctx[1])
    hint = policy.band_tq(L=L, nr=nr, mode=mode, ratio=ratio,
                          dtype=str(q.dtype), override=tq)
    tq = resolve_tq(L, nr, hint, mode, ratio)
    return _band_attention_kernel(
        q, k, v, w, nr, mode, tq, ratio, impl == "pallas_interpret")


def _sp_ctx():
    """Active sequence-parallel scope, or None (lazy import: parallel ->
    kernels is the forward direction)."""
    from repro.parallel.sp_attention import sp_ctx
    return sp_ctx()


def _sp_shardable(L, ctx, nr, mode, ratio) -> bool:
    """True when (L, mode) keeps at least one whole query block per
    shard -- the condition for the SP halo-exchange path.  Shorter
    shapes stay on the single-launch kernel."""
    d = dict(ctx[0].shape).get(ctx[1], 1)
    if L % d:
        return False
    lloc = L // d
    blk = nr * ratio if mode == h1d_block.SUB_MODE else nr
    return lloc % blk == 0 and lloc >= blk
