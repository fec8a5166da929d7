"""Plain reference for a decoder-only LM with causal hierarchical (H1D)
attention in place of softmax attention.

Written from the published descriptions alone, in plain ``jax.numpy``:
Llama-style pre-norm blocks (RMSNorm, rotate-half RoPE, grouped-query
attention, SwiGLU MLP) and the H-Transformer-1D attention of
arXiv:2107.11906 in its causal "fine-q" form.  It imports nothing of the
system under test and takes only canonical weights (``bench.weights``).

Causal fine-q H1D, for query ``i`` and key ``j <= i`` with block size
``nr``: the pair is attended at the smallest level ``l`` at which
``|i // (nr 2^l) - j // (nr 2^l)| <= 1``.  At level 0 the score is
``q_i . k_j``; at level ``l >= 1`` it is ``q_i`` against the mean of the
``2^l`` keys of ``j``'s aligned group, and the group's values enter
summed, its size entering the normaliser.  So, per level ``l >= 1``,
query block ``I`` (``nr 2^l`` rows) sees the ``nr`` coarse keys of
block ``I - 1``, less those of the block's second half when the query
lies in the first half of its own block (those pairs belong to level
``l - 1``).  One softmax runs over every level's scores.

``prec`` picks the arithmetic of every matrix product: ``"f32"`` (run
it under ``jax.default_matmul_precision("highest")``), ``"bf16"``
(operands rounded to bfloat16, activations kept in bfloat16) or
``"fp8"`` (operands scaled per tensor into float8 e4m3).  The latter two
are the lower-precision controls.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _round(x, prec):
    if prec == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    if prec == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x.astype(F32)


def mm(x, w, prec):
    """x @ w with both operands in ``prec``, accumulated in f32."""
    return jnp.matmul(_round(x, prec), _round(w, prec))


def act(x, prec):
    """Activations as stored between layers."""
    return x.astype(jnp.bfloat16).astype(F32) if prec == "bf16" else x


def rmsnorm(x, g, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """x: (..., S, H, D) rotate-half RoPE at positions ``pos`` (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv                  # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _pad_len(n, nr):
    blocks = max(1, -(-n // nr))
    return nr * (1 << (blocks - 1).bit_length())


def h1d_attention(q, k, v, nr, prec="f32"):
    """Causal fine-q H1D.  q: (B, L, Hq, D) already scaled by 1/sqrt(D);
    k, v: (B, L, Hkv, D).  Returns (B, L, Hq, D) in f32."""
    B, L, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    Lp = _pad_len(L, nr)
    pad = [(0, 0), (0, Lp - L), (0, 0), (0, 0)]
    # (B, Hkv, G, Lp, D) and (B, Hkv, Lp, D): query head h uses kv head h // G
    qh = jnp.pad(q.astype(F32), pad).reshape(B, Lp, Hkv, G, D)
    qh = qh.transpose(0, 2, 3, 1, 4)
    kh = jnp.pad(k.astype(F32), pad).transpose(0, 2, 1, 3)
    vh = jnp.pad(v.astype(F32), pad).transpose(0, 2, 1, 3)
    levels = max(1, (Lp // nr).bit_length() - 1)    # log2(Lp / nr), >= 1

    scores, values, sizes = [], [], []
    # level 0: query block I against key blocks I-1 and I, j <= i
    nb = Lp // nr
    qb = qh.reshape(B, Hkv, G, nb, nr, D)
    kb = kh.reshape(B, Hkv, nb, nr, D)
    vb = vh.reshape(B, Hkv, nb, nr, D)
    kprev = jnp.pad(kb, [(0, 0), (0, 0), (1, 0), (0, 0), (0, 0)])[:, :, :nb]
    vprev = jnp.pad(vb, [(0, 0), (0, 0), (1, 0), (0, 0), (0, 0)])[:, :, :nb]
    k0 = jnp.concatenate([kprev, kb], 3)                   # (B,Hkv,nb,2nr,D)
    v0 = jnp.concatenate([vprev, vb], 3)
    s0 = jnp.einsum("bhgnqd,bhnkd->bhgnqk", _round(qb, prec), _round(k0, prec))
    r = jnp.arange(nr)[:, None]
    c = jnp.arange(2 * nr)[None, :]
    ok = (c - nr) <= r                                     # causal, this block
    ok = ok[None] & ((jnp.arange(nb)[:, None, None] > 0) | (c >= nr)[None])
    scores.append(jnp.where(ok, s0, -jnp.inf).reshape(B, Hkv, G, Lp, 2 * nr))
    values.append((v0, nr))
    sizes.append(jnp.ones((2 * nr,), F32))

    kc, vc = kh, vh
    for l in range(1, levels):
        kc = kc.reshape(B, Hkv, -1, 2, D).mean(3)          # group means
        vc = vc.reshape(B, Hkv, -1, 2, D).sum(3)           # group sums
        span = nr << l
        nb = Lp // span
        qb = qh.reshape(B, Hkv, G, nb, span, D)
        kb = kc.reshape(B, Hkv, nb, nr, D)
        vb = vc.reshape(B, Hkv, nb, nr, D)
        kprev = jnp.pad(kb, [(0, 0), (0, 0), (1, 0), (0, 0), (0, 0)])[:, :, :nb]
        vprev = jnp.pad(vb, [(0, 0), (0, 0), (1, 0), (0, 0), (0, 0)])[:, :, :nb]
        s = jnp.einsum("bhgnqd,bhnkd->bhgnqk", _round(qb, prec),
                       _round(kprev, prec))
        first_half = jnp.arange(span)[:, None] < span // 2
        second_half = jnp.arange(nr)[None, :] >= nr // 2
        ok = ~(first_half & second_half)[None] & (jnp.arange(nb) > 0)[:, None,
                                                                      None]
        scores.append(jnp.where(ok, s, -jnp.inf).reshape(B, Hkv, G, Lp, nr))
        values.append((vprev, span))
        sizes.append(jnp.full((nr,), float(1 << l), F32))

    m = jnp.max(jnp.concatenate([s.max(-1, keepdims=True) for s in scores],
                                -1), -1, keepdims=True)
    num = 0.0
    den = 0.0
    for s, (vv, rows), w in zip(scores, values, sizes):
        p = jnp.exp(s - m)                                 # (B,Hkv,G,Lp,nk)
        den = den + jnp.sum(p * w, -1, keepdims=True)
        nb = Lp // rows
        pb = p.reshape(B, Hkv, G, nb, rows, -1)
        o = jnp.einsum("bhgnqk,bhnkd->bhgnqd", _round(pb, prec),
                       _round(vv, prec))
        num = num + o.reshape(B, Hkv, G, Lp, D)
    out = num / den
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Lp, Hq, D)
    return out[:, :L]


def block(w, i, cfg, h, prec, head_groups=1, mlp_rows=None):
    """One pre-norm decoder block; ``w`` holds stacked layer weights.
    Attention can run over ``head_groups`` groups of KV heads in turn
    and the MLP over ``mlp_rows`` rows at a time, so that long
    sequences fit."""
    B, S, d = h.shape
    Hq, Hkv, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    G = Hq // Hkv
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    x = rmsnorm(h, w["ln1"][i], eps)
    q = mm(x, w["wq"][i], prec).reshape(B, S, Hq, D)
    k = mm(x, w["wk"][i], prec).reshape(B, S, Hkv, D)
    v = mm(x, w["wv"][i], prec).reshape(B, S, Hkv, D)
    pos = jnp.arange(S)
    q = act(rope(q, pos, theta) / math.sqrt(D), prec)
    k = act(rope(k, pos, theta), prec)
    v = act(v, prec)
    n = Hkv // head_groups
    z = jnp.concatenate(
        [h1d_attention(q[:, :, g * G * n:(g + 1) * G * n],
                       k[:, :, g * n:(g + 1) * n], v[:, :, g * n:(g + 1) * n],
                       cfg["nr"], prec)
         for g in range(head_groups)], 2)
    h = act(h + mm(act(z.reshape(B, S, Hq * D), prec), w["wo"][i], prec),
            prec)
    outs = []
    rows = mlp_rows or S
    for r in range(0, S, rows):
        hr = h[:, r:r + rows]
        x = rmsnorm(hr, w["ln2"][i], eps)
        gate = jax.nn.silu(mm(x, w["wg"][i], prec))
        up = mm(x, w["wu"][i], prec)
        outs.append(act(hr + mm(act(gate * up, prec), w["wd"][i], prec),
                        prec))
    return jnp.concatenate(outs, 1)


def hidden(w, cfg, tokens, prec="f32"):
    """Final-normed hidden states (B, S, d) of ``tokens`` (B, S)."""
    h = act(w["embed"][tokens].astype(F32), prec)
    for i in range(cfg["num_layers"]):
        h = block(w, i, cfg, h, prec)
    return rmsnorm(h, w["final_norm"], cfg["rms_norm_eps"])


def head(w, cfg, x, prec="f32"):
    """Logits (..., V) of final-normed hidden states."""
    wh = w["embed"].T if cfg["tie_embeddings"] else w["lm_head"]
    return mm(x, wh, prec)


def loss(w, cfg, tokens, prec="f32"):
    """Mean next-token cross-entropy over every position of every row."""
    logits = head(w, cfg, hidden(w, cfg, tokens, prec), prec)[:, :-1]
    tgt = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


def adamw_lr(tc, step):
    """Linear warm-up from 0 to ``peak_lr`` over ``warmup`` steps, then a
    cosine decay to ``lr_floor * peak_lr`` at ``total_steps``."""
    step = jnp.asarray(step, F32)
    warm = tc["peak_lr"] * step / max(tc["warmup"], 1)
    frac = jnp.clip((step - tc["warmup"])
                    / max(tc["total_steps"] - tc["warmup"], 1), 0.0, 1.0)
    fl = tc["lr_floor"]
    cos = tc["peak_lr"] * (fl + (1 - fl) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < tc["warmup"], warm, cos)


def adamw_step(w, m, v, g, step, tc):
    """AdamW with global-norm clipping and decoupled weight decay on
    every leaf.  ``step`` counts from 1.  Returns (w, m, v, clipped g)."""
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, tc["clip_norm"] / jnp.maximum(norm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, g)
    b1, b2, eps, wd = tc["b1"], tc["b2"], tc["eps"], tc["weight_decay"]
    lr = adamw_lr(tc, step)
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    step = jnp.asarray(step, F32)
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
    w = jax.tree.map(
        lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + eps)
                                  + wd * p), w, m, v)
    return w, m, v, g
