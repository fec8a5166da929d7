"""Pallas TPU kernel: fused banded block attention for H-Transformer-1D.

This is the compute hot-spot of the paper (Algorithm 1 steps 2/4/5): for
one hierarchy level, every query block attends its self/prev/next key
blocks, with the level masks, producing the unnormalized output ``Y``,
the normalizer contribution ``D`` and the row-max ``m`` in ONE VMEM pass
-- no (L x L) or even (L x 3*nr) attention tensor ever hits HBM.

TPU adaptation (DESIGN.md section 2): the paper's logical block size
``nr`` (16 in the LM experiments) is far below the 128x128 MXU tile, so
the kernel processes *groups* of blocks: a TQ-row query tile (TQ >= 128)
against its own TQ-key tile plus the ``nr``-wide halo edges of the two
neighbouring tiles.  The band/quadrant/causal masks are generated from
global indices with ``broadcasted_iota`` -- no mask tensors in HBM.

Grid: ``(B, G, Lq // TQ)``; GQA is handled by letting the K/V/W
BlockSpec index maps ignore the group axis ``g`` (no KV replication in
HBM).  All matmuls accumulate in float32.

Modes (must mirror ``repro.kernels.ref``):
  * ``l0_bidir``     -- level-0 tridiagonal
  * ``l0_causal``    -- level-0 causal (tril diagonal + sub-diagonal)
  * ``coarse_bidir`` -- level>=1 bi-diagonal with quadrant exclusions
  * ``coarse_causal``-- level>=1 sub-diagonal with quadrant exclusion
  * ``sub``          -- level>=1 leak-free causal with FINE queries
    (``causal_mode='fine-q'``): queries keep length ``Lq`` while K/V/W
    are the level-l coarse sequence of length ``Lk = Lq / ratio``
    (``ratio = 2**l``).  Query block I (``nr * ratio`` fine rows)
    attends coarse key block I-1 under the 'sub' quadrant exclusion --
    the same partition as ``core.h1d_attention._level_fine_q``, fused
    into one VMEM pass per query tile (DESIGN.md section 2).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.contracts import launch

NEG_INF = -3.0e38
_MIN_M = -1e30

MODES = ("l0_bidir", "l0_causal", "coarse_bidir", "coarse_causal")
SUB_MODE = "sub"   # fine-q causal level>=1: fine queries x coarse keys

# operand names (contract diagnostics) for sub_kv_specs' two layouts
SUB_KV_NAMES = {
    "wide": ("k_self", "k_prev", "v_self", "v_prev", "w_self", "w_prev"),
    "deep": ("k_blk", "v_blk", "w_blk"),
}


def band_mask(qi, ki, nr: int, mode: str, lk: int, ratio: int = 1):
    """Allowed-mask from *global* row/col indices (broadcastable shapes).

    Single source of truth for the band structure -- used both inside the
    kernel (with iota-generated indices) and by the jnp reference.

    ``mode='sub'``: ``qi`` are FINE query indices, ``ki`` level-l coarse
    key indices, ``ratio = 2**l``.  A fine query in coarse-resolution
    block I attends coarse key block I-1; the quadrant exclusion drops
    (first-half queries x last-half keys) of the span -- those pairs are
    covered at a finer level.  ``qi // ratio`` maps a fine query to its
    coarse row, after which the structure is exactly ``coarse_causal``.
    """
    if mode == SUB_MODE:
        return band_mask(qi // ratio, ki, nr, "coarse_causal", lk)
    inb = (ki >= 0) & (ki < lk)
    bq = qi // nr
    bk = ki // nr
    diff = bq - bk
    if mode == "l0_bidir":
        allow = jnp.abs(diff) <= 1
    elif mode == "l0_causal":
        allow = ((diff == 0) & (ki <= qi)) | (diff == 1)
    else:
        half = nr // 2
        base = (diff == 1) if mode == "coarse_causal" else (jnp.abs(diff) == 1)
        sub_excl = (diff == 1) & ((qi % nr) < half) & ((ki % nr) >= half)
        sup_excl = (diff == -1) & ((qi % nr) >= half) & ((ki % nr) < half)
        allow = base & ~sub_excl & ~sup_excl
    return allow & inb


def _fwd_kernel(*refs, nr: int, mode: str, tq: int, lk: int):
    causal = mode.endswith("causal")
    if causal:
        (q_ref, ks_ref, kp_ref, vs_ref, vp_ref, ws_ref, wp_ref,
         y_ref, dn_ref, m_ref) = refs
    else:
        (q_ref, ks_ref, kp_ref, kn_ref, vs_ref, vp_ref, vn_ref,
         ws_ref, wp_ref, wn_ref, y_ref, dn_ref, m_ref) = refs

    it = pl.program_id(2)
    f32 = jnp.float32

    q = q_ref[0, 0].astype(f32)                       # (TQ, d)
    qi = it * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)

    def term(k, v, w, k0):
        """k: (TK, d), v: (TK, dv), w: (TK, 1), k0: global col offset."""
        tk = k.shape[0]
        ki = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        s = jax.lax.dot_general(
            q, k.astype(f32), (((1,), (1,)), ((), ())),
            preferred_element_type=f32)                # (TQ, TK)
        w = key_row(w)
        allow = band_mask(qi, ki, nr, mode, lk) & (w > 0)
        return jnp.where(allow, s, NEG_INF), v.astype(f32), w

    # halo refs are exact nr-row blocks (the BlockSpecs fetch only the
    # needed edge of the neighbouring tile, not the whole tile)
    terms = [
        term(ks_ref[0], vs_ref[0], ws_ref[0], it * tq),
        term(kp_ref[0], vp_ref[0], wp_ref[0], it * tq - nr),
    ]
    if not causal:
        terms.append(
            term(kn_ref[0], vn_ref[0], wn_ref[0], (it + 1) * tq))

    _combine(terms, y_ref, dn_ref, m_ref)


def _combine(terms, y_ref, dn_ref, m_ref):
    """Single-max softmax over a tile's bands -> (y, dn, m) blocks.
    Row statistics are (TQ, 1) columns, the layout of their HBM blocks."""
    f32 = jnp.float32
    m = jnp.maximum(
        functools.reduce(jnp.maximum,
                         [s.max(axis=1, keepdims=True) for s, _, _ in terms]),
        _MIN_M)                                        # (TQ, 1)
    y = None
    dn = None
    for s, v, w in terms:
        a = jnp.exp(s - m)
        yt = jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=f32)
        dt = jnp.sum(a * w, axis=1, keepdims=True)
        y = yt if y is None else y + yt
        dn = dt if dn is None else dn + dt

    y_ref[0, 0] = y.astype(y_ref.dtype)
    dn_ref[0, 0] = dn.astype(dn_ref.dtype)
    m_ref[0, 0] = m.astype(m_ref.dtype)


def key_row(w):
    """(TK, 1) key-weight column block -> (1, TK) f32 row.  Key weights
    travel as (B, L, 1) columns so that nr-row halo blocks satisfy
    Mosaic's tiling rule; the masks need them along the lane axis."""
    return w.astype(jnp.float32).reshape(1, w.shape[0])


def _fwd_sub_kernel(*refs, nr: int, ratio: int, tq: int, lk: int):
    """Fine-q causal forward: fine query tile x shifted coarse KV band.

    Two static layouts (the wrapper normalizes ``tq`` so exactly one
    applies):
      * nq <= tq ("wide tile"): the tile covers >= 1 whole query blocks;
        its keys are the coarse window [it*tqc - nr, (it+1)*tqc - nr),
        i.e. the nr-wide tail of the PREV coarse tile plus the head of
        the SELF coarse tile -- the same halo machinery as the l0 modes.
      * nq > tq ("deep level"): the tile lies inside ONE query block I,
        whose keys are the single coarse block I-1 (nr rows).
    """
    nq = nr * ratio
    if nq <= tq:
        (q_ref, ks_ref, kp_ref, vs_ref, vp_ref, ws_ref, wp_ref,
         y_ref, dn_ref, m_ref) = refs
    else:
        q_ref, kb_ref, vb_ref, wb_ref, y_ref, dn_ref, m_ref = refs

    it = pl.program_id(2)
    f32 = jnp.float32
    q = q_ref[0, 0].astype(f32)                       # (TQ, d)
    qi = it * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)

    def term(k, v, w, k0):
        tk = k.shape[0]
        ki = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        s = jax.lax.dot_general(
            q, k.astype(f32), (((1,), (1,)), ((), ())),
            preferred_element_type=f32)               # (TQ, TK)
        w = key_row(w)
        allow = band_mask(qi, ki, nr, SUB_MODE, lk, ratio) & (w > 0)
        return jnp.where(allow, s, NEG_INF), v.astype(f32), w

    if nq <= tq:
        tqc = tq // ratio                             # coarse rows per tile
        # prev-halo refs are exact nr-row coarse blocks (see sub_kv_specs)
        terms = [term(kp_ref[0], vp_ref[0], wp_ref[0], it * tqc - nr)]
        if tqc > nr:
            terms.append(term(ks_ref[0, :tqc - nr, :], vs_ref[0, :tqc - nr, :],
                              ws_ref[0, :tqc - nr, :], it * tqc))
    else:
        s_blk = nq // tq                              # query tiles per block
        k0 = (it // s_blk - 1) * nr                   # coarse block I-1
        terms = [term(kb_ref[0], vb_ref[0], wb_ref[0], k0)]

    _combine(terms, y_ref, dn_ref, m_ref)


def sub_kv_specs(nr: int, ratio: int, tq: int):
    """BlockSpec builder for the coarse K/V/W operands of the ``sub``
    mode on a (b, g, i) query-tile grid (forward / dQ kernels).

    Returns ``(build, layout)``: ``build(k, v, w, d, dv)`` yields the
    (specs, inputs) lists in the unpack order of the sub kernels, and
    ``layout`` is 'wide' (self coarse tile + exact nr-row prev-halo
    block, nq <= tq) or 'deep' (single coarse block I-1, nq > tq)."""
    nq = nr * ratio
    if nq <= tq:
        tqc = tq // ratio
        tbc = tqc // nr          # nr-row coarse blocks per coarse tile
        self_map = lambda b, g, i: (b, i, 0)
        # prev-halo: the single nr-row coarse block just before this
        # tile's coarse window (exact fetch, index map in nr units)
        prev_map = lambda b, g, i: (b, jnp.maximum(i * tbc - 1, 0), 0)

        def build(k, v, w, d_, dv_):
            specs = [pl.BlockSpec((1, tqc, d_), self_map),
                     pl.BlockSpec((1, nr, d_), prev_map),
                     pl.BlockSpec((1, tqc, dv_), self_map),
                     pl.BlockSpec((1, nr, dv_), prev_map),
                     pl.BlockSpec((1, tqc, 1), self_map),
                     pl.BlockSpec((1, nr, 1), prev_map)]
            return specs, [k, k, v, v, w, w]
        return build, "wide"
    s_blk = nq // tq
    blk_map = lambda b, g, i: (b, jnp.maximum(i // s_blk - 1, 0), 0)

    def build(k, v, w, d_, dv_):
        specs = [pl.BlockSpec((1, nr, d_), blk_map),
                 pl.BlockSpec((1, nr, dv_), blk_map),
                 pl.BlockSpec((1, nr, 1), blk_map)]
        return specs, [k, v, w]
    return build, "deep"


def band_attention_sub_fwd(
    q: jnp.ndarray,   # (B, G, Lq, d) -- pre-scaled FINE queries
    k: jnp.ndarray,   # (B, Lk, d)  level-l coarse keys, Lk = Lq / ratio
    v: jnp.ndarray,   # (B, Lk, dv) level-l coarse values (pairwise sums)
    w: jnp.ndarray,   # (B, Lk)     level-l coarse key weights
    *,
    nr: int,
    ratio: int,
    tq: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused fine-q causal level (mode='sub').  Returns float32
    y (B, G, Lq, dv), dn (B, G, Lq), m (B, G, Lq)."""
    B, G, Lq, d = q.shape
    Lk = k.shape[1]
    dv = v.shape[-1]
    nq = nr * ratio
    assert ratio >= 2 and Lq == Lk * ratio, (Lq, Lk, ratio)
    assert Lq % tq == 0 and tq % nr == 0, (Lq, tq, nr)
    assert (tq % nq == 0) or (nq % tq == 0), (tq, nq)
    if nq <= tq:
        assert (tq // ratio) % nr == 0, (tq, ratio, nr)
    nt = Lq // tq

    in_specs = [pl.BlockSpec((1, 1, tq, d), lambda b, g, i: (b, g, i, 0))]
    build, layout = sub_kv_specs(nr, ratio, tq)
    kv_specs, kv_inputs = build(k, v, w[..., None], d, dv)
    in_specs += kv_specs
    inputs = [q] + kv_inputs

    kernel = functools.partial(_fwd_sub_kernel, nr=nr, ratio=ratio, tq=tq,
                               lk=Lk)
    y, dn, m = launch(
        kernel, family="sub_fwd", grid=(B, G, nt),
        in_specs=in_specs, out_specs=_out_specs(tq, dv),
        out_shape=_out_shape(B, G, Lq, dv),
        operands=inputs, interpret=interpret,
        in_names=("q",) + SUB_KV_NAMES[layout],
        out_names=("y", "dn", "m"),
        meta=dict(mode=SUB_MODE, nr=nr, ratio=ratio, tq=tq, lk=Lk,
                  layout=layout))
    return y, dn[..., 0], m[..., 0]


def _out_shape(B, G, L, dv):
    """f32 (y, dn, m); the row statistics are (B, G, L, 1) columns so
    their (tq, 1) blocks meet Mosaic's tiling rule for any G."""
    f32 = jnp.float32
    return (jax.ShapeDtypeStruct((B, G, L, dv), f32),
            jax.ShapeDtypeStruct((B, G, L, 1), f32),
            jax.ShapeDtypeStruct((B, G, L, 1), f32))


def _out_specs(tq, dv):
    tile = lambda b, g, i: (b, g, i, 0)
    return (pl.BlockSpec((1, 1, tq, dv), tile),
            pl.BlockSpec((1, 1, tq, 1), tile),
            pl.BlockSpec((1, 1, tq, 1), tile))


def band_attention_fwd(
    q: jnp.ndarray,   # (B, G, L, d) -- pre-scaled queries
    k: jnp.ndarray,   # (B, L, d)
    v: jnp.ndarray,   # (B, L, dv)
    w: jnp.ndarray,   # (B, L) key weights (>0 == valid)
    *,
    nr: int,
    mode: str,
    tq: int = 128,
    ratio: int = 1,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused banded block attention.  Returns float32 (y, dn, m):
    y (B, G, L, dv), dn (B, G, L), m (B, G, L).

    ``mode='sub'`` is the fine-q causal coarse level: q keeps the fine
    length while k/v/w are ``ratio``x coarser (see module docstring)."""
    if mode == SUB_MODE:
        return band_attention_sub_fwd(q, k, v, w, nr=nr, ratio=ratio,
                                      tq=tq, interpret=interpret)
    assert mode in MODES, mode
    B, G, L, d = q.shape
    dv = v.shape[-1]
    assert L % tq == 0 and tq % nr == 0 and tq >= nr, (L, tq, nr)
    nt = L // tq
    causal = mode.endswith("causal")

    # self operand: the full tile; halo operands: exact nr-row blocks
    # at the neighbouring tile's edge (index maps count nr-row blocks),
    # so halo HBM fetch is nr rows, not tq, per tensor per grid step.
    nb = L // nr
    tb = tq // nr
    self_map = lambda b, g, i: (b, i, 0)
    prev_map = lambda b, g, i: (b, jnp.maximum(i * tb - 1, 0), 0)
    next_map = lambda b, g, i: (b, jnp.minimum((i + 1) * tb, nb - 1), 0)

    in_specs = [pl.BlockSpec((1, 1, tq, d), lambda b, g, i: (b, g, i, 0))]
    inputs = [q]
    kmaps = [(tq, self_map), (nr, prev_map)] + (
        [] if causal else [(nr, next_map)])
    for arr, width in ((k, d), (v, dv), (w[..., None], 1)):
        for rows, mp in kmaps:
            in_specs.append(pl.BlockSpec((1, rows, width), mp))
            inputs.append(arr)

    kernel = functools.partial(_fwd_kernel, nr=nr, mode=mode, tq=tq, lk=L)
    halo = ("self", "prev") if causal else ("self", "prev", "next")
    y, dn, m = launch(
        kernel, family="band_fwd", grid=(B, G, nt),
        in_specs=in_specs, out_specs=_out_specs(tq, dv),
        out_shape=_out_shape(B, G, L, dv),
        operands=inputs, interpret=interpret,
        in_names=("q",) + tuple(f"{a}_{h}" for a in "kvw" for h in halo),
        out_names=("y", "dn", "m"),
        meta=dict(mode=mode, nr=nr, tq=tq, lk=L))
    return y, dn[..., 0], m[..., 0]
