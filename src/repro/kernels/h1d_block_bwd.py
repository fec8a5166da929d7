"""Hand-written Pallas TPU backward for the banded block attention.

Flash-style recompute backward (DESIGN.md section 3): the forward saves
only its inputs and its three outputs ``(y, dn, m)`` -- no (L x 3*nr)
score or probability tensor ever hits HBM.  Both backward kernels
re-materialize the banded scores per tile in VMEM from ``(q, k, w, m)``
using the shared :func:`~repro.kernels.h1d_block.band_mask` helper, so
the band semantics cannot drift between passes.

Math.  Forward (per level, per query row ``i``):

    s_ij = q_i . k_j         (NEG_INF off-band / where w_j == 0)
    m_i  = max(max_j s_ij, _MIN_M)
    a_ij = exp(s_ij - m_i)
    y_i  = sum_j a_ij v_j,   dn_i = sum_j a_ij w_j

Given output cotangents ``(gy, gdn, gm)``:

    delta_i  = gy_i . y_i + gdn_i * dn_i     (= sum_j a_ij * da_ij)
    gmh_i    = gm_i - delta_i                (cotangent reaching m)
    da_ij    = gy_i . v_j + gdn_i * w_j
    ds_ij    = a_ij * da_ij + (gmh_i / c_i) * 1[s_ij == m_i]
    dq_i     = sum_j ds_ij k_j
    dk_j     = sum_{g,i} ds_ij q_i
    dv_j     = sum_{g,i} a_ij  gy_i
    dw_j     = sum_{g,i} a_ij  gdn_i

``c_i`` counts the argmax ties of row ``i`` (JAX's ``reduce_max`` VJP
splits the cotangent equally among ties); ``delta`` needs only the saved
outputs, which is why ``(y, dn, m)`` are the whole residual.

Two kernels (mirroring the FlashAttention-2 split):

* ``_dq_kernel``   -- query-tile grid ``(B, G, L//TQ)``.  Each tile sees
  its full band (self tile + nr-wide halo edges of both neighbours), so
  it also computes the row tie-count and emits the per-row max-gradient
  scale ``gmn = gmh / c`` consumed by the key-grid pass.
* ``_dkvw_kernel`` -- key-tile grid ``(B, L//TQ, G)`` with ``g``
  innermost: dK/dV/dW blocks accumulate across the GQA group axis in
  VMEM (output index maps ignore ``g``), so shared-KV gradients never
  materialize a per-group copy in HBM.  Halo contributions come from the
  first ``nr`` query rows of tile ``t+1`` (which read this tile's last
  ``nr`` keys as their 'prev' band) and -- bidirectional modes only --
  the last ``nr`` query rows of tile ``t-1``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.contracts import launch

from .h1d_block import (band_mask, key_row, sub_kv_specs, NEG_INF, MODES,
                        SUB_MODE, SUB_KV_NAMES)


def _recompute(q, k, w, m, qi, ki, *, nr: int, mode: str, lk: int,
               ratio: int = 1, lq: int = None):
    """Re-materialize one band: masked scores -> (a, ind).

    q: (nq, d) f32; k: (nk, d) f32; w: (1, nk) f32 key-weight row; m:
    (nq, 1) f32 saved row-max column; qi: (nq, 1) / ki: (1, nk) global
    indices.  Returns ``a = exp(s - m)`` (exactly 0 off-band via the
    NEG_INF mask) and the argmax indicator ``ind = (s == m)`` as f32.
    Query rows outside [0, lq) (clamped neighbour tiles at the sequence
    edges) are masked here -- ``band_mask`` itself only bounds-checks
    keys.  ``lq`` defaults to ``lk``; the ``sub`` mode passes the fine
    query length (= lk * ratio) since its key axis is coarse.
    """
    f32 = jnp.float32
    lq = lk if lq is None else lq
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)
    allow = band_mask(qi, ki, nr, mode, lk, ratio) & (w > 0)
    allow = allow & (qi >= 0) & (qi < lq)
    s = jnp.where(allow, s, NEG_INF)
    a = jnp.exp(s - m)
    ind = (s == m).astype(f32)
    return a, ind


def _add_rows(x, h, start: int):
    """``x`` with rows ``[start, start + len(h))`` incremented by ``h``
    (static offsets, built as a sublane concatenate)."""
    n = h.shape[0]
    parts = [x[:start]] if start else []
    parts.append(x[start:start + n] + h)
    if start + n < x.shape[0]:
        parts.append(x[start + n:])
    return jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]


def _dq_kernel(*refs, nr: int, mode: str, tq: int, lk: int):
    causal = mode.endswith("causal")
    if causal:
        (q_ref, ks_ref, kp_ref, vs_ref, vp_ref, ws_ref, wp_ref,
         m_ref, gy_ref, gdn_ref, gmh_ref, dq_ref, gmn_ref) = refs
    else:
        (q_ref, ks_ref, kp_ref, kn_ref, vs_ref, vp_ref, vn_ref,
         ws_ref, wp_ref, wn_ref,
         m_ref, gy_ref, gdn_ref, gmh_ref, dq_ref, gmn_ref) = refs

    it = pl.program_id(2)
    f32 = jnp.float32
    q = q_ref[0, 0].astype(f32)                        # (TQ, d)
    m = m_ref[0, 0].astype(f32)                        # (TQ, 1)
    gy = gy_ref[0, 0].astype(f32)                      # (TQ, dv)
    gdn = gdn_ref[0, 0].astype(f32)                    # (TQ, 1)
    gmh = gmh_ref[0, 0].astype(f32)                    # (TQ, 1)
    qi = it * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)

    def band(k, v, w, k0):
        k, v, w = k.astype(f32), v.astype(f32), key_row(w)
        tk = k.shape[0]
        ki = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        a, ind = _recompute(q, k, w, m, qi, ki, nr=nr, mode=mode, lk=lk)
        da = jax.lax.dot_general(gy, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)
        da = da + gdn * w
        return a * da, ind, k

    # halo refs are exact nr-row blocks (see band_attention_fwd's specs)
    bands = [
        band(ks_ref[0], vs_ref[0], ws_ref[0], it * tq),
        band(kp_ref[0], vp_ref[0], wp_ref[0], it * tq - nr),
    ]
    if not causal:
        bands.append(band(kn_ref[0], vn_ref[0], wn_ref[0], (it + 1) * tq))
    _store_dq(bands, gmh, dq_ref, gmn_ref)


def _store_dq(bands, gmh, dq_ref, gmn_ref):
    """Tie-split max gradient ``gmn = gmh / count`` and ``dq`` from a
    query tile's bands of ``(a * da, ind, k)``."""
    f32 = jnp.float32
    count = functools.reduce(
        jnp.add, [ind.sum(axis=1, keepdims=True) for _, ind, _ in bands])
    gmn = jnp.where(count > 0, gmh / jnp.maximum(count, 1.0), 0.0)

    dq = None
    for ds0, ind, k in bands:
        ds = ds0 + gmn * ind
        dqt = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                  preferred_element_type=f32)
        dq = dqt if dq is None else dq + dqt

    dq_ref[0, 0] = dq.astype(dq_ref.dtype)
    gmn_ref[0, 0] = gmn.astype(gmn_ref.dtype)


def _band_dkvw(qrows, gyrows, gdnrows, mrows, gmnrows, q0,
               krows, vrows, wrows, k0, *, nr, mode, lk, ratio=1, lq=None):
    """One (query-rows x key-rows) band of the dK/dV/dW pass.  Query
    statistics are (nq, 1) columns; ``wrows`` is the (nk, 1) key-weight
    column.  Returns dK (nk, d), dV (nk, dv), dW (nk, 1)."""
    f32 = jnp.float32
    nq = qrows.shape[0]
    nk = krows.shape[0]
    qi = q0 + jax.lax.broadcasted_iota(jnp.int32, (nq, 1), 0)
    ki = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, nk), 1)
    w = key_row(wrows)
    a, ind = _recompute(qrows, krows, w, mrows, qi, ki, nr=nr, mode=mode,
                        lk=lk, ratio=ratio, lq=lq)
    da = jax.lax.dot_general(gyrows, vrows, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)
    da = da + gdnrows * w
    ds = a * da + gmnrows * ind
    dk_b = jax.lax.dot_general(ds, qrows, (((0,), (0,)), ((), ())),
                               preferred_element_type=f32)   # (nk, d)
    dv_b = jax.lax.dot_general(a, gyrows, (((0,), (0,)), ((), ())),
                               preferred_element_type=f32)   # (nk, dv)
    dw_b = jnp.sum(a * gdnrows, axis=0, keepdims=True)       # (1, nk)
    return dk_b, dv_b, dw_b.reshape(nk, 1)


def _tile_rows(*refs, rows=slice(None)):
    """Read the f32 (rows, ...) slab of each (1, 1, R, ...) query ref."""
    return [r[0, 0, rows].astype(jnp.float32) for r in refs]


def _accumulate(first, dk, dvv, dw, dk_ref, dv_ref, dw_ref):
    """Write the key tile's gradients on the ``first`` visit of its
    output block and accumulate on later ones: the output index maps
    ignore the inner grid axes, so the block stays resident in VMEM."""
    @pl.when(first)
    def _init():
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dvv.astype(dv_ref.dtype)
        dw_ref[0] = dw.astype(dw_ref.dtype)

    @pl.when(jnp.logical_not(first))
    def _acc():
        dk_ref[0] += dk.astype(dk_ref.dtype)
        dv_ref[0] += dvv.astype(dv_ref.dtype)
        dw_ref[0] += dw.astype(dw_ref.dtype)


def _dkvw_kernel(*refs, nr: int, mode: str, tq: int, lk: int):
    causal = mode.endswith("causal")
    if causal:
        (k_ref, v_ref, w_ref,
         qs_ref, qn_ref, gys_ref, gyn_ref, gdns_ref, gdnn_ref,
         ms_ref, mn_ref, gmns_ref, gmnn_ref,
         dk_ref, dv_ref, dw_ref) = refs
    else:
        (k_ref, v_ref, w_ref,
         qs_ref, qn_ref, qp_ref, gys_ref, gyn_ref, gyp_ref,
         gdns_ref, gdnn_ref, gdnp_ref, ms_ref, mn_ref, mp_ref,
         gmns_ref, gmnn_ref, gmnp_ref,
         dk_ref, dv_ref, dw_ref) = refs

    it = pl.program_id(1)
    g = pl.program_id(2)
    f32 = jnp.float32
    k = k_ref[0].astype(f32)                           # (TK, d)
    v = v_ref[0].astype(f32)                           # (TK, dv)
    w = w_ref[0].astype(f32)                           # (TK, 1)
    band = functools.partial(_band_dkvw, nr=nr, mode=mode, lk=lk)

    # self band: query tile `it` against this whole key tile.
    dk, dvv, dw = band(
        *_tile_rows(qs_ref, gys_ref, gdns_ref, ms_ref, gmns_ref), it * tq,
        k, v, w, it * tq)

    # prev-halo: the first nr query rows of tile it+1 read this tile's
    # last nr keys as their 'prev' band (refs are exact nr-row blocks).
    dk_h, dv_h, dw_h = band(
        *_tile_rows(qn_ref, gyn_ref, gdnn_ref, mn_ref, gmnn_ref),
        (it + 1) * tq, k[tq - nr:], v[tq - nr:], w[tq - nr:],
        it * tq + tq - nr)
    dk = _add_rows(dk, dk_h, tq - nr)
    dvv = _add_rows(dvv, dv_h, tq - nr)
    dw = _add_rows(dw, dw_h, tq - nr)

    if not causal:
        # next-halo: the last nr query rows of tile it-1 read this
        # tile's first nr keys as their 'next' band.
        dk_h, dv_h, dw_h = band(
            *_tile_rows(qp_ref, gyp_ref, gdnp_ref, mp_ref, gmnp_ref),
            it * tq - nr, k[:nr], v[:nr], w[:nr], it * tq)
        dk = _add_rows(dk, dk_h, 0)
        dvv = _add_rows(dvv, dv_h, 0)
        dw = _add_rows(dw, dw_h, 0)

    # accumulate across the (innermost) GQA group axis
    _accumulate(g == 0, dk, dvv, dw, dk_ref, dv_ref, dw_ref)


def _dq_sub_kernel(*refs, nr: int, ratio: int, tq: int, lk: int):
    """Fine-q causal dQ pass: mirrors ``_fwd_sub_kernel``'s band layout
    (wide: prev-tail + self-head coarse window; deep: single coarse
    block I-1) and emits the per-row max-gradient scale ``gmn``."""
    nq = nr * ratio
    if nq <= tq:
        (q_ref, ks_ref, kp_ref, vs_ref, vp_ref, ws_ref, wp_ref,
         m_ref, gy_ref, gdn_ref, gmh_ref, dq_ref, gmn_ref) = refs
    else:
        (q_ref, kb_ref, vb_ref, wb_ref,
         m_ref, gy_ref, gdn_ref, gmh_ref, dq_ref, gmn_ref) = refs

    it = pl.program_id(2)
    f32 = jnp.float32
    q, m, gy, gdn, gmh = _tile_rows(q_ref, m_ref, gy_ref, gdn_ref, gmh_ref)
    qi = it * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)

    def band(k, v, w, k0):
        k, v, w = k.astype(f32), v.astype(f32), key_row(w)
        tk = k.shape[0]
        ki = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        a, ind = _recompute(q, k, w, m, qi, ki, nr=nr, mode=SUB_MODE,
                            lk=lk, ratio=ratio, lq=lk * ratio)
        da = jax.lax.dot_general(gy, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)
        da = da + gdn * w
        return a * da, ind, k

    if nq <= tq:
        tqc = tq // ratio
        # prev-halo refs are exact nr-row coarse blocks (sub_kv_specs)
        bands = [band(kp_ref[0], vp_ref[0], wp_ref[0], it * tqc - nr)]
        if tqc > nr:
            bands.append(band(ks_ref[0, :tqc - nr, :], vs_ref[0, :tqc - nr, :],
                              ws_ref[0, :tqc - nr, :], it * tqc))
    else:
        s_blk = nq // tq
        bands = [band(kb_ref[0], vb_ref[0], wb_ref[0],
                      (it // s_blk - 1) * nr)]
    _store_dq(bands, gmh, dq_ref, gmn_ref)


def _dkvw_sub_wide_kernel(*refs, nr: int, ratio: int, tq: int, lk: int):
    """sub dK/dV/dW, wide layout (nq <= tq): key-tile grid (B, NT, G)
    over coarse tiles of ``tqc = tq // ratio`` rows, aligned with the
    fine query tiles.  The queries reading coarse tile ``it`` are the
    fine window [it*tq + nq, (it+1)*tq + nq): the tail of the SELF fine
    tile plus the first ``nq`` rows of the NEXT fine tile (the exact
    transpose of the forward's prev-tail/self-head key window)."""
    (k_ref, v_ref, w_ref,
     qs_ref, qn_ref, gys_ref, gyn_ref, gdns_ref, gdnn_ref,
     ms_ref, mn_ref, gmns_ref, gmnn_ref,
     dk_ref, dv_ref, dw_ref) = refs

    it = pl.program_id(1)
    g = pl.program_id(2)
    f32 = jnp.float32
    nq = nr * ratio
    tqc = tq // ratio
    k = k_ref[0].astype(f32)                           # (tqc, d)
    v = v_ref[0].astype(f32)
    w = w_ref[0].astype(f32)                           # (tqc, 1)
    band = functools.partial(_band_dkvw, nr=nr, mode=SUB_MODE, lk=lk,
                             ratio=ratio, lq=lk * ratio)

    # next-halo: first nq query rows of tile it+1 x this tile's last nr
    # keys (the query refs are exact nq-row blocks, see the wide specs)
    dk, dvv, dw = band(
        *_tile_rows(qn_ref, gyn_ref, gdnn_ref, mn_ref, gmnn_ref),
        (it + 1) * tq, k[tqc - nr:], v[tqc - nr:], w[tqc - nr:],
        (it + 1) * tqc - nr)

    # self band: query rows [nq:] of tile it x this tile's head keys;
    # the two bands cover disjoint key rows of the tile
    if nq < tq:
        heads = band(
            *_tile_rows(qs_ref, gys_ref, gdns_ref, ms_ref, gmns_ref,
                        rows=slice(nq, None)),
            it * tq + nq, k[:tqc - nr], v[:tqc - nr], w[:tqc - nr],
            it * tqc)
    else:
        heads = [jnp.zeros((tqc - nr, x.shape[1]), f32)
                 for x in (dk, dvv, dw)]
    if tqc > nr:
        dk, dvv, dw = [jnp.concatenate([hd, tl], axis=0)
                       for hd, tl in zip(heads, (dk, dvv, dw))]
    _accumulate(g == 0, dk, dvv, dw, dk_ref, dv_ref, dw_ref)


def _dkvw_sub_deep_kernel(*refs, nr: int, ratio: int, tq: int, lk: int):
    """sub dK/dV/dW, deep layout (nq > tq): grid (B, NKB, S, G) -- one
    coarse key BLOCK per ``j`` step, its nq = S*tq reading query rows
    split over the S innermost-but-one grid steps.  The (1, nr, *)
    output blocks' index maps ignore (s, g), so the accumulation over
    query sub-tiles AND the GQA group happens in VMEM."""
    (k_ref, v_ref, w_ref, q_ref, gy_ref, gdn_ref, m_ref, gmn_ref,
     dk_ref, dv_ref, dw_ref) = refs

    jt = pl.program_id(1)
    s = pl.program_id(2)
    g = pl.program_id(3)
    f32 = jnp.float32
    s_blk = (nr * ratio) // tq
    q0 = ((jt + 1) * s_blk + s) * tq
    dk, dvv, dw = _band_dkvw(
        *_tile_rows(q_ref, gy_ref, gdn_ref, m_ref, gmn_ref), q0,
        k_ref[0].astype(f32), v_ref[0].astype(f32), w_ref[0].astype(f32),
        jt * nr, nr=nr, mode=SUB_MODE, lk=lk, ratio=ratio, lq=lk * ratio)
    _accumulate((s == 0) & (g == 0), dk, dvv, dw, dk_ref, dv_ref, dw_ref)


def band_attention_sub_bwd(q, k, v, w, y, dn, m, gy, gdn, gm, *,
                           nr: int, ratio: int, tq: int = 128,
                           interpret: bool = False):
    """Fused backward of the ``sub`` (fine-q causal) level.  Same
    recompute strategy as the symmetric modes: only ``(q, k, v, w)`` and
    the saved outputs ``(y, dn, m)`` are read; the banded scores are
    re-materialized per tile in VMEM.  Returns (dq, dk, dv, dw)."""
    B, G, Lq, d = q.shape
    Lk = k.shape[1]
    dv = v.shape[-1]
    nq = nr * ratio
    assert ratio >= 2 and Lq == Lk * ratio, (Lq, Lk, ratio)
    assert Lq % tq == 0 and tq % nr == 0, (Lq, tq, nr)
    assert (tq % nq == 0) or (nq % tq == 0), (tq, nq)
    nt = Lq // tq
    f32 = jnp.float32

    gy, gdn, gmh, m, w3 = _row_stats(y, dn, m, gy, gdn, gm, w)

    qtile_map = lambda b, g_, i: (b, g_, i, 0)

    # ---- pass 1: dQ (fine query-tile grid) + per-row max-grad scale -------
    in_specs = [pl.BlockSpec((1, 1, tq, d), qtile_map)]
    build, layout = sub_kv_specs(nr, ratio, tq)
    kv_specs, kv_inputs = build(k, v, w3, d, dv)
    in_specs += kv_specs
    inputs = [q] + kv_inputs
    in_specs += [pl.BlockSpec((1, 1, tq, width), qtile_map)
                 for width in (1, dv, 1, 1)]
    inputs += [m, gy, gdn, gmh]

    dq, gmn = launch(
        functools.partial(_dq_sub_kernel, nr=nr, ratio=ratio, tq=tq, lk=Lk),
        family="sub_bwd", grid=(B, G, nt),
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((1, 1, tq, d), qtile_map),
                   pl.BlockSpec((1, 1, tq, 1), qtile_map)),
        out_shape=(jax.ShapeDtypeStruct((B, G, Lq, d), f32),
                   jax.ShapeDtypeStruct((B, G, Lq, 1), f32)),
        operands=inputs, interpret=interpret,
        in_names=(("q",) + SUB_KV_NAMES[layout]
                  + ("m", "gy", "gdn", "gmh")),
        out_names=("dq", "gmn"),
        meta=dict(mode=SUB_MODE, nr=nr, ratio=ratio, tq=tq, lk=Lk,
                  layout=layout, phase="dq"))

    # ---- pass 2: dK/dV/dW on the coarse key axis --------------------------
    if layout == "wide":
        tqc = tq // ratio
        # next-halo query operands are exact nq-row blocks: only the
        # first nq fine rows of tile it+1 read this coarse tile's keys
        nbq = Lq // nq
        tbq = tq // nq
        kv_self = lambda b, i, g_: (b, i, 0)
        q_self = lambda b, i, g_: (b, g_, i, 0)
        q_next = lambda b, i, g_: (
            b, g_, jnp.minimum((i + 1) * tbq, nbq - 1), 0)

        in_specs = [pl.BlockSpec((1, tqc, width), kv_self)
                    for width in (d, dv, 1)]
        inputs = [k, v, w3]
        for tensor, width in ((q, d), (gy, dv), (gdn, 1), (m, 1), (gmn, 1)):
            for rows, mp in ((tq, q_self), (nq, q_next)):
                in_specs.append(pl.BlockSpec((1, 1, rows, width), mp))
                inputs.append(tensor)

        dk, dvv, dw = launch(
            functools.partial(_dkvw_sub_wide_kernel, nr=nr, ratio=ratio,
                              tq=tq, lk=Lk),
            family="sub_bwd", grid=(B, nt, G),
            in_specs=in_specs,
            out_specs=tuple(pl.BlockSpec((1, tqc, width), kv_self)
                            for width in (d, dv, 1)),
            out_shape=_kv_grad_shapes(B, Lk, d, dv),
            operands=inputs, interpret=interpret,
            in_names=("k", "v", "w", "q_self", "q_next",
                      "gy_self", "gy_next", "gdn_self", "gdn_next",
                      "m_self", "m_next", "gmn_self", "gmn_next"),
            out_names=("dk", "dv", "dw"),
            meta=dict(mode=SUB_MODE, nr=nr, ratio=ratio, tq=tq, lk=Lk,
                      layout="wide", phase="dkvw"))
    else:
        s_blk = nq // tq
        nkb = Lk // nr
        kv_blk = lambda b, j, s, g_: (b, j, 0)
        q_map = lambda b, j, s, g_: (
            b, g_, jnp.minimum((j + 1) * s_blk + s, nt - 1), 0)

        in_specs = ([pl.BlockSpec((1, nr, width), kv_blk)
                     for width in (d, dv, 1)]
                    + [pl.BlockSpec((1, 1, tq, width), q_map)
                       for width in (d, dv, 1, 1, 1)])
        inputs = [k, v, w3, q, gy, gdn, m, gmn]

        dk, dvv, dw = launch(
            functools.partial(_dkvw_sub_deep_kernel, nr=nr, ratio=ratio,
                              tq=tq, lk=Lk),
            family="sub_bwd", grid=(B, nkb, s_blk, G),
            in_specs=in_specs,
            out_specs=tuple(pl.BlockSpec((1, nr, width), kv_blk)
                            for width in (d, dv, 1)),
            out_shape=_kv_grad_shapes(B, Lk, d, dv),
            operands=inputs, interpret=interpret,
            in_names=("k", "v", "w", "q", "gy", "gdn", "m", "gmn"),
            out_names=("dk", "dv", "dw"),
            meta=dict(mode=SUB_MODE, nr=nr, ratio=ratio, tq=tq, lk=Lk,
                      layout="deep", phase="dkvw"))

    return (dq.astype(q.dtype), dk.astype(k.dtype),
            dvv.astype(v.dtype), dw[..., 0].astype(w.dtype))


def _row_stats(y, dn, m, gy, gdn, gm, w):
    """f32 cotangents plus the max-gradient ``gmh = gm - delta``, with
    every per-row statistic (and the key weights) as a trailing-1
    column -- the layout whose (rows, 1) blocks Mosaic accepts for any
    G and any nr-row halo.  ``delta_i = sum_j a_ij da_ij`` needs only
    the saved outputs."""
    f32 = jnp.float32
    gy = gy.astype(f32)
    gdn = gdn.astype(f32)
    gm = gm.astype(f32)
    gmh = gm - (jnp.sum(gy * y, axis=-1) + gdn * dn)
    return gy, gdn[..., None], gmh[..., None], m[..., None], w[..., None]


def _kv_grad_shapes(B, Lk, d, dv):
    f32 = jnp.float32
    return (jax.ShapeDtypeStruct((B, Lk, d), f32),
            jax.ShapeDtypeStruct((B, Lk, dv), f32),
            jax.ShapeDtypeStruct((B, Lk, 1), f32))


def band_attention_bwd(
    q: jnp.ndarray,    # (B, G, L, d) -- pre-scaled queries (fwd input)
    k: jnp.ndarray,    # (B, L, d)
    v: jnp.ndarray,    # (B, L, dv)
    w: jnp.ndarray,    # (B, L)
    y: jnp.ndarray,    # (B, G, L, dv) f32 -- saved fwd outputs
    dn: jnp.ndarray,   # (B, G, L) f32
    m: jnp.ndarray,    # (B, G, L) f32
    gy: jnp.ndarray,   # cotangents of (y, dn, m)
    gdn: jnp.ndarray,
    gm: jnp.ndarray,
    *,
    nr: int,
    mode: str,
    tq: int = 128,
    ratio: int = 1,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused backward.  Returns (dq, dk, dv, dw) in the input dtypes."""
    if mode == SUB_MODE:
        return band_attention_sub_bwd(q, k, v, w, y, dn, m, gy, gdn, gm,
                                      nr=nr, ratio=ratio, tq=tq,
                                      interpret=interpret)
    assert mode in MODES, mode
    B, G, L, d = q.shape
    dv = v.shape[-1]
    assert L % tq == 0 and tq % nr == 0 and tq >= nr, (L, tq, nr)
    nt = L // tq
    causal = mode.endswith("causal")
    f32 = jnp.float32

    gy, gdn, gmh, m, w3 = _row_stats(y, dn, m, gy, gdn, gm, w)

    # self operands: full tiles; halo operands: exact nr-row blocks at
    # the neighbouring tile's edge (index maps count nr-row blocks)
    nb = L // nr
    tb = tq // nr
    self_map = lambda b, g_, i: (b, i, 0)
    prev_map = lambda b, g_, i: (b, jnp.maximum(i * tb - 1, 0), 0)
    next_map = lambda b, g_, i: (b, jnp.minimum((i + 1) * tb, nb - 1), 0)
    qtile_map = lambda b, g_, i: (b, g_, i, 0)

    # ---- pass 1: dQ (query-tile grid) + per-row max-grad scale ------------
    in_specs = [pl.BlockSpec((1, 1, tq, d), qtile_map)]
    inputs = [q]
    kmaps = [(tq, self_map), (nr, prev_map)] + (
        [] if causal else [(nr, next_map)])
    for arr, width in ((k, d), (v, dv), (w3, 1)):
        for rows, mp in kmaps:
            in_specs.append(pl.BlockSpec((1, rows, width), mp))
            inputs.append(arr)
    in_specs += [pl.BlockSpec((1, 1, tq, width), qtile_map)
                 for width in (1, dv, 1, 1)]
    inputs += [m, gy, gdn, gmh]

    halo = ("self", "prev") if causal else ("self", "prev", "next")
    dq, gmn = launch(
        functools.partial(_dq_kernel, nr=nr, mode=mode, tq=tq, lk=L),
        family="band_bwd", grid=(B, G, nt),
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((1, 1, tq, d), qtile_map),
                   pl.BlockSpec((1, 1, tq, 1), qtile_map)),
        out_shape=(jax.ShapeDtypeStruct((B, G, L, d), f32),
                   jax.ShapeDtypeStruct((B, G, L, 1), f32)),
        operands=inputs, interpret=interpret,
        in_names=(("q",) + tuple(f"{a}_{h}" for a in "kvw" for h in halo)
                  + ("m", "gy", "gdn", "gmh")),
        out_names=("dq", "gmn"),
        meta=dict(mode=mode, nr=nr, tq=tq, lk=L, phase="dq"))

    # ---- pass 2: dK/dV/dW (key-tile grid, g innermost accumulates) --------
    # halo query operands (the nr edge rows of the neighbouring tile)
    # are fetched as exact nr-row blocks, mirroring pass 1.
    kv_self = lambda b, i, g_: (b, i, 0)
    q_self = lambda b, i, g_: (b, g_, i, 0)
    q_next = lambda b, i, g_: (b, g_, jnp.minimum((i + 1) * tb, nb - 1), 0)
    q_prev = lambda b, i, g_: (b, g_, jnp.maximum(i * tb - 1, 0), 0)

    qmaps = [(tq, q_self), (nr, q_next)] + ([] if causal else [(nr, q_prev)])

    in_specs = [pl.BlockSpec((1, tq, width), kv_self) for width in (d, dv, 1)]
    inputs = [k, v, w3]
    for tensor, width in ((q, d), (gy, dv), (gdn, 1), (m, 1), (gmn, 1)):
        for rows, mp in qmaps:
            in_specs.append(pl.BlockSpec((1, 1, rows, width), mp))
            inputs.append(tensor)

    qhalo = ("self", "next") if causal else ("self", "next", "prev")
    dk, dvv, dw = launch(
        functools.partial(_dkvw_kernel, nr=nr, mode=mode, tq=tq, lk=L),
        family="band_bwd", grid=(B, nt, G),
        in_specs=in_specs,
        out_specs=tuple(pl.BlockSpec((1, tq, width), kv_self)
                        for width in (d, dv, 1)),
        out_shape=_kv_grad_shapes(B, L, d, dv),
        operands=inputs, interpret=interpret,
        in_names=(("k", "v", "w")
                  + tuple(f"q_{h}" for h in qhalo)
                  + tuple(f"gy_{h}" for h in qhalo)
                  + tuple(f"{a}_{h}" for a in ("gdn", "m", "gmn")
                          for h in qhalo)),
        out_names=("dk", "dv", "dw"),
        meta=dict(mode=mode, nr=nr, tq=tq, lk=L, phase="dkvw"))

    return (dq.astype(q.dtype), dk.astype(k.dtype),
            dvv.astype(v.dtype), dw[..., 0].astype(w.dtype))
