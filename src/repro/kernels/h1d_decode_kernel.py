"""Pallas TPU kernels for the serving hot path: fused single-token
hierarchical-KV decode (DESIGN.md section 4).

Two kernels, both on a ``(R,)`` grid where ``R = slots * Hkv`` (batch
rows with kv-heads folded in, the ``core.h1d_decode`` cache layout):

* :func:`decode_attend_fused` -- ONE launch computes the whole
  O(nr log L) decode attention for every row: the per-row position ``t``
  is scalar-prefetched, so the BlockSpec index maps gather exactly the
  own/prev level-0 blocks plus the single ``(I_l - 1)`` coarse block per
  level straight from HBM (one ``nr``-row read per needed block), and
  the span/quadrant masks, per-level weights ``2^l`` and the weighted
  LSE combine all happen in VMEM.  The jnp path this replaces launches
  ~``2 (M+1)`` one-hot einsums that each stream the ENTIRE cache level
  through the MXU plus a concat/softmax epilogue (EXPERIMENTS.md P25).

* :func:`update_cache_fused` -- ONE launch appends a token: for each
  level ``l`` it reads the 2-row sibling pair containing the token's
  ancestor ``t >> l``, substitutes the freshly computed row (carried in
  VMEM from level ``l-1``), and writes the pair back --
  ``input_output_aliases`` makes it an in-place scatter, so the whole
  O(log L) ancestor chain costs 2 rows read + 2 rows written per level
  instead of M+1 vmap'd ``dynamic_update_slice`` launches.

Both kernels are bit-faithful to the ``impl='jnp'`` oracle in
``core.h1d_decode`` (same masks, same single-max softmax, same pairwise
mean/sum order); ``tests/test_decode_kernel.py`` sweeps the parity.

Two PAGED variants (:func:`decode_attend_paged` /
:func:`update_cache_paged`) serve the block-pool cache of
``serve/paged_cache.py``: same bodies, same single-launch structure, but
the BlockSpec index maps read physical page rows from one
scalar-prefetched indirection table per level (the host walks the page
tables; the kernels never see logical block indices).  Two SP variants
(``*_partial``) serve sequence-sharded caches (DESIGN.md section 7).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.analysis.contracts import launch

_MIN_M = -1e30

# an unbounded "position" domain for the paged kernels: their index
# maps consume t only through masks / in-page arithmetic, so any
# non-negative int32 is legal (the page tables carry the geometry).
_T_MAX = (1 << 30) - 1


def _band_names(nbands: int):
    return ["own", "prev"] + [f"lvl{l}" for l in range(1, nbands - 1)]


def _band_levels(nbands: int):
    """Hierarchy level of each attend band (bands 0/1 are the own/prev
    fine blocks, band ``b >= 2`` is coarse level ``b - 1``).  Exposed in
    the attend contracts' meta so ``analysis/dist.py`` can align a
    contract's per-band index maps with the cache level they read."""
    return tuple([0, 0] + list(range(1, nbands - 1)))


def _hc():
    """Lazy ``core.hierarchy`` import (module-level would cycle through
    core/__init__ -> h1d_attention -> kernels/__init__), keeping one
    source of truth for num_levels / NEG_INF."""
    from repro.core import hierarchy as hc
    return hc


def _qz():
    """Lazy ``core.quantization`` import (same cycle as :func:`_hc`);
    the kernels inline its rounding rule but source QMAX/EPS here so the
    int8 wire format has one definition."""
    from repro.core import quantization as qz
    return qz


def _pairs(a):
    """(..., L, D) level array -> (..., L/2, 2, D) sibling-pair view.
    Mosaic refuses a 2-row block over the (L, D) dims (the second-minor
    block dim must be a multiple of 8 or the whole dim); over the pair
    view the block's trailing (2, D) is the whole of those dims.  At
    head_dim 128 XLA compiles the reshape of an f32 or bf16 level to a
    bitcast (``tests/test_tpu_compile.py`` checks it); an int8 level's
    does not fold, so the int8 update moves whole pages instead."""
    return a.reshape(*a.shape[:-2], a.shape[-2] // 2, 2, a.shape[-1])


def _rows3(x):
    """(R, D) new rows -> (R, 1, D), so a (1, 1, D) row block's
    trailing dims are the array's own."""
    return x[:, None, :]


# ---------------------------------------------------------------------------
# fused decode attention
# ---------------------------------------------------------------------------

def _attend_kernel(t_ref, q_ref, *refs, nr: int, nbands: int, scale: float,
                   neg_inf: float, quant=()):
    """One grid step = one cache row: q (1, G, D) against ``nbands``
    nr-key bands (own, prev, coarse levels 1..M-1), weighted-LSE
    combined entirely in VMEM.

    ``quant`` (per-band bools, empty = all fp) marks int8 bands: their
    K/V blocks arrive as int8 pages and are dequantized in VMEM with the
    row's gathered ``(nq, nr, 1)`` k-scale and v-scale blocks, appended
    after the V refs (quantized bands in band order)."""
    nq = sum(quant)
    k_refs = refs[:nbands]
    v_refs = refs[nbands:2 * nbands]
    if nq:
        ksc_ref, vsc_ref = refs[2 * nbands:2 * nbands + 2]
    o_ref = refs[2 * nbands + (2 if nq else 0)]
    r = pl.program_id(0)
    t = t_ref[r]
    f32 = jnp.float32

    q = q_ref[0].astype(f32) * scale                     # (G, D)
    ki = jax.lax.broadcasted_iota(jnp.int32, (1, nr), 1)  # key idx in band
    b0 = t // nr

    logits, values, weights = [], [], []
    si = 0
    for band in range(nbands):
        kb = k_refs[band][0].astype(f32)                 # (nr, D)
        vb = v_refs[band][0].astype(f32)                 # (nr, Dv)
        if quant and quant[band]:
            kb = kb * ksc_ref[0, si]                    # (nr, 1) column
            vb = vb * vsc_ref[0, si]
            si += 1
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32)   # (G, nr)
        if band == 0:          # own level-0 block, causal within the block
            pos = b0 * nr + ki
            mask = pos <= t
            wgt = jnp.full((1, nr), 1.0, f32)
        elif band == 1:        # previous level-0 block
            mask = jnp.broadcast_to(b0 >= 1, (1, nr))
            wgt = jnp.full((1, nr), 1.0, f32)
        else:                  # coarse level l: block I_l - 1, quadrant mask
            l = band - 1
            span = nr << l
            Il = t // span
            first_half_q = (t % span) < (span // 2)
            key_last_half = ki >= (nr // 2)
            mask = (Il >= 1) & ~(first_half_q & key_last_half)
            wgt = jnp.full((1, nr), float(1 << l), f32)
        logits.append(jnp.where(mask, s, neg_inf))
        values.append(vb)
        weights.append(jnp.where(mask, wgt, 0.0))

    s_all = jnp.concatenate(logits, axis=-1)             # (G, K)
    v_all = jnp.concatenate(values, axis=-2)             # (K, Dv)
    w_all = jnp.concatenate(weights, axis=-1)            # (1, K)
    m = jnp.maximum(s_all.max(axis=-1, keepdims=True), _MIN_M)
    a = jnp.exp(s_all - m)
    num = jax.lax.dot_general(a, v_all, (((1,), (0,)), ((), ())),
                              preferred_element_type=f32)     # (G, Dv)
    den = jnp.sum(a * w_all, axis=-1, keepdims=True)     # (G, 1)
    o_ref[0] = num / jnp.maximum(den, 1e-9)


def decode_attend_fused(cache, q: jnp.ndarray, t: jnp.ndarray, *, nr: int,
                        softmax_scale=None,
                        interpret: bool = False) -> jnp.ndarray:
    """Fused batched single-token attention.  ``cache`` is an
    ``H1DCache``; ``q``: (R, G, D); ``t``: (R,) int32 per-row positions.
    Returns (R, G, Dv) in ``q.dtype`` -- same contract and numerics as
    ``core.h1d_decode.decode_attend(impl='jnp')``."""
    hc = _hc()
    R, G, D = q.shape
    Lmax = cache.k.shape[-2]
    Dv = cache.v.shape[-1]
    M = hc.num_levels(Lmax, nr)
    levels = len(cache.ck)
    assert levels == max(M - 1, 0), (levels, M)
    nbands = 2 + levels
    scale = softmax_scale if softmax_scale is not None else 1 / math.sqrt(D)

    nb0 = Lmax // nr
    own_map = lambda r, tref: (r, jnp.minimum(tref[r] // nr, nb0 - 1), 0)
    prev_map = lambda r, tref: (r, jnp.maximum(tref[r] // nr - 1, 0), 0)

    def lvl_map(l):
        nbl = (Lmax >> l) // nr
        return lambda r, tref: (
            r, jnp.clip(tref[r] // (nr << l) - 1, 0, nbl - 1), 0)

    maps = [own_map, prev_map] + [lvl_map(l) for l in range(1, M)]
    k_arrs = [cache.k, cache.k] + list(cache.ck)
    v_arrs = [cache.v, cache.v] + list(cache.cv)

    in_specs = [pl.BlockSpec((1, G, D), lambda r, tref: (r, 0, 0))]
    in_specs += [pl.BlockSpec((1, nr, D), mp) for mp in maps]
    in_specs += [pl.BlockSpec((1, nr, Dv), mp) for mp in maps]

    kernel = functools.partial(_attend_kernel, nr=nr, nbands=nbands,
                               scale=float(scale), neg_inf=hc.NEG_INF)
    bn = _band_names(nbands)
    out = launch(
        kernel, family="decode_attend", grid=(R,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G, Dv), lambda r, tref: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, G, Dv), jnp.float32),
        operands=[q, *k_arrs, *v_arrs],
        scalars=(t.astype(jnp.int32),),
        scalar_bounds=((0, Lmax - 1),),
        scalar_names=("t",),
        in_names=(["q"] + [f"k_{b}" for b in bn] + [f"v_{b}" for b in bn]),
        out_names=("o",), interpret=interpret,
        meta=dict(nr=nr, Lmax=Lmax, levels=levels,
                  band_levels=_band_levels(nbands)))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# sequence-parallel partial attend (sharded index maps)
# ---------------------------------------------------------------------------

def _attend_partial_kernel(t_ref, bidx_ref, own_ref, q_ref, *refs, nr: int,
                           nbands: int, scale: float, neg_inf: float):
    """Per-shard variant of :func:`_attend_kernel` for the SP path: the
    BlockSpec index maps read shard-LOCAL block indices from the
    scalar-prefetched ``bidx`` array (``repro.parallel.sp_attention``
    computes them from the global position and the shard index), each
    band is additionally masked by its ownership bit, and the outputs
    are the *partial* ``(num, den, m)`` triple instead of the
    normalized result -- the cross-shard merge is one pmax + psum."""
    k_refs = refs[:nbands]
    v_refs = refs[nbands:2 * nbands]
    num_ref, den_ref, m_ref = refs[2 * nbands:2 * nbands + 3]
    r = pl.program_id(0)
    t = t_ref[r]
    f32 = jnp.float32

    q = q_ref[0].astype(f32) * scale                     # (G, D)
    ki = jax.lax.broadcasted_iota(jnp.int32, (1, nr), 1)
    b0 = t // nr

    logits, values, weights = [], [], []
    for band in range(nbands):
        kb = k_refs[band][0].astype(f32)                 # (nr, D)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32)   # (G, nr)
        if band == 0:
            pos = b0 * nr + ki
            mask = pos <= t
            wgt = jnp.full((1, nr), 1.0, f32)
        elif band == 1:
            mask = jnp.broadcast_to(b0 >= 1, (1, nr))
            wgt = jnp.full((1, nr), 1.0, f32)
        else:
            l = band - 1
            span = nr << l
            Il = t // span
            first_half_q = (t % span) < (span // 2)
            key_last_half = ki >= (nr // 2)
            mask = (Il >= 1) & ~(first_half_q & key_last_half)
            wgt = jnp.full((1, nr), float(1 << l), f32)
        mask = mask & (own_ref[r, band] > 0)
        logits.append(jnp.where(mask, s, neg_inf))
        values.append(v_refs[band][0].astype(f32))
        weights.append(jnp.where(mask, wgt, 0.0))

    s_all = jnp.concatenate(logits, axis=-1)             # (G, K)
    v_all = jnp.concatenate(values, axis=-2)             # (K, Dv)
    w_all = jnp.concatenate(weights, axis=-1)            # (1, K)
    m = jnp.maximum(s_all.max(axis=-1, keepdims=True), _MIN_M)   # (G, 1)
    a = jnp.exp(s_all - m)
    num_ref[0] = jax.lax.dot_general(a, v_all, (((1,), (0,)), ((), ())),
                                     preferred_element_type=f32)
    den_ref[0] = jnp.sum(a * w_all, axis=-1, keepdims=True)
    m_ref[0] = m


def decode_attend_partial(cache, q: jnp.ndarray, t: jnp.ndarray,
                          bidx: jnp.ndarray, owned: jnp.ndarray, *,
                          nr: int, softmax_scale=None,
                          t_hi: int = None,
                          interpret: bool = False):
    """Partial fused decode attention on shard-LOCAL cache arrays.

    ``bidx`` (R, nbands) int32 holds the local block index of each band
    in this shard's cache slab (levels may have fewer local blocks than
    the global cache); ``owned`` (R, nbands) gates bands this shard
    does not own.  ``t`` stays GLOBAL (the in-kernel masks compare
    global positions); ``t_hi`` declares its domain -- the SP caller
    passes ``Lmax - 1``, the default covers a single-shard slab.
    Returns float32 ``(num (R,G,Dv), den (R,G),
    m (R,G))`` -- merge across shards with
    ``num * exp(m - pmax(m))`` psums (``sp_attention.sp_decode_attend``).
    """
    hc = _hc()
    R, G, D = q.shape
    Dv = cache.v.shape[-1]
    levels = len(cache.ck)
    nbands = 2 + levels
    scale = softmax_scale if softmax_scale is not None else 1 / math.sqrt(D)

    def band_map(band):
        return lambda r, tref, bref, oref: (r, bref[r, band], 0)

    maps = [band_map(b) for b in range(nbands)]
    k_arrs = [cache.k, cache.k] + list(cache.ck)
    v_arrs = [cache.v, cache.v] + list(cache.cv)

    in_specs = [pl.BlockSpec((1, G, D), lambda r, tref, bref, oref: (r, 0, 0))]
    in_specs += [pl.BlockSpec((1, nr, D), mp) for mp in maps]
    in_specs += [pl.BlockSpec((1, nr, Dv), mp) for mp in maps]

    kernel = functools.partial(_attend_partial_kernel, nr=nr, nbands=nbands,
                               scale=float(scale), neg_inf=hc.NEG_INF)
    f32 = jnp.float32
    # per-band bidx domain: local nr-row block count of that band's slab
    bidx_hi = np.array([a.shape[-2] // nr - 1 for a in k_arrs],
                       dtype=np.int32)
    Lloc = cache.k.shape[-2]
    bn = _band_names(nbands)
    row_map = lambda r, tref, bref, oref: (r, 0, 0)
    num, den, m = launch(
        kernel, family="decode_attend_partial", grid=(R,),
        in_specs=in_specs,
        # den/m as (G, 1) columns: a (1, G) block over (R, G) is refused
        out_specs=(pl.BlockSpec((1, G, Dv), row_map),
                   pl.BlockSpec((1, G, 1), row_map),
                   pl.BlockSpec((1, G, 1), row_map)),
        out_shape=(jax.ShapeDtypeStruct((R, G, Dv), f32),
                   jax.ShapeDtypeStruct((R, G, 1), f32),
                   jax.ShapeDtypeStruct((R, G, 1), f32)),
        operands=[q, *k_arrs, *v_arrs],
        scalars=(t.astype(jnp.int32), bidx.astype(jnp.int32),
                 owned.astype(jnp.int32)),
        scalar_bounds=((0, Lloc - 1 if t_hi is None else t_hi),
                       (0, bidx_hi), (0, 1)),
        scalar_names=("t", "bidx", "owned"),
        in_names=(["q"] + [f"k_{b}" for b in bn] + [f"v_{b}" for b in bn]),
        out_names=("num", "den", "m"), interpret=interpret,
        meta=dict(nr=nr, Lloc=Lloc, levels=levels,
                  band_levels=_band_levels(nbands)))
    return num, den[..., 0], m[..., 0]


# ---------------------------------------------------------------------------
# fused ancestor update
# ---------------------------------------------------------------------------

def _update_kernel(t_ref, knew_ref, vnew_ref, *refs, nlev: int):
    """One grid step = one cache row: substitute the new fine row into
    its level-0 sibling pair, then walk the ancestor chain upward -- the
    level-l row is the pairwise mean/sum of the level-(l-1) pair, which
    is already updated in VMEM."""
    in_refs = refs[:2 * nlev]
    out_refs = refs[2 * nlev:]
    r = pl.program_id(0)
    t = t_ref[r]
    f32 = jnp.float32
    sel_row = jax.lax.broadcasted_iota(jnp.int32, (2, 1), 0)

    new_k = knew_ref[0].astype(f32)                      # (1, D)
    new_v = vnew_ref[0].astype(f32)                      # (1, Dv)
    for l in range(nlev):
        sel = sel_row == ((t >> l) & 1)
        pk = jnp.where(sel, new_k, in_refs[2 * l][0, 0].astype(f32))
        pv = jnp.where(sel, new_v, in_refs[2 * l + 1][0, 0].astype(f32))
        out_refs[2 * l][0, 0] = pk.astype(out_refs[2 * l].dtype)
        out_refs[2 * l + 1][0, 0] = pv.astype(out_refs[2 * l + 1].dtype)
        if l + 1 < nlev:
            new_k = pk.mean(axis=0, keepdims=True)       # Eq. 25/26
            new_v = pv.sum(axis=0, keepdims=True)        # Eq. 27


def update_cache_fused(cache, k_new: jnp.ndarray, v_new: jnp.ndarray,
                       t: jnp.ndarray, *, interpret: bool = False):
    """Fused batched cache append.  ``k_new``: (R, D), ``v_new``:
    (R, Dv), ``t``: (R,).  Returns an updated ``H1DCache`` -- same
    contract as ``core.h1d_decode.update_cache(impl='jnp')``.

    Every level array is aliased input->output, so rows outside the
    written sibling pairs are untouched in HBM (in-place scatter)."""
    R, D = k_new.shape
    Dv = v_new.shape[-1]
    Lmax = cache.k.shape[-2]
    nlev = 1 + len(cache.ck)        # fine + coarse levels

    arrs, in_specs, out_specs, out_shape = [], [], [], []
    lvls = [(cache.k, cache.v)] + list(zip(cache.ck, cache.cv))
    for l, (ka, va) in enumerate(lvls):
        npairs = ka.shape[-2] // 2

        def pair_map(r, tref, l=l, npairs=npairs):
            return (r, jnp.minimum(tref[r] >> (l + 1), npairs - 1), 0, 0)

        for a, d_ in ((ka, D), (va, Dv)):
            arrs.append(_pairs(a))
            in_specs.append(pl.BlockSpec((1, 1, 2, d_), pair_map))
            out_specs.append(pl.BlockSpec((1, 1, 2, d_), pair_map))
            out_shape.append(jax.ShapeDtypeStruct(arrs[-1].shape, a.dtype))

    # alias each cache operand to its output (operand-indexed; launch()
    # translates to pallas call-arg indices past the scalar args)
    aliases = {2 + i: i for i in range(2 * nlev)}
    kernel = functools.partial(_update_kernel, nlev=nlev)
    lvl_names = [f"{a}_l{l}" for l in range(nlev) for a in ("k", "v")]
    row_map = lambda r, tref: (r, 0, 0)
    outs = launch(
        kernel, family="decode_update", grid=(R,),
        in_specs=[pl.BlockSpec((1, 1, D), row_map),
                  pl.BlockSpec((1, 1, Dv), row_map)] + in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        operands=[_rows3(k_new), _rows3(v_new), *arrs],
        scalars=(t.astype(jnp.int32),),
        scalar_bounds=((0, Lmax - 1),),
        scalar_names=("t",),
        in_names=["k_new", "v_new"] + lvl_names,
        out_names=lvl_names, aliases=aliases, interpret=interpret,
        meta=dict(Lmax=Lmax, nlev=nlev))
    return _unpair(cache, outs, nlev)


def _unpair(cache, outs, nlev):
    """Rebuild a cache of ``cache``'s type from the pair-view outputs
    (k_l0, v_l0, k_l1, v_l1, ...) of an update launch."""
    outs = [o.reshape(*o.shape[:-3], -1, o.shape[-1]) for o in outs]
    ck = tuple(outs[2 + 2 * i] for i in range(nlev - 1))
    cv = tuple(outs[3 + 2 * i] for i in range(nlev - 1))
    return type(cache)(k=outs[0], v=outs[1], ck=ck, cv=cv)


# ---------------------------------------------------------------------------
# paged decode attention (scalar-prefetched page-table indirection)
# ---------------------------------------------------------------------------

def _attend_paged_kernel(t_ref, bidx_ref, *rest, **kw):
    """Paged variant of :func:`_attend_kernel`: the body is IDENTICAL --
    masks and the weighted-LSE combine depend only on the global
    position ``t`` -- the page indirection lives entirely in the
    BlockSpec index maps, which read physical page rows from the
    scalar-prefetched ``bidx`` table instead of computing block indices
    from ``t``."""
    return _attend_kernel(t_ref, *rest, **kw)


def decode_attend_paged(pool, q: jnp.ndarray, t: jnp.ndarray,
                        bidx: jnp.ndarray, *, nr: int, softmax_scale=None,
                        interpret: bool = False) -> jnp.ndarray:
    """Fused single-token attention over a PAGED hierarchical KV pool.

    ``pool`` is a ``core.h1d_decode.PagedH1DCache``: per level a pool of
    ``nr``-row pages, fine ``k``/``v`` (NP0, nr, D/Dv) and coarse
    ``ck[l-1]``/``cv[l-1]`` (NP_l, nr, ...).  ``q``: (R, G, D); ``t``:
    (R,) global positions; ``bidx``: (R, 2 + levels) int32 physical page
    rows -- column 0 the own level-0 page, column 1 the previous level-0
    page, column 1+l the level-l page ``I_l - 1`` (host-side page-table
    walk; invalid bands carry any in-range page, the in-kernel masks
    zero them exactly like the dense kernel).  ONE launch on the (R,)
    grid, one ``nr``-row HBM read per band -- the dense cache's
    ``decode_attend_fused`` contract, with the block-index maps
    generalized to one scalar-prefetched indirection table per level.
    """
    hc = _hc()
    R, G, D = q.shape
    Dv = pool.v.shape[-1]
    levels = len(pool.ck)
    nbands = 2 + levels
    assert bidx.shape == (R, nbands), (bidx.shape, R, nbands)
    scale = softmax_scale if softmax_scale is not None else 1 / math.sqrt(D)

    def band_map(band):
        return lambda r, tref, bref: (bref[r, band], 0, 0)

    maps = [band_map(b) for b in range(nbands)]
    k_arrs = [pool.k, pool.k] + list(pool.ck)
    v_arrs = [pool.v, pool.v] + list(pool.cv)

    in_specs = [pl.BlockSpec((1, G, D), lambda r, tref, bref: (r, 0, 0))]
    in_specs += [pl.BlockSpec((1, nr, D), mp) for mp in maps]
    in_specs += [pl.BlockSpec((1, nr, Dv), mp) for mp in maps]

    kernel = functools.partial(_attend_paged_kernel, nr=nr, nbands=nbands,
                               scale=float(scale), neg_inf=hc.NEG_INF)
    # per-band page domain: that band's pool page count
    bidx_hi = np.array([a.shape[0] - 1 for a in k_arrs], dtype=np.int32)
    bn = _band_names(nbands)
    out = launch(
        kernel, family="decode_attend_paged", grid=(R,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G, Dv), lambda r, tref, bref: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, G, Dv), jnp.float32),
        operands=[q, *k_arrs, *v_arrs],
        scalars=(t.astype(jnp.int32), bidx.astype(jnp.int32)),
        scalar_bounds=((0, _T_MAX), (0, bidx_hi)),
        scalar_names=("t", "bidx"),
        in_names=(["q"] + [f"k_{b}" for b in bn] + [f"v_{b}" for b in bn]),
        out_names=("o",), interpret=interpret,
        meta=dict(nr=nr, levels=levels))
    return out.astype(q.dtype)


def decode_attend_paged_quant(pool, q: jnp.ndarray, t: jnp.ndarray,
                              bidx: jnp.ndarray, *, nr: int,
                              softmax_scale=None,
                              interpret: bool = False) -> jnp.ndarray:
    """Quantized-pool variant of :func:`decode_attend_paged`.

    ``pool`` is a ``core.h1d_decode.QuantPagedH1DCache``: int8 pages for
    any subset of levels, with per-row f32 scales ``(NP_l, nr)``.  The
    scale rows of each row's quantized bands are gathered by XLA through
    the same ``bidx`` table into two small ``(R, nq, nr, 1)`` operands:
    the TPU keeps ``(NP, nr)`` arrays lane-transposed, so a kernel
    operand over the scale pool itself would cost a relayout copy of
    all of it per launch.  The dequantize (one multiply per gathered
    row) happens in VMEM right before the QK^T dot.  Still one launch on
    the (R,) grid; fp32 levels of a mixed-precision pool have no scale
    operands (which levels are quantized is static in the array
    dtypes)."""
    hc = _hc()
    R, G, D = q.shape
    Dv = pool.v.shape[-1]
    levels = len(pool.ck)
    nbands = 2 + levels
    assert bidx.shape == (R, nbands), (bidx.shape, R, nbands)
    scale = softmax_scale if softmax_scale is not None else 1 / math.sqrt(D)

    lvl_quant = tuple(bool(a.dtype == jnp.int8) for a in (pool.k, *pool.ck))
    band_lvl = [0, 0] + list(range(1, 1 + levels))
    quant = tuple(lvl_quant[band_lvl[b]] for b in range(nbands))

    def band_map(band):
        return lambda r, tref, bref: (bref[r, band], 0, 0)

    maps = [band_map(b) for b in range(nbands)]
    k_arrs = [pool.k, pool.k] + list(pool.ck)
    v_arrs = [pool.v, pool.v] + list(pool.cv)
    ksc_all = [pool.ksc, pool.ksc] + list(pool.cksc)
    vsc_all = [pool.vsc, pool.vsc] + list(pool.cvsc)
    qbands = [b for b in range(nbands) if quant[b]]
    # k-scales, then v-scales: (R, nq, nr, 1) per-row gathers
    sc_arrs = [jnp.stack([scs[b][bidx[:, b]] for b in qbands],
                         axis=1)[..., None]
               for scs in (ksc_all, vsc_all)] if qbands else []
    row4 = lambda r, tref, bref: (r, 0, 0, 0)

    in_specs = [pl.BlockSpec((1, G, D), lambda r, tref, bref: (r, 0, 0))]
    in_specs += [pl.BlockSpec((1, nr, D), mp) for mp in maps]
    in_specs += [pl.BlockSpec((1, nr, Dv), mp) for mp in maps]
    in_specs += [pl.BlockSpec((1, len(qbands), nr, 1), row4)
                 for _ in sc_arrs]

    kernel = functools.partial(_attend_paged_kernel, nr=nr, nbands=nbands,
                               scale=float(scale), neg_inf=hc.NEG_INF,
                               quant=quant)
    bidx_hi = np.array([a.shape[0] - 1 for a in k_arrs], dtype=np.int32)
    bn = _band_names(nbands)
    sc_names = ["ksc", "vsc"] if qbands else []
    out = launch(
        kernel, family="decode_attend_paged_quant", grid=(R,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G, Dv), lambda r, tref, bref: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, G, Dv), jnp.float32),
        operands=[q, *k_arrs, *v_arrs, *sc_arrs],
        scalars=(t.astype(jnp.int32), bidx.astype(jnp.int32)),
        scalar_bounds=((0, _T_MAX), (0, bidx_hi)),
        scalar_names=("t", "bidx"),
        in_names=(["q"] + [f"k_{b}" for b in bn] + [f"v_{b}" for b in bn]
                  + sc_names),
        out_names=("o",), interpret=interpret,
        meta=dict(nr=nr, levels=levels, quant=quant))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# paged ancestor update
# ---------------------------------------------------------------------------

def _update_paged_kernel(t_ref, utab_ref, *rest, **kw):
    """Paged variant of :func:`_update_kernel`: identical body (the
    within-pair row select and the carried mean/sum use only ``t``);
    the sibling-pair location comes from the prefetched ``utab`` page
    table via the BlockSpec index maps."""
    return _update_kernel(t_ref, *rest, **kw)


def update_cache_paged(pool, k_new: jnp.ndarray, v_new: jnp.ndarray,
                       t: jnp.ndarray, utab: jnp.ndarray, *,
                       interpret: bool = False):
    """Fused batched append into a PAGED hierarchical KV pool.

    ``k_new``: (R, D), ``v_new``: (R, Dv), ``t``: (R,) global positions,
    ``utab``: (R, 1 + levels) int32 physical page rows -- column ``l``
    is the page holding the token's level-l ancestor row ``t >> l``
    (the engine COWs / allocates these pages before the tick, and points
    inactive rows at a per-level trash page so their writes are inert).
    Within the page the sibling pair sits at local pair index
    ``(t >> (l+1)) mod (nr/2)``.  Every pool operand is aliased
    input->output (in-place scatter), same as ``update_cache_fused``."""
    R, D = k_new.shape
    Dv = v_new.shape[-1]
    nr = pool.k.shape[-2]
    nlev = 1 + len(pool.ck)
    assert utab.shape == (R, nlev), (utab.shape, R, nlev)

    arrs, in_specs, out_specs, out_shape = [], [], [], []
    lvls = [(pool.k, pool.v)] + list(zip(pool.ck, pool.cv))
    for l, (ka, va) in enumerate(lvls):

        def pair_map(r, tref, uref, l=l):
            return (uref[r, l], (tref[r] >> (l + 1)) & (nr // 2 - 1), 0, 0)

        for a, d_ in ((ka, D), (va, Dv)):
            arrs.append(_pairs(a))
            in_specs.append(pl.BlockSpec((1, 1, 2, d_), pair_map))
            out_specs.append(pl.BlockSpec((1, 1, 2, d_), pair_map))
            out_shape.append(jax.ShapeDtypeStruct(arrs[-1].shape, a.dtype))

    row_map = lambda r, tref, uref: (r, 0, 0)
    # aliases are operand-indexed ((k_new, v_new, *arrs): pool operands
    # start at 2); launch() shifts past the scalar args.
    aliases = {2 + i: i for i in range(2 * nlev)}
    kernel = functools.partial(_update_paged_kernel, nlev=nlev)
    # per-level utab domain: that level's pool page count (k page count
    # == v page count per level, lvls order == utab column order)
    utab_hi = np.array([ka.shape[0] - 1 for ka, _ in lvls], dtype=np.int32)
    lvl_names = [f"{a}_l{l}" for l in range(nlev) for a in ("k", "v")]
    outs = launch(
        kernel, family="decode_update_paged", grid=(R,),
        in_specs=[pl.BlockSpec((1, 1, D), row_map),
                  pl.BlockSpec((1, 1, Dv), row_map)] + in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        operands=[_rows3(k_new), _rows3(v_new), *arrs],
        scalars=(t.astype(jnp.int32), utab.astype(jnp.int32)),
        scalar_bounds=((0, _T_MAX), (0, utab_hi)),
        scalar_names=("t", "utab"),
        in_names=["k_new", "v_new"] + lvl_names,
        out_names=lvl_names, aliases=aliases, interpret=interpret,
        meta=dict(nr=nr, nlev=nlev))
    return _unpair(pool, outs, nlev)


def _update_paged_quant_kernel(t_ref, utab_ref, knew_ref, vnew_ref, *refs,
                               nlev: int, nr: int, quant, qmax: float,
                               recip: float, eps: float):
    """Quantized variant of :func:`_update_kernel` over whole pages: at
    each level the 2-row sibling pair is picked out of its ``(nr, D)``
    page, dequantized with its two per-row scales (int8 levels), the new
    row substituted, and the pair REquantized (fresh absmax scales --
    the same rounding as ``core.quantization.quantize_int8``, inlined so
    it runs on the VMEM-resident pair); the page goes back with its
    other rows unchanged.  The ancestor carry is the PRE-quantization
    f32 pair mean/sum, so quantization error does not compound up the
    hierarchy within a tick.  ``osc``/``nsc`` hold each int8 level's
    old/new pair scales as a ``(4, 1)`` column: k0, k1, v0, v1."""
    nq = sum(quant)
    in_data = refs[:2 * nlev]
    out_data = refs[2 * nlev + (1 if nq else 0):4 * nlev + (1 if nq else 0)]
    if nq:
        osc_ref, nsc_ref = refs[2 * nlev], refs[-1]
    r = pl.program_id(0)
    t = t_ref[r]
    f32 = jnp.float32
    sel_row = jax.lax.broadcasted_iota(jnp.int32, (2, 1), 0)
    page_row = jax.lax.broadcasted_iota(jnp.int32, (nr, 1), 0)

    new_k = knew_ref[0].astype(f32)                      # (1, D)
    new_v = vnew_ref[0].astype(f32)                      # (1, Dv)
    si = 0
    for l in range(nlev):
        sel = sel_row == ((t >> l) & 1)
        p0 = 2 * ((t >> (l + 1)) & (nr // 2 - 1))       # pair's first row
        at0, at1 = page_row == p0, page_row == p0 + 1

        def pair(page):             # (nr, D) -> the pair's (2, D)
            return jnp.concatenate(
                [jnp.sum(jnp.where(at, page, 0.0), axis=0, keepdims=True)
                 for at in (at0, at1)], axis=0)

        def put(page, p2):          # the page with the pair's rows = p2
            return jnp.where(at0, p2[0:1], jnp.where(at1, p2[1:2], page))

        kpage = in_data[2 * l][0].astype(f32)            # (nr, D)
        vpage = in_data[2 * l + 1][0].astype(f32)
        kd, vd = pair(kpage), pair(vpage)
        if quant[l]:
            osc = osc_ref[0, si]                         # (4, 1)
            kd = kd * osc[0:2]
            vd = vd * osc[2:4]
        pk = jnp.where(sel, new_k, kd)
        pv = jnp.where(sel, new_v, vd)
        if quant[l]:
            ksc = jnp.maximum(jnp.max(jnp.abs(pk), axis=1, keepdims=True),
                              eps) * recip
            vsc = jnp.maximum(jnp.max(jnp.abs(pv), axis=1, keepdims=True),
                              eps) * recip
            qk = jnp.clip(jnp.round(pk / ksc), -qmax, qmax)
            qv = jnp.clip(jnp.round(pv / vsc), -qmax, qmax)
            out_data[2 * l][0] = put(kpage, qk).astype(jnp.int8)
            out_data[2 * l + 1][0] = put(vpage, qv).astype(jnp.int8)
            nsc_ref[0, si] = jnp.concatenate([ksc, vsc], axis=0)
            si += 1
        else:
            out_data[2 * l][0] = put(kpage, pk).astype(out_data[2 * l].dtype)
            out_data[2 * l + 1][0] = put(vpage, pv).astype(
                out_data[2 * l + 1].dtype)
        if l + 1 < nlev:
            new_k = pk.mean(axis=0, keepdims=True)       # Eq. 25/26
            new_v = pv.sum(axis=0, keepdims=True)        # Eq. 27


def update_cache_paged_quant(pool, k_new: jnp.ndarray, v_new: jnp.ndarray,
                             t: jnp.ndarray, utab: jnp.ndarray, *,
                             interpret: bool = False):
    """Fused batched append into a QUANTIZED paged pool
    (``core.h1d_decode.QuantPagedH1DCache``).

    Same single-launch in-place scatter as :func:`update_cache_paged`,
    but each level moves its whole ``(1, nr, D)`` page, aliased
    input->output: an int8 pair view ``(..., nr/2, 2, D)`` does not fold
    into the TPU's int8 tiling, and would cost a relayout copy of the
    pool per launch.  The scales stay out of the kernel for the same
    reason (the ``(NP, nr)`` scale arrays are lane-transposed on TPU):
    XLA gathers each int8 level's two old pair scales into one small
    ``(R, nq, 4, 1)`` operand and scatters the kernel's fresh ones back
    into the (donated) scale arrays.  fp32 levels of a mixed pool keep
    their scale arrays untouched."""
    qz = _qz()
    R, D = k_new.shape
    Dv = v_new.shape[-1]
    nr = pool.k.shape[-2]
    nlev = 1 + len(pool.ck)
    assert utab.shape == (R, nlev), (utab.shape, R, nlev)
    quant = tuple(bool(a.dtype == jnp.int8) for a in (pool.k, *pool.ck))
    nq = sum(quant)
    t = t.astype(jnp.int32)
    utab = utab.astype(jnp.int32)

    lvls = list(zip([pool.k] + list(pool.ck), [pool.v] + list(pool.cv)))
    ksc = [pool.ksc] + list(pool.cksc)
    vsc = [pool.vsc] + list(pool.cvsc)
    qlev = [l for l in range(nlev) if quant[l]]
    # each int8 level's pair-scale columns (R, 2) and their page rows
    cols = {l: (2 * ((t >> (l + 1)) & (nr // 2 - 1)))[:, None]
            + jnp.arange(2) for l in qlev}
    pages = {l: utab[:, l][:, None] for l in qlev}

    data, data_specs = [], []
    for l, (ka, va) in enumerate(lvls):

        def page_map(r, tref, uref, l=l):
            return (uref[r, l], 0, 0)

        for a, d_ in ((ka, D), (va, Dv)):
            data.append(a)
            data_specs.append(pl.BlockSpec((1, nr, d_), page_map))
    sc_ops, sc_specs, sc_shape = [], [], []
    if nq:
        osc = jnp.stack([jnp.concatenate(
            [ksc[l][pages[l], cols[l]], vsc[l][pages[l], cols[l]]], axis=1)
            for l in qlev], axis=1)[..., None]           # (R, nq, 4, 1)
        sc_ops = [osc]
        sc_specs = [pl.BlockSpec((1, nq, 4, 1),
                                 lambda r, tref, uref: (r, 0, 0, 0))]
        sc_shape = [jax.ShapeDtypeStruct(osc.shape, jnp.float32)]

    row_map = lambda r, tref, uref: (r, 0, 0)
    # (k_new, v_new, *data, osc): every pool page operand aliases its
    # mirror output; operand-indexed.  nsc is a fresh per-row output.
    aliases = {2 + i: i for i in range(2 * nlev)}
    kernel = functools.partial(_update_paged_quant_kernel, nlev=nlev, nr=nr,
                               quant=quant, qmax=qz.QMAX,
                               recip=qz.RECIP_QMAX, eps=qz.EPS)
    utab_hi = np.array([ka.shape[0] - 1 for ka, _ in lvls], dtype=np.int32)
    lvl_names = [f"{a}_l{l}" for l in range(nlev) for a in ("k", "v")]
    outs = launch(
        kernel, family="decode_update_paged_quant", grid=(R,),
        in_specs=[pl.BlockSpec((1, 1, D), row_map),
                  pl.BlockSpec((1, 1, Dv), row_map)] + data_specs + sc_specs,
        out_specs=tuple(data_specs + sc_specs),
        out_shape=tuple([jax.ShapeDtypeStruct(a.shape, a.dtype)
                         for a in data] + sc_shape),
        operands=[_rows3(k_new), _rows3(v_new), *data, *sc_ops],
        scalars=(t, utab),
        scalar_bounds=((0, _T_MAX), (0, utab_hi)),
        scalar_names=("t", "utab"),
        in_names=["k_new", "v_new"] + lvl_names + (["osc"] if nq else []),
        out_names=lvl_names + (["nsc"] if nq else []), aliases=aliases,
        interpret=interpret,
        meta=dict(nr=nr, nlev=nlev, quant=quant))
    for si, l in enumerate(qlev):
        nsc = outs[2 * nlev][:, si, :, 0]                # (R, 4)
        ksc[l] = ksc[l].at[pages[l], cols[l]].set(nsc[:, 0:2])
        vsc[l] = vsc[l].at[pages[l], cols[l]].set(nsc[:, 2:4])
    return type(pool)(
        k=outs[0], v=outs[1],
        ck=tuple(outs[2 + 2 * i] for i in range(nlev - 1)),
        cv=tuple(outs[3 + 2 * i] for i in range(nlev - 1)),
        ksc=ksc[0], vsc=vsc[0], cksc=tuple(ksc[1:]), cvsc=tuple(vsc[1:]))


# ---------------------------------------------------------------------------
# sequence-parallel partial update (owned rows only + carried ancestor)
# ---------------------------------------------------------------------------

def _update_partial_kernel(t_ref, own_ref, knew_ref, vnew_ref, *refs,
                           nlev: int):
    """SP variant of :func:`_update_kernel`: ``t`` is shard-LOCAL, the
    substitution is gated per row by the ownership bit (non-owners
    write their clamped pair back unchanged -- a no-op scatter), and
    the pair mean/sum carried past the LAST level is emitted so the
    caller can broadcast it to the replicated deep levels."""
    in_refs = refs[:2 * nlev]
    out_refs = refs[2 * nlev:4 * nlev]
    ck_ref, cv_ref = refs[4 * nlev:4 * nlev + 2]
    r = pl.program_id(0)
    t = t_ref[r]
    owned = own_ref[r] > 0
    f32 = jnp.float32
    sel_row = jax.lax.broadcasted_iota(jnp.int32, (2, 1), 0)

    new_k = knew_ref[0].astype(f32)                      # (1, D)
    new_v = vnew_ref[0].astype(f32)                      # (1, Dv)
    for l in range(nlev):
        sel = (sel_row == ((t >> l) & 1)) & owned
        pk = jnp.where(sel, new_k, in_refs[2 * l][0, 0].astype(f32))
        pv = jnp.where(sel, new_v, in_refs[2 * l + 1][0, 0].astype(f32))
        out_refs[2 * l][0, 0] = pk.astype(out_refs[2 * l].dtype)
        out_refs[2 * l + 1][0, 0] = pv.astype(out_refs[2 * l + 1].dtype)
        new_k = pk.mean(axis=0, keepdims=True)
        new_v = pv.sum(axis=0, keepdims=True)
    # carried row for the first level ABOVE this sharded chain; garbage
    # on non-owner rows (the caller masks it with `owned` before psum)
    ck_ref[0] = new_k.astype(ck_ref.dtype)
    cv_ref[0] = new_v.astype(cv_ref.dtype)


def update_cache_partial(cache, k_new: jnp.ndarray, v_new: jnp.ndarray,
                         t_loc: jnp.ndarray, owned: jnp.ndarray, *,
                         t_hi: int = None, interpret: bool = False):
    """Fused ancestor update on shard-LOCAL cache arrays.

    ``cache`` holds only the SHARDED levels of the hierarchy (this
    shard's slab); ``t_loc`` (R,) is the shard-local position (low-
    clamped only, so a non-owner left of the owning shard sees values up
    to the GLOBAL length -- ``t_hi`` declares that real domain, the
    default covers a single-shard slab) and ``owned`` (R,) marks the
    rows whose token lives on this shard.  Returns ``(updated_cache,
    carry_k (R, D), carry_v (R, Dv))`` where the carry is the freshly
    computed row for the first level above the sharded chain (valid on
    owner rows)."""
    R, D = k_new.shape
    Dv = v_new.shape[-1]
    nlev = 1 + len(cache.ck)

    arrs, in_specs, out_specs, out_shape = [], [], [], []
    lvls = [(cache.k, cache.v)] + list(zip(cache.ck, cache.cv))
    for l, (ka, va) in enumerate(lvls):
        npairs = ka.shape[-2] // 2

        def pair_map(r, tref, oref, l=l, npairs=npairs):
            return (r, jnp.minimum(tref[r] >> (l + 1), npairs - 1), 0, 0)

        for a, d_ in ((ka, D), (va, Dv)):
            arrs.append(_pairs(a))
            in_specs.append(pl.BlockSpec((1, 1, 2, d_), pair_map))
            out_specs.append(pl.BlockSpec((1, 1, 2, d_), pair_map))
            out_shape.append(jax.ShapeDtypeStruct(arrs[-1].shape, a.dtype))

    row_map = lambda r, tref, oref: (r, 0, 0)
    out_specs += [pl.BlockSpec((1, 1, D), row_map),
                  pl.BlockSpec((1, 1, Dv), row_map)]
    out_shape += [jax.ShapeDtypeStruct((R, 1, D), cache.k.dtype),
                  jax.ShapeDtypeStruct((R, 1, Dv), cache.v.dtype)]

    # (k_new, v_new, *arrs): cache operands start at operand index 2;
    # the two carry outputs at the end are not aliased.
    aliases = {2 + i: i for i in range(2 * nlev)}
    kernel = functools.partial(_update_partial_kernel, nlev=nlev)
    Lloc = cache.k.shape[-2]
    lvl_names = [f"{a}_l{l}" for l in range(nlev) for a in ("k", "v")]
    outs = launch(
        kernel, family="decode_update_partial", grid=(R,),
        in_specs=[pl.BlockSpec((1, 1, D), row_map),
                  pl.BlockSpec((1, 1, Dv), row_map)] + in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        operands=[_rows3(k_new), _rows3(v_new), *arrs],
        scalars=(t_loc.astype(jnp.int32), owned.astype(jnp.int32)),
        scalar_bounds=((0, Lloc - 1 if t_hi is None else t_hi), (0, 1)),
        scalar_names=("t_loc", "owned"),
        in_names=["k_new", "v_new"] + lvl_names,
        out_names=lvl_names + ["carry_k", "carry_v"],
        aliases=aliases, interpret=interpret,
        meta=dict(Lloc=Lloc, nlev=nlev))
    upd = _unpair(cache, outs[:2 * nlev], nlev)
    return upd, outs[2 * nlev][:, 0], outs[2 * nlev + 1][:, 0]
