"""Sequence-parallel (SP) execution layer for the fused Pallas kernels.

The fused band/decode kernels were single-chip until this layer: a
sequence-sharded operand handed to ``pallas_call`` is gathered whole,
so every caller with an ``L``-sharded cache or activation fell back to
``impl='jnp'`` (EXPERIMENTS.md P21/P22 measured why).  This module wraps
the *unmodified* kernels in ``shard_map`` over the ``data`` mesh axis
and makes the cross-shard structure explicit:

* each shard runs the Pallas band kernels on its local ``L/d`` rows --
  the banded structure is translation-invariant by multiples of the
  query-block size, so a local launch computes every contribution
  except the ones that cross the left/right shard boundary;
* the boundary needs exactly one ``nr``-row block per level per
  direction (level 0: the neighbouring fine block; level ``l``: the
  single coarse block ``I-1`` owned by the left shard).  All levels'
  halo rows are packed into ONE buffer and exchanged with one
  ``ppermute`` per direction (causal modes need only the left->right
  direction);
* the cross-level streaming LSE combine (``_stream_combine``, PR 2)
  gains a cross-shard epilogue: the halo contributions are merged into
  the affected edge rows with the same log-sum-exp shift.  Each fine
  query row is owned by exactly one shard, so the epilogue is
  psum-free;
* levels too deep to keep an ``nr``-row block per shard (local coarse
  length < ``nr``) are computed from one ``all_gather`` of the tiny
  transition-level coarse KV (<= ``d * nr / 2`` rows total -- see
  DESIGN.md section 7 for the communication accounting);
* the decode kernels run per shard with *sharded index maps*: block
  indices are translated to shard-local coordinates outside the kernel
  and scalar-prefetched together with a per-band ownership bit, so a
  token's ancestor pair is read/updated on its owning shard only; the
  per-shard partial ``(num, den, m)`` triples merge with one
  ``pmax`` + ``psum`` pair.

Entry points
------------
``sp_band_attention``   -- one banded level under SP (all five modes).
``sp_h1d_attention``    -- the full hierarchical operator under SP.
``sp_decode_attend`` / ``sp_update_cache`` -- fused decode tick under a
sequence-sharded ``H1DCache``.
``sp_scope`` / ``sp_ctx`` -- trace-time context: callers enter
``sp_scope(mesh)`` around tracing and the kernel dispatchers in
``kernels/ops.py`` / ``core/h1d_attention.py`` / ``core/h1d_decode.py``
route through this module automatically.
``sp_cache_specs``      -- PartitionSpec tree for an ``H1DCache`` under
SP (deep levels replicated; loud fallback when the kv-head dim does not
divide the ``model`` axis).
"""
from __future__ import annotations

import math
import threading
import warnings
from contextlib import contextmanager
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.core import hierarchy as hc
from repro.kernels import h1d_block

NEG_INF = h1d_block.NEG_INF
_MIN_M = -1e30


def _note_dispatch(op: str, shards: int) -> None:
    """Trace-time SP dispatch counter (one per traced shard_map shape,
    like the kernel-launch accounting)."""
    obs.counter("sp.dispatches", op=op, shards=shards).inc()


# ---------------------------------------------------------------------------
# trace-time SP context
# ---------------------------------------------------------------------------

_state = threading.local()


@contextmanager
def sp_scope(mesh: Optional[Mesh], axis: str = "data"):
    """Enable SP dispatch while tracing.  ``h1d_attention`` /
    ``band_attention`` / the decode entry points check :func:`sp_ctx`
    and route through this module when a mesh with ``mesh.shape[axis] >
    1`` is active.  A ``None`` mesh (or a trivial axis) is a no-op, so
    callers can wrap unconditionally."""
    prev = getattr(_state, "ctx", None)
    active = mesh is not None and dict(mesh.shape).get(axis, 1) > 1
    _state.ctx = (mesh, axis) if active else None
    try:
        yield
    finally:
        _state.ctx = prev


def sp_ctx() -> Optional[Tuple[Mesh, str]]:
    """The active (mesh, axis) SP context, or None."""
    return getattr(_state, "ctx", None)


@contextmanager
def _local_region():
    """Suppress SP re-dispatch while tracing a shard_map body: the
    kernels called inside already see shard-local arrays."""
    prev = getattr(_state, "ctx", None)
    _state.ctx = None
    try:
        yield
    finally:
        _state.ctx = prev


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _dim0_spec(mesh: Mesh, n: int, what: str):
    """Shard the folded ``batch * kv_heads`` dim over ``model`` when it
    divides; otherwise fall back LOUDLY (a silent wrong-shape shard
    would wrong-answer GQA head counts not divisible by the axis)."""
    msz = dict(mesh.shape).get("model", 1)
    if msz <= 1:
        return None
    if n % msz == 0:
        return "model"
    warnings.warn(
        f"SP {what}: dim0={n} (batch*kv_heads) does not divide the "
        f"'model' axis ({msz}); replicating heads instead of sharding "
        f"them (correct but slower)", stacklevel=3)
    return None


# ---------------------------------------------------------------------------
# halo pack / edge-correction helpers
# ---------------------------------------------------------------------------

def _pack_kvw(k, v, w):
    """(B, R, Dk) + (B, R, Dv) + (B, R) -> one (B, R, Dk+Dv+1) buffer so
    the whole exchange is ONE ppermute per direction."""
    return jnp.concatenate([k, v, w[..., None]], axis=-1)


def _unpack_kvw(buf, dk, dv):
    return buf[..., :dk], buf[..., dk:dk + dv], buf[..., dk + dv]


def _ppermute_right(x, axis, d):
    """Shard s -> s+1 (receives the LEFT neighbour's buffer; shard 0
    receives zeros, which the global masks / w>0 kill anyway)."""
    return jax.lax.ppermute(x, axis, [(i, i + 1) for i in range(d - 1)])


def _ppermute_left(x, axis, d):
    return jax.lax.ppermute(x, axis, [(i + 1, i) for i in range(d - 1)])


def sp_n_shallow(M: int, Lloc: int, nr: int) -> int:
    """Number of hierarchy levels (fine level 0 included) the
    training/prefill path runs LOCALLY per shard: level ``l`` keeps at
    least one whole ``nr``-row coarse block per shard iff
    ``Lloc >> l >= nr``.  Levels at or above the returned count go
    through the gathered deep path.  One definition shared by
    :func:`sp_h1d_attention` and ``analysis/dist.py``."""
    return min(M, int(math.log2(Lloc // nr)) + 1)


def sp_halo_pack(kc_l, vc_l, wc_l, n_shallow: int, nr: int, side: str):
    """Pack the shard-boundary ``nr``-row block of every shallow level
    into ONE ``(B, n_shallow * nr, Dk + Dv + 1)`` buffer -- the whole
    multi-level halo then costs a single ppermute per direction.
    ``side='prev'`` takes each level's LAST block (sent rightward),
    ``side='next'`` the FIRST (sent leftward)."""
    sl = slice(-nr, None) if side == "prev" else slice(None, nr)
    return jnp.concatenate(
        [_pack_kvw(kc_l[l][:, sl], vc_l[l][:, sl], wc_l[l][:, sl])
         for l in range(n_shallow)], axis=1)


def _edge_term(qe, ke, ve, we, mask):
    """Partial banded softmax of an edge query slab against one halo
    key block.  qe: (B, G, nq, D); ke/ve: (B, nk, *); we: (B, nk);
    mask: broadcastable (.., nq, nk) allowed-mask.  Returns float32
    (y, dn, m) like one band kernel launch."""
    f32 = jnp.float32
    s = jnp.einsum("bgqd,bkd->bgqk", qe.astype(f32), ke.astype(f32),
                   preferred_element_type=f32)
    allow = jnp.logical_and(mask, (we > 0)[:, None, None, :])
    s = jnp.where(allow, s, NEG_INF)
    m = jnp.maximum(s.max(-1), _MIN_M)
    a = jnp.exp(s - m[..., None])
    y = jnp.einsum("bgqk,bkv->bgqv", a, ve.astype(f32),
                   preferred_element_type=f32)
    dn = jnp.einsum("bgqk,bk->bgq", a, we.astype(f32),
                    preferred_element_type=f32)
    return y, dn, m


def _merge_rows(acc, corr, start):
    """LSE-merge a correction triple into rows [start, start+n) of a
    (y, dn, m) accumulator (the cross-shard epilogue of
    ``_stream_combine``)."""
    y, dn, m = acc
    yl, dl, ml = corr
    n = yl.shape[-2]
    y0 = jax.lax.dynamic_slice_in_dim(y, start, n, axis=-2)
    d0 = jax.lax.dynamic_slice_in_dim(dn, start, n, axis=-1)
    m0 = jax.lax.dynamic_slice_in_dim(m, start, n, axis=-1)
    mn = jnp.maximum(m0, ml)
    e0 = jnp.exp(m0 - mn)
    el = jnp.exp(ml - mn)
    y = jax.lax.dynamic_update_slice_in_dim(
        y, y0 * e0[..., None] + yl * el[..., None], start, axis=-2)
    dn = jax.lax.dynamic_update_slice_in_dim(
        dn, d0 * e0 + dl * el, start, axis=-1)
    m = jax.lax.dynamic_update_slice_in_dim(m, mn, start, axis=-1)
    return y, dn, m


def _halo_mask(mode, nr, ratio, lkg, q0, k0, nq_rows, nk_rows):
    """Allowed-mask of an edge correction from GLOBAL indices (q0/k0 may
    be traced: they depend on the shard index)."""
    qi = q0 + jnp.arange(nq_rows)[:, None]
    ki = k0 + jnp.arange(nk_rows)[None, :]
    return h1d_block.band_mask(qi, ki, nr, mode, lkg, ratio)[None, None]


# ---------------------------------------------------------------------------
# single banded level under SP
# ---------------------------------------------------------------------------

def _validate_sp_shape(L, d, nr, what):
    if L % d:
        raise ValueError(f"{what}: L={L} not divisible by the data axis "
                         f"size {d}")
    Lloc = L // d
    if Lloc % nr or Lloc < nr:
        raise ValueError(
            f"{what}: local length L/d={Lloc} must be a multiple of "
            f"nr={nr} and >= nr; use fewer shards for this sequence")
    return Lloc


def sp_band_attention(q, k, v, w, *, nr: int, mode: str, ratio: int = 1,
                      impl: str = "pallas", tq: Optional[int] = None,
                      mesh: Mesh, axis: str = "data"):
    """One banded level under sequence parallelism.

    Same contract as ``kernels.ops.band_attention`` (returns the float32
    ``(y, dn, m)`` triple at fine/query resolution), but the query and
    key sequence axes are sharded over ``mesh[axis]``: each shard runs
    the unmodified Pallas kernel on its rows and the boundary blocks are
    fixed up from one packed halo exchange per direction.

    ``mode='sub'`` requires the local query slab to hold at least one
    whole ``nr * ratio``-row query block (deeper levels are the
    gathered path of :func:`sp_h1d_attention`).
    """
    from repro.kernels.ops import band_attention

    d = dict(mesh.shape)[axis]
    if d == 1:
        with _local_region():
            return band_attention(q, k, v, w, nr=nr, mode=mode, ratio=ratio,
                                  impl=impl, tq=tq)
    _note_dispatch("band_attention", d)
    B, G, Lq, dk = q.shape
    dv = v.shape[-1]
    Lk = k.shape[1]
    causal = mode.endswith("causal") or mode == h1d_block.SUB_MODE
    Lq_loc = _validate_sp_shape(Lq, d, nr, "sp_band_attention")
    if mode == h1d_block.SUB_MODE:
        nq = nr * ratio
        if nq > Lq_loc:
            raise ValueError(
                f"sp_band_attention(mode='sub'): query block nq={nq} "
                f"exceeds the local slab L/d={Lq_loc}; deep levels go "
                f"through sp_h1d_attention's gathered path")
    else:
        nq = nr
    spec0 = _dim0_spec(mesh, B, "band_attention")

    def body(q, k, v, w):
        with _local_region():
            s = jax.lax.axis_index(axis)
            lloc = q.shape[2]
            kloc = k.shape[1]
            acc = band_attention(q, k, v, w, nr=nr, mode=mode, ratio=ratio,
                                 impl=impl, tq=tq)
            # one packed halo buffer per direction
            halo = _ppermute_right(
                _pack_kvw(k[:, -nr:], v[:, -nr:], w[:, -nr:]), axis, d)
            kh, vh, wh = _unpack_kvw(halo, dk, dv)
            # left boundary: the first query block attends the left
            # neighbour's last key block (masked out by the local call)
            q0 = s * lloc if mode == h1d_block.SUB_MODE else s * kloc
            corr = _edge_term(
                q[:, :, :nq], kh, vh, wh,
                _halo_mask(mode, nr, ratio, Lk, q0, s * kloc - nr, nq, nr))
            acc = _merge_rows(acc, corr, 0)
            if not causal:
                nhalo = _ppermute_left(
                    _pack_kvw(k[:, :nr], v[:, :nr], w[:, :nr]), axis, d)
                kn, vn, wn = _unpack_kvw(nhalo, dk, dv)
                corr = _edge_term(
                    q[:, :, -nr:], kn, vn, wn,
                    _halo_mask(mode, nr, ratio, Lk, s * kloc + kloc - nr,
                               (s + 1) * kloc, nr, nr))
                acc = _merge_rows(acc, corr, lloc - nr)
            return acc

    fn = _shard_map(
        body, mesh,
        in_specs=(P(spec0, None, axis, None), P(spec0, axis, None),
                  P(spec0, axis, None), P(spec0, axis)),
        out_specs=(P(spec0, None, axis, None), P(spec0, None, axis),
                   P(spec0, None, axis)))
    return fn(q, k, v, w)


# ---------------------------------------------------------------------------
# full hierarchical operator under SP
# ---------------------------------------------------------------------------

def sp_h1d_attention(q, k, v, *, mesh: Mesh, axis: str = "data",
                     nr: int = 16, causal: bool = False,
                     causal_mode: str = "fine-q", kv_weight=None,
                     softmax_scale: Optional[float] = None,
                     impl: str = "pallas", tq: Optional[int] = None):
    """``core.h1d_attention`` semantics with the L axis sharded over
    ``mesh[axis]``.  Every level that keeps an ``nr``-row block per
    shard runs the unmodified fused kernel locally (+ halo epilogue);
    deeper levels are computed from ONE ``all_gather`` of the
    transition-level coarse KV (<= ``d*nr/2`` rows in total).  The
    output stays sequence-sharded: no psum touches the fine rows."""
    from repro.core.h1d_attention import _stream_combine
    from repro.kernels.ops import band_attention

    d = dict(mesh.shape)[axis]
    B, G, L, D = q.shape
    if k.ndim == 4:
        raise ValueError("sp_h1d_attention: per-head 4-D KV is the "
                         "GSPMD jnp layout; SP is the kernel path")
    Dk = k.shape[-1]
    Dv = v.shape[-1]
    if d == 1:
        from repro.core.h1d_attention import h1d_attention
        with _local_region():
            return h1d_attention(q, k, v, nr=nr, causal=causal,
                                 causal_mode=causal_mode,
                                 kv_weight=kv_weight,
                                 softmax_scale=softmax_scale,
                                 impl=impl, tq=tq)
    _note_dispatch("h1d_attention", d)
    Lloc = _validate_sp_shape(L, d, nr, "sp_h1d_attention")
    M = hc.num_levels(L, nr)
    fine_q = causal and causal_mode == "fine-q"
    # levels 0..n_shallow-1 keep >= one nr-row coarse block per shard
    n_shallow = sp_n_shallow(M, Lloc, nr)
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    out_dtype = v.dtype
    spec0 = _dim0_spec(mesh, B, "h1d_attention")
    l0_mode = "l0_causal" if causal else "l0_bidir"
    coarse_mode = "coarse_causal" if causal else "coarse_bidir"
    f32 = jnp.float32

    w_in = (jnp.ones((B, L), f32) if kv_weight is None
            else jnp.broadcast_to(kv_weight.astype(f32), (B, L)))

    def body(q, k, v, w):
      with _local_region():
        s = jax.lax.axis_index(axis)
        q = q.astype(f32) * scale
        k = k.astype(f32)
        v = v.astype(f32) * w[..., None]

        # ---- local coarse pyramid (pairwise ops never cross shards) --
        # levels 1..n_shallow-1 run the fused kernel; the extra level
        # n_shallow (if any) only exists to seed the deep-level gather.
        n_pyr = min(M - 1, n_shallow)
        kc_l, vc_l, wc_l = [k], [v], [w]
        qc_l, wq_l = [q], [w]
        for l in range(1, n_pyr + 1):
            kcl, _ = hc.coarsen_weighted_mean(kc_l[-1], wc_l[-1])
            kc_l.append(kcl)
            vc_l.append(hc.coarsen_sum(vc_l[-1], axis=-2))
            wc_l.append(hc.coarsen_sum(wc_l[-1], axis=-1))
            if causal and not fine_q or not causal:
                qcl, _ = hc.coarsen_weighted_mean(qc_l[-1], wq_l[-1])
                qc_l.append(qcl)
                wq_l.append(hc.coarsen_sum(wq_l[-1], axis=-1))

        # ---- one packed halo exchange per direction ------------------
        prev_halo = _ppermute_right(
            sp_halo_pack(kc_l, vc_l, wc_l, n_shallow, nr, "prev"), axis, d)
        if not causal:
            next_halo = _ppermute_left(
                sp_halo_pack(kc_l, vc_l, wc_l, n_shallow, nr, "next"),
                axis, d)

        def halo(buf, l):
            return _unpack_kvw(buf[:, l * nr:(l + 1) * nr], Dk, Dv)

        # ---- level 0 seeds the streaming accumulator -----------------
        acc = band_attention(q, k, v, w, nr=nr, mode=l0_mode, impl=impl,
                             tq=tq)
        kh, vh, wh = halo(prev_halo, 0)
        acc = _merge_rows(acc, _edge_term(
            q[:, :, :nr], kh, vh, wh,
            _halo_mask(l0_mode, nr, 1, L, s * Lloc, s * Lloc - nr, nr, nr)),
            0)
        if not causal:
            kh, vh, wh = halo(next_halo, 0)
            acc = _merge_rows(acc, _edge_term(
                q[:, :, -nr:], kh, vh, wh,
                _halo_mask(l0_mode, nr, 1, L, (s + 1) * Lloc - nr,
                           (s + 1) * Lloc, nr, nr)), Lloc - nr)

        # ---- shallow coarse levels: local kernel + halo epilogue -----
        for l in range(1, n_shallow):
            kc, vc, wc = kc_l[l], vc_l[l], wc_l[l]
            cl = Lloc >> l                     # local coarse length
            lkg = L >> l                       # global coarse length
            kh, vh, wh = halo(prev_halo, l)
            if fine_q:
                ratio = 1 << l
                yl, dl, ml = band_attention(q, kc, vc, wc, nr=nr, mode="sub",
                                            ratio=ratio, impl=impl, tq=tq)
                nq = nr * ratio
                corr = _edge_term(
                    q[:, :, :nq], kh, vh, wh,
                    _halo_mask("sub", nr, ratio, lkg, s * Lloc,
                               s * cl - nr, nq, nr))
                yl, dl, ml = _merge_rows((yl, dl, ml), corr, 0)
            else:
                qc = qc_l[l]
                yl, dl, ml = band_attention(qc, kc, vc, wc, nr=nr,
                                            mode=coarse_mode, impl=impl,
                                            tq=tq)
                corr = _edge_term(
                    qc[:, :, :nr], kh, vh, wh,
                    _halo_mask(coarse_mode, nr, 1, lkg, s * cl,
                               s * cl - nr, nr, nr))
                yl, dl, ml = _merge_rows((yl, dl, ml), corr, 0)
                if not causal:
                    kh, vh, wh = halo(next_halo, l)
                    corr = _edge_term(
                        qc[:, :, -nr:], kh, vh, wh,
                        _halo_mask(coarse_mode, nr, 1, lkg,
                                   (s + 1) * cl - nr, (s + 1) * cl, nr, nr))
                    yl, dl, ml = _merge_rows((yl, dl, ml), corr, cl - nr)
                rep = 1 << l
                yl = hc.interp_repeat(yl, rep, axis=-2)
                dl = hc.interp_repeat(dl, rep, axis=-1)
                ml = hc.interp_repeat(ml, rep, axis=-1)
            acc = _stream_combine(acc, yl, dl, ml)

        # ---- deep levels: gathered tiny coarse KV --------------------
        if n_shallow < M:
            lt = n_shallow
            kg = jax.lax.all_gather(kc_l[lt], axis, axis=1, tiled=True)
            vg = jax.lax.all_gather(vc_l[lt], axis, axis=1, tiled=True)
            wg = jax.lax.all_gather(wc_l[lt], axis, axis=1, tiled=True)
            if not fine_q:
                qg = jax.lax.all_gather(qc_l[lt], axis, axis=2, tiled=True)
                wqg = jax.lax.all_gather(wq_l[lt], axis, axis=1, tiled=True)
            fidx = s * Lloc + jnp.arange(Lloc)
            for l in range(lt, M):
                lkg = L >> l
                if fine_q:
                    qi = fidx[:, None]
                    ki = jnp.arange(lkg)[None, :]
                    mask = h1d_block.band_mask(qi, ki, nr, "sub", lkg,
                                               1 << l)[None, None]
                    yl, dl, ml = _edge_term(q, kg, vg, wg, mask)
                else:
                    qi = jnp.arange(lkg)[:, None]
                    ki = jnp.arange(lkg)[None, :]
                    mask = h1d_block.band_mask(qi, ki, nr, coarse_mode,
                                               lkg)[None, None]
                    yc, dc, mc = _edge_term(qg, kg, vg, wg, mask)
                    cidx = fidx >> l
                    yl = jnp.take(yc, cidx, axis=-2)
                    dl = jnp.take(dc, cidx, axis=-1)
                    ml = jnp.take(mc, cidx, axis=-1)
                acc = _stream_combine(acc, yl, dl, ml)
                if l + 1 < M:
                    kg, _ = hc.coarsen_weighted_mean(kg, wg)
                    vg = hc.coarsen_sum(vg, axis=-2)
                    wg = hc.coarsen_sum(wg, axis=-1)
                    if not fine_q:
                        qg, _ = hc.coarsen_weighted_mean(qg, wqg)
                        wqg = hc.coarsen_sum(wqg, axis=-1)

        y, dn, _ = acc
        z = y / jnp.maximum(dn, 1e-9)[..., None]
        return z.astype(out_dtype)

    fn = _shard_map(
        body, mesh,
        in_specs=(P(spec0, None, axis, None), P(spec0, axis, None),
                  P(spec0, axis, None), P(spec0, axis)),
        out_specs=P(spec0, None, axis, None))
    return fn(q, k, v, w_in)


# ---------------------------------------------------------------------------
# sequence-sharded fused decode
# ---------------------------------------------------------------------------

def sp_sharded_levels(Lmax: int, nr: int, d: int) -> int:
    """Number of cache levels (fine level 0 included) whose sequence
    axis shards over a ``d``-way data axis: level ``l`` keeps a whole
    ``nr``-row block per shard iff ``Lmax >> l >= d * nr``.  Deeper
    levels replicate (they are tiny)."""
    n = 0
    while (Lmax >> n) >= d * nr and (Lmax >> n) % (d * nr) == 0:
        n += 1
    return n


def sp_update_owner(t, Lloc: int, d: int):
    """Owning shard of a decode-update row at global position ``t``.
    Out-of-range ``t`` (defensive: the engine freezes slots before this
    can happen) is owned by the LAST shard, whose kernel then clamps the
    pair index exactly like the single-chip launch -- without the clip
    no shard owns the row and the masked-psum carry would write ZEROS
    into the deep levels."""
    return jnp.clip(t // Lloc, 0, d - 1)


def sp_update_local_t(t, s, Lloc: int):
    """Shard-local position handed to ``update_cache_partial``.  Keeps
    the raw low bits (no upper clip): the kernel's pair_map min()-clamps
    the index, and the sibling parity ``(t >> l) & 1`` must match the
    unclamped single-chip value."""
    return jnp.maximum(t - s * Lloc, 0)


def sp_cache_specs(cache, mesh: Mesh, *, nr: int, axis: str = "data"):
    """PartitionSpec tree for an ``H1DCache`` under SP: fine + shallow
    coarse levels shard their sequence axis over ``axis``; deep levels
    replicate.  Dim0 (batch*kv_heads) shards over ``model`` when it
    divides -- the fallback when it does not is loud (a warning), never
    a silent wrong answer."""
    d = dict(mesh.shape)[axis]
    Lmax = cache.k.shape[-2]
    spec0 = _dim0_spec(mesh, cache.k.shape[0], "decode cache")
    nsh = sp_sharded_levels(Lmax, nr, d)
    if nsh < 1:
        raise ValueError(
            f"SP decode: Lmax={Lmax} < data_axis*nr = {d * nr}; the fine "
            f"level cannot keep an nr-row block per shard -- use fewer "
            f"shards")
    ck = tuple(P(spec0, axis if l + 1 < nsh else None, None)
               for l in range(len(cache.ck)))
    return type(cache)(k=P(spec0, axis, None), v=P(spec0, axis, None),
                       ck=ck, cv=ck)


def _band_geometry(t, s, nr, Lmax, d, nsh, nlevels):
    """Per-row (local block index, owned) for every decode band.

    t: (R,) global positions; s: traced shard index.  Band 0/1 are the
    own/prev fine blocks; band ``l+1`` is coarse level ``l``'s single
    ``I_l - 1`` block.  Sharded levels translate the global block index
    to shard-local coordinates and set ``owned`` on the owning shard
    only; replicated levels are owned by shard 0 (any single shard --
    the merge is a psum)."""
    idx, own = [], []
    for band in range(2 + nlevels):
        if band == 0:
            l, gb = 0, t // nr
        elif band == 1:
            l, gb = 0, jnp.maximum(t // nr - 1, 0)
        else:
            l = band - 1
            gb = t // (nr << l) - 1
        nbl = (Lmax >> l) // nr
        gb = jnp.clip(gb, 0, nbl - 1)
        if l < nsh:
            nbl_loc = nbl // d
            owner = gb // nbl_loc
            idx.append(jnp.clip(gb - s * nbl_loc, 0, nbl_loc - 1))
            own.append((owner == s).astype(jnp.int32))
        else:
            idx.append(gb)
            own.append((s == 0).astype(jnp.int32)
                       * jnp.ones_like(gb, jnp.int32))
    return (jnp.stack(idx, axis=-1).astype(jnp.int32),
            jnp.stack(own, axis=-1).astype(jnp.int32))


def sp_decode_attend(cache, q, t, *, nr: int, softmax_scale=None,
                     impl: str = "pallas", mesh: Mesh, axis: str = "data"):
    """Fused decode attention over a sequence-sharded ``H1DCache``.

    Same contract as ``core.h1d_decode.decode_attend``: ``q`` (R, G, D),
    ``t`` (R,) -> (R, G, Dv).  Each shard launches the partial-output
    variant of the fused kernel over the bands it owns (shard-local
    block indices + ownership bits scalar-prefetched), then the partial
    ``(num, den, m)`` triples merge with one ``pmax`` + ``psum``."""
    from repro.kernels import h1d_decode_kernel as dk
    from repro.kernels.tuning import get_policy

    d = dict(mesh.shape)[axis]
    impl = get_policy().resolve_impl(impl, "decode_attend")
    interpret = impl == "pallas_interpret"
    if d == 1:
        return dk.decode_attend_fused(cache, q, t, nr=nr,
                                      softmax_scale=softmax_scale,
                                      interpret=interpret)
    _note_dispatch("decode_attend", d)
    R, G, D = q.shape
    Lmax = cache.k.shape[-2]
    M = hc.num_levels(Lmax, nr)
    nsh = sp_sharded_levels(Lmax, nr, d)
    scale = softmax_scale if softmax_scale is not None else 1 / math.sqrt(D)
    cache_specs = sp_cache_specs(cache, mesh, nr=nr, axis=axis)
    spec0 = cache_specs.k[0]

    def body(cache, q, t):
        with _local_region():
            s = jax.lax.axis_index(axis)
            bidx, owned = _band_geometry(t, s, nr, Lmax, d, nsh, M - 1)
            # t stays GLOBAL inside the partial kernel (the band masks
            # compare global positions), so its declared domain is the
            # full sequence, not the local slab
            num, den, m = dk.decode_attend_partial(
                cache, q, t, bidx, owned, nr=nr, softmax_scale=scale,
                t_hi=Lmax - 1, interpret=interpret)
            mg = jax.lax.pmax(m, axis)
            e = jnp.exp(m - mg)
            num = jax.lax.psum(num * e[..., None], axis)
            den = jax.lax.psum(den * e, axis)
            return (num / jnp.maximum(den, 1e-9)[..., None]).astype(q.dtype)

    fn = _shard_map(
        body, mesh,
        in_specs=(cache_specs, P(spec0, None, None), P(spec0)),
        out_specs=P(spec0, None, None))
    return fn(cache, q, t)


def sp_update_cache(cache, k_new, v_new, t, *, impl: str = "pallas",
                    mesh: Mesh, axis: str = "data"):
    """Fused ancestor update over a sequence-sharded ``H1DCache``.

    All of a token's sharded-level ancestors live on ONE shard (the
    hierarchy is a binary tree over a contiguous shard span), so the
    owning shard runs the fused in-place kernel with shard-local pair
    indices while the others write their pairs back unchanged.  The
    carried pair mean/sum at the top of the sharded chain is broadcast
    with one masked ``psum`` and the (tiny, replicated) deep levels are
    updated identically everywhere by the unmodified kernel."""
    from repro.kernels import h1d_decode_kernel as dk
    from repro.kernels.tuning import get_policy

    d = dict(mesh.shape)[axis]
    impl = get_policy().resolve_impl(impl, "decode_update")
    interpret = impl == "pallas_interpret"
    if d == 1:
        return dk.update_cache_fused(cache, k_new, v_new, t,
                                     interpret=interpret)
    if not cache.ck:
        # a coarse-less cache (M <= 1) is ambiguous for the nr recovery
        # below AND too small to shard usefully: single-launch kernel
        return dk.update_cache_fused(cache, k_new, v_new, t,
                                     interpret=interpret)
    _note_dispatch("update_cache", d)
    Lmax = cache.k.shape[-2]
    Lloc = Lmax // d
    # the update signature has no nr, but a cache with >= 1 coarse level
    # fixes it: init_cache builds M = num_levels(Lmax, nr) - 1 coarse
    # levels, so Lmax = nr << (len(ck) + 1) -- recover nr to keep the
    # sharded-level rule identical between attend and update (ONE cache
    # layout).
    nr = Lmax >> (len(cache.ck) + 1)
    cache_specs = sp_cache_specs(cache, mesh, nr=nr, axis=axis)
    nsh = sp_sharded_levels(Lmax, nr, d)
    spec0 = cache_specs.k[0]
    nlev = 1 + len(cache.ck)

    def body(cache, k_new, v_new, t):
        with _local_region():
            s = jax.lax.axis_index(axis)
            owner = sp_update_owner(t, Lloc, d)
            owned = (owner == s).astype(jnp.int32)
            t_loc = sp_update_local_t(t, s, Lloc)
            sharded = type(cache)(k=cache.k, v=cache.v,
                                  ck=cache.ck[:nsh - 1],
                                  cv=cache.cv[:nsh - 1])
            # t_hi: non-owner rows keep t_loc = t - s*Lloc up to Lmax
            # (shard 0 under a last-shard row); the contract must
            # declare the real domain, not the local slab's
            upd, carry_k, carry_v = dk.update_cache_partial(
                sharded, k_new, v_new, t_loc, owned, t_hi=Lmax,
                interpret=interpret)
            ck = list(upd.ck) + list(cache.ck[nsh - 1:])
            cv = list(upd.cv) + list(cache.cv[nsh - 1:])
            if nsh <= nlev - 1:
                # broadcast the carried ancestor row from its owner and
                # walk the replicated deep levels with the stock kernel
                carry_k = jax.lax.psum(
                    carry_k * owned[:, None].astype(carry_k.dtype), axis)
                carry_v = jax.lax.psum(
                    carry_v * owned[:, None].astype(carry_v.dtype), axis)
                deep = type(cache)(k=cache.ck[nsh - 1],
                                   v=cache.cv[nsh - 1],
                                   ck=cache.ck[nsh:], cv=cache.cv[nsh:])
                dout = dk.update_cache_fused(deep, carry_k, carry_v,
                                             t >> nsh, interpret=interpret)
                ck[nsh - 1:] = [dout.k] + list(dout.ck)
                cv[nsh - 1:] = [dout.v] + list(dout.cv)
            return type(cache)(k=upd.k, v=upd.v, ck=tuple(ck), cv=tuple(cv))

    fn = _shard_map(
        body, mesh,
        in_specs=(cache_specs, P(spec0, None), P(spec0, None), P(spec0)),
        out_specs=cache_specs)
    return fn(cache, k_new, v_new, t)
