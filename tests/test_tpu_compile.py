"""Compile every main-path Pallas kernel for a described TPU v5e chip.

Interpret mode cannot judge Mosaic's tiling rules (the last two block
dims must be multiples of (8, 128) or equal the array's dims), its VMEM
limits or which vector ops it can lower; these tests hand the real
kernels, at published widths, to the TPU compiler that ships with
jaxlib -- no chip is needed, nothing runs.  Each asserts the fused
kernel is in the compiled program (``tpu_custom_call``).

The topology is described inside a module fixture (never at import):
only one process may load the TPU library at a time, so the worker that
runs this file is the only one that touches it.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import h1d_decode as hd
from repro.core import hierarchy as hc
from repro.kernels import ops

# published widths: head_dim 64 (llama3.2-1b, yi-6b) and 128 (h1d-lm-144m,
# qwen2.5-14b), the paper's nr=16, GQA group 4 (llama3.2-1b's 32/8)
NR = 16
G = 4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _band_grad(mode, ratio):
    def loss(q, k, v, w):
        y, dn, m = ops.band_attention(q, k, v, w, nr=NR, mode=mode,
                                      ratio=ratio, impl="pallas")
        return jnp.sum(y) + jnp.sum(dn) + jnp.sum(m)
    return jax.grad(loss, argnums=(0, 1, 2, 3))


@pytest.mark.parametrize("mode", ["l0_causal", "l0_bidir",
                                  "coarse_causal", "coarse_bidir"])
@pytest.mark.parametrize("d,dtype", [(64, jnp.bfloat16), (128, jnp.float32)])
def test_band_fwd_bwd_compiles(one_chip, mode, d, dtype):
    B, L = 2, 4096
    args = (_sds(one_chip, (B, G, L, d), dtype),
            _sds(one_chip, (B, L, d), dtype),
            _sds(one_chip, (B, L, d), dtype),
            _sds(one_chip, (B, L)))
    _compiled_text(_band_grad(mode, 1), *args)


# ratio 2 keeps whole query blocks inside a 128-row tile (wide layout);
# ratio 16 puts one query block across several tiles (deep layout)
@pytest.mark.parametrize("ratio,L", [(2, 4096), (16, 4096), (64, 32768)])
@pytest.mark.parametrize("d,dtype", [(64, jnp.bfloat16), (128, jnp.float32)])
def test_sub_fwd_bwd_compiles(one_chip, ratio, L, d, dtype):
    B, Lk = 1, L // ratio
    args = (_sds(one_chip, (B, G, L, d), dtype),
            _sds(one_chip, (B, Lk, d), dtype),
            _sds(one_chip, (B, Lk, d), dtype),
            _sds(one_chip, (B, Lk)))
    _compiled_text(_band_grad("sub", ratio), *args)


def _decode_step(cache_kind):
    """One decode tick on the kernel path: append a token, then attend."""
    def step(cache, q, k_new, v_new, t, *tables):
        if cache_kind == "dense":
            cache = hd.update_cache(cache, k_new, v_new, t, impl="pallas")
            z = hd.decode_attend(cache, q, t, nr=NR, impl="pallas")
        else:
            attend, update = tables
            cache = hd.update_cache_paged(cache, k_new, v_new, t, update,
                                          impl="pallas")
            z = hd.decode_attend_paged(cache, q, t, attend, nr=NR,
                                       impl="pallas")
        return cache, z
    return step


def _decode_args(one_chip, cache_kind, d, dtype):
    R, Lmax = 32, 4096                 # 4 slots x 8 kv heads
    levels = hc.num_levels(Lmax, NR) - 1
    S = lambda shape, dt=dtype: _sds(one_chip, shape, dt)  # noqa: E731
    if cache_kind == "dense":
        cache = jax.eval_shape(
            lambda: hd.init_cache(R, Lmax, d, d, nr=NR, dtype=dtype))
    elif cache_kind == "paged":
        cache = jax.eval_shape(lambda: hd.init_paged_pool(
            [R * (Lmax >> l) // NR for l in range(levels + 1)], NR, d, d,
            dtype=dtype))
    else:
        cache = jax.eval_shape(lambda: hd.init_quant_paged_pool(
            [R * (Lmax >> l) // NR for l in range(levels + 1)], NR, d, d,
            dtype=dtype))
    cache = jax.tree.map(lambda a: S(a.shape, a.dtype), cache)
    args = [cache, S((R, G, d)), S((R, d)), S((R, d)),
            S((R,), jnp.int32)]
    if cache_kind != "dense":
        args += [S((R, 2 + levels), jnp.int32), S((R, 1 + levels), jnp.int32)]
    return args


@pytest.mark.parametrize("cache_kind", ["dense", "paged", "int8_paged"])
@pytest.mark.parametrize("d,dtype", [(64, jnp.bfloat16), (128, jnp.float32)])
def test_decode_attend_update_compiles(one_chip, cache_kind, d, dtype):
    _compiled_text(_decode_step(cache_kind),
                   *_decode_args(one_chip, cache_kind, d, dtype))


def _assert_cache_in_place(text, leaves, ndim):
    """No copy or transpose of a level-sized array (a cache leaf of
    ``ndim`` dims) in the compiled ``text``, and every cache leaf, the
    program's first parameters, aliased to the same output."""
    levels = [a for a in leaves if a.ndim == ndim]
    big = {tuple(a.shape) for a in levels}
    big |= {(*a.shape[:-2], a.shape[-2] // 2, 2, a.shape[-1])
            for a in levels}
    moved = [m.group(0)[:120] for m in re.finditer(
        r"= \(?\w+\[([\d,]*)\]\S* (?:copy|copy-start|transpose)\(.*",
        text)
        if tuple(int(x) for x in m.group(1).split(",") if x) in big]
    assert not moved, moved
    header = text.split("\n", 1)[0]
    # "{output}: (parameter, {}, may-alias)"; both number the leaves in
    # order, cache first
    aliased = {(int(o), int(p)) for o, p in
               re.findall(r"\{(\d+)\}: \((\d+), \{", header)}
    want = {(i, i) for i in range(len(leaves))}
    assert want <= aliased, sorted(want - aliased)


# head_dim 128 only: the TPU lays out an array whose minor dim is 64 with
# that dim off the lanes, so every kernel over a head_dim-64 cache pays
# a relayout of it (PERF.md, open questions)
@pytest.mark.parametrize("cache_kind", ["dense", "paged", "int8_paged"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_step_updates_cache_in_place(one_chip, cache_kind, dtype):
    """With the cache donated, a decode tick moves O(rows) bytes of the
    K/V levels: no copy or transpose of any level-sized array, and every
    cache leaf aliased input -> output.  (The int8 pool's (NP, nr) scale
    arrays, 1/32 of its bytes at head_dim 128, are not held to this: the
    TPU keeps them lane-transposed, and the attend's row gather
    relayouts them -- PERF.md, open questions.)"""
    args = _decode_args(one_chip, cache_kind, 128, dtype)
    text = jax.jit(_decode_step(cache_kind), donate_argnums=0).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text
    _assert_cache_in_place(text, jax.tree.leaves(args[0]), ndim=3)


def test_page_copy_program_writes_in_place(one_chip):
    """A flush's page copies for ``yi6b-decode-8k``'s pool (8 stacked
    layers, 10 levels, bf16, head_dim 128, 8 slots, 4 kv heads) compile
    to one program that moves O(pages) bytes: no copy or transpose of
    any level-sized array, and every cache leaf aliased input -> output."""
    from repro.serve import paged_cache as pc
    Hkv, layers = 4, 8
    pool = pc.PagePool(slots=8, max_len=16384, nr=NR, pool_pages=8 * 1024)
    assert pool.M == 10
    one = jax.eval_shape(lambda: hd.init_paged_pool(
        [n * Hkv for n in pool.num_pages], NR, 128, 128, jnp.bfloat16))
    caches = jax.tree.map(
        lambda a: _sds(one_chip, (layers,) + a.shape, a.dtype), one)
    table = _sds(one_chip, pc._copy_table_shape(caches, Hkv, True) + (2,),
                 jnp.int32)
    text = pc._copy_program.lower(caches, table, Hkv,
                                  True).compile().as_text()
    _assert_cache_in_place(text, jax.tree.leaves(caches), ndim=4)
