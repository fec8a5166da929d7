"""Seeded weights in one canonical layout, and the map into the layout
the system under test keeps them in.

The benchmark makes the weights; the program and the plain reference
both get them from here, the program through :func:`to_program`.  Norm
gains are ones; every projection is normal with a ``1/sqrt(fan_in)``
scale, the embedding and output head ``0.02``.  Layer weights are
stacked over the leading axis.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int, stream: int):
    """A raw JAX key from any non-negative whole number (64 bits and
    more are fine) and a stream number."""
    data = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jnp.asarray(data.astype(np.uint32))


def shapes(cfg):
    """{name: (shape, init scale or None for ones)} of the canonical
    weights of configuration ``cfg`` (a dict from a config file)."""
    n, d, V = cfg["num_layers"], cfg["d_model"], cfg["vocab_size"]
    hq = cfg["num_heads"] * cfg["head_dim"]
    hkv = cfg["num_kv_heads"] * cfg["head_dim"]
    ff = cfg["d_ff"]
    out = {
        "embed": ((V, d), 0.02),
        "final_norm": ((d,), None),
        "ln1": ((n, d), None),
        "ln2": ((n, d), None),
        "wq": ((n, d, hq), 1 / math.sqrt(d)),
        "wk": ((n, d, hkv), 1 / math.sqrt(d)),
        "wv": ((n, d, hkv), 1 / math.sqrt(d)),
        "wo": ((n, hq, d), 1 / math.sqrt(hq)),
        "wg": ((n, d, ff), 1 / math.sqrt(d)),
        "wu": ((n, d, ff), 1 / math.sqrt(d)),
        "wd": ((n, ff, d), 1 / math.sqrt(ff)),
    }
    if not cfg["tie_embeddings"]:
        out["lm_head"] = ((d, V), 0.02)
    return out


def canonical(key, cfg, dtype):
    """Every canonical weight in ``dtype``; call under ``jax.jit`` with
    ``cfg`` and ``dtype`` static.  The values do not depend on ``dtype``
    beyond its rounding."""
    out = {}
    for i, (name, (shape, scale)) in enumerate(sorted(shapes(cfg).items())):
        if scale is None:
            out[name] = jnp.ones(shape, dtype)
        else:
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = (x * scale).astype(dtype)
    return out


def to_program(w, cfg):
    """Canonical weights -> the parameter tree of ``repro.models``'
    dense decoder (layers stacked for its scan; K and V fused in one
    projection, K first)."""
    layers = {
        "ln1": {"g": w["ln1"]},
        "ln2": {"g": w["ln2"]},
        "attn": {"wq": {"w": w["wq"]},
                 "wkv": {"w": jnp.concatenate([w["wk"], w["wv"]], -1)},
                 "wo": {"w": w["wo"]}},
        "mlp": {"wg": {"w": w["wg"]}, "wu": {"w": w["wu"]},
                "wd": {"w": w["wd"]}},
    }
    p = {"embed": {"w": w["embed"]}, "final_norm": {"g": w["final_norm"]},
         "layers": layers}
    if "lm_head" in w:
        p["lm_head"] = {"w": w["lm_head"]}
    return p


def from_program(p, cfg):
    """Inverse of :func:`to_program`, for trees shaped like the
    parameters (gradients, optimizer moments)."""
    hkv = cfg["num_kv_heads"] * cfg["head_dim"]
    lay = p["layers"]
    wkv = lay["attn"]["wkv"]["w"]
    w = {"embed": p["embed"]["w"], "final_norm": p["final_norm"]["g"],
         "ln1": lay["ln1"]["g"], "ln2": lay["ln2"]["g"],
         "wq": lay["attn"]["wq"]["w"], "wk": wkv[..., :hkv],
         "wv": wkv[..., hkv:], "wo": lay["attn"]["wo"]["w"],
         "wg": lay["mlp"]["wg"]["w"], "wu": lay["mlp"]["wu"]["w"],
         "wd": lay["mlp"]["wd"]["w"]}
    if "lm_head" in p:
        w["lm_head"] = p["lm_head"]["w"]
    return w


def leaf_norms(w):
    """{leaf: L2 norm} with stacked layer weights split per layer
    (``wq.3`` is layer 3's query projection); call under ``jax.jit``."""
    out = {}
    for name, x in w.items():
        x = x.astype(jnp.float32)
        if name in ("embed", "final_norm", "lm_head"):
            out[name] = jnp.sqrt(jnp.sum(x * x))
        else:
            for i in range(x.shape[0]):
                out[f"{name}.{i}"] = jnp.sqrt(jnp.sum(x[i] * x[i]))
    return out
