"""The dense decoder's canonical weights, their map into the parameter
tree of ``repro.models``, and the work counts that depend on them.
Norm gains are ones; every projection is normal with a ``1/sqrt(fan_in)``
scale, the embedding and output head ``0.02``."""
from __future__ import annotations

import math

import jax.numpy as jnp

# Leaves not stacked over layers, which ``weights.leaf_norms`` keeps whole
UNSTACKED = ("embed", "final_norm", "lm_head")


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


def matmul_params(cfg: dict) -> int:
    """Weights of the matrix products one token computes with: every
    layer's projections and the output head (a tied head included; the
    embedding gather is no product)."""
    d, ff = cfg["d_model"], cfg["d_ff"]
    hq = cfg["num_heads"] * cfg["head_dim"]
    hkv = cfg["num_kv_heads"] * cfg["head_dim"]
    per_layer = d * hq + 2 * d * hkv + hq * d + 3 * d * ff
    return cfg["num_layers"] * per_layer + d * cfg["vocab_size"]


def tick_weights(cfg: dict, tokens: int) -> int:
    """Weights a decode tick of ``tokens`` tokens reads: every product's
    weights and norm gains once, and one embedding row per token."""
    d = cfg["d_model"]
    return matmul_params(cfg) + (2 * cfg["num_layers"] + 1 + tokens) * d
