"""The work an operation requires, from its shapes alone: the numerator
of every roofline share and utilisation the benchmark reports.

Nothing here depends on how the system implements the operation, so a
change of tiling or kernel leaves these numbers as they are.

* Matrix products: 2 FLOPs per multiply-add.  A model's token costs
  ``2 * matmul_params`` forward (the embedding gather is no product; a
  tied output head is).  Training costs three times the forward.
* The causal fine-q H1D operator: each attended (query, key) pair costs
  ``2 D`` for the score and ``2 D`` for the value product; a coarse key
  counts as one pair.  The backward pass needs twice the forward's
  products (dQ, dK, dV and dP); recomputation is not required work.
  The coarsening sums are left out (under 1% of the products).
* Least bytes: every input read once and every output written once:
  q, k, v and the output, plus one f32 log-sum-exp per query row and
  head, which the backward pass reads back.
* A decode tick reads every weight once, the embedding rows of its
  tokens, and per session, layer and KV head the cache rows its query
  attends (a coarse row is one row), and reads and writes one row per
  hierarchy level for the cache update.
"""
from __future__ import annotations

import numpy as np

BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def matmul_params(cfg: dict) -> int:
    d, ff = cfg["d_model"], cfg["d_ff"]
    hq = cfg["num_heads"] * cfg["head_dim"]
    hkv = cfg["num_kv_heads"] * cfg["head_dim"]
    per_layer = d * hq + 2 * d * hkv + hq * d + 3 * d * ff
    return cfg["num_layers"] * per_layer + d * cfg["vocab_size"]


def tick_weight_bytes(cfg: dict, tokens: int) -> int:
    """Weights a decode tick of ``tokens`` tokens reads: every product's
    weights and norm gains once, and one embedding row per token."""
    d = cfg["d_model"]
    n = matmul_params(cfg) + (2 * cfg["num_layers"] + 1 + tokens) * d
    return n * BYTES[cfg["dtype"]]


def h1d_pairs_at(i, nr: int):
    """Attended pairs of query position(s) ``i`` (causal fine-q)."""
    i = np.asarray(i, np.int64)
    n = i % nr + 1 + np.where(i >= nr, nr, 0)
    span = 2 * nr
    while True:
        inside = i >= span
        if not inside.any():
            return n
        second = (i % span) >= span // 2
        n = n + np.where(inside, np.where(second, nr, nr // 2), 0)
        span *= 2


def h1d_pairs(L: int, nr: int) -> int:
    """Attended pairs of a whole causal sequence of length ``L``."""
    return int(h1d_pairs_at(np.arange(L), nr).sum())


def h1d_forward(cfg: dict, B: int, L: int, dtype=None) -> tuple:
    """(FLOPs, least bytes) of one layer's H1D forward over B rows."""
    D, hq, hkv = cfg["head_dim"], cfg["num_heads"], cfg["num_kv_heads"]
    b = BYTES[dtype or cfg["dtype"]]
    flops = 4 * D * hq * B * h1d_pairs(L, cfg["nr"])
    bytes_ = B * L * (D * (2 * hq + 2 * hkv) * b + 4 * hq)
    return flops, bytes_


def h1d_backward(cfg: dict, B: int, L: int, dtype=None) -> tuple:
    """(FLOPs, least bytes) of one layer's H1D backward over B rows:
    reads q, k, v, out, d_out and the log-sum-exp, writes dq, dk, dv."""
    D, hq, hkv = cfg["head_dim"], cfg["num_heads"], cfg["num_kv_heads"]
    b = BYTES[dtype or cfg["dtype"]]
    flops = 8 * D * hq * B * h1d_pairs(L, cfg["nr"])
    bytes_ = B * L * (D * (3 * hq + 2 * hkv + hq + 2 * hkv) * b + 4 * hq)
    return flops, bytes_


def train_step(cfg: dict, B: int, L: int) -> dict:
    """FLOPs of one training step over B rows of L tokens, and the H1D
    operator's share of the work (FLOPs, bytes) across all layers."""
    n = cfg["num_layers"]
    ff, fb = h1d_forward(cfg, B, L)
    bf, bb = h1d_backward(cfg, B, L)
    return {"flops": 6 * matmul_params(cfg) * B * L + n * (ff + bf),
            "h1d_flops": n * (ff + bf), "h1d_bytes": n * (fb + bb)}


def prefill(cfg: dict, lengths) -> dict:
    """FLOPs of prefilling prompts of the given true lengths, and the
    H1D operator's (FLOPs, bytes) across all layers."""
    n = cfg["num_layers"]
    fl = by = 0
    for L in lengths:
        f, b = h1d_forward(cfg, 1, int(L))
        fl, by = fl + f, by + b
    return {"flops": 2 * matmul_params(cfg) * int(np.sum(lengths))
            + n * fl, "h1d_flops": n * fl, "h1d_bytes": n * by}


def decode_tick(cfg: dict, positions) -> dict:
    """FLOPs and least bytes of one decode tick in which each session
    writes position ``positions[s]`` and attends everything before, and
    the H1D decode operator's (FLOPs, bytes) within it."""
    pos = np.asarray(positions, np.int64)
    D, hq, hkv = cfg["head_dim"], cfg["num_heads"], cfg["num_kv_heads"]
    n, b = cfg["num_layers"], BYTES[cfg["dtype"]]
    pairs = int(h1d_pairs_at(pos, cfg["nr"]).sum())
    levels = sum(int(p).bit_length() for p in pos // cfg["nr"]) + len(pos)
    attn_flops = n * 4 * D * hq * pairs
    attn_bytes = n * hkv * D * 2 * b * (pairs + 2 * levels) \
        + n * len(pos) * 2 * hq * D * b
    flops = 2 * matmul_params(cfg) * len(pos) + attn_flops
    bytes_ = tick_weight_bytes(cfg, len(pos)) + attn_bytes
    return {"flops": flops, "bytes": bytes_, "h1d_flops": attn_flops,
            "h1d_bytes": attn_bytes}


def roofline_seconds(flops: float, bytes_: float, peak: dict) -> tuple:
    """(least seconds, "flops" or "bytes": which bound holds)."""
    tf = flops / peak["bf16_flops_per_s"]
    tb = bytes_ / peak["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
