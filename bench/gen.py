"""The one traffic generator.  It reads a mix's parameters from
``bench/traffic/<mix>.json`` and draws everything from the seed.

Two kinds of mix:

* ``train`` -- batches of packed documents.  Document lengths are
  lognormal (median ``doc_median``, shape ``doc_sigma``, clipped to
  ``doc_min..doc_max``); documents are joined by ``eos_id`` and cut into
  rows of ``seq_len``.  Token ids are Zipf-ranked over the vocabulary
  (exponent ``zipf_alpha``), with a planted bigram share ``bigram``
  (the next id a fixed hash of the previous one), as ``repro.data.ZipfLM``
  draws them.  Every batch has the same shape, so every seed does the
  same work.
* ``serve`` -- a closed batch of requests, each with a prompt of
  ``prompt_len`` random ids and a number of tokens to generate drawn
  uniformly from ``out_min..out_max``.  Every request is admitted
  before the window.  The sizes come from the mix alone, so every seed
  does the same work.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(mix: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{mix}.json")) as f:
        return json.load(f)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def zipf_ids(r, shape, vocab, alpha, bigram, avoid):
    ranks = r.zipf(alpha, size=shape).astype(np.int64)
    base = np.minimum(ranks, vocab - 1)
    mix = r.random(shape) < bigram
    shifted = (np.roll(base, 1, axis=-1) * 7919 + 13) % vocab
    ids = np.where(mix, shifted, base)
    return np.where(ids == avoid, (ids + 1) % vocab, ids).astype(np.int32)


def train_batches(mix: dict, vocab: int, seed: int, n: int) -> np.ndarray:
    """(n, batch, seq_len) int32 of packed documents."""
    r = rng(seed, 10)
    B, S = mix["batch"], mix["seq_len"]
    total = n * B * S
    ids = zipf_ids(r, (total,), vocab, mix["zipf_alpha"], mix["bigram"],
                   mix["eos_id"])
    # document boundaries: lognormal lengths, each document ends in EOS
    lens = []
    used = 0
    while used < total:
        k = int(np.exp(r.normal(np.log(mix["doc_median"]), mix["doc_sigma"])))
        k = min(max(k, mix["doc_min"]), mix["doc_max"])
        lens.append(k)
        used += k
    ends = np.cumsum(lens) - 1
    ids[ends[ends < total]] = mix["eos_id"]
    return ids.reshape(n, B, S)


def serve_requests(mix: dict, vocab: int, seed: int) -> list:
    """[{"uid", "prompt", "max_new"}] of a closed batch."""
    n = mix["requests"]
    out = rng(0, 20).integers(mix["out_min"], mix["out_max"] + 1, n)
    ids = rng(seed, 22)
    return [{"uid": i, "prompt": ids.integers(0, vocab, mix["prompt_len"],
                                               dtype=np.int32),
             "max_new": int(out[i])}
            for i in range(n)]
