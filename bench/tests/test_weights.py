"""The canonical weights of each configuration at its small shapes, from
a fixed seed, pinned by hash: a reordered, renamed or redrawn leaf
changes the bits that the program and the reference both start from."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.tests import tiny

SEED = 2**40 + 7
PINNED = {
    "h1d-lm-144m":
        "c02c1b36b9f42cf0a1752a04b58bbaebef5b10d3e7c57d3bb167ed853e2045ea",
    "yi-6b":
        "259521e9856a89dcf0c5e75924b42521b6c106134987e0b638df24f332a22304",
}


def digest(w) -> str:
    h = hashlib.sha256()
    for name in sorted(w):
        x = np.asarray(w[name])
        h.update(f"{name}:{x.shape}:{x.dtype};".encode())
        h.update(x.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PINNED))
def test_canonical_weights_hash(name):
    cfg = tiny.config(name)
    w = jax.jit(lambda k: weights.canonical(k, cfg, jnp.dtype(cfg["dtype"])))(
        weights.key_from_seed(SEED, 1))
    assert digest(w) == PINNED[name]


def test_config_without_layout_raises():
    cfg = {k: v for k, v in tiny.config("yi-6b").items() if k != "layout"}
    with pytest.raises(KeyError, match="layout"):
        weights.canonical(weights.key_from_seed(SEED, 1), cfg, jnp.float32)
