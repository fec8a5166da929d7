"""Seeded weights in a configuration's canonical layout.

The benchmark makes the weights; the program and the plain reference
both get them from here, the program through its layout's
``to_program``.  The layout (``bench/layouts/<layout>.py``, named by
the configuration's ``layout`` key) gives each leaf's shape and init
scale; layer weights are stacked over the leading axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness


def key_from_seed(seed: int, stream: int):
    """A raw JAX key from any non-negative whole number (64 bits and
    more are fine) and a stream number."""
    data = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jnp.asarray(data.astype(np.uint32))


def canonical(key, cfg, dtype):
    """Every canonical weight in ``dtype``; call under ``jax.jit`` with
    ``cfg`` and ``dtype`` static.  Leaf ``i`` in sorted order draws from
    ``fold_in(key, i)``, so the values do not depend on ``dtype`` beyond
    its rounding.  Norm gains (scale None) are ones."""
    out = {}
    shapes = harness.layout(cfg).shapes(cfg)
    for i, (name, (shape, scale)) in enumerate(sorted(shapes.items())):
        if scale is None:
            out[name] = jnp.ones(shape, dtype)
        else:
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = (x * scale).astype(dtype)
    return out


def leaf_norms(w, cfg):
    """{leaf: L2 norm} with stacked layer weights split per layer
    (``wq.3`` is layer 3's query projection) and the layout's unstacked
    leaves whole; call under ``jax.jit``."""
    whole = harness.layout(cfg).UNSTACKED
    out = {}
    for name, x in w.items():
        x = x.astype(jnp.float32)
        if name in whole:
            out[name] = jnp.sqrt(jnp.sum(x * x))
        else:
            for i in range(x.shape[0]):
                out[f"{name}.{i}"] = jnp.sqrt(jnp.sum(x[i] * x[i]))
    return out
