"""GPipe-style pipeline parallelism via shard_map + collective_permute.

For depth beyond what DP x TP covers (1000+ nodes), the ``pod`` axis can
be repurposed as a ``stage`` axis: layers are split into S contiguous
stages; M microbatches flow through; each tick every stage applies its
layers and ppermutes its activation to the next stage.  Bubble fraction
is (S-1)/(M+S-1) as usual.

``pipeline_apply`` is deliberately model-agnostic: it takes stacked
per-stage params (leading dim S, sharded over the stage axis) and a
per-stage apply ``fn(stage_params, x) -> x``.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(fn: Callable, stage_params: Any, x: jnp.ndarray, *,
                   mesh: Mesh, axis: str = "stage") -> jnp.ndarray:
    """x: (M, B_m, ...) microbatched input (M >= num_stages is sensible).
    stage_params leaves have leading dim = num_stages.
    Returns (M, B_m, ...) outputs of the final stage, in order."""
    S = mesh.shape[axis]
    M = x.shape[0]

    pspec = jax.tree.map(lambda _: P(axis), stage_params)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(pspec, P(axis)), out_specs=P(axis),
             check_vma=False)
    def run(params, xs):
        # params leaves: (1, ...) local stage slice; xs: (M/S, Bm, ...)
        # We want every stage to see ALL microbatches in sequence, so we
        # first all-gather the microbatch stream along the stage axis.
        params = jax.tree.map(lambda p: p[0], params)
        xs = jax.lax.all_gather(xs, axis, axis=0, tiled=True)  # (M, Bm, ...)
        idx = jax.lax.axis_index(axis)

        state = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        nticks = M + S - 1

        def tick(t, carry):
            state, outs = carry
            # stage 0 ingests microbatch t (if any)
            take = xs[jnp.minimum(t, M - 1)]
            state = jnp.where(idx == 0,
                              jnp.where(t < M, take, state), state)
            state = fn(params, state)
            # last stage emits microbatch t-(S-1)
            emit = t - (S - 1)
            outs = jax.lax.cond(
                emit >= 0,
                lambda o: o.at[jnp.maximum(emit, 0)].set(
                    jnp.where(idx == S - 1, state, o[jnp.maximum(emit, 0)])),
                lambda o: o, outs)
            # shift all states one stage forward
            perm = [(i, (i + 1) % S) for i in range(S)]
            state = jax.lax.ppermute(state, axis, perm)
            return state, outs

        state, outs = jax.lax.fori_loop(0, nticks, tick, (state, outs))
        # every device now holds the outputs of the LAST stage only on
        # device S-1; psum the (zero-elsewhere) buffers to broadcast.
        outs = jnp.where(idx == S - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, axis)
        # shard_map splits the output along the stage axis again
        return outs.reshape((S, M // S) + outs.shape[1:])[idx]

    assert M % S == 0, (M, S)
    return run(stage_params, x)
