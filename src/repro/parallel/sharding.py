"""Sharding rules: logical-to-mesh mapping for params, batches, and caches.

Mesh axes: ``("pod", "data", "model")`` (multi-pod) or
``("data", "model")`` (single pod).

* params     -- specs come from the model init (divisibility-aware TP,
                EP for experts); anything else replicated.
* train batch-- leading batch dim over ("pod", "data")  (DP).
* decode     -- cache leading dim over DP axes when the batch is large;
                for batch=1 long-context decode the *sequence* axis of
                the KV cache shards over "data" (SP) and kv-heads over
                "model" when divisible.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dp_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in dp_axes(mesh)]))


def tp_axis(mesh: Mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


def tp_size(mesh: Mesh) -> int:
    return mesh.shape.get("model", 1)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def param_shardings(mesh: Mesh, specs: Any):
    """Model init specs -> NamedSharding tree (axes absent from the mesh
    dropped)."""
    names = set(mesh.axis_names)

    def fix(spec: P) -> NamedSharding:
        clean = []
        for ax in spec:
            if ax is None:
                clean.append(None)
            elif isinstance(ax, str):
                clean.append(ax if ax in names else None)
            else:
                sub = tuple(a for a in ax if a in names)
                clean.append(sub if sub else None)
        return NamedSharding(mesh, P(*clean))

    return jax.tree.map(fix, specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_shardings(mesh: Mesh, batch_tree: Any):
    """Leading dim of every batch leaf over the DP axes."""
    bd = dp_axes(mesh)

    def one(leaf):
        nd = len(leaf.shape)
        return NamedSharding(mesh, P(bd, *([None] * (nd - 1))))

    return jax.tree.map(one, batch_tree)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def cache_shardings(mesh: Mesh, cache_tree: Any, *, batch: int,
                    kv_heads: int, long_context: bool,
                    num_layers: int = 0):
    """Decode-cache shardings (see module docstring).

    Heuristic per leaf: batch-major leaves shard dim0 over DP (and over
    "model" too when it divides); in long-context (batch==1) mode the
    longest axis shards over "data" (sequence parallelism) and dim0 over
    "model" when the kv-head count divides.
    """
    bd = dp_axes(mesh)
    dsz = dp_size(mesh)
    tsz = tp_size(mesh)

    def one(leaf):
        shape = leaf.shape
        nd = len(shape)
        if nd == 0:
            return replicated(mesh)
        # scanned models stack caches with a leading LAYER dim -- never
        # shard that; the batch dim is dim1 there
        off = 1 if (num_layers and nd >= 2 and shape[0] == num_layers) else 0
        if not long_context:
            # dim0 over the DP axes ONLY: the decode compute (q from the
            # batch-sharded tokens) lives on DP, and a dp x model cache
            # sharding forces a full cache all-to-all every step
            ax0 = shape[off]
            spec = [None] * nd
            if tsz > 1 and ax0 % (dsz * tsz) == 0:
                spec[off] = bd + ("model",)
                return NamedSharding(mesh, P(*spec))
            if ax0 % dsz == 0:
                spec[off] = bd
                return NamedSharding(mesh, P(*spec))
            return replicated(mesh)
        # long-context: SP over the sequence axis
        spec = [None] * nd
        if shape[off] % tsz == 0 and tsz > 1:
            spec[off] = "model"
        if nd >= off + 2:
            seq_ax = int(np.argmax(shape[off + 1:])) + off + 1
            if shape[seq_ax] % mesh.shape.get("data", 1) == 0:
                spec[seq_ax] = "data"
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, cache_tree)
