"""Analytic HBM-traffic and FLOP model for traced LaunchContracts.

This turns the EXPERIMENTS.md hand accounting (P25 fused-decode DMA
ledger, P27 fixed-HBM concurrency) into executable code: given a
:class:`~repro.analysis.contracts.LaunchContract`, compute how many
bytes each operand moves between HBM and VMEM over the whole grid, and
an estimate of the arithmetic the kernel performs.

HBM model.  Pallas fetches one block per operand per grid step, but
ELIDES the fetch when the block index is unchanged from the previous
step (the revisit-contiguity rule the static checker enforces makes
this the only legal revisit shape).  So per operand:

    bytes = n_fetches * prod(block) * itemsize
    n_fetches = 1 + (# of consecutive block-index changes over the
                     row-major grid walk)

evaluated with the same index-map machinery as the checker.  Outputs
are written with the same elision rule.  Scalar-prefetch tables live
in SMEM and are excluded (they are KBs against MBs, same stance as the
VMEM estimator).  Scalar-dependent index maps are evaluated under a
deterministic "spread" sample -- distinct in-domain values -- so
table-driven operands (page gathers) count one fetch per distinct
entry rather than collapsing onto a corner value.

FLOP model.  Attention families (``*_fwd``, ``*_bwd``,
``decode_attend*``) are scored with the standard form

    2 * Q * K * (d + dv) + C_softmax * Q * K        per grid step

where Q/K are the block row counts of the ``q`` and ``k_*`` operands
and ``C_softmax = 8`` covers exp/max/sum/scale; backward passes cost
~2.5x forward.  Everything else (``decode_update*``, packers) is
scored as elementwise traffic: ``4`` ops per output element.  These
are *analytic estimates* for roofline ratios and regression tracking,
not hardware counters.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.analysis.contracts import LaunchContract, Operand

from . import metrics as _m

# beyond this many grid steps, skip index-map evaluation and use the
# conservative one-fetch-per-step closed form
_MAX_EVAL_STEPS = 1 << 20

_SOFTMAX_OPS_PER_SCORE = 8
_ELEMENTWISE_OPS = 4
_BWD_FACTOR = 2.5


def _itemsize(dtype: str) -> int:
    try:
        return np.dtype(dtype).itemsize
    except TypeError:
        # bfloat16 & friends when ml_dtypes is not registered with numpy
        import jax.numpy as jnp
        return jnp.dtype(dtype).itemsize


def _grid_arrays(grid: Tuple[int, ...]) -> List[np.ndarray]:
    axes = [np.arange(g, dtype=np.int64) for g in grid]
    if not axes:
        return []
    return [m.ravel() for m in np.meshgrid(*axes, indexing="ij")]


def _spread_scalars(contract: LaunchContract) -> Tuple[np.ndarray, ...]:
    """Deterministic in-domain scalar tables with distinct consecutive
    values: ``lo + arange(size) % span`` reshaped to the table shape."""
    tabs = []
    for s in contract.scalars:
        lo = np.broadcast_to(np.asarray(s.lo, dtype=np.int64), s.shape)
        hi = np.broadcast_to(np.asarray(s.hi, dtype=np.int64), s.shape)
        span = np.maximum(hi - lo + 1, 1)
        n = int(np.prod(s.shape)) if s.shape else 1
        walk = np.arange(n, dtype=np.int64).reshape(s.shape)
        tabs.append(lo + walk % span)
    return tuple(tabs)


def _n_fetches(op: Operand, grid: Tuple[int, ...],
               gargs: List[np.ndarray],
               stabs: Tuple[np.ndarray, ...]) -> int:
    """Number of HBM block fetches for one operand over the grid walk
    (consecutive identical block indices fetch once)."""
    n = int(np.prod(grid)) if grid else 1
    if not grid:
        return 1
    idx = op.index_map(*gargs, *stabs)
    if not isinstance(idx, tuple):
        idx = (idx,)
    cols = [np.broadcast_to(np.asarray(c, dtype=np.int64), (n,))
            for c in idx]
    bidx = np.stack(cols, axis=-1)
    changed = (bidx[1:] != bidx[:-1]).any(axis=1)
    return 1 + int(changed.sum())


def _block_bytes(op: Operand) -> int:
    return int(np.prod(op.block)) * _itemsize(op.dtype)


def contract_hbm_bytes(contract: LaunchContract) -> Dict[str, Any]:
    """Analytic HBM traffic for one launch.

    Returns ``{"read_bytes", "write_bytes", "by_operand": {name:
    {"fetches", "block_bytes", "bytes", "dir"}}}``.  Aliased in/out
    pairs are counted on both sides (the update kernels genuinely read
    then write the aliased page).
    """
    grid = contract.grid
    n_steps = int(np.prod(grid)) if grid else 1
    use_eval = n_steps <= _MAX_EVAL_STEPS
    gargs = _grid_arrays(grid) if use_eval else []
    stabs = _spread_scalars(contract) if use_eval else ()

    by_op: Dict[str, Any] = {}
    totals = {"in": 0, "out": 0}
    # the hook fires while the enclosing jit/eval_shape trace is still
    # active; force the index-map jnp ops eager so concrete numpy grid
    # walks stay concrete instead of being staged into the trace
    with jax.ensure_compile_time_eval():
        for direction, ops in (("in", contract.inputs),
                               ("out", contract.outputs)):
            for op in ops:
                if use_eval:
                    fetches = _n_fetches(op, grid, gargs, stabs)
                else:
                    fetches = n_steps
                bb = _block_bytes(op)
                by_op[op.name] = {"fetches": fetches, "block_bytes": bb,
                                  "bytes": fetches * bb, "dir": direction}
                totals[direction] += fetches * bb
    return {"read_bytes": totals["in"], "write_bytes": totals["out"],
            "by_operand": by_op}


def _rows(op: Operand, d: int) -> int:
    n = int(np.prod(op.block))
    return n // d if d > 0 else n


def contract_flops(contract: LaunchContract) -> int:
    """Analytic FLOPs for one launch (whole grid)."""
    n_steps = int(np.prod(contract.grid)) if contract.grid else 1
    fam = contract.family
    q = next((o for o in contract.inputs if o.name == "q"), None)
    ks = [o for o in contract.inputs if o.name.startswith("k")]
    if q is not None and ks:
        d = int(q.block[-1])
        dv = next((int(o.block[-1]) for o in contract.inputs
                   if o.name.startswith("v")), d)
        q_rows = _rows(q, d)
        k_rows = sum(_rows(o, d) for o in ks)
        per_step = (2 * q_rows * k_rows * (d + dv)
                    + _SOFTMAX_OPS_PER_SCORE * q_rows * k_rows)
        if "bwd" in fam:
            per_step = int(per_step * _BWD_FACTOR)
        return per_step * n_steps
    out_elems = sum(int(np.prod(o.block)) for o in contract.outputs)
    return _ELEMENTWISE_OPS * out_elems * n_steps


def on_launch(contract: LaunchContract) -> None:
    """Launch hook (registered by ``obs.enable()``): account one traced
    ``pallas_call`` into the ``kernel.*`` counters of its family."""
    traffic = contract_hbm_bytes(contract)
    fam = contract.family
    _m.counter("kernel.launches", family=fam).inc()
    _m.counter("kernel.hbm_read_bytes", family=fam).inc(
        traffic["read_bytes"])
    _m.counter("kernel.hbm_write_bytes", family=fam).inc(
        traffic["write_bytes"])
    _m.counter("kernel.flops", family=fam).inc(contract_flops(contract))
