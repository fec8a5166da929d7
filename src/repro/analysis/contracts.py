"""Declarative launch contracts for every Pallas kernel launch.

Every ``pl.pallas_call`` in the kernel modules goes through one shared
:func:`launch` builder.  Besides dispatching the actual call (plain
``grid=`` launch, or a ``PrefetchScalarGridSpec`` when scalar-prefetch
tables are present), ``launch`` records a :class:`LaunchContract` -- a
frozen, declarative description of exactly what was launched:

* the grid and every operand's array shape/dtype, block shape and
  BlockSpec index map (the live lambdas, not copies);
* the scalar-prefetch tables with their *bound domains* (the legal
  value range of every table entry, declared from the call site's
  geometry -- e.g. a page index is bounded by the pool's page count);
* ``input_output_aliases`` normalized to *operand* indices, so the
  hand-maintained "+3"/"+4" call-arg offsets live in exactly one place
  (here) instead of at every aliased call site.

The static checker (:mod:`repro.analysis.checker`) consumes these
contracts: because they are recorded by the same code path that issues
the launch, the checker verifies what the runtime actually runs -- the
contract cannot drift from the call (``tests/test_analysis.py`` pins
this with a ``pallas_call``-shim agreement test).

Capture model: contracts are recorded at *trace* time.  ``jax.eval_shape``
of a kernel wrapper inside :func:`capture` yields the wrapper's
contracts without compiling or executing anything -- that is how both
the checker CLI and the VMEM estimator obtain contracts for arbitrary
shapes.  A bounded deque of recent contracts (:func:`recent`) is also
kept for interactive inspection.

This module imports only jax/pallas (never the kernel modules), so the
kernels can import it without cycles.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@dataclasses.dataclass(frozen=True)
class Operand:
    """One (non-scalar) kernel operand: array geometry + its BlockSpec."""
    name: str
    shape: Tuple[int, ...]
    dtype: str
    block: Tuple[int, ...]
    index_map: Callable[..., Tuple[Any, ...]]


@dataclasses.dataclass(frozen=True)
class ScalarSpec:
    """One scalar-prefetch table and the declared domain of its values.

    ``lo``/``hi`` are inclusive bounds, either ints or integer arrays
    broadcastable to ``shape`` (e.g. a per-column page-count bound for a
    ``(R, nbands)`` page table)."""
    name: str
    shape: Tuple[int, ...]
    dtype: str
    lo: Any
    hi: Any


@dataclasses.dataclass(frozen=True)
class LaunchContract:
    """Everything the static checker needs about one ``pallas_call``.

    ``aliases`` maps input *operand* index (position in ``inputs``, not
    counting scalar-prefetch args) to output index.  Grid iteration is
    row-major with the LAST axis fastest (the Pallas TPU order) -- the
    checker's revisit-contiguity rule depends on it.
    """
    family: str
    grid: Tuple[int, ...]
    scalars: Tuple[ScalarSpec, ...]
    inputs: Tuple[Operand, ...]
    outputs: Tuple[Operand, ...]
    aliases: Tuple[Tuple[int, int], ...]
    meta: Dict[str, Any]

    @property
    def alias_map(self) -> Dict[int, int]:
        return dict(self.aliases)

    def describe(self) -> str:
        ins = ", ".join(f"{o.name}{list(o.block)}" for o in self.inputs)
        outs = ", ".join(f"{o.name}{list(o.block)}" for o in self.outputs)
        return (f"{self.family} grid={self.grid} "
                f"scalars={[s.name for s in self.scalars]} "
                f"in=[{ins}] out=[{outs}] aliases={dict(self.aliases)}")


# -- recording --------------------------------------------------------------

_RECENT: collections.deque = collections.deque(maxlen=256)
_CAPTURES: List[List[LaunchContract]] = []
_LAUNCH_HOOKS: List[Callable[[LaunchContract], None]] = []


def _record(contract: LaunchContract) -> None:
    _RECENT.append(contract)
    for buf in _CAPTURES:
        buf.append(contract)
    for hook in _LAUNCH_HOOKS:
        hook(contract)


def add_launch_hook(hook: Callable[[LaunchContract], None]) -> None:
    """Register a callback fired on every recorded contract (i.e. once
    per *traced* ``pallas_call``, not per device execution).  This is
    how the telemetry layer (:mod:`repro.obs`) observes launches
    without this module importing it; the disabled path costs an
    iteration over an empty list."""
    if hook not in _LAUNCH_HOOKS:
        _LAUNCH_HOOKS.append(hook)


def remove_launch_hook(hook: Callable[[LaunchContract], None]) -> None:
    if hook in _LAUNCH_HOOKS:
        _LAUNCH_HOOKS.remove(hook)


@contextlib.contextmanager
def capture():
    """Collect every contract recorded while the context is active.

    ``jax.eval_shape`` of a kernel wrapper inside this context yields
    the wrapper's contracts without running (or compiling) anything."""
    buf: List[LaunchContract] = []
    _CAPTURES.append(buf)
    try:
        yield buf
    finally:
        _CAPTURES.remove(buf)


def recent(family: Optional[str] = None) -> List[LaunchContract]:
    """Recently recorded contracts (newest last), optionally filtered."""
    return [c for c in _RECENT if family is None or c.family == family]


# -- the shared launch builder ----------------------------------------------

def _as_tuple(x) -> tuple:
    return tuple(x) if isinstance(x, (list, tuple)) else (x,)


def _operands(names, arrays, specs, kind: str) -> Tuple[Operand, ...]:
    if len(arrays) != len(specs):
        raise ValueError(
            f"launch: {len(arrays)} {kind} operands vs {len(specs)} specs")
    if names is None:
        names = tuple(f"{kind}{i}" for i in range(len(arrays)))
    if len(names) != len(arrays):
        raise ValueError(
            f"launch: {len(names)} {kind} names vs {len(arrays)} operands")
    return tuple(
        Operand(name=str(nm), shape=tuple(a.shape), dtype=str(a.dtype),
                block=tuple(sp.block_shape), index_map=sp.index_map)
        for nm, a, sp in zip(names, arrays, specs))


def _kernel_name(kernel) -> str:
    """The kernel body's function name, through ``functools.partial``."""
    while isinstance(kernel, functools.partial):
        kernel = kernel.func
    return getattr(kernel, "__name__", type(kernel).__name__)


def launch(kernel, *, family: str, grid: Tuple[int, ...],
           in_specs: Sequence[pl.BlockSpec], out_specs, out_shape,
           operands: Sequence[Any], scalars: Sequence[Any] = (),
           scalar_bounds: Sequence[Tuple[Any, Any]] = (),
           aliases: Optional[Dict[int, int]] = None,
           interpret: bool = False,
           in_names: Optional[Sequence[str]] = None,
           out_names: Optional[Sequence[str]] = None,
           scalar_names: Optional[Sequence[str]] = None,
           meta: Optional[Dict[str, Any]] = None):
    """Issue one ``pallas_call`` and record its :class:`LaunchContract`.

    ``operands`` are the non-scalar inputs (aligned with ``in_specs``);
    ``scalars`` are scalar-prefetch tables, each with an inclusive
    ``(lo, hi)`` domain in ``scalar_bounds``.  ``aliases`` maps operand
    index -> output index; the translation to Pallas call-arg indices
    (which count the scalar args first) happens here, once.

    The call is named after ``family`` and carries ``{"family",
    "kernel"}`` as its kernel metadata, which the compiled program keeps
    in the custom call's ``kernel_metadata`` attribute: a profiler trace
    names each kernel event by it.
    """
    out_specs_t = _as_tuple(out_specs)
    out_shape_t = _as_tuple(out_shape)
    if len(out_specs_t) != len(out_shape_t):
        raise ValueError(
            f"launch: {len(out_specs_t)} out_specs vs "
            f"{len(out_shape_t)} out_shapes")
    if len(scalar_bounds) != len(scalars):
        raise ValueError(
            f"launch: {len(scalars)} scalars need {len(scalars)} bounds, "
            f"got {len(scalar_bounds)}")
    if scalar_names is None:
        scalar_names = tuple(f"s{i}" for i in range(len(scalars)))

    alias_items = tuple(sorted((aliases or {}).items()))
    for i, o in alias_items:
        if not (0 <= i < len(operands) and 0 <= o < len(out_shape_t)):
            raise ValueError(f"launch: alias {i}->{o} out of range "
                             f"({len(operands)} operands, "
                             f"{len(out_shape_t)} outputs)")

    contract = LaunchContract(
        family=family, grid=tuple(int(g) for g in grid),
        scalars=tuple(
            ScalarSpec(name=str(nm), shape=tuple(s.shape),
                       dtype=str(s.dtype), lo=lo, hi=hi)
            for nm, s, (lo, hi) in zip(scalar_names, scalars,
                                       scalar_bounds)),
        inputs=_operands(in_names, operands, in_specs, "in"),
        outputs=_operands(out_names, out_shape_t, out_specs_t, "out"),
        aliases=alias_items,
        meta=dict(meta or {}))
    _record(contract)

    # call args are (*scalars, *operands): Pallas alias keys count the
    # scalar-prefetch args, so shift the operand index by len(scalars).
    ns = len(scalars)
    call_aliases = {ns + i: o for i, o in alias_items}
    named = dict(name=family, metadata={"family": family,
                                        "kernel": _kernel_name(kernel)})
    if ns:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=ns, grid=tuple(grid),
            in_specs=list(in_specs), out_specs=out_specs)
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape,
            input_output_aliases=call_aliases, interpret=interpret,
            **named)(*scalars, *operands)
    return pl.pallas_call(
        kernel, grid=tuple(grid), in_specs=list(in_specs),
        out_specs=out_specs, out_shape=out_shape,
        input_output_aliases=call_aliases, interpret=interpret,
        **named)(*operands)
