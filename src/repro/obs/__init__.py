"""Process-wide telemetry: metrics, spans, kernel-launch accounting.

Quickstart::

    from repro import obs
    obs.enable()
    with obs.tracing.profile("trace_dir"):     # load in ui.perfetto.dev
        ... run serve / train / bench ...
    print(obs.export.prometheus_text())
    snap = obs.export.snapshot()

Spans (:func:`span`) are profiler annotations and always on: they cost
nothing measurable unless a ``jax.profiler`` session records them, and
then they share the device's clock (:mod:`repro.obs.tracing`).

Metrics are OFF by default and cost one branch per instrumentation
site when off (:mod:`repro.obs.metrics` returns shared no-op stubs).
:func:`enable` flips the registry live and registers the kernel-launch
hook on :mod:`repro.analysis.contracts`, so every ``pallas_call``
traced while enabled is counted per family with its analytic HBM
bytes and FLOPs (:mod:`repro.obs.traffic`).  CLIs expose this as
``--telemetry`` / ``--trace-out DIR`` / ``--prom-out``.
"""
from __future__ import annotations

from . import export, metrics, tracing, traffic
from .metrics import (NULL_COUNTER, NULL_GAUGE, NULL_HISTOGRAM, Histogram,
                      counter, enabled, gauge, histogram, registry)
from .tracing import span

__all__ = [
    "enable", "disable", "enabled", "reset",
    "counter", "gauge", "histogram", "span",
    "registry", "Histogram",
    "metrics", "tracing", "traffic", "export",
    "NULL_COUNTER", "NULL_GAUGE", "NULL_HISTOGRAM",
]

_HOOKED = False


def enable() -> None:
    """Turn telemetry on and hook kernel-launch accounting."""
    global _HOOKED
    metrics._set_enabled(True)
    if not _HOOKED:
        from repro.analysis import contracts
        contracts.add_launch_hook(traffic.on_launch)
        _HOOKED = True


def disable() -> None:
    """Turn telemetry off (hot paths revert to the one-branch no-op).
    Collected metrics are kept until :func:`reset`."""
    global _HOOKED
    metrics._set_enabled(False)
    if _HOOKED:
        from repro.analysis import contracts
        contracts.remove_launch_hook(traffic.on_launch)
        _HOOKED = False


def reset() -> None:
    """Clear all collected metrics (enabled state is unchanged)."""
    metrics.registry().reset()
