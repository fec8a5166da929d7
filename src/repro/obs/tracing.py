"""Spans on the profiler's clock.

:func:`span` enters a ``jax.profiler.TraceAnnotation``: a host event
that lands in the same ``jax.profiler`` trace as the device's
operations, so a span and the kernels it launched share one timeline.
Spans are always on.  With no profiler session recording, entering one
costs well under a microsecond; ``obs.enabled()`` governs only the
metrics registry.

:func:`profile` records a run into a trace directory
(``<dir>/plugins/profile/<run>/``: the ``.xplane.pb`` that
``jax.profiler.ProfileData`` reads and a ``perfetto_trace.json.gz``
for ui.perfetto.dev).  :func:`host_spans` reads the host events back.
"""
from __future__ import annotations

import contextlib
import glob
import os
from typing import List, Optional, Tuple

from jax.profiler import TraceAnnotation


def span(name: str) -> TraceAnnotation:
    """``with span("serve.tick"):`` -- a host event named ``name``."""
    return TraceAnnotation(name)


@contextlib.contextmanager
def profile(trace_dir: Optional[str]):
    """Record the body into ``trace_dir`` (nothing when it is None),
    with the options the benchmark's traced runs use: the Python tracer
    off, which would record every Python call."""
    if not trace_dir:
        yield
        return
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # no event per Python call
    opts.host_tracer_level = 2         # the spans and the runtime's events
    jax.profiler.start_trace(trace_dir, create_perfetto_trace=True,
                             profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_files(trace_dir: str) -> List[str]:
    """The ``.xplane.pb`` files :func:`profile` wrote under ``trace_dir``."""
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def host_spans(path: str) -> List[Tuple[str, int, int]]:
    """``(name, start_ns, end_ns)`` of every event on the host planes of
    one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events)
    return out
