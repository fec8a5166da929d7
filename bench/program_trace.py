"""What the program itself names in a profiler trace of the window, for
the per-layer metrics of its kernels and its serving engine.

* ``kernel_seconds``: ``{family: device seconds}`` inside the window,
  from the operations whose name carries ``tpu_custom_call`` (every
  Pallas kernel).  The family is the one ``contracts.launch`` writes
  into the call's ``kernel_metadata``, which the trace prints in the
  event's name; a kernel without one counts under ``"unnamed"``.
* ``idle_by_program_span``: ``{span: seconds}`` of the device's idle
  time inside the window, each idle gap split by overlap among the
  innermost of the program's own spans (``program_spans.json``) that
  cover each part of it; parts no program span covers go under
  ``"no program span"``.  Other host events, such as the runtime's
  ``Linearize`` inside ``serve.decode``, take no time from the program
  span they nest in.

The window and the gaps are those of ``reduce_trace``.  The harness
hands each reader its reduction, which holds every operation's time
(enough for ``kernel_seconds``) but not the events that the idle split
needs: :func:`program` reads those from the trace file of the run and
takes a file only where its reduction equals the reader's.

A metric these readers cannot read honestly is left out of the run's
line, and the reason goes to standard error, so that nothing drops out
unseen: where no trace file matches the run's reduction, where no
program span covers the idle time, and where a kernel in the window
carries no family or one the metric does not count.
"""
from __future__ import annotations

import glob
import json
import os
import re
import tempfile

from bench import harness, reduce_trace

HERE = os.path.dirname(os.path.abspath(__file__))

UNNAMED = "unnamed"
NO_SPAN = "no program span"
# "family":"band_fwd" inside kernel_metadata, quotes escaped or not
_FAMILY = re.compile(r'family[\\"]*\s*:\s*[\\"]*(\w+)')


def program_spans() -> list:
    with open(os.path.join(HERE, "program_spans.json")) as f:
        return json.load(f)["program_spans"]


def kernel_seconds(op_seconds: dict) -> dict:
    """``{family: seconds}`` of the Pallas kernels among a reduction's
    ``op_seconds``."""
    out = {}
    for name, t in op_seconds.items():
        if "tpu_custom_call" not in name:
            continue
        m = _FAMILY.search(name)
        fam = m.group(1) if m else UNNAMED
        out[fam] = out.get(fam, 0.0) + t
    return out


def idle_by_program_span(gaps, host, spans=None) -> dict:
    """``{span name: seconds}``: each gap ``(start_ns, end_ns)`` split by
    overlap among the innermost (shortest) program span covering each
    part; ``host`` is ``[(name, start_ns, end_ns)]`` of all host events,
    of which only the program's spans count."""
    names = set(spans or program_spans())
    mine = [h for h in host if h[0] in names]
    points = []
    for i, (_, s, e) in enumerate(mine):
        points += [(s, 1, i), (e, -1, i)]
    for s, e in gaps:
        points += [(s, 2, -1), (e, -2, -1)]
    points.sort(key=lambda p: p[0])
    out, active, in_gap, prev = {}, set(), False, None
    for t, kind, i in points:
        if in_gap and t > prev:
            inner = min(active, key=lambda j: mine[j][2] - mine[j][1],
                        default=None)
            name = mine[inner][0] if inner is not None else NO_SPAN
            out[name] = out.get(name, 0.0) + (t - prev) * 1e-9
        prev = t
        if kind == 1:
            active.add(i)
        elif kind == -1:
            active.discard(i)
        else:
            in_gap = kind == 2
    return out


def window(dev, host, *, devs=1) -> dict:
    """The window, the chips' mean busy time and the first chip's idle
    gaps, computed as ``reduce_trace.reduce_events`` computes them
    (without its per-operation sums and gap labels)."""
    spans = set(reduce_trace.window_spans())
    mine = [s for s in host if s[0] in spans]
    lo = min(s for _, s, _ in mine)
    hi = max(e for _, _, e in mine)
    chips = sorted(dev)[:devs]
    busy, gaps = 0.0, []
    for c in chips:
        merged = reduce_trace.union(reduce_trace.clip(
            [(s, e) for _, s, e in dev[c].get("XLA Ops", [])
             if e > lo and s < hi], lo, hi))
        busy += sum(e - s for s, e in merged) * 1e-9
        if c == chips[0]:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy / len(chips),
            "gaps": gaps}


def reduce(path, *, devs=1) -> dict:
    """``reduce_trace``'s window and busy time of one trace file, with
    ``idle_by_program_span``."""
    dev, host = reduce_trace.events(path)
    w = window(dev, host, devs=devs)
    return {"window_s": w["window_s"], "busy_s": w["busy_s"],
            "idle_by_program_span": idle_by_program_span(w["gaps"], host)}


def trace_files() -> list:
    """The trace files ``harness.traced`` has written and not yet
    removed, newest first."""
    found = glob.glob(os.path.join(tempfile.gettempdir(), "bench_trace_*",
                                   "**", "*.xplane.pb"), recursive=True)
    return sorted(found, key=os.path.getmtime, reverse=True)


def program(r):
    """The program's reduction of the run a reader reads, or None (said
    on standard error) where no trace file matches the harness's
    reduction or no program span covers the window's idle time.  The
    first reader of a run keeps the answer in ``r`` for the others."""
    if "program_trace" not in r:
        r["program_trace"] = _match(r)
    return r["program_trace"]


def _match(r):
    t = r["trace"]
    want = (t["window_s"], t["busy_s"])
    found = trace_files()
    for path in found:
        got = reduce(path, devs=r["chips"])
        if (got["window_s"], got["busy_s"]) == want:
            if set(got["idle_by_program_span"]) <= {NO_SPAN}:
                harness.log("program_trace: no program span covers the "
                            "window's idle time; the idle metrics are "
                            "left out")
                return None
            return got
    why = (f"none of the {len(found)} trace files under "
           f"{tempfile.gettempdir()} has the run's window {want[0]!r} s "
           f"and busy time {want[1]!r} s")
    if len(found) == 1:
        red = reduce_trace.reduce(found[0], devs=r["chips"])
        if (red["window_s"], red["busy_s"]) == want:
            why = ("program_trace.window no longer computes the window "
                   "and busy time as reduce_trace.reduce_events does")
    harness.log(f"program_trace: {why}; the idle metrics are left out")
    return None


def idle_ms_per_tick(r, names) -> float | None:
    """Idle ms a window tick spent inside the given program spans; None
    where :func:`program` finds nothing to read."""
    ticks = r["window"].get("ticks")
    got = program(r) if ticks else None
    if got is None:
        return None
    idle = got["idle_by_program_span"]
    return 1000.0 * sum(idle.get(n, 0.0) for n in names) / ticks


def family_seconds(r, prefixes) -> float | None:
    """Device seconds of the kernel families starting with one of
    ``prefixes`` in the window.  None where none ran, and None (said on
    standard error) where any kernel of the window is unnamed or of
    another family: its time would leave the metric unseen, so that a
    rename or an unnamed launch read as a gain.  The families' times
    go to standard error once a run."""
    if "kernel_seconds" not in r:
        r["kernel_seconds"] = kernel_seconds(r["trace"]["op_seconds"])
        harness.log("kernel ms by family: " + json.dumps(
            {f: 1000.0 * v for f, v in sorted(r["kernel_seconds"].items())}))
    ks = r["kernel_seconds"]
    other = sorted(f for f in ks if not f.startswith(tuple(prefixes)))
    if other:
        harness.log(f"program_trace: kernels {other} ran in the window "
                    f"beside {list(prefixes)}; the metric is left out")
        return None
    return sum(ks.values()) or None
