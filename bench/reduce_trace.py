"""Reduce a profiler trace (``.xplane.pb``) of a measured window to the
numbers the per-layer metrics read.

* the traced window: from the first to the last host span that the
  harness wrote around its calls into the system (``bench/spans.json``,
  ``window_spans``);
* device busy time: the union of the intervals of the device's
  operations (``XLA Ops`` line of each ``/device:TPU:<n>`` plane) inside
  the window, averaged over the chips;
* each operation's total time, under the name the trace gives it;
* the idle gaps between busy intervals, each labelled with the
  innermost host span it fell in;
* the device programs (``XLA Modules`` line): count, time and the gaps
  between consecutive programs.

    python3 bench/reduce_trace.py <file.xplane.pb>   # prints the reduction
"""
from __future__ import annotations

import bisect
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def window_spans() -> list:
    with open(os.path.join(HERE, "spans.json")) as f:
        return json.load(f)["window_spans"]


def union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def label_gaps(gaps, spans):
    """Each gap (s, e) with the innermost host span (name, s, e) that
    covers its midpoint, or ``"no host span"``."""
    spans = sorted(spans, key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        best = None
        for name, ss, se in spans[:bisect.bisect_right(starts, mid)]:
            if ss <= mid <= se and (best is None or se - ss < best[1]):
                best = (name, se - ss)
        out.append((best[0] if best else "no host span", s, e))
    return out


def events(path):
    """({device index: {line name: [(name, start_ns, end_ns)]}},
    [(span name, start_ns, end_ns)] of host spans)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev, host = {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            lines = dev.setdefault(int(m.group(1)), {})
            for line in plane.lines:
                lines[line.name] = [(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
    return dev, host


def reduce_events(dev, host, *, devs=1, spans=None, top=10) -> dict:
    """The reduction of already-read events (see the module docstring);
    times in seconds."""
    spans = spans or window_spans()
    mine = [s for s in host if s[0] in spans]
    if not mine:
        raise ValueError("no harness span in the trace")
    lo = min(s for _, s, _ in mine)
    hi = max(e for _, _, e in mine)
    window = (hi - lo) * 1e-9
    chips = sorted(dev)[:devs]
    busy, per_op, gaps = 0.0, {}, []
    programs = {"count": 0, "seconds": 0.0, "gaps": []}
    for c in chips:
        ops = [(n, s, e) for n, s, e in dev[c].get("XLA Ops", [])
               if e > lo and s < hi]
        merged = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy += sum(e - s for s, e in merged) * 1e-9
        if c == chips[0]:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
        for n, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            per_op[n] = per_op.get(n, 0.0) + (e - s) * 1e-9 / len(chips)
        if c == chips[0]:
            mods = sorted((s, e) for _, s, e in dev[c].get("XLA Modules", [])
                          if s >= lo and e <= hi)
            programs["count"] = len(mods)
            programs["seconds"] = sum(e - s for s, e in mods) * 1e-9
            programs["gaps"] = [max(0, mods[i + 1][0] - mods[i][1]) * 1e-9
                                for i in range(len(mods) - 1)]
    labelled = label_gaps(gaps, host)
    by_label = {}
    for name, s, e in labelled:
        by_label[name] = by_label.get(name, 0.0) + (e - s) * 1e-9
    longest = sorted(labelled, key=lambda g: g[1] - g[2])[:top]
    return {
        "window_s": window,
        "busy_s": busy / len(chips),
        "idle_s": window - busy / len(chips),
        "op_seconds": per_op,
        "idle_by_span": by_label,
        "programs": programs,
        "breakdown": {
            "device_ops": [[n, t] for n, t in sorted(
                per_op.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": [[n, (e - s) * 1e-9] for n, s, e in longest],
        },
    }


def reduce(path, *, devs=1) -> dict:
    dev, host = events(path)
    return reduce_events(dev, host, devs=devs)


def describe(path, n=8) -> str:
    """The planes, lines and a few events of a trace, for a reader who
    has not seen one."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            ev = list(line.events)
            out.append(f"  line {line.name!r}: {len(ev)} events")
            for e in ev[:n]:
                stats = {}
                try:
                    stats = {k: str(v)[:80] for k, v in e.stats}
                except Exception as exc:  # stats layout differs by version
                    stats = {"unreadable": repr(exc)}
                out.append(f"    {e.name[:100]!r} start={e.start_ns} "
                           f"dur={e.duration_ns} {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]), indent=1))
