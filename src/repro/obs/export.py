"""Export surfaces: snapshot dict, Prometheus text, JSONL.

Three ways out of the in-process registry:

* :func:`snapshot` -- one JSON-able dict: metrics (counters / gauges /
  histogram summaries) and the kernel tuning state (backend, digest,
  aggregated decision-log counts).
* :func:`prometheus_text` -- Prometheus text exposition (0.0.4):
  ``repro_``-prefixed names with dots flattened to underscores,
  histograms as cumulative ``_bucket{le=...}`` series.
* :class:`JsonlEmitter` -- appends a snapshot line to a file at most
  once per ``period_s`` (drive it from any loop; ``emit()`` forces).

Spans go to the ``jax.profiler`` trace instead (:mod:`.tracing`).

The ``validate_*`` functions are the *pinned schemas*: tests and the CI
telemetry smoke (``scripts/check_telemetry.py``) call the same code, so
the exporters cannot drift from what CI checks.
"""
from __future__ import annotations

import collections
import json
import math
import re
import time
from typing import Any, Dict, List, Sequence

from . import metrics as _m
from . import tracing as _t


def tuning_snapshot() -> Dict[str, Any]:
    """Backend + digest + the decision log aggregated to
    {family: {source: count}} (satellite: tuning observability)."""
    from repro.kernels.tuning import get_policy
    p = get_policy()
    agg: Dict[str, Dict[str, int]] = collections.defaultdict(
        lambda: collections.defaultdict(int))
    for d in p.decisions:
        agg[d["family"]][d["source"]] += 1
    return {
        "backend": p.backend,
        "tuning_digest": p.tuning_digest(),
        "decisions": {f: dict(s) for f, s in sorted(agg.items())},
        "decision_log_len": len(p.decisions),
    }


def snapshot() -> Dict[str, Any]:
    return {
        "schema": "repro.obs.snapshot/1",
        "enabled": _m.enabled(),
        "metrics": _m.registry().snapshot(),
        "tuning": tuning_snapshot(),
    }


# -- Prometheus text exposition ----------------------------------------------

_NAME_OK = re.compile(r"[^a-zA-Z0-9_]")


def _prom_name(name: str) -> str:
    return "repro_" + _NAME_OK.sub("_", name)


def _prom_labels(labels: Dict[str, Any]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{labels[k]}"' for k in sorted(labels))
    return "{" + inner + "}"


def _prom_float(v: float) -> str:
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    return repr(float(v))


def prometheus_text() -> str:
    """Prometheus text-format exposition of the whole registry."""
    by_name: Dict[str, List[Any]] = collections.defaultdict(list)
    for (name, _lk), m in _m.registry():
        by_name[name].append(m)
    lines: List[str] = []
    for name in sorted(by_name):
        ms = by_name[name]
        pname = _prom_name(name)
        kind = type(ms[0]).__name__
        if kind == "Counter":
            lines.append(f"# TYPE {pname} counter")
            for m in ms:
                lines.append(
                    f"{pname}_total{_prom_labels(m.labels)} {m.value}")
        elif kind == "Gauge":
            lines.append(f"# TYPE {pname} gauge")
            for m in ms:
                lines.append(
                    f"{pname}{_prom_labels(m.labels)} "
                    f"{_prom_float(m.value)}")
        else:
            lines.append(f"# TYPE {pname} histogram")
            for m in ms:
                base = dict(m.labels)
                for edge, cum in m.cumulative():
                    lab = _prom_labels(dict(base, le=_prom_float(edge)))
                    lines.append(f"{pname}_bucket{lab} {cum}")
                lines.append(f"{pname}_sum{_prom_labels(base)} "
                             f"{_prom_float(m.sum)}")
                lines.append(f"{pname}_count{_prom_labels(base)} "
                             f"{m.count}")
    return "\n".join(lines) + ("\n" if lines else "")


def write_prometheus(path: str) -> None:
    with open(path, "w") as f:
        f.write(prometheus_text())


def write_snapshot(path: str) -> None:
    with open(path, "w") as f:
        json.dump(snapshot(), f, indent=1, sort_keys=True)


class JsonlEmitter:
    """Appends one snapshot JSON line to ``path`` at most every
    ``period_s`` seconds of wall clock.  Call :meth:`maybe_emit` from
    any loop; :meth:`emit` writes unconditionally (use it once at
    shutdown so short runs still produce a line)."""

    def __init__(self, path: str, period_s: float = 10.0):
        self.path = path
        self.period_s = float(period_s)
        self._last = None        # never emitted: the first call emits
        self.emitted = 0

    def maybe_emit(self) -> bool:
        now = time.monotonic()
        if self._last is not None and now - self._last < self.period_s:
            return False
        self._last = now
        self.emit()
        return True

    def emit(self) -> None:
        line = dict(snapshot(), unix_time=time.time())
        with open(self.path, "a") as f:
            f.write(json.dumps(line, sort_keys=True) + "\n")
        self.emitted += 1


# -- pinned schemas (shared by tests and the CI telemetry smoke) -------------

def validate_snapshot(doc: Dict[str, Any]) -> List[str]:
    """Schema errors for a snapshot dict ([] when valid)."""
    errs: List[str] = []
    if doc.get("schema") != "repro.obs.snapshot/1":
        errs.append(f"bad schema tag: {doc.get('schema')!r}")
    m = doc.get("metrics")
    if not isinstance(m, dict):
        errs.append("metrics: not a dict")
    else:
        for sec in ("counters", "gauges", "histograms"):
            if not isinstance(m.get(sec), dict):
                errs.append(f"metrics.{sec}: not a dict")
        for k, h in (m.get("histograms") or {}).items():
            for field in ("count", "sum", "buckets"):
                if field not in h:
                    errs.append(f"histogram {k}: missing {field!r}")
    t = doc.get("tuning")
    if not isinstance(t, dict):
        errs.append("tuning: not a dict")
    else:
        for field in ("backend", "tuning_digest", "decisions"):
            if field not in t:
                errs.append(f"tuning: missing {field!r}")
        dig = t.get("tuning_digest", "")
        if not re.fullmatch(r"[0-9a-f]{12}", str(dig)):
            errs.append(f"tuning_digest not 12-hex: {dig!r}")
    return errs


def validate_trace_dir(trace_dir: str,
                       require_spans: Sequence[str] = ()) -> List[str]:
    """Errors for a profiler trace directory ([] when valid): it holds
    an ``.xplane.pb`` whose host planes carry every span named in
    ``require_spans``."""
    files = _t.xplane_files(trace_dir)
    if not files:
        return [f"no .xplane.pb under {trace_dir}"]
    seen = {name for f in files for name, _, _ in _t.host_spans(f)}
    return [f"span {name!r} not on the host plane"
            for name in require_spans if name not in seen]


_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? "
    r"[-+]?([0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?|Inf|NaN)$")


def validate_prometheus_text(text: str,
                             require_metrics: tuple = (),
                             ) -> List[str]:
    """Schema errors for a Prometheus exposition ([] when valid)."""
    errs: List[str] = []
    seen: set = set()
    for ln, line in enumerate(text.splitlines(), 1):
        if not line or line.startswith("#"):
            continue
        if not _PROM_LINE.match(line):
            errs.append(f"line {ln}: not prometheus text format: "
                        f"{line!r}")
            continue
        seen.add(line.split("{")[0].split(" ")[0])
    for name in require_metrics:
        if name not in seen:
            errs.append(f"required metric missing: {name}")
    return errs
