"""What every cell shares: finding its files by name, the chip check,
the compile cache, the clocks, tracing and the result line.

A cell (``workloads`` entry of ``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``).  The mix's ``kind`` picks the driver
(``bench/drivers/<kind>.py``), the configuration its plain reference
(``bench/refs/<reference>.py``) and its weight layout
(``bench/layouts/<layout>.py``), the cell's name its limits
(``bench/limits/<cell>.json``), and each per-layer metric its reader
(``bench/metrics/<metric>.py``).  Adding any of them is adding files and
entries.
"""
from __future__ import annotations

import contextlib
import glob
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# The longest window a traced run measures: a trace of 51 s of decoding
# could not be written and read within the 360 s that a run may take.
TRACE_SECONDS = 10.0


class NoChip(RuntimeError):
    """The run cannot be measured here: no accelerator, too few chips,
    or a device kind the peak table does not know."""


def since_process_start() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start_ticks / os.sysconf("SC_CLK_TCK")


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(root, "BENCHMARK.json")


def workload(name: str, root: str = ROOT) -> dict:
    for w in benchmark(root)["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, root: str = ROOT) -> dict:
    entry = [c for c in benchmark(root)["configs"] if c["name"] == name][0]
    return load_json(root, entry["file"])


def limits(cell: str) -> dict:
    return load_json(HERE, "limits", f"{cell}.json")


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def reference(cfg: dict):
    """The plain reference a configuration names (``bench/refs/<name>.py``)."""
    return importlib.import_module(f"bench.refs.{cfg['reference']}")


def layout(cfg: dict):
    """The weight layout a configuration names (``bench/layouts/<name>.py``);
    there is no default."""
    return importlib.import_module(f"bench.layouts.{cfg['layout']}")


def metric_reader(name: str):
    return load_module(os.path.join(HERE, "metrics", f"{name}.py"),
                       "bench_metric_" + name.replace(".", "_"))


def peaks(kind: str) -> dict:
    table = load_json(HERE, "peaks.json")["devices"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def check_devices(chips: int):
    """The devices to run on; raises :class:`NoChip` where they are not
    TPUs, are too few, or have no row in the peak table."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"{chips} chip(s) needed, {len(devs)} found")
    peaks(devs[0].device_kind)
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's own persistent cache (``<checkout>/.jax_cache``, or
    ``$JAX_COMPILATION_CACHE_DIR``), with every program kept."""
    import jax
    from repro.launch import compile_cache
    d = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d


def peak_bytes(devs) -> int:
    """The fullest chip's peak so far: the allocator's peak of buffers,
    or, where larger, its buffers now together with the scratch it holds
    reserved for compiled programs' temporaries (apart from the buffers;
    the two peaks need not fall together, so they are not summed)."""
    def one(d):
        s = d.memory_stats() or {}
        return int(max(s.get("peak_bytes_in_use", 0),
                       s.get("bytes_in_use", 0)
                       + s.get("bytes_reserved", 0)))
    return max(one(d) for d in devs)


def span(name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def traced(enabled: bool, out: dict):
    """Profile the body when ``enabled``; ``out["xplane"]`` is then the
    path of the written ``.xplane.pb`` (removed when the run ends)."""
    if not enabled:
        yield
        return
    import jax
    d = tempfile.mkdtemp(prefix="bench_trace_")
    out["dir"] = d
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # no event per Python call
    opts.host_tracer_level = 2         # keeps the harness's spans
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield
    finally:
        log("window closed, writing the trace")
        jax.profiler.stop_trace()
        log("trace written")
        found = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        out["xplane"] = found[0] if found else None


def cleanup_trace(out: dict):
    if out.get("dir"):
        shutil.rmtree(out["dir"], ignore_errors=True)


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Clock:
    """``setup_s`` runs from process start to :meth:`start_window`."""

    def __init__(self):
        self.t0 = time.perf_counter() - since_process_start()
        self.window_start = None

    def start_window(self) -> float:
        self.window_start = time.perf_counter()
        return self.window_start

    @property
    def setup_s(self) -> float:
        return self.window_start - self.t0


def checks_line(checks: dict) -> dict:
    """``{name: {"value", "limit"}}`` -> printed on stderr, last."""
    for k, c in checks.items():
        print(f"[check] {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return checks
