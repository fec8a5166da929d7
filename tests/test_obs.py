"""Telemetry layer: disabled-path no-op guarantees, pinned export
schemas, spans and named kernels in the profiler's trace, and the
analytic HBM/FLOP accounting cross-checked against the EXPERIMENTS.md
P25/P27 hand arithmetic.

The analytic-traffic tests are the paper-notebook numbers as executable
code: the P25 decode-tick figure (one fused attend launch reads
``nbands * nr`` cache rows of K and V per grid row) and the P27
fixed-HBM budget (245,760 dense cache bytes for the smoke llama config)
must both be reproduced by the generic traffic model in
``repro.obs.traffic`` from nothing but the traced LaunchContract.
"""
import json
import math
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.analysis import contracts
from repro.configs import get_smoke_config
from repro.obs import export, metrics, tracing, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:            # the benchmark's trace reader
    sys.path.insert(0, ROOT)

pytestmark = []


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with telemetry off and empty."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# -- disabled path: true no-op -----------------------------------------------

def test_disabled_accessors_return_shared_stubs():
    assert not obs.enabled()
    # object IDENTITY, not just no-op behaviour: the disabled hot path
    # must never allocate or touch the registry dict
    assert obs.counter("serve.ticks") is obs.NULL_COUNTER
    assert obs.counter("other", family="x") is obs.NULL_COUNTER
    assert obs.gauge("pool.occupancy") is obs.NULL_GAUGE
    assert obs.histogram("serve.ttft_s") is obs.NULL_HISTOGRAM
    obs.counter("serve.ticks").inc()
    obs.gauge("pool.occupancy").set(0.5)
    obs.histogram("serve.ttft_s").observe(1.0)
    with obs.span("serve.tick"):         # spans never touch the registry
        pass
    snap = metrics.registry().snapshot()
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}


def test_disabled_overhead_is_tiny():
    """1e5 fully-instrumented iterations of the disabled path in well
    under a second -- i.e. the per-site cost is a branch + a no-op
    call, microseconds at most (the acceptance bound is < 1% on a real
    decode tick, which is milliseconds)."""
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        obs.counter("serve.ticks").inc()
        obs.gauge("serve.queue_depth").set(3)
        obs.histogram("serve.itl_s").observe(1e-3)
    dt = time.perf_counter() - t0
    assert dt < 2.0, f"{n} disabled-path iterations took {dt:.2f}s"


def test_disabled_launches_record_no_metrics():
    """contracts.launch() fires no telemetry while disabled (the hook
    is only registered by obs.enable())."""
    c = _capture_decode_contract(Lmax=64, nr=8, d=16, G=2, R=2)
    assert c is not None
    assert metrics.registry().snapshot()["counters"] == {}


def test_span_cost_without_profiler():
    """1e5 span enters and exits with no profiler session recording in
    well under a second: spans stay on in every run."""
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("serve.tick"):
            pass
    dt = time.perf_counter() - t0
    assert dt < 2.0, f"{n} spans took {dt:.2f}s"


# -- spans and named kernels in the profiler's trace -------------------------

def _profiled_host_events(tmp_path, body):
    """Run ``body`` under ``tracing.profile`` and return the host events
    of the trace as the benchmark's reduction reads them."""
    from bench import reduce_trace
    d = str(tmp_path / "trace")
    with tracing.profile(d):
        body()
    (path,) = tracing.xplane_files(d)
    _, host = reduce_trace.events(path)
    return host


def test_span_lands_in_profiler_trace_and_nests(tmp_path):
    def body():
        with obs.span("serve.tick"):
            with obs.span("serve.decode"):
                jnp.ones(4).block_until_ready()
            with obs.span("serve.readback"):
                pass
    host = _profiled_host_events(tmp_path, body)
    got = {n: (s, e) for n, s, e in host
           if n in ("serve.tick", "serve.decode", "serve.readback")}
    assert set(got) == {"serve.tick", "serve.decode", "serve.readback"}
    tick = got["serve.tick"]
    for child in ("serve.decode", "serve.readback"):
        assert tick[0] <= got[child][0] <= got[child][1] <= tick[1]
    assert got["serve.decode"][1] <= got["serve.readback"][0]
    # telemetry stayed off: spans need no registry
    assert metrics.registry().snapshot()["counters"] == {}


def test_trace_dir_validator(tmp_path):
    def body():
        with obs.span("serve.tick"):
            pass
    d = str(tmp_path / "trace")
    with tracing.profile(d):
        body()
    assert export.validate_trace_dir(d, require_spans=("serve.tick",)) == []
    assert export.validate_trace_dir(d, require_spans=("serve.tables",))
    (tmp_path / "empty").mkdir()
    assert export.validate_trace_dir(str(tmp_path / "empty"))
    assert any(f.endswith("perfetto_trace.json.gz")
               for _, _, fs in os.walk(d) for f in fs)


def _band_fwd_launch():
    from repro.kernels import ops
    S, f32 = jax.ShapeDtypeStruct, jnp.float32
    B, G, L, d = 1, 4, 256, 128
    return (lambda q, k, v, w: ops.band_attention(
        q, k, v, w, nr=16, mode="l0_causal", ratio=1, impl="pallas"),
        (S((B, G, L, d), f32), S((B, L, d), f32), S((B, L, d), f32),
         S((B, L), f32)))


def _decode_attend_paged_launch():
    from repro.core import h1d_decode as hd
    from repro.core import hierarchy as hc
    S, nr = jax.ShapeDtypeStruct, 16
    R, G, Lmax, d = 8, 4, 256, 128
    levels = hc.num_levels(Lmax, nr) - 1
    pool = jax.eval_shape(lambda: hd.init_paged_pool(
        [R * (Lmax >> l) // nr for l in range(levels + 1)], nr, d, d,
        dtype=jnp.float32))
    return (lambda c, q, t, a: hd.decode_attend_paged(
        c, q, t, a, nr=nr, impl="pallas"),
        (pool, S((R, G, d), jnp.float32), S((R,), jnp.int32),
         S((R, 2 + levels), jnp.int32)))


_LAUNCHES = {"band_fwd": _band_fwd_launch,
             "decode_attend_paged": _decode_attend_paged_launch}


def _tpu_lowering(family) -> str:
    fn, args = _LAUNCHES[family]()
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("family", sorted(_LAUNCHES))
def test_launch_names_kernel_in_tpu_lowering(family):
    """Every Mosaic call ``contracts.launch`` issues carries its family
    in ``kernel_metadata``, the attribute a device trace prints in the
    kernel event's name."""
    text = _tpu_lowering(family)
    calls = text.count("@tpu_custom_call")
    assert calls >= 1
    meta = re.findall(r'kernel_metadata = "([^"]*)"', text)
    assert len(meta) == calls
    assert all(re.search(r'\\22family\\22:\\22' + family + r'\\22', m)
               for m in meta), meta
    assert re.findall(r'kernel_name = "(\w+)"', text) == [family] * calls


# -- enabled path ------------------------------------------------------------

def test_counters_gauges_labels_and_kind_conflict():
    obs.enable()
    obs.counter("kernel.launches", family="decode_attend").inc()
    obs.counter("kernel.launches", family="decode_attend").inc(2)
    obs.counter("kernel.launches", family="band_fwd").inc()
    obs.gauge("pool.occupancy").set(0.25)
    snap = metrics.registry().snapshot()
    assert snap["counters"][
        "kernel.launches{family=decode_attend}"] == 3
    assert snap["counters"]["kernel.launches{family=band_fwd}"] == 1
    assert snap["gauges"]["pool.occupancy"] == 0.25
    with pytest.raises(TypeError):
        obs.gauge("kernel.launches", family="band_fwd")


def test_histogram_exact_quantiles_match_numpy():
    rng = np.random.default_rng(0)
    xs = rng.exponential(0.01, size=200)
    h = obs.Histogram(keep_samples=len(xs))
    for x in xs:
        h.observe(float(x))
    assert h.exact
    assert h.quantile(0.5) == pytest.approx(np.median(xs), rel=1e-12)
    assert h.quantile(0.99) == pytest.approx(
        np.percentile(xs, 99), rel=1e-12)
    assert h.quantile(0.0) == pytest.approx(xs.min())
    assert h.quantile(1.0) == pytest.approx(xs.max())


def test_histogram_bucket_fallback_after_reservoir_overflow():
    h = obs.Histogram(keep_samples=8)
    rng = np.random.default_rng(1)
    xs = rng.uniform(1e-4, 1e-1, size=100)
    for x in xs:
        h.observe(float(x))
    assert not h.exact
    q = h.quantile(0.5)
    assert h.min <= q <= h.max
    # cumulative counts are monotone and end at the total
    cum = h.cumulative()
    assert [c for _, c in cum] == sorted(c for _, c in cum)
    assert cum[-1][0] == math.inf and cum[-1][1] == h.count


# -- pinned export schemas ---------------------------------------------------

def _populate():
    obs.enable()
    obs.counter("serve.ticks").inc(4)
    obs.counter("kernel.launches", family="decode_attend").inc()
    obs.gauge("pool.occupancy").set(0.5)
    for v in (1e-3, 2e-3, 5e-3):
        obs.histogram("serve.ttft_s").observe(v)


def test_snapshot_schema_pinned():
    _populate()
    snap = export.snapshot()
    assert export.validate_snapshot(snap) == []
    assert snap["schema"] == "repro.obs.snapshot/1"
    h = snap["metrics"]["histograms"]["serve.ttft_s"]
    assert h["count"] == 3 and h["sum"] == pytest.approx(8e-3)
    assert h["min"] == pytest.approx(1e-3)
    assert h["p50"] == pytest.approx(2e-3)
    # tuning state rides in every snapshot (satellite: tuning obs)
    assert snap["tuning"]["backend"]
    # the snapshot round-trips through JSON unchanged
    assert export.validate_snapshot(
        json.loads(json.dumps(snap))) == []


def test_snapshot_validator_rejects_drift():
    _populate()
    snap = export.snapshot()
    bad = dict(snap, schema="repro.obs.snapshot/2")
    assert export.validate_snapshot(bad)
    bad = json.loads(json.dumps(snap))
    del bad["metrics"]["histograms"]["serve.ttft_s"]["buckets"]
    assert export.validate_snapshot(bad)
    bad = json.loads(json.dumps(snap))
    bad["tuning"]["tuning_digest"] = "nope"
    assert export.validate_snapshot(bad)


def test_prometheus_text_schema_pinned():
    _populate()
    text = export.prometheus_text()
    assert export.validate_prometheus_text(
        text, require_metrics=("repro_serve_ticks_total",
                               "repro_pool_occupancy",
                               "repro_serve_ttft_s_bucket",
                               "repro_serve_ttft_s_sum",
                               "repro_serve_ttft_s_count")) == []
    lines = text.splitlines()
    assert "# TYPE repro_serve_ticks counter" in lines
    assert "repro_serve_ticks_total 4" in lines
    assert ('repro_kernel_launches_total{family="decode_attend"} 1'
            in lines)
    # histogram buckets are cumulative and close with le="+Inf"
    buckets = [ln for ln in lines
               if ln.startswith("repro_serve_ttft_s_bucket")]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert counts == sorted(counts)
    assert buckets[-1].startswith('repro_serve_ttft_s_bucket{le="+Inf"}')
    assert counts[-1] == 3
    # drift guard: a malformed line fails the validator
    assert export.validate_prometheus_text("bad line here\n")


def test_jsonl_emitter(tmp_path):
    _populate()
    path = tmp_path / "metrics.jsonl"
    em = export.JsonlEmitter(str(path), period_s=3600.0)
    assert em.maybe_emit()          # first call always emits
    assert not em.maybe_emit()      # inside the period: skipped
    em.emit()                       # forced shutdown line
    lines = path.read_text().splitlines()
    assert len(lines) == 2
    for ln in lines:
        doc = json.loads(ln)
        assert export.validate_snapshot(doc) == []
        assert "unix_time" in doc


# -- analytic HBM/FLOP accounting vs P25/P27 ---------------------------------

def _capture_decode_contract(Lmax, nr, d, G, R):
    """Trace decode_attend_fused via eval_shape (no compile, no
    device) and return its LaunchContract."""
    from repro.core import h1d_decode as hd
    from repro.kernels.h1d_decode_kernel import decode_attend_fused
    cache = hd.init_cache(R, Lmax, d, d, nr=nr, dtype=jnp.float32)
    q = jnp.zeros((R, G, d), jnp.float32)
    t = jnp.full((R,), Lmax - 1, jnp.int32)
    with contracts.capture() as buf:
        jax.eval_shape(
            lambda c, q, t: decode_attend_fused(c, q, t, nr=nr),
            cache, q, t)
    (c,) = [c for c in buf if c.family == "decode_attend"]
    return c


def _capture_update_contract(Lmax, nr, d, R):
    from repro.core import h1d_decode as hd
    from repro.kernels.h1d_decode_kernel import update_cache_fused
    cache = hd.init_cache(R, Lmax, d, d, nr=nr, dtype=jnp.float32)
    kn = jnp.zeros((R, d), jnp.float32)
    vn = jnp.zeros((R, d), jnp.float32)
    t = jnp.full((R,), Lmax - 1, jnp.int32)
    with contracts.capture() as buf:
        jax.eval_shape(lambda c, k, v, t: update_cache_fused(c, k, v, t),
                       cache, kn, vn, t)
    (c,) = [c for c in buf if c.family == "decode_update"]
    return c


def test_analytic_hbm_matches_p25_decode_attend():
    """EXPERIMENTS.md P25, fused decode attend at Lmax=1024, nr=16,
    d=64: the kernel reads nbands 16-row K+V bands per grid row --
    ``nbands * nr * 2 * d * 4`` bytes -- and writes one (G, d) output
    block.  The generic per-contract traffic model must reproduce the
    hand count within 5% (its only extra term is the (G, d) q block)."""
    Lmax, nr, d, G, R = 1024, 16, 64, 4, 8
    c = _capture_decode_contract(Lmax, nr, d, G, R)
    # band count straight off the contract: own + prev + one per level
    nbands = 2 + sum(1 for o in c.inputs if o.name.startswith("k_lvl"))
    hand_read_per_row = nbands * nr * 2 * d * 4      # K+V bands, f32
    tr = traffic.contract_hbm_bytes(c)
    read_per_row = tr["read_bytes"] / R
    assert abs(read_per_row - hand_read_per_row) / hand_read_per_row \
        <= 0.05, (read_per_row, hand_read_per_row)
    # output writes are exact: one (1, G, d) f32 block per row
    assert tr["write_bytes"] == R * G * d * 4
    # FLOPs: 2*Q*K*(d+dv) matmul + softmax terms, Q=G, K=nbands*nr
    fl = traffic.contract_flops(c)
    K = nbands * nr
    hand_flops = R * (2 * G * K * (d + d) + 8 * G * K)
    assert abs(fl - hand_flops) / hand_flops <= 0.05, (fl, hand_flops)


def test_analytic_hbm_matches_p25_cache_update():
    """P25's update launch: per level, read AND write the 2-row K+V
    sibling pair -- ``M * 2 * 2 * d * 4`` bytes each way per row (reads
    add the two (1, d) new-token operands)."""
    Lmax, nr, d, R = 1024, 16, 64, 8
    c = _capture_update_contract(Lmax, nr, d, R)
    M = sum(1 for o in c.inputs if o.name.startswith("k_l"))
    tr = traffic.contract_hbm_bytes(c)
    hand_write_per_row = M * 2 * 2 * d * 4
    hand_read_per_row = hand_write_per_row + 2 * d * 4   # + k_new/v_new
    assert tr["write_bytes"] / R == hand_write_per_row
    assert tr["read_bytes"] / R == hand_read_per_row


def test_analytic_traffic_vs_p25_scaling_in_lmax():
    """The analytic read count must scale like the P25 accounting: one
    extra 2*nr-row band (K+V) per doubling of Lmax."""
    reads = {}
    for Lmax in (256, 512, 1024):
        c = _capture_decode_contract(Lmax, nr=16, d=64, G=4, R=4)
        reads[Lmax] = traffic.contract_hbm_bytes(c)["read_bytes"] / 4
    band = 16 * 2 * 64 * 4                       # nr * (K+V) * d * f32
    assert reads[512] - reads[256] == band
    assert reads[1024] - reads[512] == band


def test_p27_fixed_hbm_budget_hand_accounting():
    """EXPERIMENTS.md P27: the fixed-HBM concurrency headline budget is
    the DENSE engine's cache footprint -- slots x (layers x kv_heads) x
    (hierarchy rows) x head_dim x (K+V) x 4 bytes = 245,760 for the
    smoke llama3.2-1b at max_len 128 with 2 slots.  pool_bytes and the
    committed BENCH_serve.json must both equal the hand formula."""
    import os
    from repro.core import hierarchy
    from repro.models import get_model
    from repro.serve import paged_cache as pc
    cfg = get_smoke_config("llama3.2-1b")
    max_len, slots = 128, 2
    fns = get_model(cfg)
    params, _ = fns.init(jax.random.PRNGKey(0), cfg)
    caches = fns.init_caches(params, cfg, slots, max_len)
    # hierarchy row count: level-l has max_len >> l rows, down to the
    # coarsest level the decode cache keeps (2*nr rows)
    levels = hierarchy.num_levels(max_len, cfg.nr)
    rows = sum(max_len >> l for l in range(levels))
    head_dim = cfg.d_model // cfg.num_heads
    hand = slots * cfg.num_layers * cfg.num_kv_heads \
        * rows * head_dim * 2 * 4
    assert pc.pool_bytes(caches) == hand == 245_760
    root = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(root, "BENCH_serve.json")) as f:
        rows_json = json.load(f)["rows"]
    derived = next(r["derived"] for r in rows_json
                   if r["name"] == "serve_concurrency_fixed_hbm")
    assert int(derived.split("hbm_bytes=")[1].split()[0]) == hand


def test_launch_hook_feeds_registry_and_trace():
    """With telemetry on, a traced launch lands as kernel.* counters
    that agree with the direct traffic-model call, and the same launch
    names its family where the device trace reads it."""
    obs.enable()
    c = _capture_decode_contract(Lmax=256, nr=8, d=16, G=2, R=4)
    snap = metrics.registry().snapshot()["counters"]
    assert snap["kernel.launches{family=decode_attend}"] >= 1
    tr = traffic.contract_hbm_bytes(c)
    assert snap["kernel.hbm_read_bytes{family=decode_attend}"] \
        == tr["read_bytes"]
    assert snap["kernel.hbm_write_bytes{family=decode_attend}"] \
        == tr["write_bytes"]
    assert snap["kernel.flops{family=decode_attend}"] \
        == traffic.contract_flops(c)
    text = _tpu_lowering("decode_attend_paged")
    snap = metrics.registry().snapshot()["counters"]
    assert snap["kernel.launches{family=decode_attend_paged}"] == 1
    assert 'kernel_name = "decode_attend_paged"' in text


# -- serve-path integration --------------------------------------------------

def _tiny_engine(paged):
    from repro.models import get_model
    from repro.serve import Request, ServeEngine
    cfg = get_smoke_config("llama3.2-1b")
    fns = get_model(cfg)
    params, _ = fns.init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, slots=2, max_len=64, paged=paged,
                      token_budget=64)
    prompts = [np.arange(4, 16) % cfg.vocab_size,
               np.arange(4, 16) % cfg.vocab_size,       # shared prefix
               (np.arange(3, 27) * 5) % cfg.vocab_size]
    reqs = [Request(uid=i, prompt=p.astype(np.int32), max_new_tokens=4)
            for i, p in enumerate(prompts)]
    return eng, reqs


@pytest.mark.slow
def test_serve_engine_emits_ticks_latencies_and_pool_counters(tmp_path):
    obs.enable()
    eng, reqs = _tiny_engine(paged=True)
    for r in reqs:
        eng.submit(r)
    host = _profiled_host_events(tmp_path, eng.run)
    assert all(len(r.out_tokens) == 4 for r in reqs)
    snap = export.snapshot()
    cs, hs = snap["metrics"]["counters"], snap["metrics"]["histograms"]
    assert cs["serve.requests"] == 3
    assert cs["serve.finished"] == 3
    assert cs["serve.ticks"] >= 1
    assert cs["serve.admissions"] >= 3
    # one TTFT per request; ITL for every subsequent token
    assert hs["serve.ttft_s"]["count"] == 3
    assert hs["serve.itl_s"]["count"] == sum(
        len(r.out_tokens) - 1 for r in reqs)
    assert hs["serve.request_latency_s"]["count"] == 3
    # pool counters mirrored from PoolStats: the duplicate prompt hits
    # the prefix registry
    assert cs.get("pool.prefix_hits", 0) >= 1
    assert "pool.occupancy" in snap["metrics"]["gauges"]
    assert "serve.token_budget_util" in snap["metrics"]["gauges"]
    # serve.tick spans in the profiler's trace cover every engine tick
    ticks = [n for n, _, _ in host if n == "serve.tick"]
    assert len(ticks) == cs["serve.ticks"]
    assert export.validate_snapshot(snap) == []


@pytest.mark.slow
def test_serve_engine_disabled_leaves_no_telemetry(tmp_path):
    """Metrics off: the registry stays empty.  Spans are on regardless,
    so a profiler trace still holds every phase of the dense tick."""
    eng, reqs = _tiny_engine(paged=False)
    for r in reqs:
        eng.submit(r)
    host = _profiled_host_events(tmp_path, eng.run)
    assert all(len(r.out_tokens) == 4 for r in reqs)
    assert metrics.registry().snapshot() == {
        "counters": {}, "gauges": {}, "histograms": {}}
    names = {n for n, _, _ in host}
    assert {"serve.tick", "serve.admit", "serve.decode", "serve.sample",
            "serve.readback", "serve.bookkeep"} <= names


def test_serve_cli_trace_out_records_engine_spans(tmp_path):
    """``launch/serve.py --trace-out DIR`` writes a profiler trace whose
    host plane carries every phase of the paged tick."""
    from repro.launch import serve
    d = str(tmp_path / "trace")
    serve.main(["--smoke", "--requests", "2", "--slots", "2",
                "--new-tokens", "2", "--max-len", "64", "--paged",
                "--trace-out", d])
    assert export.validate_trace_dir(d, require_spans=(
        "serve.tick", "serve.admit", "serve.prepare", "serve.tables",
        "serve.decode", "serve.sample", "serve.readback",
        "serve.bookkeep")) == []


def test_pool_stats_snapshot_and_reset():
    from repro.serve.paged_cache import PoolStats
    st = PoolStats()
    st.prefix_hits += 3
    st.prefix_misses += 1
    st.cow_copies += 2
    snap = st.snapshot()
    assert snap["prefix_hits"] == 3 and snap["cow_copies"] == 2
    assert st.prefix_hit_rate() == pytest.approx(0.75)
    st.reset()
    assert st.prefix_hits == 0 and st.cow_copies == 0
    assert st.prefix_hit_rate() == 0.0          # no division by zero
    assert set(PoolStats().snapshot()) == set(snap)


def test_tuning_state_rides_in_snapshot():
    """Satellite: the KernelPolicy decision log is exportable through
    the obs snapshot, and the digest matches the policy's own."""
    from repro.kernels.tuning import get_policy
    p = get_policy()
    p.resolve_impl("auto")                      # force >= 1 decision
    ts = export.tuning_snapshot()
    assert ts["backend"] == p.backend
    assert ts["tuning_digest"] == p.tuning_digest()
    assert ts["decision_log_len"] == len(p.decisions)
    assert ts["decision_log_len"] >= 1
    total = sum(n for srcs in ts["decisions"].values()
                for n in srcs.values())
    assert total == ts["decision_log_len"]
