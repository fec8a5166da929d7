#!/usr/bin/env python
"""CI telemetry smoke validator.

Validates the artifacts a serve run wrote with ``--trace-out DIR`` and
``--prom-out`` -- the ``jax.profiler`` trace directory, whose host plane
must carry the engine's tick spans, and the Prometheus text exposition
-- against the pinned schemas in ``repro.obs.export`` (the same
validators the unit tests use, so CI and tests cannot drift apart).

    python scripts/check_telemetry.py --trace-dir /tmp/trace \
        --prom /tmp/metrics.prom [--require-kernel-traffic]

Exits non-zero listing every schema violation.
"""
import argparse
import sys

# every phase of a paged engine tick, as ``scripts/ci.sh``'s serve run
# writes them
TICK_SPANS = ("serve.tick", "serve.admit", "serve.prepare", "serve.tables",
              "serve.decode", "serve.sample", "serve.readback",
              "serve.bookkeep")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-dir", required=True,
                    help="profiler trace directory written by --trace-out")
    ap.add_argument("--prom", required=True,
                    help="Prometheus text written by --prom-out")
    ap.add_argument("--require-kernel-traffic", action="store_true",
                    help="fail unless the kernel.* analytic HBM/FLOP "
                         "counters are exported (needs a kernel-path "
                         "impl, e.g. --decode-impl pallas_interpret on "
                         "CPU)")
    args = ap.parse_args(argv)

    from repro.obs import export

    errs = [f"trace: {e}" for e in export.validate_trace_dir(
        args.trace_dir, require_spans=TICK_SPANS)]

    with open(args.prom) as f:
        text = f.read()
    required = ("repro_serve_ticks_total", "repro_serve_requests_total",
                "repro_serve_finished_total", "repro_serve_ttft_s_bucket")
    if args.require_kernel_traffic:
        required += ("repro_kernel_launches_total",
                     "repro_kernel_hbm_read_bytes_total",
                     "repro_kernel_flops_total")
    errs += [f"prom: {e}" for e in export.validate_prometheus_text(
        text, require_metrics=required)]

    if errs:
        for e in errs:
            print(f"FAIL {e}", file=sys.stderr)
        return 1
    print(f"telemetry OK: {len(TICK_SPANS)} engine spans on the trace's host "
          f"plane, prometheus text valid")
    return 0


if __name__ == "__main__":
    sys.exit(main())
