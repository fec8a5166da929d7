"""Roofline share of the H1D decode kernels, in %: for every tick of
the window, the least time of the H1D decode operator's work at that
tick's positions (``work.decode_tick`` ``h1d_flops`` and ``h1d_bytes``:
the cache rows each session attends and the rows the update reads and
writes, at ``work.roofline_seconds``), summed, over the device time of
the ``decode_*`` kernel families."""
from bench import program_trace


def read(r):
    pos = r["window"].get("positions")
    t = program_trace.family_seconds(r, ("decode_",))
    if not pos or t is None:
        return None
    work, cfg, peak = r["work"], r["cfg"], r["peaks"]
    least = 0.0
    for p in pos:
        w = work.decode_tick(cfg, p)
        least += work.roofline_seconds(w["h1d_flops"], w["h1d_bytes"],
                                       peak)[0]
    return 100.0 * least / (t * r["chips"])
