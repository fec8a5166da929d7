"""Whole-tick roofline share of decoding, in %: for every tick of the
window, the least time of its work (``work.decode_tick``: every weight
read once plus the cache rows each session attends, against the FLOPs,
whichever bound is larger), summed, over the device's busy time in the
window.  Decoding is bound by bytes."""


def read(r):
    pos = r["window"].get("positions")
    busy = r["trace"]["busy_s"]
    if not pos or not busy:
        return None
    work, cfg, peak = r["work"], r["cfg"], r["peaks"]
    least = 0.0
    for p in pos:
        t = work.decode_tick(cfg, p)
        least += work.roofline_seconds(t["flops"], t["bytes"], peak)[0]
    return 100.0 * least / (busy * r["chips"])
