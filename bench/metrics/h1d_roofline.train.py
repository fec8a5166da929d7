"""Roofline share of the H1D kernels in training, in %: the least time
of the H1D operator's required work (``work.train_step`` ``h1d_flops``
and ``h1d_bytes``, forward and backward over every layer, at
``work.roofline_seconds``) times the window's steps, over the device
time of the ``band_*`` and ``sub_*`` kernel families."""
from bench import program_trace


def read(r):
    steps = r["window"].get("steps")
    t = program_trace.family_seconds(r, ("band_", "sub_"))
    if not steps or t is None:
        return None
    mix, work = r["mix"], r["work"]
    w = work.train_step(r["cfg"], mix["batch"], mix["seq_len"])
    least = work.roofline_seconds(w["h1d_flops"], w["h1d_bytes"],
                                  r["peaks"])[0]
    return 100.0 * least * steps / (t * r["chips"])
