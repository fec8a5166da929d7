"""Device time of the H1D decode kernels (the ``decode_*`` families:
paged attend and cache update) per engine tick of the window, in ms:
their kernel events' time (``program_trace.kernel_seconds``) over the
ticks."""
from bench import program_trace


def read(r):
    ticks = r["window"].get("ticks")
    t = program_trace.family_seconds(r, ("decode_",))
    if not ticks or t is None:
        return None
    return 1000.0 * t / ticks
