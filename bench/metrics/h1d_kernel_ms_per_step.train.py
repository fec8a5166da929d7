"""Device time of the H1D kernels (the ``band_*`` and ``sub_*``
families, forward and backward) per training step of the window, in ms:
their kernel events' time (``program_trace.kernel_seconds``) over the
steps."""
from bench import program_trace


def read(r):
    steps = r["window"].get("steps")
    t = program_trace.family_seconds(r, ("band_", "sub_"))
    if not steps or t is None:
        return None
    return 1000.0 * t / steps
