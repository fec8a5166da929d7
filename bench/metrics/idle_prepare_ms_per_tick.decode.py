"""Device idle time per engine tick spent in admission and page
preparation (the engine's ``serve.admit`` and ``serve.prepare`` spans:
page allocation and copies), in ms; ``program_trace``'s split of the
window's idle gaps by program span."""
from bench import program_trace


def read(r):
    return program_trace.idle_ms_per_tick(r, ("serve.admit",
                                              "serve.prepare"))
