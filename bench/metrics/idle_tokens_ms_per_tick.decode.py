"""Device idle time per engine tick spent on the sampled tokens (the
engine's ``serve.sample``, ``serve.readback`` and ``serve.bookkeep``
spans, and ``serve.tick`` outside its phases), in ms;
``program_trace``'s split of the window's idle gaps by program span."""
from bench import program_trace


def read(r):
    return program_trace.idle_ms_per_tick(r, ("serve.sample",
                                              "serve.readback",
                                              "serve.bookkeep",
                                              "serve.tick"))
