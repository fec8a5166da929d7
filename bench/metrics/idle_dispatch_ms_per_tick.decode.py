"""Device idle time per engine tick spent in the jitted tick's call
(the engine's ``serve.decode`` span: argument transfer, output
allocation, enqueue), in ms; ``program_trace``'s split of the window's
idle gaps by program span."""
from bench import program_trace


def read(r):
    return program_trace.idle_ms_per_tick(r, ("serve.decode",))
