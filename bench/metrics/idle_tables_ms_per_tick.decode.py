"""Device idle time per engine tick spent building and sending the page
tables (the engine's ``serve.tables`` span), in ms; ``program_trace``'s
split of the window's idle gaps by program span."""
from bench import program_trace


def read(r):
    return program_trace.idle_ms_per_tick(r, ("serve.tables",))
