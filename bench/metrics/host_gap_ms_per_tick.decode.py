"""Device idle time per engine tick, in ms: the traced window less the
device's busy time, over the ticks of the window.  What the host's
per-tick work (scheduling, page tables, the token read-back) costs the
device."""


def read(r):
    ticks = r["window"].get("ticks")
    t = r["trace"]
    if not ticks or not t["busy_s"]:
        return None
    return 1000.0 * (t["window_s"] - t["busy_s"]) / ticks
