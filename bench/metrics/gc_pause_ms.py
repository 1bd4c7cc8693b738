"""Pause of the serving process's garbage collector over the window: the
telemetry's ``gc_pause_s`` counter, every pass of every generation."""

UNIT = "ms"


def read(r):
    if "gc_pause_s" not in r.counters:
        return None
    return r.counters["gc_pause_s"] * 1e3
