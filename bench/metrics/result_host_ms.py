"""Host time per flush from dispatch to answers: the scheduler's
``transfer`` (``device_get``, so it includes the wait for the device)
plus ``reduce`` (unpack and finalize) spans, over the window's flushes."""

UNIT = "ms"


def read(r):
    if not r.flushes:
        return None
    s = sum(b - a for name, a, b in r.spans if name in ("transfer", "reduce"))
    return s / r.flushes * 1e3
