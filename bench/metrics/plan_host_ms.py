"""Host time per flush in admission, plan compile, CSE and flush assembly:
the scheduler's ``compile`` plus ``dispatch`` spans, over the window's
flushes."""

UNIT = "ms"


def read(r):
    if not r.flushes:
        return None
    s = sum(b - a for name, a, b in r.spans if name in ("compile", "dispatch"))
    return s / r.flushes * 1e3
