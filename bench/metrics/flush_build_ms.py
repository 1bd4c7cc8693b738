"""Host time per flush looking up or building the flush's CSE rewrite and
fused program: the scheduler's ``stage.build_s`` counter (the ``build``
stage inside ``dispatch``), over the window's flushes."""

UNIT = "ms"


def per_flush_ms(r, *counters):
    """The sum of the ``counters`` deltas over the window's flushes, in ms;
    None where a counter is absent (a program that does not record it)."""
    if not r.flushes or any(c not in r.counters for c in counters):
        return None
    return sum(r.counters[c] for c in counters) / r.flushes * 1e3


def read(r):
    return per_flush_ms(r, "stage.build_s")
