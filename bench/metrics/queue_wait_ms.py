"""Admission-queue wait per query served in the window: the scheduler's
``queue_wait_s`` counter (from a query's submission to the start of the
flush that serves it) over ``queries_served``, both as deltas."""

UNIT = "ms"


def read(r):
    served = r.counters.get("queries_served", 0)
    if "queue_wait_s" not in r.counters or not served:
        return None
    return r.counters["queue_wait_s"] / served * 1e3
