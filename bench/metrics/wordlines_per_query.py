"""Pages sensed per query served in the window, after CSE and
materialization: the ``wordlines_sensed`` counter over ``queries_served``,
both as deltas."""

UNIT = "pages/query"


def read(r):
    served = r.counters.get("queries_served", 0)
    if not served:
        return None
    return r.counters.get("wordlines_sensed", 0) / served
