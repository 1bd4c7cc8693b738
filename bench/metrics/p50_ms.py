"""Median latency of the requests due in the window, from each request's
due time to its last answer."""

from bench.stats import percentile

UNIT = "ms"


def read(r):
    p = percentile(r.latencies_s, 50)
    return None if p is None else p * 1e3
