"""Share of the HBM roofline that the flush programs reach.

The bytes are the work the flushes' plans must move, whatever the
implementation: every page sensed after CSE and materialization (the
``wordlines_sensed`` delta, aggregate planes included, padding left out)
read once, plus one result page written for each MASK answer, at the
store's page size.  Their least time is bytes over the chip's HBM
bandwidth (``bench/peaks.json``); the share is that time over the device
time of the flush programs in the traced window.
"""

from bench.metrics.flush_device_ms import flush_runs

UNIT = "%"


def work_bytes(wordlines_sensed: float, mask_answers: int, page_bytes: int) -> float:
    return (wordlines_sensed + mask_answers) * page_bytes


def read(r):
    if r.trace is None or not r.flushes:
        return None
    seconds, _ = flush_runs(r)
    moved = work_bytes(
        r.counters.get("wordlines_sensed", 0), r.mask_answers, r.page_bytes
    )
    if not moved:
        return None
    return 100.0 * moved / r.peak["hbm_bytes_per_s"] / seconds
