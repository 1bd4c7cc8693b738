"""Host-to-device bytes per flush, in KiB: the scheduler's ``h2d_bytes``
counter (index arrays and gathers of each flush program built, extra-plane
stacks, the valid-words mask and whole-store snapshots), over the window's
flushes."""

UNIT = "KiB/flush"


def read(r):
    if "h2d_bytes" not in r.counters or not r.flushes:
        return None
    return r.counters["h2d_bytes"] / r.flushes / 1024
