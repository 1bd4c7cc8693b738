"""Device time per flush in FlashQL's Pallas kernels: the traced window's
``XLA Ops`` time of ops named ``flashql_*`` (each ``pallas_call`` on the
served path carries such a ``name``), over the window's flushes."""

UNIT = "ms"
PREFIX = "flashql_"
# a counter of the program's flush stages: a program that records it also
# names its kernels, so a traced window of its flushes must show them
STAGED = "stage.launch_s"


def read(r):
    if r.trace is None or not r.flushes:
        return None
    seconds = sum(s for op, s in r.trace.op_s.items() if op.startswith(PREFIX))
    if seconds:
        return seconds / r.flushes * 1e3
    if STAGED not in r.counters:
        return None
    raise LookupError(
        f"no {PREFIX}* kernel op in a traced window of {r.flushes} flushes; "
        f"longest ops: {r.trace.breakdown()['device_ops'][:3]}"
    )
