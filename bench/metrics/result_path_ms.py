"""Host time per flush on the result path once the device is done: the
scheduler's ``stage.copy_s`` (``device_get`` of the ready payload) plus
``stage.reduce_s`` (unpacking it) counters, over the window's flushes."""

from bench.metrics.flush_build_ms import per_flush_ms

UNIT = "ms"


def read(r):
    return per_flush_ms(r, "stage.copy_s", "stage.reduce_s")
