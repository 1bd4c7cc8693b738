"""Host time per flush blocked on the device: the scheduler's
``stage.wait_s`` counter (``block_until_ready`` on the flush's payload,
the first part of ``transfer``), over the window's flushes."""

from bench.metrics.flush_build_ms import per_flush_ms

UNIT = "ms"


def read(r):
    return per_flush_ms(r, "stage.wait_s")
