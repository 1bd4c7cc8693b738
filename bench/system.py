"""The system under test, as the benchmark drives it.

The only module of the benchmark that imports the program: FlashQL's
``BitmapStore`` ingests the generated table, ``FlashDevice`` holds it on
the default device with the default kernel path, and ``BatchScheduler``
serves it through ``submit``/``flush``.  Predicates arrive as the plain
nested tuples of :mod:`bench.traffic` and answers leave in the plain form
of :mod:`bench.reference`; nothing else crosses this boundary except the
scheduler's telemetry spans and counters.
"""

from __future__ import annotations

import gc

import numpy as np

# flush-lifecycle spans of the scheduler's telemetry (perf_counter clock)
FLUSH_SPANS = ("compile", "dispatch", "transfer", "reduce")


def to_pred(spec: tuple):
    from repro.query import Eq, In
    from repro.query.ast import and_, or_

    op = spec[0]
    if op == "eq":
        return Eq(spec[1], spec[2])
    if op == "in":
        return In(spec[1], list(spec[2]))
    if op in ("and", "or"):
        kids = [to_pred(c) for c in spec[1:]]
        return and_(*kids) if op == "and" else or_(*kids)
    raise ValueError(f"unknown predicate {op!r}")


def to_query(agg: str, spec: tuple):
    from repro.query import Count, Mask, Query

    return Query(to_pred(spec), agg=Mask() if agg == "mask" else Count())


class System:
    """One ingested, programmed and served FlashQL store."""

    def __init__(self, cfg: dict, table: dict, placement: list[tuple]):
        from repro.query import (
            BatchScheduler,
            BitmapStore,
            FlashDevice,
            Query,
            Telemetry,
        )

        store = BitmapStore()
        store.ingest(table, reserve_rows=int(cfg["reserve_rows"]))
        device = FlashDevice(num_planes=int(cfg["planes"]))
        store.program(device, warmup=[Query(to_pred(p)) for p in placement])
        self.sched = BatchScheduler(
            device,
            store,
            max_batch=int(cfg["max_batch"]),
            # room for every span of a long window (about 13 per flush)
            telemetry=Telemetry(trace_capacity=1 << 18),
        )
        self.page_bytes = int(store.words) * 4
        self.pages = len(device.store)
        self.store_bytes = int(device.store.snapshot().nbytes)

    @property
    def pending(self) -> int:
        return self.sched.pending

    def submit(self, agg: str, spec: tuple) -> int:
        return self.sched.submit(to_query(agg, spec))

    def flush(self) -> dict[int, object]:
        """One flush of at most ``max_batch`` pending queries, FIFO;
        returns ``{ticket: answer}`` in the reference's plain form."""
        out = {}
        for ticket, res in self.sched.flush().items():
            v = res.value
            out[ticket] = (
                np.asarray(v.words, dtype=np.uint32)
                if hasattr(v, "words")
                else int(v)
            )
        return out

    def counters(self) -> dict[str, float]:
        return dict(self.sched.telemetry.counters)

    def spans(self, since: float) -> list[tuple[str, float, float]]:
        """Flush-lifecycle spans that started at or after ``since``."""
        return [
            (name, t0, t1)
            for name, _cat, _tid, t0, t1, _args in self.sched.telemetry.trace
            if name in FLUSH_SPANS and t0 >= since
        ]

    def close(self) -> None:
        """Free the store, its device copy and the compiled flushes."""
        del self.sched
        gc.collect()
