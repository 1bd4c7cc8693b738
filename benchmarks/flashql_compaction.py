"""Compaction benchmark: tombstone deletes, stripe rebuild, serving parity.

A live FlashQL index absorbs a heavy delete wave (40% of rows), compacts
the tombstoned capacity away, and keeps serving.  Three acceptance
criteria (all asserted, in ``--smoke`` too — the wall-clock gate is a
ratio between two sides timed in the same interleaved rep window, so it
is robust to machine-load swings):

* **bit-exact serving across the rebuild** — the compacted index must
  serve exactly what a fresh ingest of the surviving rows serves, before
  AND after follow-up appends into the reclaimed headroom;
* **post-compaction serving within 1.1x of fresh-ingest serving** — a
  rebuilt stripe is a first-class stripe: same layout, same fused plans,
  no lingering tombstone overhead beyond the one valid-page wordline
  every plan (fresh or compacted) already senses;
* **capacity actually reclaimed** — ``capacity_rows - live_rows``
  headroom is restored to at least the pre-delete reserve, and the
  flashsim projection charges the erases + ESP reprograms the rebuild
  paid (write amplification is reported from the same counters).

Timing is best-of-REPS *interleaved* via ``benchmarks/_harness.py``.

Run:  PYTHONPATH=src python benchmarks/flashql_compaction.py [--smoke]
"""

from __future__ import annotations

import sys

import numpy as np

from _harness import interleaved_best_of
from repro.compile_cache import enable_compile_cache
from repro.query import (
    BatchScheduler,
    BitmapStore,
    Eq,
    FlashDevice,
    In,
    Query,
    Range,
    Sum,
)
from repro.query.ast import and_ as qand

DELETE_FRAC = 0.4  # rows tombstoned before the compaction under test


def build_table(rng, n):
    return {
        "region": rng.integers(0, 8, n),
        "status": rng.integers(0, 4, n),
        "sales": rng.integers(0, 1_000, n),
    }


def build_queries(rng, num_queries) -> list[Query]:
    qs: list[Query] = []
    while len(qs) < num_queries:
        r = int(rng.integers(0, 8))
        s = int(rng.integers(0, 4))
        qs.append(Query(qand(Eq("region", r), Eq("status", s))))
        qs.append(Query(In("status", [s, (s + 1) % 4]), agg=Sum("sales")))
        qs.append(Query(Range("sales", 100, 700), agg=Sum("sales")))
    return qs[:num_queries]


def build_scheduler(table, queries, reserve) -> BatchScheduler:
    store = BitmapStore()
    store.ingest(table, reserve_rows=reserve)
    dev = FlashDevice(num_planes=4)
    store.program(dev, warmup=queries[:2])
    sched = BatchScheduler(dev, store, max_batch=len(queries))
    sched.serve(queries)  # warm: jit + plan caches
    return sched


def main() -> None:
    smoke = "--smoke" in sys.argv[1:]
    num_rows = 4_000 if smoke else 50_000
    num_queries = 9 if smoke else 30
    reserve = max(256, num_rows // 8)

    rng = np.random.default_rng(7)
    table = build_table(rng, num_rows)
    queries = build_queries(rng, num_queries)
    doomed = rng.choice(num_rows, int(num_rows * DELETE_FRAC), replace=False)
    print(
        f"rows={num_rows}  queries={num_queries}  "
        f"deletes={doomed.size}  (smoke={smoke})"
    )

    # -- mutate: delete wave, then compact the tombstones away -------------
    sched = build_scheduler(table, queries, reserve)
    sched.delete(doomed)
    assert sched.store.tombstone_density > 0.35
    stats = sched.compact()
    print(
        f"compact: dropped {stats['rows_dropped']} rows, "
        f"{stats['blocks_erased']} block erases, "
        f"{stats['words_reprogrammed']} words reprogrammed "
        f"in {stats['seconds']:.3f}s"
    )
    headroom = sched.store.capacity_rows - sched.store.live_rows
    assert headroom >= reserve, (
        f"compaction must restore reserve headroom: {headroom} < {reserve}"
    )

    # -- baseline: fresh ingest of exactly the surviving rows, at the SAME
    # capacity the compacted store kept (identical page widths — the gate
    # isolates rebuild artifacts, not reserve-sizing choices)
    live = np.setdiff1d(np.arange(num_rows), doomed)
    fresh = build_scheduler(
        {c: v[live] for c, v in table.items()},
        queries,
        sched.store.capacity_rows - live.size,
    )

    # -- correctness: compacted serving == fresh-ingest serving, and the
    # reclaimed headroom absorbs appends identically on both sides
    def check_parity():
        got = sched.serve(queries)
        want = fresh.serve(queries)
        for q, g, w in zip(queries, got, want):
            assert g.count == w.count and g.value == w.value, (
                f"compacted index diverges from fresh ingest on {q}"
            )

    check_parity()
    batch = build_table(rng, 128)
    sched.append(batch)
    fresh.append(batch)
    check_parity()
    print("parity: compacted serving == fresh-ingest serving OK")

    # -- gate: post-compaction serving within 1.1x of fresh ingest ---------
    rounds = 20 if smoke else 5  # amortise fixed per-serve overhead

    def serve_rounds(s):
        for _ in range(rounds):
            s.serve(queries)

    best = interleaved_best_of(
        {
            "compacted": lambda: serve_rounds(sched),
            "fresh": lambda: serve_rounds(fresh),
        }
    )
    t_c = best["compacted"] / rounds
    t_f = best["fresh"] / rounds
    print(
        f"compacted    : {t_c:7.3f}s  {num_queries / t_c:8.1f} q/s\n"
        f"fresh ingest : {t_f:7.3f}s  {num_queries / t_f:8.1f} q/s"
    )
    assert t_c <= 1.1 * t_f, (
        f"post-compaction serving must stay within 1.1x of fresh-ingest "
        f"serving, got {t_c / t_f:.2f}x"
    )
    print(f"acceptance: {t_c / t_f:.2f}x <= 1.1x OK")

    # -- wear accounting: WA + erases out of one telemetry snapshot --------
    snap = sched.telemetry.snapshot()
    counters = snap["counters"]
    s = sched.stats()
    print(
        f"write amplification: {s['write_amplification']:.2f} "
        f"({counters['words_programmed']} words programmed / "
        f"{counters['words_written']} logical)  "
        f"block erases: {counters['block_erases']}"
    )
    assert s["write_amplification"] > 1.0, (
        "a compaction that reprograms live pages must show up as WA > 1"
    )
    proj = snap["projection"]
    assert proj["block_erases"] == counters["block_erases"]
    print(
        f"SSD projection incl. rebuild: {proj['fc_time_s'] * 1e3:.2f} ms, "
        f"{proj['fc_energy_j']:.3f} J, {proj['esp_programs']} ESP "
        f"programs, {proj['block_erases']} erases"
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
