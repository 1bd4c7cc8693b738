"""Incremental-ingest benchmark: delta-page programming + warm-plan serving.

A live FlashQL index absorbs append batches between query flushes.  Two
acceptance criteria (the first always asserted, the wall-clock gate
skipped under ``--smoke``):

* **append cost scales with delta rows, not total rows** — the SAME
  batch appended to a base store and to a 10x larger store programs the
  SAME number of pages (asserted via the flashsim ESP-program counter),
  and a small fraction of what a full index reprogram pays;
* **warm-plan reuse across appends beats full-rebuild serving** — the
  steady-state update loop (append the batch, serve the query mix on the
  live index, plans warm) must reach >= the baseline that handles every
  update the only way pre-mutable FlashQL could: rebuild the bitmap
  store from scratch, ESP-program a fresh device, recompile every plan,
  then serve.

Timing is best-of-REPS *interleaved* via ``benchmarks/_harness.py`` —
run-to-run noise on shared machines is 3-4x.

Run:  PYTHONPATH=src python benchmarks/flashql_ingest.py [--smoke]
"""

from __future__ import annotations

import sys

import numpy as np

from _harness import REPS, interleaved_best_of
from repro.compile_cache import enable_compile_cache
from repro.query import (
    BatchScheduler,
    BitmapStore,
    Eq,
    FlashDevice,
    In,
    Query,
    Sum,
)
from repro.query.ast import and_ as qand

BATCH = 64  # appended rows per update


def build_table(rng, n):
    """OLAP-style table whose value universe is fully populated, so the
    same append batch grows the same pages at every store size."""
    t = {
        "region": rng.integers(0, 8, n),
        "status": rng.integers(0, 4, n),
        "sales": rng.integers(0, 1_000, n),
    }
    for col, card in (("region", 8), ("status", 4), ("sales", 1_000)):
        k = min(card, n)
        t[col][:k] = np.arange(k)
    return t


def build_queries(rng, num_queries) -> list[Query]:
    qs: list[Query] = []
    while len(qs) < num_queries:
        r = int(rng.integers(0, 8))
        s = int(rng.integers(0, 4))
        qs.append(Query(qand(Eq("region", r), Eq("status", s))))
        qs.append(Query(In("status", [s, (s + 1) % 4]), agg=Sum("sales")))
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
    num_queries = 8 if smoke else 32
    reserve = BATCH * (REPS + 6)

    rng = np.random.default_rng(0)
    table = build_table(rng, num_rows)
    queries = build_queries(rng, num_queries)
    batch = {  # values drawn from the (fully populated) base universe
        "region": rng.integers(0, 8, BATCH),
        "status": rng.integers(0, 4, BATCH),
        "sales": rng.integers(0, 1_000, BATCH),
    }
    print(
        f"rows={num_rows}  queries={num_queries}  batch={BATCH}  "
        f"reps={REPS}  (smoke={smoke})"
    )

    # -- criterion 1: O(delta) page programs, independent of store size ----
    sched = build_scheduler(table, queries, reserve)
    big = build_scheduler(
        build_table(np.random.default_rng(1), 10 * num_rows),
        queries,
        reserve,
    )
    rebuild_pages = len(sched.store.logical)  # a full reprogram writes all
    p_base = sched.append(batch)
    p_big = big.append(batch)
    print(
        f"append({BATCH} rows): {p_base} delta page programs at "
        f"{num_rows} rows, {p_big} at {10 * num_rows} rows "
        f"(full reprogram = {rebuild_pages} pages)"
    )
    assert p_base == p_big, (
        f"append cost must scale with delta rows, not total rows: "
        f"{p_base} vs {p_big} pages"
    )
    assert p_base < rebuild_pages / 2, (
        f"delta programs ({p_base}) must stay well below a full "
        f"reprogram ({rebuild_pages})"
    )

    # -- correctness: the live index now equals base + batch; it must
    # serve exactly what a rebuild-from-scratch on the same rows serves
    updated = {c: np.concatenate([table[c], batch[c]]) for c in table}

    def rebuild_and_serve():
        store = BitmapStore()
        store.ingest(updated)
        dev = FlashDevice(num_planes=4)
        store.program(dev)
        return BatchScheduler(dev, store, max_batch=len(queries)).serve(
            queries
        )

    got = [r.value for r in sched.serve(queries)]
    want = [r.value for r in rebuild_and_serve()]
    assert got == want, "incremental serving diverges from rebuild oracle"

    # -- criterion 2a: appends from a stable value universe keep EVERY
    # plan warm (no recompiles across the update)
    misses = sched.compiler.misses
    sched.append(batch)
    sched.serve(queries)
    assert sched.compiler.misses == misses, (
        "value-stable appends must not invalidate any cached plan"
    )

    # -- criterion 2b: live update loop vs full-rebuild serving ------------
    def append_and_serve():
        sched.append(batch)
        return sched.serve(queries)
    best = interleaved_best_of(
        {"incremental": append_and_serve, "rebuild": rebuild_and_serve}
    )
    t_inc, t_reb = best["incremental"], best["rebuild"]
    qps_inc = num_queries / t_inc
    qps_reb = num_queries / t_reb
    print(
        f"incremental (append+serve, warm) : {t_inc:7.3f}s  "
        f"{qps_inc:8.1f} q/s"
    )
    print(
        f"full rebuild (reingest+reprogram): {t_reb:7.3f}s  "
        f"{qps_reb:8.1f} q/s"
    )
    print(f"speedup: {qps_inc / qps_reb:.2f}x")
    # ingest + plan-cache accounting and the SSD projection all read out
    # of one telemetry snapshot (counters + registered provider sections)
    snap = sched.telemetry.snapshot()
    counters, cache = snap["counters"], snap["plan_cache"]
    print(
        f"rows appended: {counters['rows_appended']}  delta ESP programs: "
        f"{counters['esp_delta_programs']}  plan cache: "
        f"{cache['hits']} hits / {cache['misses']} misses"
    )
    proj = snap["projection"]
    print(
        f"SSD projection incl. delta programs: "
        f"{proj['fc_time_s'] * 1e3:.2f} ms, {proj['fc_energy_j']:.3f} J, "
        f"{proj['esp_programs']} ESP programs "
        f"({proj['speedup_vs_osp']:.1f}x vs OSP)"
    )

    if not smoke:
        assert qps_inc >= qps_reb, (
            f"warm-plan incremental serving must reach the full-rebuild "
            f"baseline, got {qps_inc / qps_reb:.2f}x"
        )
        print(f"acceptance: {qps_inc / qps_reb:.2f}x >= 1x OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
