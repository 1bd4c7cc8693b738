"""The FlashQL query optimizer: sense once, answer many.

A dashboard fleet keeps re-asking a handful of hot filters (plus a MASK
drill-down) over one orders table.  With Flash-Cosmos the unit of device
work is the multi-wordline *sensing*, not the query — so the optimizer's
whole job is to answer the same stream with fewer sensings:

* operand-order variants (``status AND region`` vs ``region AND
  status``) canonicalize into one plan-cache entry and one sensing;
* queries sharing the expensive bit-sliced Range subtree sense it ONCE
  per flush — the shared latch result is spliced into every member of
  the fused flush program (cross-query CSE);
* after ``materialize_after`` compiles, a hot predicate's whole bitmap
  is ESP-programmed as a cached page, and later queries sense two
  wordlines instead of re-running the comparison network.  Appending
  rows invalidates the cached page (watch the counter); deleting rows
  does not — tombstones compose at read time.

Run:  PYTHONPATH=src python examples/flashql_optimizer.py
"""

from __future__ import annotations

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.query import Agg, Eq, In, Query, Range, build_sharded_flashql
from repro.query.ast import and_ as qand

NUM_ROWS = 8_000


def dashboards(tick: int) -> list[Query]:
    big = Range("amount", 150, 800)  # 10-bit BSI comparison network
    qs = [
        Query(qand(Eq("region", 1), big), tag="big in EU"),
        Query(qand(big, Eq("region", 1)), tag="big in EU (commuted)"),
        Query(qand(Eq("region", 3), big), tag="big in APAC"),
        Query(qand(In("status", [0, 1]), big), tag="big open"),
        Query(qand(Eq("region", 1), big), agg=Agg.MASK, tag="EU drill-down"),
    ]
    return qs


def main() -> None:
    rng = np.random.default_rng(0)
    table = {
        "region": rng.integers(0, 5, NUM_ROWS),
        "status": rng.integers(0, 3, NUM_ROWS),
        "amount": rng.integers(0, 1_000, NUM_ROWS),
    }
    fleet = build_sharded_flashql(
        table, num_shards=2, num_planes=2, pipeline=True,
        reserve_rows=2_000, materialize_after=4,
    )
    baseline = build_sharded_flashql(
        table, num_shards=2, num_planes=2, pipeline=True,
        reserve_rows=2_000, optimize=False,
    )

    for tick in range(1, 7):
        qs = dashboards(tick)
        m0, b0 = fleet.stats()["mws_commands"], baseline.stats()["mws_commands"]
        results = fleet.serve(qs)
        ref = baseline.serve(qs)
        for r, b in zip(results, ref):  # optimizer is semantically invisible
            assert r.query.agg is Agg.MASK or r.count == b.count
        spq = (fleet.stats()["mws_commands"] - m0) / len(qs)
        spq_base = (baseline.stats()["mws_commands"] - b0) / len(qs)
        opt = fleet.telemetry.snapshot()["optimizer"]
        print(
            f"tick {tick}: {spq:6.2f} sensings/query "
            f"(baseline {spq_base:6.2f})  "
            f"cse_hits={opt['cse_plan_hits']} "
            f"shared_senses={opt['cse_shared_senses']} "
            f"mat={opt['materializations']}/{opt['materialization_hits']} hits"
        )
        for r in results[:1]:
            print(f"  {r.query.tag:12s} -> {r.count}")

    # appends invalidate materialized pages (their bitmap would zero-miss
    # the new rows); deletes never do
    fleet.append({
        "region": rng.integers(0, 5, 500),
        "status": rng.integers(0, 3, 500),
        "amount": rng.integers(0, 1_000, 500),
    })
    fleet.serve(dashboards(7))
    fleet.delete(np.arange(10))
    fleet.serve(dashboards(8))
    opt = fleet.telemetry.snapshot()["optimizer"]
    print(
        f"after append+delete: invalidations="
        f"{opt['materialization_invalidations']} (append only — deletes "
        f"compose the tombstone page at read time)"
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
