"""Streaming FlashQL: a live feed appends batches between query flushes.

An order stream lands on a sharded FlashQL fleet in small batches while
dashboards keep querying COUNT / SUM / GROUP BY between appends.  Each
append ESP-programs only its *delta* pages (tail words of the bitmaps the
new rows set, plus fresh pages for first-seen values), and plans over
columns whose index metadata did not change stay warm in every shard's
plan cache — watch the miss counter stop moving after the first tick.

Run:  PYTHONPATH=src python examples/flashql_streaming.py
"""

from __future__ import annotations

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.query import (
    Count,
    Eq,
    GroupBy,
    In,
    Query,
    Range,
    Sum,
    build_sharded_flashql,
)

REGIONS, STATUSES = 5, 3


def order_batch(rng, n, tick):
    return {
        # tick 3 introduces a brand-new region (id 7): GROUP BY grows a
        # group, and only region-sensing plans recompile
        "region": (
            np.full(n, 7) if tick == 3 else rng.integers(0, REGIONS, n)
        ),
        "status": rng.integers(0, STATUSES, n),
        "amount": rng.integers(1, 500, n),
    }


def main() -> None:
    rng = np.random.default_rng(0)
    base = order_batch(rng, 5_000, tick=0)
    fleet = build_sharded_flashql(
        base, num_shards=2, num_planes=2, reserve_rows=2_000
    )

    dashboards = [
        Query(Range("amount", 100, None), tag="big orders"),
        Query(In("status", [0, 1]), agg=Sum("amount"), tag="open value"),
        Query(Eq("status", 2), agg=GroupBy("region", Count()),
              tag="closed by region"),
        # senses the region column: recompiles exactly once, at tick 3,
        # when region 7 first appears (every other plan stays warm)
        Query(Eq("region", 7), tag="launch region"),
    ]

    total = 5_000
    for tick in range(1, 6):
        batch = order_batch(rng, 400, tick)
        pages = fleet.append(batch)
        total += 400
        results = fleet.serve(dashboards)
        s = fleet.stats()
        print(f"tick {tick}: +400 rows (total {total}), "
              f"{pages} delta page programs")
        for r in results:
            print(f"  {r.query.tag:18s} -> {r.value}")
        print(f"  plan cache: {s['plan_cache_hits']} hits / "
              f"{s['plan_cache_misses']} misses; "
              f"delta ESP programs so far: {s['esp_delta_programs']}")

    proj = fleet.projection()
    print(
        f"fleet SSD projection: FC {proj['fc_time_s'] * 1e3:.2f} ms, "
        f"{proj['fc_energy_j']:.3f} J on {proj['num_devices']} chips, "
        f"{sum(p['esp_programs'] for p in proj['per_shard'])} delta ESP "
        f"programs ({proj['speedup_vs_osp']:.1f}x vs OSP)"
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
