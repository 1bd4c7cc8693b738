"""FlashQL aggregates: an OLAP-style workload on the bitmap index.

``SELECT SUM(sales) WHERE region IN (...) GROUP BY status`` — the classic
bit-sliced-index trick (Pinatubo/DrAcc lineage): SUM is the weighted
popcount Σ_b 2^b · popcount(mask ∧ slice_b) over the BSI slices the store
already programs, MIN/MAX walk the slices MSB→LSB, and TOP-K / GROUP BY
reduce per-group masks from the equality bitmaps.  Every aggregate is a
pluggable :class:`repro.query.aggregate.Aggregator`, so the same queries
run unchanged on one device or on a sharded fleet — here a range-striped,
``stripe_key``-sorted fleet that routes key-range queries to the few
shards whose stripe can match.

Run:  PYTHONPATH=src python examples/flashql_aggregates.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.query import (
    Avg,
    BatchScheduler,
    BitmapStore,
    Eq,
    FlashDevice,
    GroupBy,
    In,
    Max,
    Min,
    Query,
    Range,
    Sum,
    TopK,
    build_sharded_flashql,
)
from repro.query.ast import and_


def main() -> None:
    rng = np.random.default_rng(0)
    n = 50_000
    table = {
        "region": rng.integers(0, 8, n),  # 8 sales regions
        "status": rng.integers(0, 4, n),  # order status
        "sales": rng.integers(0, 1_000, n),  # order value
        "uid": rng.integers(0, 10_000, n),  # customer id
    }

    store = BitmapStore()
    store.ingest(table)
    dev = FlashDevice(num_planes=4)
    store.program(dev)
    sched = BatchScheduler(dev, store)

    eu = In("region", [0, 1, 2])
    queries = [
        Query(eu, agg=Sum("sales"), tag="SUM(sales) WHERE region EU"),
        Query(eu, agg=Avg("sales"), tag="AVG(sales) WHERE region EU"),
        Query(eu, agg=Min("sales"), tag="MIN(sales) WHERE region EU"),
        Query(eu, agg=Max("sales"), tag="MAX(sales) WHERE region EU"),
        Query(
            eu,
            agg=TopK("status", 2),
            tag="TOP-2 status WHERE region EU",
        ),
        Query(
            eu,
            agg=GroupBy("status", Sum("sales")),
            tag="SUM(sales) GROUP BY status",
        ),
        Query(
            and_(eu, Eq("status", 1)),
            agg=GroupBy("region", Avg("sales")),
            tag="AVG(sales) GROUP BY region",
        ),
    ]
    for r in sched.serve(queries):
        print(f"{r.query.tag:32s} -> {r.value}")

    # numpy cross-check for the headline query
    sel = np.isin(table["region"], [0, 1, 2])
    assert sched.serve([Query(eu, agg=Sum("sales"))])[0].value == int(
        table["sales"][sel].sum()
    )

    # the same aggregates on a range-striped fleet: Range on the stripe
    # key routes to the shards whose stripe overlaps [2000, 2999]
    sq = build_sharded_flashql(
        table, 4, policy="range", stripe_key="uid", num_planes=4
    )
    (r,) = sq.serve(
        [Query(Range("uid", 2000, 2999), agg=Sum("sales"))]
    )
    sel = (table["uid"] >= 2000) & (table["uid"] <= 2999)
    assert r.value == int(table["sales"][sel].sum())
    st = sq.stats()
    print(
        f"\nsharded fleet: SUM over uid range -> {r.value} "
        f"({st['shards_pruned']} of {st['num_shards']} shards pruned "
        "by range routing)"
    )
    print(sq.projection()["workload"], "projection OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
