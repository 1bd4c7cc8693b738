"""FlashQL quickstart: table -> bitmap index -> batched queries -> SSD model.

The BMI scenario of the paper's §7 as a *query service*: ingest a user
table, ESP-program its bitmap indexes, serve a mixed batch of COUNT/MASK
queries on the vectorized multi-plane engine, and project the served
traffic onto the full-scale SSD model.

Run:  PYTHONPATH=src python examples/flashql_demo.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.query import (
    Agg,
    BatchScheduler,
    BitmapStore,
    Eq,
    FlashDevice,
    In,
    Not,
    Query,
    Range,
)
from repro.query.ast import and_, or_


def main() -> None:
    rng = np.random.default_rng(0)
    n = 100_000
    table = {
        "country": rng.integers(0, 8, n),
        "device": rng.integers(0, 4, n),
        "age": rng.integers(13, 90, n),
    }

    # 1. ingest: equality bitmaps per (column, value) + bit-sliced index
    store = BitmapStore()
    store.ingest(table)

    # 2. program a 4-plane device; warmup queries steer §6.3 placement
    dev = FlashDevice(num_planes=4)
    store.program(dev, warmup=[Query(In("country", [0, 1, 2]))])

    # 3. serve a batch of queries
    sched = BatchScheduler(dev, store)
    queries = [
        Query(Eq("country", 3), tag="users in country 3"),
        Query(
            and_(Eq("country", 3), Eq("device", 1)),
            tag="... on mobile",
        ),
        Query(In("country", [0, 1, 2]), tag="EU countries"),
        Query(Range("age", 18, 35), tag="18-35 year olds"),
        Query(
            and_(Not(Eq("device", 0)), Range("age", None, 17)),
            tag="minors off desktop",
        ),
        Query(
            or_(Eq("device", 2), Eq("device", 3)),
            agg=Agg.MASK,
            tag="tablet/tv bitmap",
        ),
    ]
    for r in sched.serve(queries):
        if r.query.agg is Agg.COUNT:
            print(f"{r.query.tag:24s} -> {r.count:7d} rows")
        else:
            bits = np.asarray(r.mask.to_bits())
            print(f"{r.query.tag:24s} -> bitmap, {int(bits.sum())} set")

    # 4. stats + full-scale time/energy projection (Table-1 SSD)
    s = sched.stats()
    print(
        f"\nserved {s['queries_served']} queries in "
        f"{s['vmap_batches']} vmap batches + {s['eager_plans']} eager; "
        f"plan cache {s['plan_cache_hits']}/{s['plan_cache_misses']} h/m"
    )
    p = sched.projection()
    print(
        f"full-scale SSD projection: {p['fc_time_s'] * 1e3:.2f} ms, "
        f"{p['fc_energy_j']:.3f} J "
        f"({p['speedup_vs_osp']:.1f}x vs OSP, "
        f"{p['energy_ratio_vs_osp']:.1f}x energy)"
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
