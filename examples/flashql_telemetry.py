"""FlashQL observability end to end: serve a mixed workload on a
pipelined 4-shard fleet, read the unified telemetry snapshot, inspect
per-query sensing attribution and the slow-query log, and export a
Chrome trace of the flush lifecycle.

Open the written trace in chrome://tracing or https://ui.perfetto.dev —
the per-shard rows show shard k+1's compile/dispatch overlapping shard
k's in-flight transfer, which IS the pipelined flush.

Run:  PYTHONPATH=src python examples/flashql_telemetry.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.query import (
    Avg,
    Count,
    Eq,
    GroupBy,
    In,
    Query,
    Range,
    Sum,
    TopK,
    build_sharded_flashql,
    validate_trace,
)
from repro.query.ast import and_ as qand

TRACE_PATH = "flashql_trace.json"


def main():
    rng = np.random.default_rng(0)
    n = 20_000
    table = {
        "region": rng.integers(0, 8, n),
        "status": rng.integers(0, 4, n),
        "sales": rng.integers(0, 1_000, n),
    }
    queries = [
        Query(Eq("region", 3), agg=Count()),
        Query(qand(Eq("region", 1), Eq("status", 2)), agg=Sum("sales")),
        Query(In("status", [0, 3]), agg=Avg("sales")),
        Query(Range("sales", 120, 740), agg=Count()),  # deep range: spills
        Query(Eq("status", 1), agg=TopK("region", 3)),
        Query(Range("sales", 500, 999), agg=GroupBy("status")),
    ]

    sq = build_sharded_flashql(
        table, 4, num_planes=4, queue_depth=8, pipeline=True
    )
    # log any ticket that costs > 5 ms or > 40 sensing operations
    sq.telemetry.slow_latency_s = 5e-3
    sq.telemetry.slow_sensings = 40

    sq.serve(queries)  # warm: jit + plan/flush-program caches
    results = sq.serve(queries)

    print("== per-query sensing + latency attribution ==")
    for r in results:
        a = r.attribution
        print(
            f"  ticket {r.ticket:2d}  {r.query.where!r:48s} "
            f"sensings={a['sensings']:3d}  wordlines={a['wordlines']:4d}  "
            f"spills={a['spill_steps']}  shards={a['shards']}  "
            f"latency={r.latency_s * 1e3:6.2f}ms"
        )

    snap = sq.telemetry.snapshot()
    c = snap["counters"]
    print("\n== unified snapshot ==")
    print(
        f"  served={c['queries_served']:.0f}  flushes={c['flushes']:.0f}  "
        f"fused_dispatches={c['fused_dispatches']:.0f}  "
        f"host_transfers={c['host_transfers']:.0f}"
    )
    print(
        f"  plan cache: {snap['plan_cache']['hits']} hits / "
        f"{snap['plan_cache']['misses']} misses"
    )
    fl = snap["histograms"]["flush_latency_s"]
    print(
        f"  flush latency: p50={fl['p50'] * 1e3:.2f}ms  "
        f"p95={fl['p95'] * 1e3:.2f}ms  (n={fl['count']})"
    )
    proj = snap["projection"]
    print(
        f"  SSD projection: {proj['fc_time_s'] * 1e3:.2f} ms, "
        f"{proj['fc_energy_j']:.3f} J "
        f"({proj['speedup_vs_osp']:.1f}x vs OSP)"
    )

    print(f"\n== slow-query log ({len(snap['slow_queries'])} entries) ==")
    for entry in snap["slow_queries"][-3:]:
        print(
            f"  ticket {entry['ticket']}: {entry['predicate']} "
            f"({entry['latency_s'] * 1e3:.2f}ms, "
            f"{entry['attribution']['sensings']} sensings)"
        )

    trace = sq.telemetry.export_trace(TRACE_PATH)
    n_spans = validate_trace(trace)
    print(
        f"\nwrote {TRACE_PATH} ({n_spans} spans) — open it in "
        f"chrome://tracing or https://ui.perfetto.dev"
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
