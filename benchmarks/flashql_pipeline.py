"""One-dispatch flush benchmark: fused + async sharded serving vs lockstep.

A 4-shard fleet serves a mixed-aggregate workload (COUNT/MASK/SUM/AVG/
MIN/MAX/TOP-K/GROUP-BY over recurring predicate shapes, including a
spilling deep-range) two ways:

* **lockstep** — the PR-4 flush: cross-shard jit-of-vmap per signature
  group, then one reduce dispatch + one *synchronous* host transfer per
  reduce signature, all shards barriered;
* **pipelined** — the one-dispatch flush: each shard's batch compiles into
  ONE fused device program (sensing gathers feed every aggregate's
  weighted-popcount reduce device-side) returning a single payload, and
  shards dispatch back-to-back without barriering — shard k+1's sensing
  overlaps shard k's in-flight reduce, with ``block_until_ready`` only at
  the payload gather.

Both sides are asserted exact against a numpy oracle and each other.
Timing follows the dev notes (best-of-``REPS``, interleaved); per-flush
latency is additionally reported as p50/p95 next to the dispatch and
host-transfer counts per flush — the fused path must spend exactly one
transfer per shard program (and the unsharded scheduler exactly one per
flush, asserted in tests/test_query_pipeline.py).

Acceptance (skipped under ``--smoke``): pipelined serving must reach
>= 1.5x the lockstep throughput.

Run:  PYTHONPATH=src python benchmarks/flashql_pipeline.py [--smoke]
"""

from __future__ import annotations

import sys

import numpy as np

from _harness import REPS, interleaved_best_of, latency_summary, timed
from repro.compile_cache import enable_compile_cache
from repro.query import (
    Avg,
    Count,
    Eq,
    GroupBy,
    In,
    Mask,
    Max,
    Min,
    Query,
    Range,
    Sum,
    TopK,
    build_sharded_flashql,
)
from repro.query.ast import and_ as qand
from repro.query.oracle import np_select

NUM_SHARDS = 4
QUEUE_DEPTH = 16


def build_queries(rng, num_queries) -> list[Query]:
    """Recurring predicate shapes x a mix of every aggregate kind.

    Aggregates span two target columns, so one flush holds ~12 distinct
    reduce signatures — the lockstep flush pays one blocking host
    transfer per signature, the fused flush one payload per shard.
    """
    aggs = (
        Count(),
        Mask(),
        Sum("sales"),
        Avg("sales"),
        Min("sales"),
        Max("sales"),
        Sum("region"),
        Avg("region"),
        Min("region"),
        TopK("status", 3),
        GroupBy("status", Sum("sales")),
        GroupBy("region"),
    )
    qs: list[Query] = []
    i = 0
    while len(qs) < num_queries:
        r = int(rng.integers(0, 8))
        s = int(rng.integers(0, 4))
        preds = (
            Eq("region", r),
            qand(Eq("region", r), Eq("status", s)),
            In("status", [s, (s + 1) % 4]),
            Range("sales", 100 + r, 700 + 10 * s),  # spills: deep BSI range
        )
        qs.append(Query(preds[i % 4], agg=aggs[i % len(aggs)]))
        i += 1
    return qs[:num_queries]


def check_exact(results, queries, table, n) -> None:
    for q, r in zip(queries, results):
        sel = np_select(q.where, table, n)
        if isinstance(q.agg, Count):
            assert r.value == int(sel.sum()), q
        elif isinstance(q.agg, Sum):
            assert r.value == int(table[q.agg.column][sel].sum()), q
        elif isinstance(q.agg, Mask):
            got = np.asarray(r.value.to_bits()).astype(bool)
            np.testing.assert_array_equal(got, sel)


def flush_latencies(sq, queries) -> list[float]:
    """Serve ``queries`` timing every flush() individually."""
    for q in queries:
        sq.submit(q)
    lats = []
    while sq.pending:
        t, _ = timed(sq.flush)
        lats.append(t)
    sq.flush()  # fully-pruned tickets, if any
    return lats


def main() -> None:
    smoke = "--smoke" in sys.argv[1:]
    num_rows = 4_000 if smoke else 60_000
    num_queries = 16 if smoke else 48

    rng = np.random.default_rng(0)
    table = {
        "region": rng.integers(0, 8, num_rows),
        "status": rng.integers(0, 4, num_rows),
        "sales": rng.integers(0, 1_000, num_rows),
    }
    queries = build_queries(rng, num_queries)
    print(
        f"rows={num_rows}  queries={num_queries}  shards={NUM_SHARDS}  "
        f"queue_depth={QUEUE_DEPTH}  reps={REPS}  (smoke={smoke})"
    )

    lock = build_sharded_flashql(
        table, NUM_SHARDS, num_planes=4, queue_depth=QUEUE_DEPTH
    )
    pipe = build_sharded_flashql(
        table,
        NUM_SHARDS,
        num_planes=4,
        queue_depth=QUEUE_DEPTH,
        pipeline=True,
    )

    # warm both (jit + plan/exec/flush-program caches) and assert exactness
    res_lock = lock.serve(queries)
    res_pipe = pipe.serve(queries)
    check_exact(res_lock, queries, table, num_rows)
    check_exact(res_pipe, queries, table, num_rows)
    for a, b in zip(res_lock, res_pipe):
        if isinstance(a.query.agg, Mask):
            np.testing.assert_array_equal(
                np.asarray(a.value.words), np.asarray(b.value.words)
            )
        else:
            assert a.value == b.value, (a.query, a.value, b.value)
    print("lockstep == pipelined == numpy oracle")

    # dispatch + host-transfer accounting per flush (warm steady state),
    # read from the unified telemetry registry rather than scheduler fields
    for sq, name in ((lock, "lockstep"), (pipe, "pipelined")):
        c0 = sq.telemetry.snapshot()["counters"]
        sq.serve(queries)
        c1 = sq.telemetry.snapshot()["counters"]
        flushes = c1["flushes"] - c0.get("flushes", 0)
        transfers = c1["host_transfers"] - c0.get("host_transfers", 0)
        dispatches = c1.get("fused_dispatches", 0) - c0.get(
            "fused_dispatches", 0
        )
        print(
            f"{name:9s}: {flushes} flushes, "
            f"{transfers / flushes:.1f} host transfers and "
            f"{dispatches / flushes:.1f} fused dispatches "
            f"per flush"
        )
    active = len(pipe.store.active)
    c0 = pipe.telemetry.snapshot()["counters"]
    pipe.serve(queries)
    c1 = pipe.telemetry.snapshot()["counters"]
    assert c1["host_transfers"] - c0["host_transfers"] == (
        c1["flushes"] - c0["flushes"]
    ) * active, (
        "pipelined flush must spend exactly one transfer per shard program"
    )

    best = interleaved_best_of(
        {
            "pipelined": lambda: pipe.serve(queries),
            "lockstep": lambda: lock.serve(queries),
        }
    )
    t_pipe, t_lock = best["pipelined"], best["lockstep"]
    qps_pipe, qps_lock = num_queries / t_pipe, num_queries / t_lock
    print(
        f"lockstep : {t_lock:7.3f}s  {qps_lock:8.1f} q/s\n"
        f"pipelined: {t_pipe:7.3f}s  {qps_pipe:8.1f} q/s\n"
        f"speedup: {qps_pipe / qps_lock:.2f}x"
    )

    # per-flush latency distribution (p50/p95), interleaved across reps
    lats: dict[str, list[float]] = {"lockstep": [], "pipelined": []}
    for _ in range(REPS):
        lats["lockstep"].extend(flush_latencies(lock, queries))
        lats["pipelined"].extend(flush_latencies(pipe, queries))
    for name, samples in lats.items():
        s = latency_summary(samples)
        print(
            f"{name:9s} per-flush latency: p50={s['p50'] * 1e3:7.2f}ms  "
            f"p95={s['p95'] * 1e3:7.2f}ms  (n={s['n']})"
        )

    if not smoke:
        assert qps_pipe >= 1.5 * qps_lock, (
            f"fused + async flush must serve >= 1.5x the lockstep flush, "
            f"got {qps_pipe / qps_lock:.2f}x"
        )
        print(f"acceptance: {qps_pipe / qps_lock:.2f}x >= 1.5x OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
