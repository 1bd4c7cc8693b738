"""FlashQL query-throughput benchmark: batched FlashDevice vs sequential.

BMI-style serving (paper §7): a user-activity table is indexed into
bitmaps; clients issue COUNT queries over a handful of recurring predicate
shapes.  We compare:

* **sequential** — one ``Planner.compile`` + ``FlashArray.fc_read`` +
  ``popcount`` per query, the seed repo's only execution mode;
* **flashql** — ``BatchScheduler``: plan-cache compile, shape-grouped
  ``jax.vmap`` batches on the packed multi-plane store, ONE batched
  popcount per flush.

Also prints the full-scale SSD projection of the served traffic (Table-1
geometry) and asserts the acceptance criteria: >= 64 queries per batch,
batched path measurably faster, and every result equal to the numpy oracle.
Timing is best-of-REPS interleaved via ``benchmarks/_harness.py``.

Run:  PYTHONPATH=src python benchmarks/flashql_throughput.py
"""

from __future__ import annotations

import numpy as np

from _harness import interleaved_best_of

from repro.compile_cache import enable_compile_cache
from repro.core.engine import FlashArray
from repro.core.planner import Planner
from repro.kernels.popcount import popcount
from repro.query import (
    Agg,
    BatchScheduler,
    BitmapStore,
    Eq,
    FlashDevice,
    In,
    Query,
)
from repro.query.ast import and_ as qand
from repro.query.compile import lower

NUM_ROWS = 200_000
NUM_QUERIES = 64


def build_queries(rng) -> list[Query]:
    """BMI-style COUNT traffic: a few hot shapes, many parameterizations."""
    qs: list[Query] = []
    while len(qs) < NUM_QUERIES:
        c = int(rng.integers(0, 8))
        d = int(rng.integers(0, 4))
        qs.append(Query(qand(Eq("country", c), Eq("device", d))))
        qs.append(Query(Eq("country", c), agg=Agg.COUNT))
        qs.append(
            Query(In("device", [d, (d + 1) % 4]), agg=Agg.COUNT)
        )
    return qs[:NUM_QUERIES]


def np_count(q: Query, table) -> int:
    from repro.query.ast import And, Eq, In

    def m(p):
        if isinstance(p, Eq):
            return table[p.column] == p.value
        if isinstance(p, In):
            return np.isin(table[p.column], p.values)
        assert isinstance(p, And)
        out = np.ones(len(next(iter(table.values()))), bool)
        for c in p.children:
            out &= m(c)
        return out

    return int(m(q.where).sum())


def main() -> None:
    rng = np.random.default_rng(0)
    table = {
        "country": rng.integers(0, 8, NUM_ROWS),
        "device": rng.integers(0, 4, NUM_ROWS),
    }
    store = BitmapStore()
    store.ingest(table)
    queries = build_queries(rng)

    # Both sides get one full warm pass first (jit/plan caches populated),
    # then we time steady-state serving — the regime a query-serving
    # system lives in.
    def run_sequential(arr: FlashArray) -> list[int]:
        counts = []
        for q in queries:
            plan = Planner(arr.layout).compile(lower(q.where, store))
            counts.append(int(popcount(arr.execute(plan))))
        return counts

    # -- sequential baseline: per-query plan + execute + popcount ----------
    arr = FlashArray()
    store.program(arr)
    seq_counts = run_sequential(arr)  # warm + capture for correctness

    # -- FlashQL batched path ---------------------------------------------
    dev = FlashDevice(num_planes=4)
    store.program(dev, warmup=queries[:3])
    sched = BatchScheduler(dev, store, max_batch=NUM_QUERIES)
    results = sched.serve(queries)  # warm + capture for correctness

    # -- correctness (acceptance: bit-exact vs oracle) ----------------------
    for q, r, sc in zip(queries, results, seq_counts):
        want = np_count(q, table)
        assert r.count == want == sc, (q, r.count, sc, want)

    # -- steady-state timing: best-of-REPS, interleaved ---------------------
    best = interleaved_best_of(
        {
            "sequential": lambda: run_sequential(arr),
            "batched": lambda: sched.serve(queries),
        }
    )
    t_seq, t_batch = best["sequential"], best["batched"]

    qps_seq = NUM_QUERIES / t_seq
    qps_batch = NUM_QUERIES / t_batch
    print(f"rows={NUM_ROWS}  queries={NUM_QUERIES}")
    print(
        f"sequential FlashArray.fc_read : {t_seq:7.3f}s  "
        f"{qps_seq:8.1f} q/s"
    )
    print(
        f"FlashQL batched (vmap)        : {t_batch:7.3f}s  "
        f"{qps_batch:8.1f} q/s"
    )
    print(f"speedup: {t_seq / t_batch:.2f}x")
    s = sched.stats()
    print(
        f"plan cache: {s['plan_cache_hits']} hits / "
        f"{s['plan_cache_misses']} misses; "
        f"vmap batches: {s['vmap_batches']}"
    )
    proj = sched.projection()
    print(
        f"full-scale SSD projection: FC {proj['fc_time_s'] * 1e3:.2f} ms, "
        f"{proj['fc_energy_j']:.3f} J  "
        f"({proj['speedup_vs_osp']:.1f}x faster, "
        f"{proj['energy_ratio_vs_osp']:.1f}x less energy than OSP)"
    )
    assert qps_batch > qps_seq, "batched path must beat sequential"


if __name__ == "__main__":
    enable_compile_cache()
    main()
