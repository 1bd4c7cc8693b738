"""Aggregate-throughput benchmark: batched vs sequential weighted popcounts.

OLAP-style SUM traffic (``SUM(sales) WHERE region/status ...``) served two
ways on one FlashDevice:

* **batched** — the :class:`BatchScheduler` path: one flush compiles and
  executes every predicate under jit-of-vmap, then the pluggable
  aggregation pipeline reduces ALL queries' BSI slices with one jit'd
  weighted popcount per reduce signature and ONE host transfer;
* **sequential** — the pre-pipeline baseline: each query executes alone,
  then a Python loop popcounts ``mask ∧ slice_b`` one slice at a time —
  one kernel dispatch and one host sync per slice per query.

Both sides are asserted exact against a numpy oracle.  Timing follows the
dev notes: best-of-``REPS`` with every configuration measured inside the
same rep window (interleaved), because run-to-run noise on shared machines
is 3-4x.  Acceptance (skipped under ``--smoke``): batched SUM serving must
reach >= 1.5x the sequential throughput.

Run:  PYTHONPATH=src python benchmarks/flashql_aggregates.py [--smoke]
"""

from __future__ import annotations

import sys

import jax.numpy as jnp
import numpy as np

from _harness import REPS, interleaved_best_of
from repro.compile_cache import enable_compile_cache
from repro.kernels.popcount import popcount
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
from repro.query.bitmap import bsi_pages
from repro.query.compile import QueryCompiler


def build_queries(rng, num_queries) -> list[Query]:
    """Recurring predicate shapes, SUM aggregate, many parameterizations."""
    qs: list[Query] = []
    while len(qs) < num_queries:
        r = int(rng.integers(0, 8))
        s = int(rng.integers(0, 4))
        qs.append(Query(Eq("region", r), agg=Sum("sales")))
        qs.append(
            Query(
                qand(Eq("region", r), Eq("status", s)), agg=Sum("sales")
            )
        )
        qs.append(
            Query(In("status", [s, (s + 1) % 4]), agg=Sum("sales"))
        )
    return qs[:num_queries]


def np_sum(q: Query, table) -> int:
    from repro.query.ast import And, Eq, In

    def m(p):
        if isinstance(p, Eq):
            return table[p.column] == p.value
        if isinstance(p, In):
            return np.isin(table[p.column], p.values)
        assert isinstance(p, And)
        out = np.ones(len(table["sales"]), bool)
        for c in p.children:
            out &= m(c)
        return out

    return int(table["sales"][m(q.where)].sum())


def sequential_sums(dev, compiler, queries, valid, slices) -> list[int]:
    """One query at a time; one popcount dispatch + host sync per slice."""
    out = []
    for q in queries:
        cq = compiler.compile(q)
        mask = (
            dev.execute_batch_stacked([cq.plan], batch_key=(cq.key,))[0]
            & valid
        )
        total = 0
        for b in range(slices.shape[0]):
            total += (
                int(popcount(mask & slices[b], interpret=dev.interpret))
                << b
            )
        out.append(total)
    return out


def main() -> None:
    smoke = "--smoke" in sys.argv[1:]
    num_rows = 5_000 if smoke else 200_000
    num_queries = 8 if smoke else 32

    rng = np.random.default_rng(0)
    table = {
        "region": rng.integers(0, 8, num_rows),
        "status": rng.integers(0, 4, num_rows),
        "sales": rng.integers(0, 1_000, num_rows),
    }
    queries = build_queries(rng, num_queries)
    want = [np_sum(q, table) for q in queries]
    print(
        f"rows={num_rows}  queries={num_queries}  reps={REPS}  "
        f"(smoke={smoke})"
    )

    store = BitmapStore()
    store.ingest(table)
    dev = FlashDevice(num_planes=4)
    store.program(dev, warmup=queries[:3])

    sched = BatchScheduler(dev, store, max_batch=num_queries)
    got = [r.value for r in sched.serve(queries)]  # warm: jit + caches
    assert got == want, "batched SUM diverges from numpy oracle"

    seq_compiler = QueryCompiler(store, dev)
    valid = jnp.asarray(store.valid_words_mask())
    slices = jnp.stack(
        [store.logical[p] for p in bsi_pages(store, "sales")]
    )
    got = sequential_sums(dev, seq_compiler, queries, valid, slices)
    assert got == want, "sequential SUM diverges from numpy oracle"

    # interleaved best-of-REPS (benchmarks/_harness.py): both
    # configurations timed inside the same short window each rep so
    # machine-load swings hit both sides alike
    best = interleaved_best_of(
        {
            "batched": lambda: sched.serve(queries),
            "sequential": lambda: sequential_sums(
                dev, seq_compiler, queries, valid, slices
            ),
        }
    )
    t_batch, t_seq = best["batched"], best["sequential"]

    qps_batch = num_queries / t_batch
    qps_seq = num_queries / t_seq
    print(
        f"batched    (aggregate pipeline): {t_batch:7.3f}s  "
        f"{qps_batch:8.1f} q/s"
    )
    print(
        f"sequential (per-slice popcount): {t_seq:7.3f}s  "
        f"{qps_seq:8.1f} q/s"
    )
    print(f"speedup: {qps_batch / qps_seq:.2f}x")

    proj = sched.projection()
    print(
        f"SSD projection incl. slice reads: "
        f"{proj['fc_time_s'] * 1e3:.2f} ms, {proj['fc_energy_j']:.3f} J "
        f"({proj['speedup_vs_osp']:.1f}x vs OSP)"
    )

    if not smoke:
        assert qps_batch >= 1.5 * qps_seq, (
            f"batched SUM must serve >= 1.5x the sequential per-query "
            f"popcount loop, got {qps_batch / qps_seq:.2f}x"
        )
        print(f"acceptance: {qps_batch / qps_seq:.2f}x >= 1.5x OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
