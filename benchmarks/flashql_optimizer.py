"""Multi-query optimizer benchmark: sense once, answer many.

A Zipf-skewed dashboard workload — a few hot predicate shapes repeated
many times, most sharing one expensive bit-sliced Range subtree — is
served by twin systems over the same table with the optimizer on
(canonicalization + cost-based reordering + cross-query CSE + hot-
predicate materialization) and off:

* **unsharded** — one ``BatchScheduler`` per side;
* **pipelined fleet** — a 2-shard async ``ShardedFlashQL`` per side (the
  headline path: per-shard CSE inside each fused flush program).

Both sides are asserted bit-exact against each other and a numpy oracle,
then steady-state *sensings per query* are read from the telemetry
counters: with Flash-Cosmos a single multi-wordline sensing evaluates a
many-operand bitwise op, so sensings — not queries — are the unit of
device work, and the optimizer's whole job is to need fewer of them for
the same answers.  Wall-clock serving throughput is reported best-of-
``REPS`` (interleaved) for context.

Acceptance (deterministic, enforced even under ``--smoke``): the
optimizer must cut sensings per query by >= 1.5x on both systems.

Run:  PYTHONPATH=src python benchmarks/flashql_optimizer.py [--smoke]
"""

from __future__ import annotations

import sys

import numpy as np

from _harness import REPS, interleaved_best_of
from repro.compile_cache import enable_compile_cache
from repro.query import (
    Agg,
    BatchScheduler,
    BitmapStore,
    Eq,
    FlashDevice,
    In,
    Query,
    Range,
    build_sharded_flashql,
)
from repro.query.ast import and_ as qand
from repro.query.oracle import np_select

NUM_SHARDS = 2
ZIPF_A = 1.4
MATERIALIZE_AFTER = 6


def build_pool(rng) -> list:
    """Hot predicate pool: most entries AND a distinct Eq with one of two
    recurring deep Range subtrees (the CSE candidates); the tail entries
    are cheap standalone shapes."""
    deep_a = Range("sales", 120, 710)
    deep_b = qand(Range("sales", 50, 400), In("status", [0, 1]))
    pool = [qand(Eq("region", r), deep_a) for r in range(4)]
    pool += [qand(Eq("region", r), deep_b) for r in range(3)]
    pool += [Eq("status", 2), In("region", [1, 5]), Range("sales", 0, 80)]
    return pool


def build_queries(rng, pool, num_queries) -> list[Query]:
    """Zipf-ranked draws over the pool (rank 1 -> hottest entry), with a
    MASK sprinkled in so un-striping rides the measured path."""
    ranks = (rng.zipf(ZIPF_A, size=num_queries).astype(int) - 1) % len(pool)
    return [
        Query(pool[r], agg=Agg.MASK if i % 8 == 7 else Agg.COUNT)
        for i, r in enumerate(ranks)
    ]


def check_exact(queries, results, table, n) -> None:
    for q, r in zip(queries, results):
        sel = np_select(q.where, table, n)
        if q.agg is Agg.MASK:
            got = np.asarray(r.mask.to_bits()).astype(bool)
            np.testing.assert_array_equal(got, sel, err_msg=f"{q}")
        else:
            assert r.count == int(sel.sum()), q


def check_match(res_on, res_off) -> None:
    for a, b in zip(res_on, res_off):
        if a.query.agg is Agg.MASK:
            np.testing.assert_array_equal(
                np.asarray(a.mask.words), np.asarray(b.mask.words)
            )
        else:
            assert a.count == b.count, (a.query, a.count, b.count)


def main() -> None:
    smoke = "--smoke" in sys.argv[1:]
    num_rows = 4_000 if smoke else 40_000
    num_queries = 24 if smoke else 64

    rng = np.random.default_rng(0)
    table = {
        "region": rng.integers(0, 8, num_rows),
        "status": rng.integers(0, 4, num_rows),
        "sales": rng.integers(0, 1_000, num_rows),
    }
    pool = build_pool(rng)
    queries = build_queries(rng, pool, num_queries)
    print(
        f"rows={num_rows}  queries={num_queries}  pool={len(pool)}  "
        f"zipf_a={ZIPF_A}  reps={REPS}  (smoke={smoke})"
    )

    def build_unsharded(optimize):
        store = BitmapStore()
        store.ingest(table)
        dev = FlashDevice(num_planes=4)
        store.program(dev)
        return BatchScheduler(
            dev, store, optimize=optimize,
            materialize_after=MATERIALIZE_AFTER,
        )

    def build_fleet(optimize):
        return build_sharded_flashql(
            table, NUM_SHARDS, num_planes=4, pipeline=True,
            optimize=optimize, materialize_after=MATERIALIZE_AFTER,
        )

    systems = {
        "unsharded": (build_unsharded(True), build_unsharded(False)),
        "pipelined": (build_fleet(True), build_fleet(False)),
    }

    # warm both sides of both systems (jit + plan/flush-program caches +
    # the materialization threshold) and assert exactness every round
    for _ in range(3):
        for on, off in systems.values():
            res_on, res_off = on.serve(queries), off.serve(queries)
            check_exact(queries, res_on, table, num_rows)
            check_match(res_on, res_off)
    print("optimizer on == off == numpy oracle (bit-exact)")

    ratios = {}
    for name, (on, off) in systems.items():
        spq = {}
        for side, sysm in (("on", on), ("off", off)):
            s0 = sysm.stats()["mws_commands"]
            sysm.serve(queries)
            spq[side] = (sysm.stats()["mws_commands"] - s0) / num_queries
        opt = on.telemetry.snapshot()["optimizer"]
        ratios[name] = spq["off"] / spq["on"]
        print(
            f"{name:9s}: {spq['off']:6.2f} -> {spq['on']:6.2f} sensings/"
            f"query ({ratios[name]:.2f}x fewer)  "
            f"[cse_plan_hits={opt['cse_plan_hits']}, "
            f"cse_shared_senses={opt['cse_shared_senses']}, "
            f"materializations={opt['materializations']}, "
            f"mat_hits={opt['materialization_hits']}]"
        )

    on, off = systems["pipelined"]
    best = interleaved_best_of(
        {
            "optimizer-on": lambda: on.serve(queries),
            "optimizer-off": lambda: off.serve(queries),
        }
    )
    t_on, t_off = best["optimizer-on"], best["optimizer-off"]
    print(
        f"pipelined wall-clock: off {num_queries / t_off:8.1f} q/s, "
        f"on {num_queries / t_on:8.1f} q/s ({t_off / t_on:.2f}x)"
    )

    # deterministic device-work acceptance: counters, not wall-clock, so
    # it holds under --smoke too
    for name, ratio in ratios.items():
        assert ratio >= 1.5, (
            f"{name}: optimizer must cut sensings/query by >= 1.5x, "
            f"got {ratio:.2f}x"
        )
    print(
        "acceptance: "
        + ", ".join(f"{n} {r:.2f}x" for n, r in ratios.items())
        + " >= 1.5x OK"
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
