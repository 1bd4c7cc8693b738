"""Sharded FlashQL scaling benchmark: 1 -> N simulated FlashDevices.

The same BMI-style COUNT traffic as ``benchmarks/flashql_throughput.py``
(200k-row table, 64 recurring-shape queries) served three ways:

* **1 device** — the unsharded ``BatchScheduler`` steady state (this is
  the single-device number ``flashql_throughput.py`` reports);
* **N-device fleet (per-chip)** — rows striped round-robin over N
  ``FlashDevice``s; each chip executes its own shard batch + popcount.
  Chips are independent hardware, so the fleet's serving time is the MAX
  over per-device times (measured per device, steady state) — this is the
  scaling number;
* **N-device fused host simulation** — ``ShardedFlashQL.serve``: the
  whole fleet in one process under one ``jit(vmap)`` per signature group,
  used for correctness (counts asserted against a numpy oracle) and for
  the plan-aware-batching criterion: signature groups must stay BELOW
  shards x distinct plan shapes.

Also prints the fleet-level SSD projection (per-chip traffic replayed
through the Table-1 timing/energy model; time = max over chips, energy =
sum).

Run:  PYTHONPATH=src python benchmarks/flashql_sharded.py [--smoke]

``--smoke`` shrinks to a tiny geometry (2 shards, small store, CI-speed)
and skips the wall-clock scaling assertion — timing on shared CI runners
is noise — while still exercising every scatter/gather path.
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
    build_sharded_flashql,
)
from repro.query.ast import and_ as qand


def build_queries(rng, num_queries) -> list[Query]:
    """BMI-style COUNT traffic: a few hot shapes, many parameterizations."""
    qs: list[Query] = []
    while len(qs) < num_queries:
        c = int(rng.integers(0, 8))
        d = int(rng.integers(0, 4))
        qs.append(Query(qand(Eq("country", c), Eq("device", d))))
        qs.append(Query(Eq("country", c), agg=Agg.COUNT))
        qs.append(Query(In("device", [d, (d + 1) % 4]), agg=Agg.COUNT))
    return qs[:num_queries]


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


def single_device_scheduler(table, queries) -> BatchScheduler:
    """The unsharded flashql_throughput configuration, warmed."""
    store = BitmapStore()
    store.ingest(table)
    dev = FlashDevice(num_planes=4)
    store.program(dev, warmup=queries[:3])
    sched = BatchScheduler(dev, store, max_batch=len(queries))
    sched.serve(queries)  # warm: jit + plan caches
    return sched


def per_chip_schedulers(sq, queries) -> list[BatchScheduler]:
    """One BatchScheduler per shard device — the same serving software the
    single-device baseline runs, each on its own stripe.  A real fleet
    runs these on independent chips, so fleet batch time is the max over
    shards (plus the host-side merge, measured separately)."""
    scheds = []
    for s in sq.store.active:
        sched = BatchScheduler(
            sq.devices[s],
            sq.store.shards[s],
            max_batch=len(queries),
            compiler=sq.compilers[s],
        )
        sched.serve(queries)  # warm
        scheds.append(sched)
    return scheds


def serve_counts(sched: BatchScheduler, queries) -> list[int]:
    return [r.count for r in sched.serve(queries)]


def main() -> None:
    smoke = "--smoke" in sys.argv[1:]
    # 1M rows keeps the non-smoke gate compute-bound: the one-dispatch
    # flush (PR 5) cut per-flush host overhead to ~1.5 ms, so at the old
    # 200k rows serving was overhead-dominated and row striping could not
    # show its scaling (chips must be measurably faster on 1/N the rows)
    num_rows = 5_000 if smoke else 1_000_000
    num_queries = 16 if smoke else 64
    fleet_sizes = [2] if smoke else [2, 4]

    rng = np.random.default_rng(0)
    table = {
        "country": rng.integers(0, 8, num_rows),
        "device": rng.integers(0, 4, num_rows),
    }
    queries = build_queries(rng, num_queries)
    want = [np_count(q, table) for q in queries]
    print(f"rows={num_rows}  queries={num_queries}  reps={REPS}  "
          f"(smoke={smoke})")

    sched_1 = single_device_scheduler(table, queries)
    fleets = {}
    for n_shards in fleet_sizes:
        sq = build_sharded_flashql(
            table,
            n_shards,
            policy="roundrobin",
            num_planes=4,
            warmup=queries[:3],
            queue_depth=num_queries,
        )
        # correctness + batching criterion via the fused host simulation
        got = [r.count for r in sq.serve(queries)]
        assert got == want, "sharded counts diverge from oracle"
        st = sq.stats()
        groups, shapes = st["vmap_batches"], st["distinct_signatures"]
        assert groups < n_shards * shapes, (
            f"plan-aware batching failed: {groups} groups for "
            f"{n_shards} shards x {shapes} shapes"
        )
        chips = per_chip_schedulers(sq, queries)
        merged = [
            sum(c) for c in zip(*(serve_counts(ch, queries) for ch in chips))
        ]
        assert merged == want, "per-device merge diverges from oracle"
        fleets[n_shards] = (sq, chips, groups, shapes)

    # interleaved best-of-REPS (benchmarks/_harness.py): every
    # configuration is timed inside the same short window each rep, so
    # machine-load swings hit all sides alike instead of gating on
    # whichever ran during a quiet spell
    timers = {"1dev": lambda: sched_1.serve(queries)}
    for n, (sq, chips, _, _) in fleets.items():
        for i, ch in enumerate(chips):
            timers[("chip", n, i)] = (
                lambda c=ch: c.serve(queries)
            )
        timers[("fused", n)] = (lambda s=sq: s.serve(queries))
    best = interleaved_best_of(timers)
    t_1 = best["1dev"]
    t_chip = {
        n: [best[("chip", n, i)] for i in range(len(f[1]))]
        for n, f in fleets.items()
    }
    t_fused = {n: best[("fused", n)] for n in fleets}

    qps_1 = num_queries / t_1
    print(f"1 device  (BatchScheduler)    : {t_1:7.3f}s  {qps_1:8.1f} q/s")
    qps_fleet = {}
    for n_shards, (sq, chips, groups, shapes) in fleets.items():
        t_fleet = max(t_chip[n_shards])  # chips serve concurrently
        qps_fleet[n_shards] = num_queries / t_fleet
        print(
            f"{n_shards} devices (per-chip max)     : {t_fleet:7.3f}s  "
            f"{qps_fleet[n_shards]:8.1f} q/s  "
            f"({qps_fleet[n_shards] / qps_1:4.2f}x vs 1 device)"
        )
        print(
            f"{n_shards} devices (fused host sim)   : "
            f"{t_fused[n_shards]:7.3f}s  "
            f"{num_queries / t_fused[n_shards]:8.1f} q/s  "
            f"[{groups} vmap groups for {shapes} shapes x "
            f"{n_shards} shards]"
        )
        proj = sq.projection()
        print(
            f"  fleet SSD projection: FC {proj['fc_time_s'] * 1e3:.2f} ms, "
            f"{proj['fc_energy_j']:.3f} J on {proj['num_devices']} chips "
            f"({proj['speedup_vs_osp']:.1f}x faster, "
            f"{proj['energy_ratio_vs_osp']:.1f}x less energy than OSP)"
        )

    if not smoke:
        top = max(fleet_sizes)
        assert qps_fleet[top] >= 2.0 * qps_1, (
            f"{top}-device fleet must serve >= 2x the single-device "
            f"throughput, got {qps_fleet[top] / qps_1:.2f}x"
        )
        print(
            f"scaling: {qps_fleet[top] / qps_1:.2f}x with {top} devices "
            f"(acceptance: >= 2x)"
        )


if __name__ == "__main__":
    enable_compile_cache()
    main()
