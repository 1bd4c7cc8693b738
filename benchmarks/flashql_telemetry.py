"""Telemetry-overhead benchmark: enabled serving within 10% of disabled.

Two identical pipelined 4-shard fleets serve the same mixed-aggregate
workload, one with full telemetry (trace spans, histograms, per-ticket
attribution, slow-query log) and one with ``Telemetry(enabled=False)``
(counters only — they are ``stats()``/projection inputs and cost one dict
update per event).  The gate asserts the enabled fleet's steady-state
serve stays within ``OVERHEAD_BUDGET`` of the disabled fleet — this is
the contract behind "cheap-by-default" instrumentation, and it is
asserted in ``--smoke`` runs too (CI).

Both fleets must also return identical results (telemetry can never
change an answer), and the exported Chrome trace must parse and pass the
span-nesting validator (:func:`repro.query.telemetry.validate_trace`);
the trace file is uploaded as a CI artifact.

Run:  PYTHONPATH=src python benchmarks/flashql_telemetry.py [--smoke]
"""

from __future__ import annotations

import json
import sys

import numpy as np

from _harness import REPS, interleaved_best_of
from flashql_pipeline import build_queries, check_exact
from repro.compile_cache import enable_compile_cache
from repro.query import Mask, build_sharded_flashql, validate_trace

NUM_SHARDS = 4
QUEUE_DEPTH = 16
OVERHEAD_BUDGET = 1.10  # enabled serve <= 1.10x disabled serve
TRACE_PATH = "flashql_trace.json"
# serves per timed rep: one serve is a few ms, so a longer window keeps
# the relative overhead measurement out of the timer noise floor
SERVES_PER_REP = 4


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

    fleets = {}
    for name, enabled in (("enabled", True), ("disabled", False)):
        sq = build_sharded_flashql(
            table,
            NUM_SHARDS,
            num_planes=4,
            queue_depth=QUEUE_DEPTH,
            pipeline=True,
        )
        sq.telemetry.enabled = enabled
        fleets[name] = sq

    # warm both (jit + plan/exec/flush-program caches) and assert the
    # differential contract: telemetry can never change an answer
    res_on = fleets["enabled"].serve(queries)
    res_off = fleets["disabled"].serve(queries)
    check_exact(res_on, queries, table, num_rows)
    for a, b in zip(res_on, res_off):
        if isinstance(a.query.agg, Mask):
            np.testing.assert_array_equal(
                np.asarray(a.value.words), np.asarray(b.value.words)
            )
        else:
            assert a.value == b.value, (a.query, a.value, b.value)
    assert all(r.attribution is not None for r in res_on)
    assert all(r.attribution is None for r in res_off)
    print("enabled == disabled == numpy oracle")

    def serve_rep(sq):
        def fn():
            for _ in range(SERVES_PER_REP):
                sq.serve(queries)

        return fn

    best = interleaved_best_of(
        {
            "enabled": serve_rep(fleets["enabled"]),
            "disabled": serve_rep(fleets["disabled"]),
        }
    )
    t_on, t_off = best["enabled"], best["disabled"]
    ratio = t_on / t_off
    n_q = num_queries * SERVES_PER_REP
    print(
        f"disabled: {t_off:7.4f}s  {n_q / t_off:8.1f} q/s\n"
        f"enabled : {t_on:7.4f}s  {n_q / t_on:8.1f} q/s\n"
        f"overhead: {ratio:.3f}x (budget {OVERHEAD_BUDGET:.2f}x)"
    )

    # trace export: must parse as JSON and pass the span-nesting validator
    tele = fleets["enabled"].telemetry
    tele.export_trace(TRACE_PATH)
    with open(TRACE_PATH) as f:
        trace = json.load(f)
    n_spans = validate_trace(trace)
    assert n_spans > 0, "trace export recorded no spans"
    print(f"trace: {n_spans} spans validated -> {TRACE_PATH}")

    snap = tele.snapshot()
    c = snap["counters"]
    print(
        f"snapshot: {c['queries_served']:.0f} served, "
        f"{c['host_transfers']:.0f} transfers, "
        f"{c['fused_dispatches']:.0f} fused dispatches, "
        f"plan cache {snap['plan_cache']['hits']} hits / "
        f"{snap['plan_cache']['misses']} misses"
    )
    fl = snap["histograms"]["flush_latency_s"]
    print(
        f"flush latency: p50={fl['p50'] * 1e3:.2f}ms  "
        f"p95={fl['p95'] * 1e3:.2f}ms  p99={fl['p99'] * 1e3:.2f}ms  "
        f"(n={fl['count']})"
    )

    # the overhead gate holds in smoke runs too: "cheap by default" is a
    # CI contract, not a full-run-only property
    assert ratio <= OVERHEAD_BUDGET, (
        f"telemetry-enabled serving must stay within "
        f"{OVERHEAD_BUDGET:.2f}x of disabled, got {ratio:.3f}x"
    )
    print(f"acceptance: {ratio:.3f}x <= {OVERHEAD_BUDGET:.2f}x OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
