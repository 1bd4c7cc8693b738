"""Differential harness, optimizer streams: Zipf-skewed repeated
predicates with appends and deletes, optimizer on vs off (see
``tests/test_query_differential.py``)."""

import numpy as np
import pytest

from repro.query import (
    Agg,
    BatchScheduler,
    BitmapStore,
    FlashDevice,
    In,
    Query,
    Range,
    build_sharded_flashql,
)
from repro.query.ast import and_ as qand

from tests._hypothesis_compat import given, settings, st
from tests._differential import (
    ROW_COUNTS,
    _table,
    _random_pred,
    _check_live_round,
)

OPTIMIZER_CORPUS = [
    (41, 97, "roundrobin"),
    (42, 130, "range"),
    (43, 31, "range"),
]


def _hot_pool(rng):
    """A small predicate pool whose hottest member is guaranteed
    composite (multi-page after lowering), so a skewed stream crosses the
    materialization threshold on every seed."""
    return [qand(Range("age", 10, 60), In("device", [0, 1]))] + [
        _random_pred(rng) for _ in range(5)
    ]


def _run_optimizer_differential(seed: int, n: int, policy: str) -> None:
    """Zipf-skewed repeated-predicate stream under a LOW materialization
    threshold: hot predicates materialize mid-stream, and the interleaved
    appends/deletes drive their cached pages through the epoch guards —
    appends must invalidate (the cached bitmap would zero-miss new rows),
    deletes must not (the valid page composes at read time).  Every round
    is bit-exact vs the live-row numpy oracle on the unsharded scheduler,
    the lockstep fleet, and the pipelined fleet, all with CSE active."""
    rng = np.random.default_rng(seed)
    resident = _table(rng, n)
    live = np.ones(n, bool)
    reserve = n

    def build_unsharded():
        store = BitmapStore()
        store.ingest(dict(resident), reserve_rows=reserve)
        dev = FlashDevice(num_planes=2)
        store.program(dev)
        return BatchScheduler(dev, store, materialize_after=2)

    systems: dict[object, object] = {
        "unsharded": build_unsharded(),
        "lockstep": build_sharded_flashql(
            dict(resident), 3, policy=policy, num_planes=2,
            reserve_rows=reserve, materialize_after=2,
        ),
        "pipelined": build_sharded_flashql(
            dict(resident), 2, policy=policy, num_planes=2,
            reserve_rows=reserve, pipeline=True, materialize_after=2,
        ),
    }

    pool = _hot_pool(rng)
    for round_i in range(4):
        kind = (None, "append", "delete", "append")[round_i]
        if kind == "append":
            b = int(rng.integers(3, 8))
            batch = _table(rng, b)
            for sys in systems.values():
                sys.append(batch)
            resident = {
                c: np.concatenate([v, batch[c]]) for c, v in resident.items()
            }
            live = np.concatenate([live, np.ones(b, bool)])
        elif kind == "delete":
            rows = np.flatnonzero(live)
            ids = rng.choice(rows, min(len(rows) // 4, 15), replace=False)
            for sys in systems.values():
                sys.delete(ids)
            live[ids] = False
        # Zipf-skewed draw over the pool: rank 1 (by far the most likely)
        # maps to the composite hot predicate, so duplicates recur within
        # AND across flushes — exercising dedup, CSE, and materialization
        ranks = (rng.zipf(1.5, size=10).astype(int) - 1) % len(pool)
        preds = [pool[r] for r in ranks]
        queries = [Query(p) for p in preds] + [
            Query(pool[0], agg=Agg.MASK)
        ]
        for name, sys in systems.items():
            got = sys.serve(queries)
            try:
                _check_live_round(queries, got, resident, live)
            except AssertionError as err:
                raise AssertionError(
                    f"{(seed, n, policy, name, round_i, kind)}: {err}"
                ) from err

    # the stream is hot enough that every system materialized the hot
    # predicate, and both appends invalidated its cached page (the delete
    # round must NOT have: tombstones compose at read time)
    for name, sys in systems.items():
        comps = (
            [sys.compiler] if name == "unsharded" else list(sys.compilers)
        )
        builds = sum(c.mat_builds for c in comps)
        invals = sum(c.mat_invalidations for c in comps)
        assert builds >= 1, (seed, n, policy, name, builds)
        assert invals >= 1, (seed, n, policy, name, invals)
        assert sys.stats()["materializations"] == builds


@pytest.mark.parametrize("seed,n,policy", OPTIMIZER_CORPUS)
def test_optimizer_differential_corpus(seed, n, policy):
    """Deterministic skewed-stream corpus: always runs."""
    _run_optimizer_differential(seed, n, policy)


@settings(max_examples=3, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**16),
    n=st.sampled_from(ROW_COUNTS),
    policy=st.sampled_from(["roundrobin", "range"]),
)
def test_optimizer_differential_property(seed, n, policy):
    """Property-style skewed streams: hypothesis drives seeds when
    installed; the shim skips this (the corpus above still runs)."""
    _run_optimizer_differential(seed, n, policy)
