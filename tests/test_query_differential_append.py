"""Differential harness, append streams: interleaved appends and queries
against a numpy oracle and a store rebuilt from scratch (see
``tests/test_query_differential.py``)."""

import numpy as np
import pytest

from repro.query import (
    Agg,
    BatchScheduler,
    BitmapStore,
    Count,
    Eq,
    FlashDevice,
    GroupBy,
    Mask,
    Query,
    build_sharded_flashql,
)
from repro.query.ast import normalize_agg

from tests._hypothesis_compat import given, settings, st
from tests._differential import (
    SHARD_COUNTS,
    ROW_COUNTS,
    _table,
    _random_pred,
    _random_agg,
    _check_round,
)

# (seed, num_rows, policy) for the append stream; both policies and the
# stripe_key-routed fleet are exercised for every entry
APPEND_CORPUS = [
    (21, 97, "roundrobin"),
    (22, 97, "range"),
    (23, 130, "range"),
    (24, 31, "roundrobin"),
]


def _run_append_differential(seed: int, n: int, policy: str) -> None:
    """Interleaved append/query stream, checked bit-exactly after every
    round against (a) a numpy oracle on the resident prefix and (b) a
    BitmapStore REBUILT from scratch on the same prefix — across shard
    counts {1, 2, 3}, both striping policies, and a stripe_key fleet."""
    rng = np.random.default_rng(seed)
    table = _table(rng, n)
    n0 = max(8, (2 * n) // 3)
    cut = n0 + max(1, (n - n0) // 2)
    # force index-metadata growth mid-stream: a country value and an age
    # bit width that FIRST appear in an append (GROUP BY must grow a
    # group; Range lowering must pick up the new BSI slice)
    table["country"][n0] = 11
    table["age"][cut] = 300
    prefixes = [n0, cut, n]

    def prefix(m):
        return {c: v[:m] for c, v in table.items()}

    reserve = n - n0
    store = BitmapStore()
    store.ingest(prefix(n0), reserve_rows=reserve)
    dev = FlashDevice(num_planes=2)
    store.program(dev)
    systems: dict[object, object] = {
        "unsharded": BatchScheduler(dev, store),
        **{
            s: build_sharded_flashql(
                prefix(n0), s, policy=policy, num_planes=2,
                reserve_rows=reserve,
            )
            for s in SHARD_COUNTS
        },
    }
    # async fused flushing and append coalescing ride the same stream
    systems["pipelined"] = build_sharded_flashql(
        prefix(n0), 2, policy=policy, num_planes=2,
        reserve_rows=reserve, pipeline=True,
    )
    systems["coalesced"] = build_sharded_flashql(
        prefix(n0), 2, policy=policy, num_planes=2,
        reserve_rows=reserve, pipeline=True, coalesce_appends=True,
    )
    if policy == "range":
        systems["routed"] = build_sharded_flashql(
            prefix(n0), 3, policy="range", stripe_key="age",
            num_planes=2, reserve_rows=reserve,
        )

    warm_queries = [_random_pred(rng) for _ in range(2)]
    for round_i, m in enumerate(prefixes):
        if round_i:
            lo = prefixes[round_i - 1]
            batch = {c: v[lo:m] for c, v in table.items()}
            for sys in systems.values():
                sys.append(batch)
        preds = [_random_pred(rng) for _ in range(2)] + warm_queries
        queries = (
            [Query(p) for p in preds[:2]]
            + [Query(p, agg=Agg.MASK) for p in preds[2:3]]
            + [Query(p, agg=_random_agg(rng)) for p in preds]
            + [Query(Eq("country", 11), agg=GroupBy("country", Count()))]
        )
        # rebuild-from-scratch oracle on the same resident prefix
        rstore = BitmapStore()
        rstore.ingest(prefix(m))
        rdev = FlashDevice(num_planes=2)
        rstore.program(rdev)
        rebuilt = BatchScheduler(rdev, rstore).serve(queries)
        _check_round(queries, rebuilt, prefix(m), m)
        for name, sys in systems.items():
            got = sys.serve(queries)
            _check_round(queries, got, prefix(m), m)
            for want, have in zip(rebuilt, got):
                if isinstance(normalize_agg(want.query.agg), Mask):
                    np.testing.assert_array_equal(
                        np.asarray(want.mask.to_bits()),
                        np.asarray(have.mask.to_bits()),
                        err_msg=f"{(seed, n, policy, name)}",
                    )
                else:
                    assert want.value == have.value, (
                        seed, n, policy, name, want.query,
                    )


@pytest.mark.parametrize("seed,n,policy", APPEND_CORPUS)
def test_append_differential_corpus(seed, n, policy):
    """Deterministic append-stream corpus: always runs."""
    _run_append_differential(seed, n, policy)


@settings(max_examples=4, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**16),
    n=st.sampled_from(ROW_COUNTS),
    policy=st.sampled_from(["roundrobin", "range"]),
)
def test_append_differential_property(seed, n, policy):
    """Property-style append streams: hypothesis drives seeds when
    installed; the shim skips this (the corpus above still runs)."""
    _run_append_differential(seed, n, policy)
