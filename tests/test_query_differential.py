"""Differential test harness: sharded vs unsharded FlashQL vs oracles.

A seeded generator draws random ``Eq``/``In``/``Range``/``And``/``Or``/
``Not`` trees over mixed equality + BSI columns — each paired with COUNT,
MASK, and a randomly drawn aggregate (SUM/AVG/MIN/MAX/TOP-K/GROUP BY) —
and every query executes on

* unsharded FlashQL (``BatchScheduler`` over one ``FlashDevice``), on both
  the fused one-dispatch flush and the per-reduce-group legacy flush,
* sharded FlashQL (``ShardedFlashQL``) for shard counts {1, 2, 3} under
  both stripe policies (plus a ``stripe_key``-sorted range fleet, which
  exercises shard routing), including row counts that do not divide
  evenly,
* the asynchronous per-shard pipelined flush (``pipeline=True``) against
  the lockstep oracle — composed with routing and, in the append stream,
  with coalesced appends,

and the results are checked **bit-exact** (exact-integer for SUM and the
AVG numerator) against the ``eval_expr`` oracle on the logical bitmap
pages and a plain-numpy oracle on the raw table.

Property-style execution goes through ``tests/_hypothesis_compat``: with
`hypothesis` installed, seeds/shapes are drawn adversarially; without it,
the deterministic ``CORPUS`` below keeps the same coverage running.

The append, CRUD, optimizer and threshold streams live in
``tests/test_query_differential_*.py`` (shared pieces in
``tests/_differential.py``), so a parallel run spreads them over workers.
"""

import numpy as np
import pytest

from repro.core.engine import eval_expr
from repro.query import (
    Agg,
    BatchScheduler,
    BitmapStore,
    Count,
    Eq,
    FlashDevice,
    Mask,
    Query,
    build_sharded_flashql,
    lower,
)
from repro.query.oracle import np_select as _np_oracle
from repro.query.ast import normalize_agg

from tests._hypothesis_compat import given, settings, st
from tests._differential import (
    SHARD_COUNTS,
    ROW_COUNTS,
    _table,
    _random_pred,
    _random_agg,
    _np_agg_oracle,
)

# deterministic fallback corpus: (seed, num_rows, policy)
CORPUS = [
    (11, 97, "roundrobin"),
    (12, 97, "range"),
    (13, 130, "roundrobin"),
    (14, 130, "range"),
    (15, 31, "roundrobin"),
    (16, 31, "range"),
]



def _run_differential(seed: int, n: int, policy: str) -> None:
    rng = np.random.default_rng(seed)
    table = _table(rng, n)
    preds = [_random_pred(rng) for _ in range(5)]
    queries = (
        [Query(p) for p in preds]
        + [Query(p, agg=Agg.MASK) for p in preds]
        + [Query(p, agg=_random_agg(rng)) for p in preds]
    )

    # unsharded reference (fused one-dispatch flush), checked against the
    # per-reduce-group legacy flush on the same device
    store = BitmapStore()
    store.ingest(table)
    dev = FlashDevice(num_planes=2)
    store.program(dev)
    ref = BatchScheduler(dev, store).serve(queries)
    legacy = BatchScheduler(dev, store, fuse_flush=False).serve(queries)
    for a, b in zip(ref, legacy):
        if isinstance(normalize_agg(a.query.agg), Mask):
            np.testing.assert_array_equal(
                np.asarray(a.mask.words), np.asarray(b.mask.words)
            )
        else:
            assert a.value == b.value, (seed, n, policy, a.query)

    sharded = {
        s: build_sharded_flashql(
            table, s, policy=policy, num_planes=2
        ).serve(queries)
        for s in SHARD_COUNTS
    }
    # asynchronous per-shard fused flushing vs the lockstep oracle above
    # (submission order is preserved by construction of serve())
    sharded["pipelined"] = build_sharded_flashql(
        table, 3, policy=policy, num_planes=2, pipeline=True
    ).serve(queries)
    if policy == "range":
        # stripe_key-sorted fleet: same results, but shard routing prunes
        sharded["routed"] = build_sharded_flashql(
            table, 3, policy="range", stripe_key="age", num_planes=2
        ).serve(queries)
        # routing + async pipelining composed
        sharded["routed-pipelined"] = build_sharded_flashql(
            table,
            3,
            policy="range",
            stripe_key="age",
            num_planes=2,
            pipeline=True,
        ).serve(queries)

    for i, q in enumerate(queries):
        want_bits = _np_oracle(q.where, table, n)
        # eval_expr oracle on the unsharded logical pages
        oracle_words = np.asarray(eval_expr(lower(q.where, store), store.logical))
        oracle_bits = np.asarray(
            np.unpackbits(
                oracle_words.view(np.uint8), bitorder="little"
            )[:n]
        ).astype(bool)
        np.testing.assert_array_equal(oracle_bits, want_bits)
        spec = normalize_agg(q.agg)
        if isinstance(spec, Count):
            want = int(want_bits.sum())
            assert ref[i].count == want
            for s, res in sharded.items():
                assert res[i].count == want, (seed, n, policy, s, q)
        elif isinstance(spec, Mask):
            ref_bits = np.asarray(ref[i].mask.to_bits()).astype(bool)
            np.testing.assert_array_equal(ref_bits, want_bits)
            for s, res in sharded.items():
                got = np.asarray(res[i].mask.to_bits()).astype(bool)
                np.testing.assert_array_equal(
                    got, want_bits, err_msg=f"{(seed, n, policy, s, q)}"
                )
        else:
            # SUM/AVG are exact-integer (numerator), so == is the right
            # comparison even for the float AVG: both sides divide the
            # same two Python ints
            want = _np_agg_oracle(spec, want_bits, table)
            assert ref[i].value == want, (seed, n, policy, q, ref[i].value)
            for s, res in sharded.items():
                assert res[i].value == want, (
                    seed, n, policy, s, q, res[i].value,
                )


@pytest.mark.parametrize("seed,n,policy", CORPUS)
def test_differential_corpus(seed, n, policy):
    """Deterministic corpus: always runs, with or without hypothesis."""
    _run_differential(seed, n, policy)


@settings(max_examples=8, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**16),
    n=st.sampled_from(ROW_COUNTS),
    policy=st.sampled_from(["roundrobin", "range"]),
)
def test_differential_property(seed, n, policy):
    """Property-style: hypothesis drives seeds when installed; the shim
    skips this (the corpus above still runs) when it is not."""
    _run_differential(seed, n, policy)


def test_sharded_handles_rows_fewer_than_shards():
    """n < num_shards leaves range-policy shards empty; results must still
    be exact and the empty shard must not join execution."""
    table = {"c": np.array([1, 0])}
    sq = build_sharded_flashql(table, 3, policy="range", num_planes=1)
    assert len(sq.store.active) == 2
    r_count, r_mask = sq.serve(
        [Query(Eq("c", 1)), Query(Eq("c", 1), agg=Agg.MASK)]
    )
    assert r_count.count == 1
    np.testing.assert_array_equal(
        np.asarray(r_mask.mask.to_bits()), [1, 0]
    )


def test_roundrobin_mask_unstripes_row_order():
    """Round-robin striping permutes rows across shards; MASK gather must
    restore global row order exactly (row j lives on shard j % S)."""
    n = 10
    table = {"c": np.arange(n) % 3}
    sq = build_sharded_flashql(table, 3, policy="roundrobin", num_planes=1)
    (r,) = sq.serve([Query(Eq("c", 0), agg=Agg.MASK)])
    np.testing.assert_array_equal(
        np.asarray(r.mask.to_bits()).astype(bool), (np.arange(n) % 3) == 0
    )
