"""Shared pieces of the differential harness (``tests/test_query_differential*.py``).

Random predicate and aggregate generators over one mixed equality + BSI
table, and the numpy oracles every stream checks its results against.
The harness is split over several test files so that a parallel test run
spreads its streams over workers.
"""

import numpy as np

from repro.query import (
    Agg,
    AtLeast,
    Avg,
    BatchScheduler,
    BitmapStore,
    Count,
    Eq,
    FlashDevice,
    GroupBy,
    In,
    Majority,
    Mask,
    Max,
    Min,
    Not,
    Query,
    Range,
    Sum,
    TopK,
    build_sharded_flashql,
)
from repro.query.oracle import np_select as _np_oracle
from repro.query.ast import and_ as qand, normalize_agg, or_ as qor

SHARD_COUNTS = (1, 2, 3)
# ragged on purpose: 97 is prime (never divides), 130 straddles a word
# boundary (128 = 4 words), 31 is below one packed word
ROW_COUNTS = (97, 130, 31)


def _table(rng, n):
    """Mixed-index table: low-cardinality equality columns + a BSI column."""
    return {
        "country": rng.integers(0, 6, n),
        "device": rng.integers(0, 4, n),
        "age": rng.integers(0, 90, n),
    }


def _random_pred(rng, depth=0):
    kind = rng.integers(0, 6 if depth < 2 else 4)
    if kind == 0:
        return Eq("country", int(rng.integers(0, 7)))  # 6 may be absent
    if kind == 1:
        return In(
            "device", [int(v) for v in rng.choice(5, rng.integers(1, 4))]
        )
    if kind == 2:
        lo = int(rng.integers(0, 70))
        return Range("age", lo, lo + int(rng.integers(0, 40)))
    if kind == 3:
        return Not(_random_pred(rng, depth + 1))
    children = [
        _random_pred(rng, depth + 1) for _ in range(rng.integers(2, 4))
    ]
    return qand(*children) if kind == 4 else qor(*children)


def _random_agg(rng):
    """Draw one of the non-trivial aggregates over a random column."""
    col = ("country", "device", "age")[int(rng.integers(0, 3))]
    kind = int(rng.integers(0, 6))
    if kind == 0:
        return Sum(col)
    if kind == 1:
        return Avg(col)
    if kind == 2:
        return Min(col)
    if kind == 3:
        return Max(col)
    if kind == 4:
        return TopK(col, int(rng.integers(1, 5)))
    key = ("country", "device")[int(rng.integers(0, 2))]
    inner = (Count(), Sum("age"), Avg("age"))[int(rng.integers(0, 3))]
    return GroupBy(key, inner)


def _np_agg_oracle(spec, sel, table):
    """Plain-numpy aggregate over the selected-row mask ``sel``."""
    if isinstance(spec, Sum):
        return int(table[spec.column][sel].sum())
    if isinstance(spec, Avg):
        c = int(sel.sum())
        return int(table[spec.column][sel].sum()) / c if c else None
    if isinstance(spec, Min):
        v = table[spec.column][sel]
        return int(v.min()) if len(v) else None
    if isinstance(spec, Max):
        v = table[spec.column][sel]
        return int(v.max()) if len(v) else None
    if isinstance(spec, TopK):
        vals, counts = np.unique(table[spec.column][sel], return_counts=True)
        ranked = sorted(
            zip(vals.tolist(), counts.tolist()),
            key=lambda vc: (-vc[1], vc[0]),
        )
        return tuple((int(v), int(c)) for v, c in ranked)[: spec.k]
    assert isinstance(spec, GroupBy)
    out = {}
    for v in np.unique(table[spec.key]):
        m = sel & (table[spec.key] == v)
        c = int(m.sum())
        if not c:
            continue
        if isinstance(spec.value, Count):
            out[int(v)] = c
        else:
            out[int(v)] = _np_agg_oracle(spec.value, m, table)
    return out


def _check_round(queries, results, table, n):
    """Assert one system's results bit-exact vs the numpy oracle on the
    rows resident so far (exact integers for SUM / the AVG numerator)."""
    for q, r in zip(queries, results):
        want_bits = _np_oracle(q.where, table, n)
        spec = normalize_agg(q.agg)
        if isinstance(spec, Count):
            assert r.count == int(want_bits.sum()), (q, r.count)
        elif isinstance(spec, Mask):
            got = np.asarray(r.mask.to_bits()).astype(bool)
            np.testing.assert_array_equal(got, want_bits, err_msg=f"{q}")
        else:
            want = _np_agg_oracle(spec, want_bits, table)
            assert r.value == want, (q, r.value, want)



def _check_live_round(queries, results, resident, live):
    """One system's results vs the numpy oracle restricted to LIVE rows:
    tombstoned rows must never appear in any COUNT, MASK, or aggregate."""
    n = len(live)
    for q, r in zip(queries, results):
        want_bits = _np_oracle(q.where, resident, n) & live
        spec = normalize_agg(q.agg)
        if isinstance(spec, Count):
            assert r.count == int(want_bits.sum()), (q, r.count)
        elif isinstance(spec, Mask):
            got = np.asarray(r.mask.to_bits()).astype(bool)
            np.testing.assert_array_equal(got, want_bits, err_msg=f"{q}")
        else:
            want = _np_agg_oracle(spec, want_bits, resident)
            assert r.value == want, (q, r.value, want)


def _random_atleast(rng, depth=0):
    """Random k-of-N threshold predicate over mixed leaf/compound children.

    Deliberately includes the degenerate k values (1 => Or, N => And) so
    the canonicalization path is exercised alongside genuine thresholds,
    and nests thresholds under Not/And/Or (and inside each other one level
    deep) so every planner lowering — native ThresholdCommand, polarity
    inversion, chain expansion — gets hit by the stream.
    """
    n_kids = int(rng.integers(2, 6))
    kids = []
    for _ in range(n_kids):
        if depth < 1 and rng.integers(0, 4) == 0:
            kids.append(_random_atleast(rng, depth + 1))
        else:
            kids.append(_random_pred(rng, depth=2))
    k = int(rng.integers(1, len(kids) + 1))
    pred = AtLeast(k, kids)
    wrap = rng.integers(0, 4)
    if wrap == 0:
        return Not(pred)
    if wrap == 1:
        return qand(pred, _random_pred(rng, depth=2))
    if wrap == 2:
        return qor(pred, _random_pred(rng, depth=2))
    return pred


def _run_threshold_differential(seed: int, n: int, policy: str) -> None:
    """Interleaved append/delete/query stream of AtLeast predicates,
    bit-exact after every round vs the live-row numpy oracle — across the
    unsharded scheduler and shard counts {1, 2, 3}."""
    rng = np.random.default_rng(seed)
    resident = _table(rng, n)
    live = np.ones(n, bool)
    reserve = n

    def build_unsharded():
        store = BitmapStore()
        store.ingest(dict(resident), reserve_rows=reserve)
        dev = FlashDevice(num_planes=2)
        store.program(dev)
        return BatchScheduler(dev, store)

    systems: dict[object, object] = {
        "unsharded": build_unsharded(),
        **{
            s: build_sharded_flashql(
                dict(resident), s, policy=policy, num_planes=2,
                reserve_rows=reserve,
            )
            for s in SHARD_COUNTS
        },
    }

    warm = [_random_atleast(rng) for _ in range(2)]
    for round_i in range(4):
        kind = ("append", "delete", "append", "delete")[round_i]
        if kind == "append":
            b = int(rng.integers(3, 10))
            batch = _table(rng, b)
            for sys in systems.values():
                sys.append(batch)
            resident = {
                c: np.concatenate([v, batch[c]]) for c, v in resident.items()
            }
            live = np.concatenate([live, np.ones(b, bool)])
        else:
            pool = np.flatnonzero(live)
            ids = rng.choice(pool, min(len(pool) // 4, 20), replace=False)
            for sys in systems.values():
                sys.delete(ids)
            live[ids] = False

        preds = [_random_atleast(rng) for _ in range(3)] + warm
        queries = (
            [Query(p) for p in preds[:3]]
            + [Query(p, agg=Agg.MASK) for p in preds[3:]]
            + [Query(Majority([
                Eq("country", 1), Eq("device", 2), Range("age", 20, 60),
            ]))]
        )
        for name, sys in systems.items():
            got = sys.serve(queries)
            try:
                _check_live_round(queries, got, resident, live)
            except AssertionError as err:
                raise AssertionError(
                    f"{(seed, n, policy, name, round_i, kind)}: {err}"
                ) from err
