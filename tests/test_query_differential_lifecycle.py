"""Differential harness, CRUD streams: interleaved appends, deletes,
updates and queries with compaction, against a live-row numpy oracle (see
``tests/test_query_differential.py``)."""

import numpy as np
import pytest

from repro.query import (
    Agg,
    BatchScheduler,
    BitmapStore,
    FlashDevice,
    Query,
    build_sharded_flashql,
)

from tests._hypothesis_compat import given, settings, st
from tests._differential import (
    SHARD_COUNTS,
    ROW_COUNTS,
    _table,
    _random_pred,
    _random_agg,
    _check_live_round,
)

LIFECYCLE_CORPUS = [
    (31, 97, "roundrobin"),
    (32, 97, "range"),
    (33, 130, "range"),
    (34, 31, "roundrobin"),
]


def _run_lifecycle_differential(seed: int, n: int, policy: str) -> None:
    """Random interleaved append/delete/update/query stream, bit-exact
    after every round vs a live-row numpy oracle — across shard counts
    {1, 2, 3}, both striping policies, a stripe_key fleet, and the
    unsharded scheduler; compaction fires mid-stream and must preserve
    results exactly while bumping epochs ONLY on rewritten stripes."""
    rng = np.random.default_rng(seed)
    resident = _table(rng, n)
    live = np.ones(n, bool)
    reserve = n  # headroom for the appended/updated rows

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
    if policy == "range":
        systems["routed"] = build_sharded_flashql(
            dict(resident), 3, policy="range", stripe_key="age",
            num_planes=2, reserve_rows=reserve,
        )

    def apply_all(op):
        for sys in systems.values():
            op(sys)

    warm = [_random_pred(rng) for _ in range(2)]
    for round_i in range(5):
        # -- one random mutation per round, mirrored into the model
        kind = ("append", "delete", "update", "compact", "delete")[round_i]
        if kind == "append":
            b = int(rng.integers(3, 10))
            batch = _table(rng, b)
            apply_all(lambda s: s.append(batch))
            resident = {
                c: np.concatenate([v, batch[c]]) for c, v in resident.items()
            }
            live = np.concatenate([live, np.ones(b, bool)])
        elif kind == "delete":
            pool = np.flatnonzero(live)
            ids = rng.choice(pool, min(len(pool) // 3, 25), replace=False)
            apply_all(lambda s: s.delete(ids))
            live[ids] = False
        elif kind == "update":
            pool = np.flatnonzero(live)
            ids = rng.choice(pool, min(len(pool), 6), replace=False)
            rows = _table(rng, len(ids))
            apply_all(lambda s: s.update(ids, rows))
            live[ids] = False
            resident = {
                c: np.concatenate([v, rows[c]]) for c, v in resident.items()
            }
            live = np.concatenate([live, np.ones(len(ids), bool)])
        else:  # compact — epochs may move ONLY on rewritten stripes
            probe = systems[3]
            pre = [d.store.epoch for d in probe.devices]
            dirty = [sh.deleted_rows > 0 for sh in probe.store.shards]
            apply_all(lambda s: s.compact())
            post = [d.store.epoch for d in probe.devices]
            for was_dirty, a, b in zip(dirty, pre, post):
                assert (b > a) == was_dirty, (seed, n, policy, dirty)
            resident = {c: v[live] for c, v in resident.items()}
            live = np.ones(int(live.sum()), bool)
            assert systems["unsharded"].store.num_rows == len(live)
            assert systems[3].store.num_rows == len(live)

        # -- every system answers every query identically to the oracle
        preds = [_random_pred(rng) for _ in range(2)] + warm
        queries = (
            [Query(p) for p in preds[:2]]
            + [Query(p, agg=Agg.MASK) for p in preds]
            + [Query(preds[0], agg=_random_agg(rng))]
        )
        for name, sys in systems.items():
            got = sys.serve(queries)
            try:
                _check_live_round(queries, got, resident, live)
            except AssertionError as err:
                raise AssertionError(
                    f"{(seed, n, policy, name, round_i, kind)}: {err}"
                ) from err


@pytest.mark.parametrize("seed,n,policy", LIFECYCLE_CORPUS)
def test_lifecycle_differential_corpus(seed, n, policy):
    """Deterministic CRUD-stream corpus: always runs."""
    _run_lifecycle_differential(seed, n, policy)


@settings(max_examples=4, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**16),
    n=st.sampled_from(ROW_COUNTS),
    policy=st.sampled_from(["roundrobin", "range"]),
)
def test_lifecycle_differential_property(seed, n, policy):
    """Property-style CRUD streams: hypothesis drives seeds when
    installed; the shim skips this (the corpus above still runs)."""
    _run_lifecycle_differential(seed, n, policy)
