"""Sharded-FlashQL unit coverage: per-shard plan-cache invalidation,
plan-aware batching, scheduler stat accounting under multi-shard
admission, and the fleet projection."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core.store import IDENTITY_SLOT, ZERO_SLOT, PackedStore
from repro.query import (
    Agg,
    Eq,
    In,
    Query,
    Range,
    build_sharded_flashql,
)
from repro.query.ast import and_ as qand
from repro.query.shard import ShardedBitmapStore, stripe_rows


def _table(rng, n):
    return {
        "country": rng.integers(0, 8, n),
        "device": rng.integers(0, 4, n),
    }


# ---------------------------------------------------------------------------
# plan-cache invalidation is per device
# ---------------------------------------------------------------------------


def test_packed_store_epoch_bumps_on_writes_not_scratch():
    st = PackedStore()
    assert st.epoch == 0
    st["a"] = np.zeros(4, np.uint32)
    e1 = st.epoch
    assert e1 > 0
    st["a"] = np.ones(4, np.uint32)  # reprogram: content changed
    assert st.epoch > e1
    e2 = st.epoch
    st["__scratch0"] = np.zeros(4, np.uint32)  # plan-internal temporary
    st["__scratch0"] = np.ones(4, np.uint32)
    assert st.epoch == e2


def test_mutating_one_shard_recompiles_only_that_shards_region():
    """Reprogramming a page invalidates exactly the cached plans that
    sense that page's REGION (column) on that device: the other shards
    stay fully warm, and even on the mutated shard plans over other
    columns survive (region-granular plan-cache epochs)."""
    rng = np.random.default_rng(0)
    sq = build_sharded_flashql(_table(rng, 300), 3, num_planes=1)
    qs = [Query(Eq("country", 1)), Query(In("device", [0, 2]))]
    sq.serve(qs)
    assert [c.misses for c in sq.compilers] == [2, 2, 2]
    sq.serve(qs)
    assert [c.misses for c in sq.compilers] == [2, 2, 2]
    assert [c.hits for c in sq.compilers] == [2, 2, 2]

    # mutate shard 1's packed store (reprogram one page in place)
    dev = sq.devices[1]
    page = "country=1"
    dev.fc_write(page, sq.store.shards[1].logical[page], esp=True)

    sq.serve(qs)
    # only shard 1 recompiles, and only its country plan; the device
    # query re-keys and hits the surviving plan
    assert [c.misses for c in sq.compilers] == [2, 3, 2]
    assert [c.hits for c in sq.compilers] == [4, 3, 4]
    # ... and results stay correct after the recompile
    (r,) = sq.serve([Query(Eq("country", 1))])
    want = int((_table(np.random.default_rng(0), 300)["country"] == 1).sum())
    assert r.count == want


def test_scratch_spills_keep_shard_caches_warm():
    """Range plans spill (ESP scratch writes mid-plan); those writes must
    NOT bump the device epoch, or every flush would recompile the fleet."""
    rng = np.random.default_rng(1)
    table = {"age": rng.integers(0, 64, 400)}
    sq = build_sharded_flashql(table, 2, num_planes=1)
    q = Query(Range("age", 13, 37))
    sq.serve([q])
    misses = [c.misses for c in sq.compilers]
    sq.serve([q])
    assert [c.misses for c in sq.compilers] == misses
    assert all(c.hits >= 1 for c in sq.compilers)


# ---------------------------------------------------------------------------
# scheduler accounting under multi-shard admission
# ---------------------------------------------------------------------------


def test_stats_count_tickets_once_not_per_shard():
    rng = np.random.default_rng(2)
    sq = build_sharded_flashql(_table(rng, 500), 3, queue_depth=4)
    queries = [Query(Eq("country", c % 8)) for c in range(10)]
    res = sq.serve(queries)
    assert len(res) == 10
    s = sq.stats()
    assert s["queries_served"] == 10  # tickets, not shard-partials (30)
    assert s["flushes"] == 3  # 4 + 4 + 2 under queue_depth=4
    assert s["mean_latency_s"] > 0
    assert s["queries_per_sec"] > 0
    # latency is accumulated once per completed ticket
    assert s["mean_latency_s"] * 10 == pytest.approx(sq.total_latency_s)
    # every query ran on every shard
    assert s["mws_commands"] >= 10 * 3


def test_latency_monotone_in_queue_position():
    """Tickets admitted earlier wait through later flushes: a ticket served
    in flush k has latency >= its own flush time (sanity of accounting)."""
    rng = np.random.default_rng(3)
    sq = build_sharded_flashql(_table(rng, 200), 2, queue_depth=2)
    tickets = [sq.submit(Query(Eq("country", c % 8))) for c in range(6)]
    results = {}
    while sq.pending:
        results.update(sq.flush())
    lats = [results[t].latency_s for t in tickets]
    assert all(v > 0 for v in lats)
    # the last-flushed ticket waited at least as long as the first-flushed
    assert max(lats[4:]) >= min(lats[:2])


def test_plan_aware_batching_merges_shapes():
    """Eq over differently-sized columns yields different gather shapes of
    one family; padding must merge them into one vmap group."""
    rng = np.random.default_rng(4)
    sq = build_sharded_flashql(_table(rng, 400), 2, num_planes=1)
    # country has 8 wordlines co-located, device 4 -> different idx widths
    qs = [Query(In("country", [0, 1, 2])), Query(In("device", [0, 1]))]
    sq.serve(qs)
    s = sq.stats()
    assert s["distinct_signatures"] == 2
    assert s["vmap_batches"] == 1, "family padding should merge the group"
    assert s["fused_flushes"] == 1, "cross-shard fusion should engage"
    # correctness under padding
    t = _table(np.random.default_rng(4), 400)
    r1, r2 = sq.serve(qs)
    assert r1.count == int(np.isin(t["country"], [0, 1, 2]).sum())
    assert r2.count == int(np.isin(t["device"], [0, 1]).sum())


def test_zero_slot_is_or_neutral_under_inverse_read():
    """The ZERO_SLOT block-padding row must be OR-neutral also for
    inverse-read commands (complement happens after the cross-block OR)."""
    from repro.core.engine import fused_block_reduce
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    words = rng.integers(0, 2**32, (2, 3, 4), dtype=np.uint32)
    cube = jnp.asarray(words)
    ones = jnp.full((1, 3, 4), 0xFFFFFFFF, dtype=jnp.uint32)
    zero_block = jnp.concatenate(
        [jnp.zeros((1, 1, 4), jnp.uint32), ones[:, :2]], axis=1
    )
    padded = jnp.concatenate([cube, zero_block], axis=0)
    for inverse in (False, True):
        np.testing.assert_array_equal(
            np.asarray(fused_block_reduce(cube, inverse)),
            np.asarray(fused_block_reduce(padded, inverse)),
        )


def test_unknown_column_rejected_at_submit_without_poisoning_queues():
    """A bad query must fail at admission; failing inside flush() would
    leave shard queues out of lockstep (popped on some shards only)."""
    rng = np.random.default_rng(10)
    sq = build_sharded_flashql(_table(rng, 100), 2)
    with pytest.raises(KeyError, match="nope"):
        sq.submit(Query(qand(Eq("country", 1), Eq("nope", 1))))
    assert sq.pending == 0
    # the fleet keeps serving normally afterwards
    (r,) = sq.serve([Query(Eq("country", 1))])
    t = _table(np.random.default_rng(10), 100)
    assert r.count == int((t["country"] == 1).sum())


def test_per_device_fallback_matches_fused():
    """With cross-shard fusion disabled every shard runs its own vmap
    batches; results must be identical to the fused path."""
    rng = np.random.default_rng(8)
    table = _table(rng, 257)
    qs = [
        Query(Eq("country", 2)),
        Query(In("device", [1, 3]), agg=Agg.MASK),
    ]
    fused = build_sharded_flashql(table, 3).serve(qs)
    sq = build_sharded_flashql(table, 3)
    sq.fuse_across_shards = False
    fallback = sq.serve(qs)
    assert sq.fused_flushes == 0 and sq.stats()["vmap_batches"] >= 3
    assert fallback[0].count == fused[0].count
    np.testing.assert_array_equal(
        np.asarray(fallback[1].mask.words), np.asarray(fused[1].mask.words)
    )


def test_non_esp_page_routes_shard_to_guarded_path():
    """A non-ESP page on one shard device must disable the fused path (it
    never injects errors) and fall back to execute_batch's guard."""
    import jax.numpy as jnp

    rng = np.random.default_rng(9)
    sq = build_sharded_flashql(_table(rng, 200), 2)
    w = sq.store.shards[0].words
    sq.devices[0].fc_write(
        "telemetry",
        jnp.asarray(rng.integers(0, 2**32, (w,), dtype=np.uint32)),
        esp=False,
    )
    (r,) = sq.serve([Query(Eq("country", 1))])
    t = _table(np.random.default_rng(9), 200)
    assert r.count == int((t["country"] == 1).sum())
    assert sq.fused_flushes == 0


# ---------------------------------------------------------------------------
# striping / store mechanics
# ---------------------------------------------------------------------------


def test_stripe_rows_partitions_exactly():
    for n in (0, 1, 7, 64, 97):
        for s in (1, 2, 3, 5):
            for policy in ("roundrobin", "range"):
                parts = stripe_rows(n, s, policy)
                assert len(parts) == s
                merged = np.sort(np.concatenate(parts))
                np.testing.assert_array_equal(merged, np.arange(n))


def test_sharded_store_forces_global_schema():
    """A value present only on one stripe still gets an (all-zero) page on
    every other shard, so lowering/placement agree across the fleet."""
    table = {"c": np.array([5, 0, 0, 0])}  # round-robin: 5 lands on shard 0
    store = ShardedBitmapStore(num_shards=2)
    store.ingest(table)
    for st in store.shards:
        assert st.columns["c"].values == (0, 5)
        assert "c=5" in st.logical
    # shard 1 never saw value 5: its page must be all-zero
    assert int(np.asarray(store.shards[1].logical["c=5"]).sum()) == 0


def test_shard_devices_share_canonical_layout():
    rng = np.random.default_rng(6)
    sq = build_sharded_flashql(
        _table(rng, 300), 3, warmup=[Query(In("country", [0, 1, 2]))]
    )
    ref = sq.devices[0].layout.placements
    for dev in sq.devices[1:]:
        assert dev.layout.placements == ref
    # warmup steered placement: the In() group is co-located inverted
    pl = [sq.devices[2].layout[f"country={v}"] for v in (0, 1, 2)]
    assert all(p.inverted for p in pl) and len({p.block for p in pl}) == 1


def test_identity_and_zero_slots_always_present():
    st = PackedStore(planes=2)
    st["p"] = np.arange(6, dtype=np.uint32)
    snap = np.asarray(st.snapshot())
    assert snap[IDENTITY_SLOT].min() == 0xFFFFFFFF
    assert snap[ZERO_SLOT].max() == 0


def test_fleet_projection_aggregates_devices():
    rng = np.random.default_rng(7)
    sq = build_sharded_flashql(_table(rng, 600), 2)
    sq.serve([Query(qand(Eq("country", 1), Eq("device", 2)))] * 4)
    proj = sq.projection()
    assert proj["num_devices"] == 2
    assert len(proj["per_shard"]) == 2
    # fleet time is the max over concurrent devices, energy the sum
    assert proj["fc_time_s"] == pytest.approx(
        max(p["fc_time_s"] for p in proj["per_shard"])
    )
    assert proj["fc_energy_j"] == pytest.approx(
        sum(p["fc_energy_j"] for p in proj["per_shard"])
    )
    assert proj["speedup_vs_osp"] > 0


REPO = Path(__file__).resolve().parents[1]


def _on_four_cpu_devices(body: str) -> str:
    """Run ``body`` in a fresh interpreter that sees four virtual CPU
    devices and has ``chip_smoke`` loaded as ``smoke`` (a subprocess,
    because the device count is fixed when JAX starts); return stdout."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(REPO / "src")
    script = (
        textwrap.dedent(
            f"""
            import importlib.util, jax
            import numpy as np
            spec = importlib.util.spec_from_file_location(
                "chip_smoke", {str(REPO / "chip_smoke.py")!r}
            )
            smoke = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(smoke)
            assert len(jax.devices()) == 4
            """
        )
        + textwrap.dedent(body)
    )
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        env=env,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_fleet_places_one_stripe_per_device_and_lockstep_refuses():
    """Rehearsal of ``chip_smoke.py --chips 4`` on four virtual CPU devices:
    stripe s lives on device s, the pipelined fleet answers like the numpy
    oracle, and the lockstep path refuses to stack stripes of four devices
    onto one."""
    stdout = _on_four_cpu_devices(
        """
        smoke.FLEET_ROWS = 1 << 13
        smoke.four_chips(jax)
        """
    )
    for s in range(4):
        assert f"on device {s}" in stdout
    assert "lockstep across 4 devices refused" in stdout


def test_default_fleet_on_four_devices_stays_on_one_and_serves():
    """A fleet built without ``pipeline`` flushes in lockstep, which stacks
    its stripes into one array: on a four-device host it keeps every stripe
    on the default device and serves like the numpy oracle."""
    stdout = _on_four_cpu_devices(
        """
        from repro.query import build_sharded_flashql
        n = 1 << 13
        table = smoke.make_table(np.random.default_rng(1), n)
        fleet = build_sharded_flashql(table, 4, num_planes=4)
        placed = set()
        for dev in fleet.devices:
            placed |= dev.store.snapshot().devices()
        assert placed == {jax.devices()[0]}, placed
        queries = smoke.smoke_queries()
        wants = [smoke.oracle(q, [table], np.ones((n,), bool)) for q in queries]
        smoke.serve_checked(fleet, queries, wants, "lockstep fleet")
        print("default fleet served on one device")
        """
    )
    assert "default fleet served on one device" in stdout
