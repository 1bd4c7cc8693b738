"""FlashQL subsystem tests: query results must match the ``eval_expr``
oracle bit-exactly (error injection disabled — every page ESP-programmed),
plus targeted coverage for planner spill paths, ``auto_layout``, the packed
store, and the plan cache."""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.bitops import valid_mask
from repro.core.engine import FlashArray, eval_expr
from repro.core.expr import Page, and_, nand_, nor_, not_, or_, leaves
from repro.core.placement import Layout, auto_layout
from repro.core.planner import Planner
from repro.core.store import PackedStore
from repro.query import (
    VALID_PAGE,
    Agg,
    AtLeast,
    BatchScheduler,
    BitmapStore,
    Eq,
    FlashDevice,
    In,
    Not,
    Query,
    QueryCompiler,
    Range,
    lower,
)
from repro.query.oracle import np_select as _np_oracle
from repro.query.ast import and_ as qand, or_ as qor

W = 8  # words per page for expression-level tests


def _rand_table(rng, n):
    return {
        "country": rng.integers(0, 8, n),
        "device": rng.integers(0, 4, n),
        "age": rng.integers(0, 100, n),
    }




def _random_pred(rng, depth=0):
    kind = rng.integers(0, 6 if depth < 2 else 4)
    if kind == 0:
        return Eq("country", int(rng.integers(0, 8)))
    if kind == 1:
        return In(
            "device", [int(v) for v in rng.choice(4, rng.integers(1, 4))]
        )
    if kind == 2:
        lo = int(rng.integers(0, 80))
        return Range("age", lo, lo + int(rng.integers(0, 40)))
    if kind == 3:
        return Not(_random_pred(rng, depth + 1))
    children = [_random_pred(rng, depth + 1) for _ in range(rng.integers(2, 4))]
    return qand(*children) if kind == 4 else qor(*children)


# ---------------------------------------------------------------------------
# FlashQL end to end
# ---------------------------------------------------------------------------


def test_flashql_random_queries_match_oracles():
    """Every query result matches BOTH the numpy oracle on the raw table and
    the eval_expr oracle on the logical bitmap pages (acceptance criterion)."""
    rng = np.random.default_rng(11)
    n = 3000
    table = _rand_table(rng, n)
    store = BitmapStore()
    store.ingest(table)
    dev = FlashDevice(num_planes=4)
    store.program(dev)
    sched = BatchScheduler(dev, store)

    queries = [Query(_random_pred(rng), agg=Agg.MASK) for _ in range(20)]
    results = sched.serve(queries)
    for q, r in zip(queries, results):
        want_np = _np_oracle(q.where, table, n)
        got = np.asarray(r.mask.to_bits()).astype(bool)
        np.testing.assert_array_equal(got, want_np)
        # bit-exact vs eval_expr on the *unmasked* packed words
        expr = lower(q.where, store)
        want_words = np.asarray(eval_expr(expr, store.logical))
        got_words = np.asarray(r.mask.words)
        mask = valid_mask(n)
        np.testing.assert_array_equal(got_words & mask, want_words & mask)


def test_flashql_count_matches_mask_popcount():
    rng = np.random.default_rng(3)
    n = 1000
    table = _rand_table(rng, n)
    store = BitmapStore()
    store.ingest(table)
    dev = FlashDevice(num_planes=2)
    store.program(dev)
    sched = BatchScheduler(dev, store)
    pred = qand(Eq("country", 3), Not(Eq("device", 1)))
    (r_count, r_mask) = sched.serve(
        [Query(pred, agg=Agg.COUNT), Query(pred, agg=Agg.MASK)]
    )
    assert r_count.count == int(
        np.asarray(r_mask.mask.to_bits()).astype(bool).sum()
    )
    assert r_count.count == int(_np_oracle(pred, table, n).sum())


def test_batched_execution_equals_sequential():
    """execute_batch (vmap path) and FlashArray.execute agree bit-exactly."""
    rng = np.random.default_rng(5)
    n = 2000
    table = _rand_table(rng, n)
    store = BitmapStore()
    store.ingest(table)
    dev = FlashDevice(num_planes=4)
    store.program(dev)
    arr = FlashArray()
    store.program(arr)

    compiler = QueryCompiler(store, dev)
    queries = [Query(Eq("country", c)) for c in range(8)]
    plans = [compiler.compile(q).plan for q in queries]
    batch = dev.execute_batch(plans)

    arr_compiler = QueryCompiler(store, arr)
    for q, words in zip(queries, batch):
        seq = arr.execute(arr_compiler.compile(q).plan)
        np.testing.assert_array_equal(np.asarray(words), np.asarray(seq))


def test_plan_cache_hits_on_repeated_shapes():
    rng = np.random.default_rng(8)
    table = _rand_table(rng, 500)
    store = BitmapStore()
    store.ingest(table)
    dev = FlashDevice(num_planes=1)
    store.program(dev)
    sched = BatchScheduler(dev, store)
    qs = [Query(Eq("country", 1)), Query(Range("age", 10, 20))]
    sched.serve(qs)
    assert sched.compiler.misses == 2 and sched.compiler.hits == 0
    sched.serve(qs)
    assert sched.compiler.misses == 2 and sched.compiler.hits == 2
    # a new ingest (possibly new distinct values) invalidates the cache key
    store.ingest(_rand_table(rng, 500))
    store2_pages = [p for p in store.logical if p not in dev.layout]
    for p in store2_pages:
        dev.fc_write(p, store.logical[p])
    sched.serve([Query(Eq("country", 1))])
    assert sched.compiler.misses == 3


def test_scheduler_stats_and_projection():
    rng = np.random.default_rng(2)
    table = _rand_table(rng, 800)
    store = BitmapStore()
    store.ingest(table)
    dev = FlashDevice(num_planes=2)
    store.program(dev)
    sched = BatchScheduler(dev, store, max_batch=4)
    res = sched.serve([Query(Eq("country", c % 8)) for c in range(10)])
    assert len(res) == 10
    s = sched.stats()
    assert s["queries_served"] == 10
    assert s["flushes"] == 3  # 4 + 4 + 2 under max_batch=4
    assert s["plan_cache_hits"] == 2  # c=0,1 repeat as c=8,9
    proj = sched.projection()
    assert proj["fc_time_s"] > 0 and proj["speedup_vs_osp"] > 0


def test_warmup_placement_uses_auto_layout():
    """Pages named by a warmup query get §6.3 context placement: the OR
    group lands co-located inverted, enabling a single-sensing In()."""
    rng = np.random.default_rng(4)
    table = {"c": rng.integers(0, 4, 300)}
    store = BitmapStore()
    store.ingest(table)
    dev = FlashDevice(num_planes=1)
    q = Query(In("c", [0, 1, 2]))
    store.program(dev, warmup=[q])
    compiler = QueryCompiler(store, dev)
    plan = compiler.compile(q).plan
    placements = [dev.layout[f"c={v}"] for v in (0, 1, 2)]
    assert all(p.inverted for p in placements)
    assert len({p.block for p in placements}) == 1
    # the OR group itself resolves in ONE sensing; the second senses the
    # spliced tombstone (live-row) wordline the compiler ANDs into every
    # plan — it lives in the plain-page block, outside the inverted group
    assert plan.num_sensing_ops == 2


def test_spilling_plans_join_the_batched_flush():
    """Range plans spill; since the one-dispatch flush they lower to
    batchable ExecPlans (device-resident scratch) instead of falling back
    to eager per-query execution — and repeated flushes must not thrash
    the device snapshot (the pre-pipeline engine re-uploaded the packed
    buffer after every scratch ESP write)."""
    rng = np.random.default_rng(6)
    n = 1200
    table = {"age": rng.integers(0, 64, n)}
    store = BitmapStore()
    store.ingest(table)
    dev = FlashDevice(num_planes=2)
    store.program(dev)
    sched = BatchScheduler(dev, store)
    q = Query(Range("age", 13, 37))
    (r,) = sched.serve([q])
    assert sched.eager_plans == 0  # spilling plans batch now
    assert r.count == int(((table["age"] >= 13) & (table["age"] <= 37)).sum())
    uploads = dev.store.snapshot_uploads
    (r2,) = sched.serve([q])
    assert r2.count == r.count
    assert dev.store.snapshot_uploads == uploads, (
        "a warm spilling flush must not re-upload the packed store"
    )


# ---------------------------------------------------------------------------
# Planner spill paths and auto_layout (satellite coverage)
# ---------------------------------------------------------------------------


def _write_random(arr, expr, rng):
    logical = {}
    for p in leaves(expr):
        if p.name in logical:
            continue
        words = jnp.array(rng.integers(0, 2**32, (W,), dtype=np.uint32))
        logical[p.name] = words
        arr.fc_write(p.name, words)
    return logical


def _check_auto(expr, seed=0, min_spills=None):
    rng = np.random.default_rng(seed)
    arr = FlashArray()
    arr.layout = auto_layout(expr)
    logical = _write_random(arr, expr, rng)
    plan = Planner(arr.layout).compile(expr)
    got = arr.execute(plan)
    want = eval_expr(expr, logical)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if min_spills is not None:
        assert plan.num_spills >= min_spills, plan
    return plan


def test_nested_nand_nor_spills():
    a, b, c, d, e = map(Page, "abcde")
    _check_auto(and_(nand_(a, b), nor_(c, d), e), seed=1)
    _check_auto(or_(nand_(a, b), nor_(c, d)), seed=2)
    _check_auto(nand_(nor_(a, b), nand_(c, d), e), seed=3)
    _check_auto(nor_(nand_(a, or_(b, c)), and_(d, e)), seed=4)


def test_inverse_chunks_beyond_four_blocks_spill():
    """AND over >4 single-block inverse groups: the De Morgan merge hits the
    ≤4-block power budget, so the 5th+ group forces extra inverse chunks
    that must spill and re-sense (paper §6.2 ordering rule)."""
    groups = [
        or_(Page(f"g{i}a"), Page(f"g{i}b")) for i in range(6)
    ]  # 6 OR groups -> 6 inverse blocks under auto_layout
    expr = and_(*groups)
    plan = _check_auto(expr, seed=7, min_spills=1)
    assert plan.num_sensing_ops >= 3  # 4-block chunk + spill chunk + resense


def test_or_of_spilling_and_chains():
    """OR whose AND children themselves spill (the planner bug found by
    FlashQL's bit-sliced range queries: a spill-chunk command inside an
    inlined AND chain must never initialize the C-latch)."""
    a, b, c, d, e, f = map(Page, "abcdef")
    expr = or_(
        and_(not_(a), not_(b), not_(c)),
        and_(not_(a), not_(b), d),
        and_(e, f),
    )
    rng = np.random.default_rng(12)
    arr = FlashArray()
    arr.layout.place_colocated(list("abcdef"))  # all plain, one block
    logical = _write_random(arr, expr, rng)
    plan = Planner(arr.layout).compile(expr)
    got = arr.execute(plan)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(eval_expr(expr, logical))
    )


def test_rejected_inline_trial_does_not_leak_scratch():
    """An OR child that cannot be inlined (its AND chain spills a C-latch
    subexpression) is trial-compiled and rolled back: the trial's scratch
    placements must not leak into the layout."""
    from repro.core.expr import xor_

    a, b, c, d = map(Page, "abcd")
    expr = or_(and_(a, xor_(b, c)), d)
    rng = np.random.default_rng(13)
    arr = FlashArray()
    arr.layout.place_colocated(list("abc"))
    arr.layout.place_spread(["d"])
    logical = _write_random(arr, expr, rng)
    plan = Planner(arr.layout).compile(expr)
    placed = set(arr.layout.placements)
    used = {
        cmd.page_name
        for cmd in plan.commands
        if hasattr(cmd, "page_name")
    }
    scratch_placed = {n for n in placed if n.startswith("__scratch")}
    assert scratch_placed == used, (scratch_placed, used)
    got = arr.execute(plan)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(eval_expr(expr, logical))
    )


def test_batch_allows_unrelated_non_esp_pages():
    """A non-ESP page the batch never senses must not disable batching,
    but sensing it from the batch path must raise."""
    rng = np.random.default_rng(14)
    table = {"c": rng.integers(0, 4, 200)}
    store = BitmapStore()
    store.ingest(table)
    dev = FlashDevice(num_planes=1)
    store.program(dev)
    dev.fc_write(
        "telemetry",
        jnp.array(rng.integers(0, 2**32, (store.words,), dtype=np.uint32)),
        esp=False,
    )
    compiler = QueryCompiler(store, dev)
    plan = compiler.compile(Query(Eq("c", 1))).plan
    (out,) = dev.execute_batch([plan])  # unrelated noisy page: fine
    assert out is not None
    noisy_plan = Planner(dev.layout).compile(Page("telemetry"))
    with pytest.raises(ValueError, match="non-ESP"):
        dev.execute_batch([noisy_plan])


def test_auto_layout_or_context_inverts_nested_leaves():
    expr = and_(or_(Page("x"), Page("y")), Page("z"))
    layout = auto_layout(expr)
    assert layout["x"].inverted and layout["y"].inverted
    assert not layout["z"].inverted
    assert layout["x"].block == layout["y"].block


def test_auto_layout_shared_page_keeps_first_placement():
    shared = Page("s")
    expr = or_(shared, and_(shared, Page("u")))
    layout = auto_layout(expr)
    # 's' first appears as a direct OR leaf -> inverted; the nested AND
    # reusing it must not re-place it
    assert layout["s"].inverted
    assert not layout["u"].inverted
    _check_auto(expr, seed=9)


# ---------------------------------------------------------------------------
# Packed store / layout index / determinism
# ---------------------------------------------------------------------------


def test_packed_store_roundtrip_and_planes():
    rng = np.random.default_rng(0)
    st = PackedStore(planes=4)
    pages = {}
    for i in range(5):
        w = rng.integers(0, 2**32, (10,), dtype=np.uint32)
        pages[f"p{i}"] = w
        st[f"p{i}"] = w
    for name, w in pages.items():
        np.testing.assert_array_equal(np.asarray(st[name]), w)
    # 10 words pad to 12 over 4 planes -> 3 words per plane
    assert st.padded_words == 12 and st.words_per_plane == 3
    pv = st.plane_view()
    assert pv.shape == (4, st.num_slots, 3)
    # identity row present at slot 0
    assert np.asarray(st.snapshot())[0].min() == 0xFFFFFFFF


def test_packed_store_rejects_ragged_pages():
    st = PackedStore()
    st["a"] = np.zeros(4, np.uint32)
    with pytest.raises(ValueError):
        st["b"] = np.zeros(5, np.uint32)


def test_layout_reverse_index():
    layout = Layout()
    layout.place("a", 3, 7)
    layout.place("b", 3, 8)
    assert layout.page_at(3, 7) == "a"
    assert layout.page_at(3, 8) == "b"
    with pytest.raises(KeyError):
        layout.page_at(3, 9)
    with pytest.raises(ValueError):
        layout.place("c", 3, 7)  # location occupied


def test_error_injection_reproducible_across_runs():
    """The per-page error seed must be PYTHONHASHSEED-independent: same
    page name + seed => identical injected errors (zlib.crc32, not hash)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import numpy as np, jax.numpy as jnp;"
        "from repro.core.engine import FlashArray;"
        "from repro.core.expr import Page;"
        "rng = np.random.default_rng(0);"
        "w = jnp.array(rng.integers(0, 2**32, (256,), dtype=np.uint32));"
        "a = FlashArray(); a.fc_write('noisy', w, esp=False);"
        "a.pec[a.layout['noisy'].block] = 10_000;"
        "print(np.asarray(a.fc_read(Page('noisy'))).sum())"
    )
    outs = set()
    for hashseed in ("0", "12345"):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(repo, "src")
        env["PYTHONHASHSEED"] = hashseed
        r = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            env=env,
            cwd=repo,
            timeout=300,
        )
        assert r.returncode == 0, r.stderr
        outs.add(r.stdout.strip())
    assert len(outs) == 1, f"hash-seed-dependent injection: {outs}"


def test_range_bsi_uses_logarithmic_pages():
    """Range lowering must touch only BSI slices, not per-value bitmaps."""
    rng = np.random.default_rng(1)
    store = BitmapStore()
    store.ingest({"v": rng.integers(0, 256, 400)})
    expr = lower(Range("v", 10, 200), store)
    names = {p.name for p in leaves(expr)} - {VALID_PAGE}
    assert all("#" in n for n in names), names
    assert len(names) <= 8  # 8 BSI slices for 8-bit values


def test_in_unknown_values_and_empty():
    rng = np.random.default_rng(1)
    table = {"c": rng.integers(0, 3, 100)}
    store = BitmapStore()
    store.ingest(table)
    dev = FlashDevice(num_planes=1)
    store.program(dev)
    sched = BatchScheduler(dev, store)
    r1, r2, r3 = sched.serve(
        [
            Query(In("c", [0, 99])),  # 99 never occurs
            Query(In("c", [77])),  # no member occurs
            Query(Not(In("c", [77]))),  # complement of empty = all rows
        ]
    )
    assert r1.count == int((table["c"] == 0).sum())
    assert r2.count == 0
    assert r3.count == 100


def test_served_default_path_resolves_to_emulation_on_cpu():
    """No ``interpret`` anywhere: the device takes the kernel path from
    the platform — emulation on the CPU — and its fused flush program
    carries no TPU kernel while answering like the oracle."""
    rng = np.random.default_rng(21)
    t = _rand_table(rng, 700)
    store = BitmapStore()
    store.ingest(t)
    dev = FlashDevice(num_planes=2)
    assert dev.interpret is True
    store.program(dev)
    sched = BatchScheduler(dev, store)
    preds = [qand(Eq("country", 2), Range("age", 10, 60)), In("device", [1, 3])]
    for q, r in zip(preds, sched.serve([Query(p) for p in preds])):
        assert r.count == int(_np_oracle(q, t, 700).sum())
    mask = jnp.asarray(store.valid_words_mask())
    hlo = sched.last_program.lower(dev.store.snapshot(), mask).compile().as_text()
    assert "tpu_custom_call" not in hlo


def test_batched_executor_matches_per_plan_folds():
    """The executor a TPU runs (in-place sensing for plain MWS steps, one
    dispatch per group, the S latch ANDed in the sensing) equals the
    per-plan folds the emulation vmaps, on every group of a mix of plain,
    spilling (deep range) and threshold plans — here with the sensing
    emulated, so the latch algebra of both is compared on the CPU."""
    from repro.query.device import group_execs, plan_batch_fn, plan_step_fn

    rng = np.random.default_rng(31)
    t = _rand_table(rng, 1500)
    store = BitmapStore()
    store.ingest(t)
    dev = FlashDevice(num_planes=2)
    store.program(dev)
    compiler = QueryCompiler(store, dev)
    preds = [
        qand(In("country", [1, 3, 5]), Eq("device", 2)),
        qor(Eq("country", 0), Not(Eq("device", 1))),
        Range("age", 7, 93),  # deep range: spills
        AtLeast(2, [Eq("country", 1), Eq("device", 3), Range("age", 0, 40)]),
        AtLeast(3, [Eq("country", c) for c in range(5)]),  # threshold
    ]
    execs = [compiler.exec_for(compiler.compile(Query(p))) for p in preds]
    data = dev.store.snapshot()
    kinds = set()
    for sig, members, stacked in group_execs(execs):
        idxs = [jnp.asarray(x) for x in stacked]
        want = jax.vmap(
            plan_step_fn(sig, True), in_axes=(None, None) + (0,) * len(idxs)
        )(data, None, *idxs)
        got = plan_batch_fn(sig, True)(data, None, *idxs)
        assert got.shape == (len(members),) + data.shape[1:]
        np.testing.assert_array_equal(
            np.asarray(got).reshape(len(members), -1), np.asarray(want)
        )
        kinds |= {(st.kind, bool(st.k), bool(st.subs)) for st in sig}
    # the mix covers plain, spilling and threshold steps
    assert {("mws", False, False), ("spill", False, False)} <= kinds
    assert any(k == "mws" and thr for k, thr, _ in kinds)
    assert any(k == "mws" and subs for k, _, subs in kinds)
