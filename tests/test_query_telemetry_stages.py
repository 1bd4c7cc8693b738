"""Flush stages on the profiler's clock: ``Telemetry.stage``, the stage
counters, ``queue_wait_s``, ``h2d_bytes``, the collector hook and the
kernels' stable names.

* the four lifecycle spans (``compile``, ``dispatch``, ``transfer``,
  ``reduce``) still partition each flush exactly, and the nested stages
  (``build``/``launch`` in ``dispatch``, ``wait``/``copy`` in ``transfer``)
  lie inside their parents;
* stage counters, ``queue_wait_s`` and ``h2d_bytes`` count with telemetry
  off, while spans, profiler annotations and the collector hook do not;
* the collector hook leaves ``gc.callbacks`` with its Telemetry;
* answers are the same with telemetry on and off.
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitops import BitOp
from repro.kernels.mws.mws import (
    mws_block_reduce_pallas,
    mws_reduce_pallas,
    mws_sense_pallas,
)
from repro.kernels.mws_count.mws_count import mws_count_pallas
from repro.kernels.popcount.popcount import popcount_pallas
from repro.kernels.threshold.threshold import threshold_pallas
from repro.query import (
    AtLeast,
    BatchScheduler,
    BitmapStore,
    Count,
    Eq,
    FlashDevice,
    In,
    Mask,
    Query,
    Range,
    Sum,
    Telemetry,
    validate_trace,
)
from repro.query import telemetry as telemetry_mod
from repro.query.ast import and_ as qand
from repro.query.device import senses_in_place

LIFECYCLE = ("compile", "dispatch", "transfer", "reduce")
NESTED = {
    "build": "dispatch",
    "launch": "dispatch",
    "wait": "transfer",
    "copy": "transfer",
}
STAGES = LIFECYCLE + tuple(NESTED)


def _table(n, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "country": rng.integers(0, 6, n),
        "device": rng.integers(0, 4, n),
        "sales": rng.integers(0, 500, n),
    }


def _scheduler(table, **kw):
    store = BitmapStore()
    store.ingest(table)
    dev = FlashDevice(num_planes=2)
    store.program(dev)
    return BatchScheduler(dev, store, **kw)


def _queries():
    return [
        Query(Eq("country", 1)),
        Query(qand(Eq("country", 2), Eq("device", 1)), agg=Sum("sales")),
        Query(In("device", [0, 2]), agg=Count()),
        Query(Range("sales", 13, 437)),  # deep range: spills
        Query(Eq("device", 3), agg=Mask()),
    ]


def _hooks(tele):
    return [h for h in gc.callbacks if getattr(h, "ref", lambda: None)() is tele]


class _Annotations:
    """Records the names of the profiler annotations the telemetry makes."""

    def __init__(self, monkeypatch):
        self.names = []
        outer = self

        class Recorded:
            def __init__(self, name, **kw):
                self.name = name

            def __enter__(self):
                outer.names.append(self.name)
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(telemetry_mod, "TraceAnnotation", Recorded)


def _spans_by_flush(tele):
    out: dict[int, dict[str, tuple[float, float]]] = {}
    for name, _cat, _tid, t0, t1, args in tele.trace:
        if name == "ticket" or not args or "flush" not in args:
            continue
        out.setdefault(args["flush"], {})[name] = (t0, t1)
    return out


def test_lifecycle_spans_partition_each_flush_exactly():
    sched = _scheduler(_table(500), max_batch=2)
    sched.serve(_queries())
    flushes = _spans_by_flush(sched.telemetry)
    assert sorted(flushes) == list(range(1, int(sched.flushes) + 1))
    for spans in flushes.values():
        edges = [spans["flush"][0]]
        for name in LIFECYCLE:
            a, b = spans[name]
            assert a == edges[-1], name  # each begins where the last ended
            edges.append(b)
        assert edges[-1] == spans["flush"][1]


def test_nested_stages_lie_inside_their_parents():
    sched = _scheduler(_table(500), max_batch=2)
    sched.serve(_queries())
    assert validate_trace(sched.telemetry.export_trace()) > 0
    for spans in _spans_by_flush(sched.telemetry).values():
        assert set(STAGES) <= set(spans)
        for child, parent in NESTED.items():
            (a, b), (pa, pb) = spans[child], spans[parent]
            assert pa <= a <= b <= pb, child
        # wait then copy: the transfer is the device wait plus the copy
        assert spans["wait"][1] <= spans["copy"][0]
        assert spans["build"][1] <= spans["launch"][0]


def test_stage_counters_sum_their_spans():
    sched = _scheduler(_table(400), max_batch=3)
    sched.serve(_queries())
    tele = sched.telemetry
    for name in STAGES:
        total = sum(t1 - t0 for n, _c, _t, t0, t1, _a in tele.trace if n == name)
        assert tele.value(f"stage.{name}_s") == pytest.approx(total, rel=1e-9)


def test_tickets_carry_the_id_of_their_flush():
    sched = _scheduler(_table(300), max_batch=2)
    res = sched.serve(_queries())
    by_ticket = {
        args["ticket"]: args["flush"]
        for name, _c, _t, _a, _b, args in sched.telemetry.trace
        if name == "ticket"
    }
    assert sorted(by_ticket) == [r.ticket for r in res]
    # FIFO flushes of 2: tickets 0,1 -> flush 1, 2,3 -> flush 2, 4 -> 3
    assert [by_ticket[t] for t in sorted(by_ticket)] == [1, 1, 2, 2, 3]


def test_queue_wait_is_submit_to_flush_start():
    sched = _scheduler(_table(300), max_batch=2)
    res = sched.serve(_queries())
    want = sum(r.attribution["queue_s"] for r in res)
    assert sched.telemetry.value("queue_wait_s") == pytest.approx(want)
    h = sched.telemetry.hists["queue_wait_s"]
    assert h.count == len(res)
    assert h.total == pytest.approx(want)


def test_h2d_bytes_count_each_upload_once():
    sched = _scheduler(_table(600))
    tele = sched.telemetry
    qs = _queries()
    sched.serve(qs)
    built = sched.last_program.upload_bytes
    assert built > 0
    first = tele.value("h2d_bytes")
    mask = sched._mask_cache[1].nbytes
    snap = sched.device.store.snapshot().nbytes
    # the first flush uploads the program's indices, the mask and the store
    assert first == built + mask + snap
    sched.serve(qs)  # the same composition: program, mask and store cached
    assert tele.value("h2d_bytes") == first
    # an append changes the store: its snapshot, the mask and a fresh
    # program go up again, and the counter sees the whole-store upload
    sched.append({c: v[:5] for c, v in _table(5, seed=9).items()})
    sched.serve(qs)
    snap = sched.device.store.snapshot().nbytes
    assert tele.value("h2d_bytes") - first >= snap + mask
    assert sched.device.store.snapshot_uploads == 2


def test_disabled_telemetry_counts_but_records_nothing(monkeypatch):
    ann = _Annotations(monkeypatch)
    tele = Telemetry(enabled=False)
    sched = _scheduler(_table(400), telemetry=tele)
    sched.serve(_queries())
    for name in STAGES:
        assert tele.value(f"stage.{name}_s") > 0, name
    assert tele.value("queue_wait_s") >= 0 and "queue_wait_s" in tele.counters
    assert tele.value("h2d_bytes") > 0
    assert len(tele.trace) == 0 and tele.hists == {}
    # (other live Telemetry objects may annotate collector passes)
    assert [n for n in ann.names if n != "flashql.gc"] == []
    assert _hooks(tele) == []
    gc.collect()
    assert "gc_pause_s" not in tele.counters


def test_enabled_telemetry_annotates_every_stage(monkeypatch):
    ann = _Annotations(monkeypatch)
    sched = _scheduler(_table(400), max_batch=8)
    sched.serve(_queries())
    assert {f"flashql.{s}" for s in STAGES} <= set(ann.names)
    # one flush: each stage once, in order of entry
    staged = [n for n in ann.names if n != "flashql.gc"]
    assert staged == [
        "flashql.compile",
        "flashql.dispatch",
        "flashql.build",
        "flashql.launch",
        "flashql.transfer",
        "flashql.wait",
        "flashql.copy",
        "flashql.reduce",
    ]


def test_collector_hook_counts_passes_and_annotates(monkeypatch):
    ann = _Annotations(monkeypatch)
    tele = Telemetry()
    assert len(_hooks(tele)) == 1
    full = tele.value("gc_full_collections")
    gc.collect()
    assert tele.value("gc_full_collections") == full + 1
    assert tele.value("gc_pause_s") > 0
    assert "flashql.gc" in ann.names


def test_collector_hook_leaves_with_its_telemetry():
    gc.collect()  # earlier tests' garbage takes its hooks with it first
    n0 = len(gc.callbacks)
    sched = _scheduler(_table(200))  # scheduler <-> telemetry: a cycle
    tele = sched.telemetry
    assert len(_hooks(tele)) == 1 and len(gc.callbacks) == n0 + 1
    del tele
    del sched
    gc.collect()
    assert len(gc.callbacks) == n0
    # switching off removes it too, switching on brings one back
    tele = Telemetry()
    tele.enabled = False
    assert _hooks(tele) == [] and len(gc.callbacks) == n0
    tele.enabled = True
    assert len(_hooks(tele)) == 1
    del tele
    gc.collect()
    assert len(gc.callbacks) == n0


def test_answers_equal_with_telemetry_on_and_off():
    table = _table(700, seed=3)
    qs = _queries() * 2
    on = _scheduler(table, max_batch=3)
    off = _scheduler(table, max_batch=3, telemetry=Telemetry(enabled=False))
    for a, b in zip(on.serve(qs), off.serve(qs)):
        if hasattr(a.value, "words"):
            np.testing.assert_array_equal(
                np.asarray(a.value.words), np.asarray(b.value.words)
            )
        else:
            assert a.value == b.value
    assert on.stats()["host_transfers"] == off.stats()["host_transfers"]
    assert on.telemetry.value("h2d_bytes") == off.telemetry.value("h2d_bytes")


def _pallas_names(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    names = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return names


@pytest.mark.parametrize(
    "fn,shape,name",
    [
        (lambda x: mws_reduce_pallas(x, BitOp.AND, fan_in=8, block_words=128),
         (8, 256), "flashql_mws_reduce"),
        (lambda x: mws_block_reduce_pallas(x, or_blocks=True, block_words=128),
         (2, 3, 256), "flashql_mws_block_reduce"),
        (lambda x: popcount_pallas(x, block_rows=8, block_words=128),
         (8, 256), "flashql_popcount"),
        (lambda x: threshold_pallas(x, 2, 3, block_words=128),
         (3, 256), "flashql_threshold"),
        (lambda x: mws_count_pallas(x, BitOp.AND, fan_in=8, block_words=128),
         (8, 256), "flashql_mws_count"),
        (lambda x: mws_sense_pallas(jnp.zeros((2, 1, 3), jnp.int32), x, inverse=True),
         (4, 8, 128), "flashql_mws_sense"),
    ],
)
def test_served_kernels_carry_stable_names(fn, shape, name):
    assert _pallas_names(fn, jnp.zeros(shape, jnp.uint32)) == [name]


def _retention_scheduler():
    rng = np.random.default_rng(5)
    n = 900
    table = {f"day{d}": (rng.random(n) < 0.9).astype(np.int64) for d in range(8)}
    table["country"] = rng.integers(0, 6, n)
    store = BitmapStore()
    store.ingest(table)
    dev = FlashDevice(num_planes=2)
    days = qand(*(Eq(f"day{d}", 1) for d in range(8)))
    store.program(dev, warmup=[Query(days)])
    return BatchScheduler(dev, store)


def _static_sensings(program) -> tuple[int, int]:
    sense, _, _, cse = program.key
    placed = gathered = 0
    for sig, n in list(sense) + [(sig, 1) for sig in cse]:
        for st in sig:
            if st.kind == "mws":
                if senses_in_place(st):
                    placed += n
                else:
                    gathered += n
    return placed, gathered


def test_mws_sensings_count_in_place_and_gathered():
    """A retention-shaped flush (a cohort's country IN, ANDed with a run of
    days) senses every MWS step in place; a k-of-N threshold sensing is
    gathered.  Each launched flush adds MWS steps x members, as the flush
    program's static steps say."""
    sched = _retention_scheduler()
    tele = sched.telemetry
    cohort = In("country", [0, 2, 4])
    panels = [
        Query(qand(cohort, *(Eq(f"day{d}", 1) for d in range(8 - m, 8))))
        for m in (2, 4, 6, 8)
    ]
    sched.serve(panels)
    placed, gathered = _static_sensings(sched.last_program)
    assert gathered == 0 and placed >= len(panels)
    assert tele.value("mws_sensed_in_place") == placed
    assert tele.value("mws_sensed_gathered") == 0

    sched.serve(panels)  # the same composition counts again
    assert tele.value("mws_sensed_in_place") == 2 * placed

    # pages in five blocks: one native 3-of-5 threshold sensing
    vote = Query(AtLeast(3, [Eq("country", c) for c in range(5)]))
    sched.serve([vote])
    more_placed, more_gathered = _static_sensings(sched.last_program)
    assert more_gathered >= 1
    assert tele.value("mws_sensed_in_place") == 2 * placed + more_placed
    assert tele.value("mws_sensed_gathered") == more_gathered
