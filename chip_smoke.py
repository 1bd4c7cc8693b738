"""Smoke run of FlashQL's serving path on a TPU.

Drives the normal entry points (``BitmapStore`` -> ``FlashDevice`` ->
``BatchScheduler``, as in ``examples/flashql_demo.py``) over a seeded table
shaped like the paper's BMI workload (§7): 2^27 users — cut from the
paper's 800M so that host ingest fits in one run — with 30 daily-activity
0/1 columns, ``country`` (8 values) and ``age`` (13..89).  It serves a
COUNT over the AND of all 30 days (the 30-operand MWS), an In/Range
predicate under SUM/AVG/GROUP BY, a MASK and a k-of-N threshold query;
takes one append batch and one delete; serves the same queries again; and
checks every answer against the numpy oracle.  A smaller store then
serves the same queries on the XLA-fold path and the compiled-kernel path
in this process, which must agree bit for bit.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # only the four-chip fleet phase

``--chips 4`` serves a four-shard pipelined fleet over 2^29 rows with
stripe ``s`` on chip ``s`` and checks that the lockstep path refuses a
fleet spread over several chips.

All work runs in this one process.  Without a TPU, on a wrong answer, or
when any phase raises, the script exits non-zero and prints no ``ok``
line.  The seconds it prints are smoke timings, not benchmark numbers.
The last line is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time
from pathlib import Path

import numpy as np

ROWS = 1 << 27  # one chip
FLEET_ROWS = 1 << 29  # four chips, one stripe each
CHECK_ROWS = 1 << 20  # XLA-fold vs compiled-kernel cross-check
APPEND_ROWS = 1 << 16  # reserved capacity and the append batch
DELETE_ROWS = 4096
DAYS = 30
# the fused flush program for the smoke's query mix must fit the v5e's
# 16 GiB of HBM beside the store (tests/test_tpu_compile.py checks it)
MAX_BATCH = 8
SEED = 0


def make_table(rng: np.random.Generator, n: int) -> dict[str, np.ndarray]:
    """BMI-shaped columns in uint8 (30 int64 columns of 2^27 rows would
    take 32 GB of host memory)."""

    def u8() -> np.ndarray:  # n uniform bytes, straight from the generator
        return rng.bit_generator.random_raw(-(-n // 8)).view(np.uint8)[:n]

    table = {f"day{d}": (u8() < 243).view(np.uint8) for d in range(DAYS)}
    table["country"] = u8() & 7
    table["age"] = 13 + u8() % 77
    return table


def smoke_queries() -> list:
    from repro.query import (
        AtLeast,
        Avg,
        Eq,
        GroupBy,
        In,
        Mask,
        Query,
        Range,
        Sum,
    )
    from repro.query.ast import and_

    days = [Eq(f"day{d}", 1) for d in range(DAYS)]
    cohort = and_(In("country", [0, 2, 5]), Range("age", 18, 35))
    return [
        Query(and_(*days), tag="COUNT active all 30 days"),
        Query(cohort, agg=Sum("age"), tag="SUM age, 18-35 in 3 countries"),
        Query(cohort, agg=Avg("age"), tag="AVG age, same cohort"),
        Query(cohort, agg=GroupBy("country"), tag="GROUP BY country"),
        Query(
            and_(Eq("country", 3), Range("age", 65, None)),
            agg=Mask(),
            tag="MASK country 3, 65+",
        ),
        Query(
            AtLeast(3, [Eq(f"day{d}", 0) for d in range(8)]),
            tag="COUNT idle on >=3 of the first 8 days",
        ),
    ]


def oracle(q, parts: list[dict], valid: np.ndarray):
    """The numpy answer to ``q`` over the row-concatenated ``parts``;
    rows where ``valid`` is False are deleted."""
    from repro.query.ast import Avg, Count, GroupBy, Mask, Sum, normalize_agg
    from repro.query.oracle import np_select

    sel = np.concatenate(
        [np_select(q.where, t, len(t["age"])) for t in parts]
    )
    sel &= valid
    spec = normalize_agg(q.agg)

    def column(name):
        return np.concatenate([t[name] for t in parts])

    if isinstance(spec, Count):
        return int(sel.sum())
    if isinstance(spec, Mask):
        return sel
    if isinstance(spec, Sum):
        return int(column(spec.column)[sel].sum(dtype=np.int64))
    if isinstance(spec, Avg):
        count = int(sel.sum())
        total = int(column(spec.column)[sel].sum(dtype=np.int64))
        return total / count if count else None
    if isinstance(spec, GroupBy):
        counts = np.bincount(column(spec.key)[sel], minlength=256)
        return {int(v): int(c) for v, c in enumerate(counts) if c}
    raise TypeError(f"no oracle for {spec!r}")


def same(got, want) -> bool:
    if isinstance(want, np.ndarray):  # MASK: compare packed words
        words = np.asarray(got.words)
        packed = np.packbits(want, bitorder="little")
        ref = np.zeros((words.size * 4,), np.uint8)
        ref[: packed.size] = packed
        return bool(np.array_equal(words, ref.view(np.uint32)))
    return got == want


def show(value) -> str:
    if hasattr(value, "words"):
        return f"bitmap of {int(np.bitwise_count(np.asarray(value.words)).sum())} rows"
    return repr(value)


def host_memory() -> str:
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    total = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    return f"host memory: peak RSS {peak} B of {total} B"


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def serve_checked(sched, queries, wants, label: str) -> list:
    t0 = time.perf_counter()
    results = sched.serve(queries)
    dt = time.perf_counter() - t0
    for q, r, want in zip(queries, results, wants):
        check(
            same(r.value, want),
            f"{label}: {q.tag}: got {r.value!r}, oracle {want!r}",
        )
        print(f"  {label}: {q.tag}: {show(r.value)} == oracle")
    print(f"smoke timing: {label} served {len(queries)} queries in {dt:.3f} s")
    return results


def build(table, *, reserve_rows: int = 0, interpret=None):
    from repro.query import BatchScheduler, BitmapStore, FlashDevice

    store = BitmapStore()
    store.ingest(table, reserve_rows=reserve_rows)
    dev = FlashDevice(num_planes=4, interpret=interpret)
    # the warmup steers §6.3 placement: the 30 day bitmaps share one
    # block, so their AND is one 30-wordline MWS sensing
    store.program(dev, warmup=smoke_queries()[:1])
    return BatchScheduler(dev, store, max_batch=MAX_BATCH)


def one_chip(jax) -> None:
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    table = make_table(rng, ROWS)
    t1 = time.perf_counter()
    sched = build(table, reserve_rows=APPEND_ROWS)
    t2 = time.perf_counter()
    print(
        f"smoke timing: table of {ROWS} rows made in {t1 - t0:.1f} s, "
        f"ingested and programmed in {t2 - t1:.1f} s"
    )
    queries = smoke_queries()
    wants = [oracle(q, [table], np.ones((ROWS,), bool)) for q in queries]
    serve_checked(sched, queries, wants, "cold flush (includes compile)")
    serve_checked(sched, queries, wants, "warm flush")

    chip = jax.devices()[0]
    snap = sched.device.store.snapshot()
    stats = chip.memory_stats() or {}
    print(
        f"store: {sched.store.num_rows} rows, {len(sched.device.store)} pages, "
        f"{snap.nbytes} bytes on {chip.device_kind}; "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}"
    )

    program = sched.last_program
    t3 = time.perf_counter()
    mask = jax.numpy.asarray(sched.store.valid_words_mask())
    compiled = program.lower(snap, mask).compile()
    t4 = time.perf_counter()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    print(
        f"flush program (max_batch={MAX_BATCH}): "
        f"argument {mem.argument_size_in_bytes} B, "
        f"output {mem.output_size_in_bytes} B, "
        f"temp {mem.temp_size_in_bytes} B, "
        f"generated code {mem.generated_code_size_in_bytes} B; "
        f"smoke timing: compiled in {t4 - t3:.1f} s"
    )
    kernels = hlo.count("tpu_custom_call")
    check(kernels > 0, "the flush program dispatches no Pallas kernel")
    print(f"flush program HLO holds tpu_custom_call ({kernels} mentions)")

    append = make_table(rng, APPEND_ROWS)
    pages = sched.append(append)
    n_total = ROWS + APPEND_ROWS
    dead = np.unique(rng.integers(0, n_total, DELETE_ROWS))
    sched.delete(dead)
    valid = np.ones((n_total,), bool)
    valid[dead] = False
    print(
        f"mutations: appended {APPEND_ROWS} rows ({pages} pages), "
        f"deleted {dead.size} rows"
    )
    wants = [oracle(q, [table, append], valid) for q in queries]
    serve_checked(sched, queries, wants, "after append+delete")

    small = make_table(rng, CHECK_ROWS)
    fold = build(small, interpret=True).serve(queries)
    kern = build(small).serve(queries)
    valid = np.ones((CHECK_ROWS,), bool)
    for q, a, b in zip(queries, fold, kern):
        want = oracle(q, [small], valid)
        check(same(a.value, want), f"XLA fold: {q.tag}: {a.value!r} != oracle")
        check(same(b.value, want), f"kernels: {q.tag}: {b.value!r} != oracle")
        if hasattr(want, "shape"):
            check(
                np.array_equal(np.asarray(a.value.words), np.asarray(b.value.words)),
                f"{q.tag}: XLA fold and kernel bitmaps differ",
            )
        else:
            check(a.value == b.value, f"{q.tag}: XLA fold and kernel differ")
    print(
        f"cross-check at {CHECK_ROWS} rows: XLA-fold path and compiled-kernel "
        "path bit-identical, both == oracle"
    )


def four_chips(jax) -> None:
    from repro.query import build_sharded_flashql

    chips = jax.devices()
    check(len(chips) >= 4, f"--chips 4 needs four devices, found {len(chips)}")
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    table = make_table(rng, FLEET_ROWS)
    t1 = time.perf_counter()
    fleet = build_sharded_flashql(table, 4, pipeline=True, num_planes=4)
    t2 = time.perf_counter()
    print(
        f"smoke timing: table of {FLEET_ROWS} rows made in {t1 - t0:.1f} s, "
        f"striped, ingested and programmed in {t2 - t1:.1f} s"
    )
    for s, dev in enumerate(fleet.devices):
        placed = dev.store.snapshot().devices()
        check(placed == {chips[s]}, f"stripe {s} lives on {placed}, not {chips[s]}")
        nbytes = dev.store.snapshot().nbytes
        print(f"stripe {s}: {nbytes} bytes on device {chips[s].id}")
    queries = smoke_queries()
    wants = [oracle(q, [table], np.ones((FLEET_ROWS,), bool)) for q in queries]
    serve_checked(fleet, queries, wants, "fleet cold flush (includes compile)")
    serve_checked(fleet, queries, wants, "fleet warm flush")
    for c in chips[:4]:
        stats = c.memory_stats() or {}
        print(
            f"device {c.id}: bytes_in_use {stats.get('bytes_in_use')}, "
            f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}"
        )
    fleet.pipeline = False
    fleet.submit(queries[0])
    try:
        fleet.flush()
    except RuntimeError as e:
        check("B1" in str(e), f"unexpected lockstep error: {e}")
        print(f"lockstep across 4 devices refused: {e}")
    else:
        raise RuntimeError("lockstep flush stacked stripes from four chips")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax

    from repro.compile_cache import cache_entries, enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX sees {devices[0].platform}); "
            "this script measures nothing off the chip",
            file=sys.stderr,
        )
        return 1
    cache = enable_compile_cache()
    print(f"devices: {len(devices)} x {devices[0].device_kind}; compile cache {cache}")
    if args.chips == 4:
        four_chips(jax)
    else:
        one_chip(jax)
    print(host_memory())
    print(f"smoke: compile cache holds {cache_entries(cache)} entries")
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": devices[0].platform,
                    "kind": devices[0].device_kind,
                    "count": args.chips,
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
