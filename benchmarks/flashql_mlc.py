"""Multi-level packing + one-shot threshold sensing benchmark.

Two device-level claims, both gated on deterministic counters (they hold
under ``--smoke`` too):

* **Density** — packing 3 bitmap pages per physical page (TLC-style
  voltage levels) must cut the words physically ESP-programmed by
  >= 1.8x on a full index ingest, SLC vs TLC, and shrink the physical
  wordline footprint to match.  Delta-program traffic on an append
  stream is reported alongside (co-resident pages merge into one ISPP
  pass each).
* **Sensing** — a k-of-N fuzzy-match workload served through the native
  ``AtLeast`` threshold sensing must need >= 2x fewer sensing ops per
  query than the same workload expressed as its equivalent Or-of-And
  combination chains on a packing-off (SLC) system.

Every result is asserted bit-exact against a numpy oracle, and the
threshold side against the chain side, before any counter is read.

Run:  PYTHONPATH=src python benchmarks/flashql_mlc.py [--smoke]
"""

from __future__ import annotations

import sys
from itertools import combinations

import numpy as np

from _harness import REPS, interleaved_best_of
from repro.compile_cache import enable_compile_cache
from repro.core.placement import Layout
from repro.query import (
    AtLeast,
    BatchScheduler,
    BitmapStore,
    Count,
    Eq,
    FlashDevice,
    Query,
)
from repro.query.ast import and_ as qand, or_ as qor
from repro.query.oracle import np_select

DENSITY_GATE = 1.8  # words programmed, SLC / TLC, full ingest
SENSING_GATE = 2.0  # sensings per query, chain / threshold

NUM_COLS = 6
CARD = 6  # six-page equality regions: every level count packs differently


def make_table(rng, n):
    return {
        chr(ord("a") + i): rng.integers(0, CARD, n)
        for i in range(NUM_COLS)
    }


def build(table, levels, reserve_rows=0):
    store = BitmapStore()
    store.ingest(table, reserve_rows=reserve_rows)
    dev = FlashDevice(num_planes=4, layout=Layout(levels=levels))
    programs, words = store.program(dev)
    sch = BatchScheduler(dev, store)
    return sch, programs, words


def fuzzy_pool(rng, count):
    """k-of-N fuzzy predicates with C(N, k) large enough that the chain
    form explodes: the regime the one-shot threshold sensing exists for."""
    pool = []
    for _ in range(count):
        cols = rng.permutation(NUM_COLS)[: int(rng.integers(5, 7))]
        preds = [
            (chr(ord("a") + c), int(rng.integers(0, CARD))) for c in cols
        ]
        k = len(preds) - int(rng.integers(1, 3))  # k in {N-2, N-1}
        pool.append((k, preds))
    return pool


def threshold_query(k, preds):
    return Query(
        AtLeast(k, [Eq(c, v) for c, v in preds]), agg=Count()
    )


def chain_query(k, preds):
    """The same k-of-N match as its explicit Or over C(N, k) And-combos."""
    return Query(
        qor(
            *(
                qand(*(Eq(c, v) for c, v in combo))
                for combo in combinations(preds, k)
            )
        ),
        agg=Count(),
    )


def oracle_count(k, preds, table, n):
    hits = sum(
        (np.asarray(table[c]) == v).astype(int) for c, v in preds
    )
    return int((hits >= k).sum())


def main() -> None:
    smoke = "--smoke" in sys.argv[1:]
    num_rows = 4_000 if smoke else 40_000
    num_queries = 16 if smoke else 48
    append_rows = 256 if smoke else 2_048

    rng = np.random.default_rng(0)
    table = make_table(rng, num_rows)
    print(
        f"rows={num_rows}  cols={NUM_COLS}x{CARD}  queries={num_queries}  "
        f"reps={REPS}  (smoke={smoke})"
    )

    # -- density gate: full-ingest programmed words, SLC vs MLC vs TLC ----
    ingest = {}
    systems = {}
    for levels in (1, 2, 3):
        sch, programs, words = build(
            table, levels, reserve_rows=append_rows
        )
        systems[levels] = sch
        ingest[levels] = (programs, words)
        print(
            f"levels={levels}: ingest {programs:5d} page programs, "
            f"{words:7d} words, "
            f"{sch.device.layout.physical_wordlines():4d} physical "
            f"wordlines"
        )
    density = ingest[1][1] / ingest[3][1]

    # the SAME append stream on every packing level: delta traffic shrinks
    # because co-resident page deltas merge into one physical program
    batch = make_table(rng, append_rows)
    for levels, sch in systems.items():
        sch.append(batch)
    delta_ratio = (
        systems[1].words_programmed / systems[3].words_programmed
    )
    print(
        f"append deltas: SLC {systems[1].words_programmed} words vs TLC "
        f"{systems[3].words_programmed} words ({delta_ratio:.2f}x fewer)"
    )

    # -- sensing gate: native k-of-N thresholds vs Or-of-And chains -------
    resident = {
        c: np.concatenate([v, batch[c]]) for c, v in table.items()
    }
    n = num_rows + append_rows
    pool = fuzzy_pool(rng, 8)
    picks = [pool[i % len(pool)] for i in range(num_queries)]
    thr_queries = [threshold_query(k, p) for k, p in picks]
    chain_queries = [chain_query(k, p) for k, p in picks]

    native = systems[3]  # packing on + threshold sensing
    chain, _, _ = build(resident, 1)  # packing off, chain-form queries

    # warm both (jit + plan caches), asserting bit-exactness every round
    for _ in range(2):
        res_thr = native.serve(thr_queries)
        res_chain = chain.serve(chain_queries)
        for (k, p), a, b in zip(picks, res_thr, res_chain):
            want = oracle_count(k, p, resident, n)
            assert a.value == want, (k, p, a.value, want)
            assert b.value == want, (k, p, b.value, want)
    print("threshold == chain == numpy oracle (bit-exact)")

    spq = {}
    for name, sysm, qs in (
        ("threshold", native, thr_queries),
        ("chain", chain, chain_queries),
    ):
        s0 = sysm.stats()["mws_commands"]
        sysm.serve(qs)
        spq[name] = (sysm.stats()["mws_commands"] - s0) / num_queries
    sensing_ratio = spq["chain"] / spq["threshold"]
    print(
        f"sensings/query: chain {spq['chain']:6.2f} vs threshold "
        f"{spq['threshold']:6.2f} ({sensing_ratio:.2f}x fewer), "
        f"threshold_senses={native.stats()['threshold_senses']}"
    )

    best = interleaved_best_of(
        {
            "threshold": lambda: native.serve(thr_queries),
            "chain": lambda: chain.serve(chain_queries),
        }
    )
    print(
        f"wall-clock: chain {num_queries / best['chain']:8.1f} q/s, "
        f"threshold {num_queries / best['threshold']:8.1f} q/s "
        f"({best['chain'] / best['threshold']:.2f}x)"
    )

    # -- deterministic acceptance (counters, not wall-clock) --------------
    assert density >= DENSITY_GATE, (
        f"TLC ingest must program >= {DENSITY_GATE}x fewer words than "
        f"SLC, got {density:.2f}x"
    )
    assert native.stats()["threshold_senses"] > 0, (
        "native side never issued a threshold sensing"
    )
    assert sensing_ratio >= SENSING_GATE, (
        f"k-of-N thresholds must need >= {SENSING_GATE}x fewer sensings "
        f"per query than And/Or chains, got {sensing_ratio:.2f}x"
    )
    print(
        f"acceptance: ingest density {density:.2f}x >= {DENSITY_GATE}x, "
        f"sensings {sensing_ratio:.2f}x >= {SENSING_GATE}x OK"
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
