"""The plain reference: predicates and aggregates evaluated with numpy over
the raw columns.

It imports nothing of the system under test and takes nothing it made:
every bitmap here is packed from the generated table itself.  A predicate
is the nested tuple of :mod:`bench.traffic`.  A row's bit is set when the
predicate holds for it; bits of rows past the table are zero.

Answers have one plain form on both sides of the comparison:

* ``count``: a Python ``int``;
* ``mask``: the selected rows as little-endian bits packed into
  ``ceil(rows / 32)`` ``uint32`` words.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def pack(bits: np.ndarray) -> np.ndarray:
    """Row bits (nonzero = set) -> little-endian bits in uint64 words."""
    packed = np.packbits(bits, bitorder="little")
    out = np.zeros((-(-packed.size // 8) * 8,), np.uint8)
    out[: packed.size] = packed
    return out.view(np.uint64)


class Reference:
    """Evaluates predicates over ``table`` (column name -> 1-D array)."""

    def __init__(self, table: dict[str, np.ndarray], threads: int = 4):
        (self.rows,) = {len(v) for v in table.values()}
        self.table = table
        self.threads = threads
        self._eq: dict[tuple[str, int], np.ndarray] = {}

    def eq(self, column: str, value: int) -> np.ndarray:
        key = (column, value)
        got = self._eq.get(key)
        if got is None:
            got = self._eq[key] = pack(self.table[column] == value)
        return got

    def _leaves(self, pred: tuple, out: set) -> None:
        if pred[0] == "eq":
            out.add((pred[1], pred[2]))
        elif pred[0] == "in":
            out.update((pred[1], v) for v in pred[2])
        else:
            for child in pred[1:]:
                self._leaves(child, out)

    def bitmap(self, pred: tuple) -> np.ndarray:
        op = pred[0]
        if op == "eq":
            return self.eq(pred[1], pred[2]).copy()
        if op == "in":
            acc = np.zeros_like(self.eq(pred[1], pred[2][0]))
            for v in pred[2]:
                np.bitwise_or(acc, self.eq(pred[1], v), out=acc)
            return acc
        if op in ("and", "or"):
            acc = self.bitmap(pred[1])
            fold = np.bitwise_and if op == "and" else np.bitwise_or
            for child in pred[2:]:
                # a leaf is read in place; only inner nodes allocate
                operand = (
                    self.eq(child[1], child[2])
                    if child[0] == "eq"
                    else self.bitmap(child)
                )
                fold(acc, operand, out=acc)
            return acc
        raise ValueError(f"unknown predicate {op!r}")

    def answer(self, agg: str, pred: tuple):
        bits = self.bitmap(pred)
        if agg == "count":
            return int(np.bitwise_count(bits).sum(dtype=np.int64))
        if agg == "mask":
            return bits.view(np.uint32)[: -(-self.rows // 32)]
        raise ValueError(f"unknown aggregate {agg!r}")

    def answers(self, queries: list[tuple[str, tuple]]) -> list:
        """Answers to ``(agg, pred)`` pairs, in order.  The leaf bitmaps
        are packed once; the queries then run on ``threads`` threads
        (numpy releases the interpreter lock on large arrays)."""
        leaves: set = set()
        for _, pred in queries:
            self._leaves(pred, leaves)
        with ThreadPoolExecutor(self.threads) as pool:
            list(pool.map(lambda cv: self.eq(*cv), sorted(leaves)))
            return list(pool.map(lambda q: self.answer(*q), queries))


def same(got, want) -> bool:
    """Whether a served answer equals the reference's, exactly.  A mask
    may carry zero words past the table (reserved capacity)."""
    if isinstance(want, np.ndarray):
        got = np.asarray(got, dtype=np.uint32)
        n = want.size
        return bool(
            got.size >= n
            and np.array_equal(got[:n], want)
            and not got[n:].any()
        )
    return type(got) is int and got == want
