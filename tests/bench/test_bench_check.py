"""The comparison that decides ``correct``: sound runs of both cells pass
it at a tiny size on the CPU, and it fails the control and a run whose
answers are altered where the program produces them."""

import json
import time

import numpy as np
import pytest

from bench import control, traffic
from bench.harness import REPO, load_cell, measure
from bench.reference import Reference

CELLS = [w["name"] for w in json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]
ROWS = 1 << 14
SEED = 2**31 + 5


def run(cell, seconds=1.0):
    return measure(
        cell, SEED, seconds, False, t_start=time.perf_counter(),
        rows=ROWS, require_tpu=False,
    )


@pytest.mark.parametrize("cell", CELLS)
def test_served_answers_equal_the_reference(cell):
    out = run(cell)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["check"]["wrong_answers"] == {"value": 0, "limit": 0}
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) == {"setup_s", "p50_ms", "p95_ms", "qps"}


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_comparison(cell):
    got = control.readings(cell, SEED, 40.0, rows=ROWS)
    assert got["correct"] is False
    assert got["wrong_answers"]["value"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_fails_the_run(cell, monkeypatch):
    """One answer altered where the program finalizes it: the run is not
    correct."""
    from repro.query import aggregate

    def shifted(cls):
        orig = cls.finalize
        state = {"n": 0}

        def finalize(self, partial, store):
            value = orig(self, partial, store)
            state["n"] += 1
            if state["n"] % 3:
                return value
            if hasattr(value, "words"):
                words = np.array(value.words, copy=True)
                words[0] ^= np.uint32(1)
                return type(value)(words, value.length)
            return value + 1

        return finalize

    for cls in (aggregate.CountAggregator, aggregate.MaskAggregator):
        monkeypatch.setattr(cls, "finalize", shifted(cls))
    out = run(cell)
    assert out["correct"] is False
    assert out["check"]["wrong_answers"]["value"] >= 1


def test_reference_matches_rowwise_semantics():
    rng = np.random.default_rng(0)
    n = 1000
    table = {"a": rng.integers(0, 4, n), "b": rng.integers(0, 3, n)}
    pred = ("and", ("in", "a", (1, 3)), ("or", ("eq", "b", 0), ("eq", "b", 2)))
    rowwise = np.isin(table["a"], (1, 3)) & ((table["b"] == 0) | (table["b"] == 2))
    ref = Reference(table)
    assert ref.answer("count", pred) == int(rowwise.sum())
    mask = ref.answer("mask", pred)
    assert mask.dtype == np.uint32 and mask.size == -(-n // 32)
    bits = np.unpackbits(mask.view(np.uint8), bitorder="little")[:n]
    assert np.array_equal(bits.astype(bool), rowwise)


def test_window_requests_are_drawn_within_the_window():
    cell = load_cell(CELLS[0])
    due, requests, keep = traffic.draw_window(cell.mix, 40.0, traffic.streams(SEED))
    assert due[-1] < 40.0 and len(requests) == len(due)
    assert keep == sorted(set(keep)) and keep[-1] < len(due)


@pytest.mark.parametrize("cell", CELLS)
def test_control_compares_the_runs_own_requests(cell, monkeypatch):
    """The control answers the requests a run of the same seed keeps."""
    kept = []
    real = traffic.draw_window

    def spy(*args, **kwargs):
        out = real(*args, **kwargs)
        kept.append([out[1][r] for r in out[2]])
        return out

    monkeypatch.setattr(traffic, "draw_window", spy)
    run(cell, seconds=2.0)
    control.readings(cell, SEED, 2.0, rows=ROWS)
    assert len(kept) == 2 and kept[0] == kept[1] and kept[0]
