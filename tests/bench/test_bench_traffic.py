"""The benchmark's generators: arrivals are fixed by the mix, requests and
tables by the seed, and every seed offers the same work."""

import json

import numpy as np
import pytest

from bench import traffic
from bench.harness import BENCH, REPO, load_cell

CELLS = [w["name"] for w in json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]
BIG = 2**31 + 11  # seeds may exceed 32 signed bits


def test_schedule_same_work_for_every_seed():
    """The arrival times come from the mix's own seed: every run seed
    meets the same Poisson draw of ``rate * seconds`` arrivals."""
    a, b = traffic.schedule(8.0, 51.0, 12), traffic.schedule(8.0, 51.0, 12)
    assert np.array_equal(a, b)
    assert len(a) == 408
    assert 0 < a[0] and a[-1] < 51.0 and np.all(np.diff(a) >= 0)
    assert not np.array_equal(a, traffic.schedule(8.0, 51.0, 13))
    # unsmoothed: gaps are exponential, so bursts of short gaps occur
    gaps = np.diff(a)
    assert gaps.mean() == pytest.approx(1 / 8, rel=0.1)
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.2)
    assert (gaps < 0.02).sum() >= 30
    c = load_cell("bmi.retention-cold")
    w1 = traffic.draw_window(c.mix, 51.0, traffic.streams(BIG))
    w2 = traffic.draw_window(c.mix, 51.0, traffic.streams(7))
    assert np.array_equal(w1[0], w2[0])
    assert w1[1] != w2[1] and w1[2] != w2[2]
    assert len(w1[2]) == c.mix["check_requests"]
    odd = traffic.schedule(1.0, 13.0, BIG)
    assert len(odd) == 13 and odd[-1] < 13.0


@pytest.mark.parametrize("cell", CELLS)
def test_requests_and_table_fixed_by_seed(cell):
    c = load_cell(cell)
    draws = []
    for _ in range(2):
        rng = traffic.streams(BIG)
        reqs = [traffic.draw_request(c.mix, rng["requests"]) for _ in range(50)]
        table = c.generator.make_table(c.config, rng["table"], 4096)
        draws.append((reqs, table))
    (r1, t1), (r2, t2) = draws
    assert r1 == r2
    assert t1.keys() == t2.keys()
    assert all(np.array_equal(t1[k], t2[k]) for k in t1)
    other = traffic.streams(BIG + 1)
    assert [traffic.draw_request(c.mix, other["requests"]) for _ in range(50)] != r1


def test_retention_panels_stay_cold():
    """Each panel has at least 168 variants, so over set-up and a window
    at the cell's rate no panel predicate reaches the 32 compiles after
    which the optimizer materializes it."""
    c = load_cell("bmi.retention-cold")
    rng = traffic.streams(BIG)["requests"]
    n = round(c.mix["rate_per_s"] * 51) + 64  # the longest window + warm-up
    uses: dict = {}
    for _ in range(n):
        for _agg, pred in traffic.draw_request(c.mix, rng):
            uses[pred] = uses.get(pred, 0) + 1
    assert max(uses.values()) < 32
    # ("and", ("in", "country", S), ("and", day, ...))
    lengths = {len(p[2]) - 1 for p in uses}
    assert lengths == {7, 14, 21, 28}


def test_flush_shapes():
    assert traffic.flush_shapes(8, 8) == [(8, 1)]
    shapes = traffic.flush_shapes(1, 8)
    assert len(shapes) == sum(range(1, 9))
    assert (8, 8) in shapes and (1, 1) in shapes
    assert traffic.flush_shapes(4, 8) == [(4, 1), (8, 1), (8, 2)]
    with pytest.raises(ValueError, match="divide"):
        traffic.flush_shapes(3, 8)


def test_mix_files_declare_what_the_generator_needs(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"rate_per_s": 1, "arrival_seed": 8, "queries": []}))
    with pytest.raises(ValueError, match="check_requests"):
        traffic.load_mix(bad)
    for mix in (BENCH / "traffic").glob("*.json"):
        traffic.load_mix(mix)
