"""Open-loop traffic from a mix file: the arrival schedule and the requests.

A mix is a JSON file ``bench/traffic/<mix>.json``.  Its keys:

``rate_per_s``
    Requests per second offered to the system.
``arrival_seed``
    Arrivals are a Poisson process at the mix's rate, given its count: the
    window's share of requests, each due at a time drawn uniformly over
    the window.  The times are drawn from this seed, not from the run's:
    every run of the mix meets the same bursts, so that the tail it
    measures is the system's and not the luck of one draw (with times
    drawn per run, the p95 of a 400-request window spreads by a seventh
    or more from seed to seed).
``queries``
    The request template: a list of ``{"agg": "count" | "mask", "where":
    <predicate template>}``.  A request is every query of the list,
    submitted together.
``draws``
    Parameters drawn once per request and shared by its queries:
    ``{"kind": "subsets", "values": [...], "size": s, "count": c}`` draws
    ``c`` distinct sorted subsets of ``s`` values.
``check_requests``
    How many requests due in the window the run compares with the
    reference (drawn from the seed).

A predicate template is a JSON list: ``["eq", column, value]``,
``["in", column, values]``, ``["and", t, ...]``, ``["or", t, ...]``, or
``["run", prefix, value, length, first, last]`` — the AND of
``Eq(prefix<i>, value)`` over ``length`` consecutive columns ending at a
column drawn uniformly for each query from ``first + length - 1 ..
last``.  A value is a literal, ``{"draw": name}`` or ``{"draw": name,
"at": i}``.

Templates resolve to plain nested tuples (``("eq", col, v)``, ``("in",
col, (v, ...))``, ``("and", ...)``, ``("or", ...)``), which both the
reference and the adapter to the system read.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

AGGS = ("count", "mask")


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    for key in ("rate_per_s", "arrival_seed", "queries", "check_requests"):
        if key not in mix:
            raise ValueError(f"traffic mix {path} has no {key!r}")
    for q in mix["queries"]:
        if q["agg"] not in AGGS:
            raise ValueError(f"unknown aggregate {q['agg']!r} in {path}")
    return mix


def schedule(rate_per_s: float, seconds: float, path_seed: int) -> np.ndarray:
    """Due times (seconds from the window's opening) of the requests offered
    in a window of ``seconds``: a Poisson process at ``rate_per_s`` given
    its count, that is ``round(rate_per_s * seconds)`` times drawn
    uniformly over the window from ``path_seed``, in order."""
    n = max(1, round(rate_per_s * seconds))
    rng = np.random.default_rng(int(path_seed))
    return np.sort(rng.uniform(0.0, seconds, n))


def _draw(spec: dict, rng):
    values = list(spec["values"])
    if spec["kind"] == "subsets":
        size, count = int(spec["size"]), int(spec["count"])
        out: list[tuple] = []
        while len(out) < count:
            pick = rng.choice(len(values), size, replace=False)
            s = tuple(sorted(values[i] for i in pick))
            if s not in out:
                out.append(s)
        return tuple(out)
    raise ValueError(f"unknown draw kind {spec['kind']!r}")


def _value(v, drawn: dict):
    if isinstance(v, dict):
        got = drawn[v["draw"]]
        got = got[v["at"]] if "at" in v else got
        return tuple(got) if isinstance(got, (list, tuple)) else got
    return tuple(v) if isinstance(v, list) else v


def resolve(t, drawn: dict, rng) -> tuple:
    """One predicate template -> a plain nested-tuple predicate."""
    op = t[0]
    if op == "eq":
        return ("eq", t[1], int(_value(t[2], drawn)))
    if op == "in":
        return ("in", t[1], tuple(sorted(int(x) for x in _value(t[2], drawn))))
    if op in ("and", "or"):
        return (op,) + tuple(resolve(c, drawn, rng) for c in t[1:])
    if op == "run":
        _, prefix, value, length, first, last = t
        end = int(rng.integers(first + length - 1, last + 1))
        return ("and",) + tuple(
            ("eq", f"{prefix}{i}", int(value))
            for i in range(end - length + 1, end + 1)
        )
    raise ValueError(f"unknown predicate template {op!r}")


def draw_request(mix: dict, rng) -> tuple[tuple[str, tuple], ...]:
    """One request: ``((agg, predicate), ...)`` in template order."""
    drawn = {k: _draw(s, rng) for k, s in mix.get("draws", {}).items()}
    return tuple(
        (q["agg"], resolve(q["where"], drawn, rng)) for q in mix["queries"]
    )


def streams(seed: int) -> dict[str, np.random.Generator]:
    """Independent generators for each use of ``seed``: the table, the
    requests of the window, the warm-up's requests and the sample of
    requests compared with the reference."""
    names = ("table", "requests", "warm", "check")
    seqs = np.random.SeedSequence(int(seed)).spawn(len(names))
    return {n: np.random.default_rng(s) for n, s in zip(names, seqs)}


def draw_window(
    mix: dict, seconds: float, rng: dict, rate_per_s: float | None = None
) -> tuple[np.ndarray, list, list[int]]:
    """What a window of ``seconds`` offers, at the mix's rate unless
    ``rate_per_s`` is given: the due times, the requests (from
    ``rng["requests"]``) and the sorted indices of the requests compared
    with the reference (from ``rng["check"]``)."""
    due = schedule(rate_per_s or mix["rate_per_s"], seconds, mix["arrival_seed"])
    requests = [draw_request(mix, rng["requests"]) for _ in due]
    k = min(int(mix["check_requests"]), len(due))
    keep = sorted(int(x) for x in rng["check"].choice(len(due), k, replace=False))
    return due, requests, keep


def flush_shapes(per_request: int, max_batch: int) -> list[tuple[int, int]]:
    """The ``(queries, distinct requests)`` compositions a FIFO flush of at
    most ``max_batch`` queries can take from whole requests of
    ``per_request`` queries, which must divide ``max_batch``."""
    if max_batch % per_request:
        raise ValueError(
            f"a request of {per_request} queries does not divide a flush "
            f"of {max_batch}"
        )
    return [
        (n, u)
        for n in range(per_request, max_batch + 1, per_request)
        for u in range(1, n // per_request + 1)
    ]
