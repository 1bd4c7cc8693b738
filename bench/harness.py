"""One run of one cell: build, warm up, serve an open loop, check, report.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration's sizes (``bench/configs/<config>.json``) and table
generator (``bench/configs/<config>.py``), the traffic mix
(``bench/traffic/<mix>.json``, read by :mod:`bench.traffic`) and one
reader per metric (``bench/metrics/<metric>.py``).  Adding a cell adds
files and entries; it edits none of these.

A run, in one process:

1. makes the table from the seed and has the system ingest and program it
   (:class:`bench.system.System`);
2. warms up: one flush of every composition a FIFO flush can take
   (:func:`bench.traffic.flush_shapes`), so that no flush program compiles
   inside the window;
3. opens the window: requests fall due on the mix's schedule, each is
   submitted whole at the first loop turn after its due time, and each
   flush serves at most ``max_batch`` pending queries, FIFO.  A request's
   latency runs from its due time to its last answer.  The window closes
   when the first flush that ends after ``--seconds`` returns (or at
   ``--seconds`` if the system is idle then); requests due in the window
   are still served after it, for up to a minute;
4. reads the device's peak memory, frees the system, and compares a
   sample of the window's requests (drawn from the seed) with the plain
   reference (:mod:`bench.reference`).
"""

from __future__ import annotations

import gc
import importlib.util
import json
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import traffic
from bench.reference import Reference, same

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
# requests due in the window may be answered this long after its close
DRAIN_S = 60.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """The run cannot be measured (no chip, unknown device, bad cell)."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    generator: object  # bench/configs/<config>.py
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict[str, object]


def load_cell(name: str, spec_path: Path = REPO / "BENCHMARK.json") -> Cell:
    spec = json.loads(Path(spec_path).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in {spec_path.name}")
    w = cells[name]
    (entry,) = [c for c in spec["configs"] if c["name"] == w["config"]]
    config = json.loads((REPO / entry["file"]).read_text())
    generator = load_module(
        BENCH / "configs" / f"{w['config']}.py", f"bench_config_{w['config']}"
    )
    mix = traffic.load_mix(BENCH / "traffic" / f"{w['traffic']}.json")

    def applies(m: dict) -> bool:
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    layer = [m for m in spec["per_layer"] if applies(m)]
    readers = {}
    for m in e2e + layer:
        mod = load_module(
            BENCH / "metrics" / f"{m['name']}.py", f"bench.metrics.{m['name']}"
        )
        if mod.UNIT != m["unit"]:
            raise BenchError(
                f"metric {m['name']}: reader unit {mod.UNIT!r} != {m['unit']!r}"
            )
        readers[m["name"]] = mod
    return Cell(name, int(w["chips"]), config, generator, mix, e2e, layer, readers)


def peak_for(kind: str) -> dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def enable_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed path in
    the checkout (or ``$JAX_COMPILATION_CACHE_DIR``), holding every
    program however fast it compiled."""
    import jax

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache


def chips(needed: int):
    """The devices of a run; refuses anything but enough TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(
            f"no TPU: JAX sees {devices[0].platform}; the benchmark "
            "measures nothing off the chip"
        )
    if len(devices) < needed:
        raise BenchError(f"the cell needs {needed} chips, JAX sees {len(devices)}")
    return devices[:needed]


class CompileCounter:
    """Counts backend compiles (cache loads included) from JAX's own
    monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            self.n += 1


class Probe:
    """What the window reads at its opening and at its close: the system's
    counters and spans, the compile count and, in a traced run, the
    profiler's trace around a ``bench.window`` annotation."""

    def __init__(self, system, counter: CompileCounter, trace_dir: str | None):
        self.system = system
        self.counter = counter
        self.trace_dir = trace_dir
        self._ann = None
        self.counters: dict = {}
        self.spans: list = []
        self.compiles = 0
        self.t_open = 0.0

    def annotate(self, name: str):
        if self.trace_dir is None:
            return _NULL
        import jax

        return jax.profiler.TraceAnnotation(name)

    def open(self) -> float:
        self._c0 = self.system.counters()
        self._n0 = self.counter.n
        if self.trace_dir is not None:
            import jax

            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self.t_open = time.perf_counter()
            self._ann.__enter__()
        else:
            self.t_open = time.perf_counter()
        return self.t_open

    def close(self) -> None:
        # the profiler stops after the drain (stopping it takes seconds,
        # which would hold up requests still due); the reduction reads only
        # the annotated window
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        c1 = self.system.counters()
        self.counters = {k: c1.get(k, 0) - self._c0.get(k, 0) for k in c1}
        self.spans = self.system.spans(self.t_open)
        self.compiles = self.counter.n - self._n0


class GcWatch:
    """Full (generation 2) collections of the host's garbage collector and
    the longest of them, for the window's log line."""

    def __init__(self):
        self.n = 0
        self.longest_s = 0.0
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.n += 1
            self.longest_s = max(self.longest_s, time.perf_counter() - self._t)
            self._t = None

    def stop(self) -> None:
        gc.callbacks.remove(self._on)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


@dataclass
class Window:
    seconds: float
    t0: float
    latencies_s: list[float] = field(default_factory=list)
    late_s: list[float] = field(default_factory=list)
    answered_by_close: int = 0  # queries answered by ``seconds``
    # flushes and MASK answers up to the close, which the counters and the
    # trace cover too
    flushes: int = 0
    mask_answers: int = 0
    unanswered: int = 0  # queries due in the window never answered
    backlog_at_close: int = 0  # requests due by the close, not yet answered
    answers: dict[int, list] = field(default_factory=dict)


def open_loop(system, due, requests, seconds, keep, probe, sleep=time.sleep) -> Window:
    """Serve ``requests`` falling due at ``due`` (seconds from the opening)
    for a window of ``seconds``; keep the answers of the requests in
    ``keep``."""
    clock = time.perf_counter
    n = len(due)
    owner: dict[int, tuple[int, int]] = {}
    left = [len(r) for r in requests]
    done_at: list[float | None] = [None] * n
    t0 = probe.open()
    w = Window(seconds, t0)
    w.answers = {r: [None] * len(requests[r]) for r in keep}
    end = t0 + seconds
    closed = False
    i = 0
    while True:
        now = clock()
        while i < n and t0 + due[i] <= now:
            with probe.annotate("bench.submit"):
                for slot, (agg, pred) in enumerate(requests[i]):
                    owner[system.submit(agg, pred)] = (i, slot)
            w.late_s.append(now - (t0 + due[i]))
            i += 1
        if system.pending:
            with probe.annotate("bench.flush"):
                got = system.flush()
            t = clock()
            for ticket, answer in got.items():
                r, slot = owner.pop(ticket)
                if r in w.answers:
                    w.answers[r][slot] = answer
                left[r] -= 1
                if not left[r]:
                    done_at[r] = t
                if t <= end:
                    w.answered_by_close += 1
                if not closed:
                    w.mask_answers += isinstance(answer, np.ndarray)
            if not closed:
                w.flushes += 1
                if t >= end:
                    probe.close()
                    closed = True
                    w.backlog_at_close = sum(1 for x in left if x)
        elif i < n or not closed:
            wake = t0 + due[i] if i < n else end
            with probe.annotate("bench.wait"):
                sleep(max(0.0, wake - clock()))
            if i == n and not closed and clock() >= end:
                probe.close()
                closed = True
                w.backlog_at_close = sum(1 for x in left if x)
        else:
            break
        if clock() > end + DRAIN_S:
            break
    if not closed:
        probe.close()
    w.latencies_s = [
        t - (t0 + d) for t, d in zip(done_at, due) if t is not None
    ]
    w.unanswered = sum(left)
    return w


def warm(system, mix: dict, max_batch: int, rng) -> None:
    """Set-up's serving: one flush of every ``(queries, distinct
    requests)`` composition a FIFO flush can take."""
    per = len(mix["queries"])
    for n, u in traffic.flush_shapes(per, max_batch):
        distinct: list = []
        for _ in range(1000):
            if len(distinct) == u:
                break
            req = traffic.draw_request(mix, rng)
            if req not in distinct:
                distinct.append(req)
        if len(distinct) < u:
            continue  # the mix holds fewer distinct requests
        seq = distinct + [distinct[0]] * (n // per - u)
        for agg, pred in (q for req in seq for q in req):
            system.submit(agg, pred)
        while system.pending:
            system.flush()


def set_up(cell: Cell, rng: dict, n_rows: int, counter: CompileCounter, log):
    """The table from the seed, the system that ingests and programs it,
    and the warm-up; returns ``(system, table)``."""
    from bench.system import System

    cfg = cell.config
    t = time.perf_counter()
    table = cell.generator.make_table(cfg, rng["table"], n_rows)
    t_table = time.perf_counter()
    fixed = np.random.default_rng(0)  # placement is the configuration's
    placement = [traffic.resolve(p, {}, fixed) for p in cfg["placement_warmup"]]
    system = System(cfg, table, placement)
    t_built = time.perf_counter()
    warm(system, cell.mix, int(cfg["max_batch"]), rng["warm"])
    t_warm = time.perf_counter()
    print(
        f"set-up: table of {n_rows} rows {t_table - t:.3f} s, ingest and "
        f"program {system.pages} pages ({system.store_bytes} B) "
        f"{t_built - t_table:.3f} s, warm-up {t_warm - t_built:.3f} s with "
        f"{counter.n} compiles",
        file=log,
    )
    return system, table


@dataclass
class Readings:
    """What the metric readers read (see ``bench/metrics``)."""

    seconds: float
    setup_s: float
    latencies_s: list[float]
    answered_by_close: int
    flushes: int
    mask_answers: int
    counters: dict
    spans: list
    compiles: int
    page_bytes: int
    peak: dict | None
    trace: object | None  # bench.trace.TraceSummary


def check(window: Window, requests, table) -> dict:
    """Compare the kept answers with the reference; the numbers compared,
    each with its limit."""
    keep = sorted(window.answers)
    queries = [q for r in keep for q in requests[r]]
    served = [a for r in keep for a in window.answers[r]]
    wants = Reference(table).answers(queries)
    wrong = sum(
        1 for got, want in zip(served, wants) if got is None or not same(got, want)
    )
    return {
        "wrong_answers": {"value": wrong, "limit": 0, "of": len(queries)},
        "unanswered": {"value": window.unanswered, "limit": 0},
    }


def measure(
    name: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    rows: int | None = None,
    require_tpu: bool = True,
    log=sys.stderr,
) -> dict:
    """One run of cell ``name``; returns the result line as a dict."""
    import jax

    cell = load_cell(name)
    if require_tpu:
        devices = chips(cell.chips)
        peak = peak_for(devices[0].device_kind)
        print(f"compile cache {enable_cache()}", file=log)
    else:
        devices = jax.devices()[: cell.chips]
        peak = None
    counter = CompileCounter()
    rng = traffic.streams(seed)
    n_rows = rows or cell.generator.rows(cell.config)

    system, table = set_up(cell, rng, n_rows, counter, log)

    due, requests, keep = traffic.draw_window(cell.mix, seconds, rng)

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
    probe = Probe(system, counter, trace_dir)
    collector = GcWatch()
    window = open_loop(system, due, requests, seconds, keep, probe)
    collector.stop()
    if trace:
        jax.profiler.stop_trace()
    setup_s = window.t0 - t_start

    stats = devices[0].memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")
    page_bytes = system.page_bytes
    system.close()
    print(
        f"window: {len(due)} requests due, {window.flushes} flushes by the "
        f"close, {probe.compiles} compiles; submissions late by p50 "
        f"{_ms(window.late_s, 50)} ms, p95 {_ms(window.late_s, 95)} ms, "
        f"max {max(window.late_s, default=0) * 1e3:.3f} ms; {collector.n} "
        f"full collections, longest {collector.longest_s * 1e3:.3f} ms",
        file=log,
    )
    print(f"device: peak_bytes_in_use {peak_bytes}", file=log)

    summary = None
    if trace:
        from bench import trace as tr

        events = tr.events_from_dir(Path(trace_dir))
        win = [e for e in events if e["name"] == tr.WINDOW]
        offset = win[0]["start_ns"] - window.t0 * 1e9 if win else None
        summary = tr.reduce_events(events, probe.spans, offset)
        del events
        _rmtree(Path(trace_dir))

    readings = Readings(
        seconds=seconds,
        setup_s=setup_s,
        latencies_s=window.latencies_s,
        answered_by_close=window.answered_by_close,
        flushes=window.flushes,
        mask_answers=window.mask_answers,
        counters=probe.counters,
        spans=probe.spans,
        compiles=probe.compiles,
        page_bytes=page_bytes,
        peak=peak,
        trace=summary,
    )
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        try:
            value = cell.readers[m["name"]].read(readings)
        except LookupError as e:
            raise BenchError(f"metric {m['name']}: {e}") from None
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    t_check = time.perf_counter()
    checked = check(window, requests, table)
    print(
        f"check: reference over {checked['wrong_answers']['of']} answers "
        f"in {time.perf_counter() - t_check:.3f} s",
        file=log,
    )
    correct = all(c["value"] <= c["limit"] for c in checked.values())
    queries = sum(len(r) for r in requests)
    out = {
        "correct": correct,
        "attempted": queries,
        "failed": window.unanswered,
        "metrics": metrics,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak_bytes,
        },
    }
    if summary is not None:
        out["device"]["busy_s"] = summary.busy_s
        out["device"]["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["check"] = {
        k: {"value": v["value"], "limit": v["limit"]} for k, v in checked.items()
    }
    for k, v in checked.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=log)
    return out


def _ms(xs, q) -> str:
    from bench.stats import percentile

    p = percentile(xs, q)
    return "none" if p is None else f"{p * 1e3:.3f}"


def _rmtree(root: Path) -> None:
    import shutil

    shutil.rmtree(root, ignore_errors=True)
