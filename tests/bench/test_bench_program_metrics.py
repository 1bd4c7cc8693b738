"""The per-layer metrics read from the program's own stage counters, upload
and collector counters and named kernels: each on synthetic readings, on
a program that records none of them, and ``kernel_device_ms`` on a trace
recorded on one v5e."""

import json
from pathlib import Path

import pytest

from bench import trace as tr
from bench.harness import Readings
from bench.metrics import (
    device_wait_ms,
    flush_build_ms,
    flush_device_ms,
    gc_pause_ms,
    h2d_kb_per_flush,
    kernel_device_ms,
    plan_host_ms,
    queue_wait_ms,
    result_path_ms,
)

DATA = Path(__file__).parent / "data"
COUNTER_READERS = (
    queue_wait_ms,
    flush_build_ms,
    device_wait_ms,
    result_path_ms,
    h2d_kb_per_flush,
    gc_pause_ms,
)


def readings(counters, flushes, trace=None, spans=()):
    return Readings(
        seconds=1.0, setup_s=1.0, latencies_s=[], answered_by_close=0,
        flushes=flushes, mask_answers=0, counters=counters, spans=list(spans),
        compiles=0, page_bytes=1 << 20, peak={"hbm_bytes_per_s": 819e9},
        trace=trace,
    )


PROGRAM = {
    "queries_served": 16,
    "queue_wait_s": 0.08,
    "stage.build_s": 0.010,
    "stage.launch_s": 0.002,
    "stage.wait_s": 0.090,
    "stage.copy_s": 0.001,
    "stage.reduce_s": 0.003,
    "h2d_bytes": 4096,
    "gc_pause_s": 0.25,
}


@pytest.mark.parametrize(
    "reader,want",
    [
        (queue_wait_ms, 0.08 / 16 * 1e3),
        (flush_build_ms, 0.010 / 2 * 1e3),
        (device_wait_ms, 0.090 / 2 * 1e3),
        (result_path_ms, (0.001 + 0.003) / 2 * 1e3),
        (h2d_kb_per_flush, 4096 / 2 / 1024),
        (gc_pause_ms, 250.0),
    ],
)
def test_counter_readers(reader, want):
    assert reader.read(readings(PROGRAM, flushes=2)) == pytest.approx(want)


@pytest.mark.parametrize("reader", COUNTER_READERS + (kernel_device_ms,))
def test_a_program_without_the_counters_reads_nothing(reader):
    """A program that records no stage, upload or collector counter (and
    names no kernel): the metric is left out, and nothing raises."""
    old = {"queries_served": 16, "wordlines_sensed": 344, "flushes": 2}
    summary = tr.reduce_events(_synthetic())
    assert reader.read(readings(old, flushes=2, trace=summary)) is None


@pytest.mark.parametrize("reader", COUNTER_READERS[1:4] + (h2d_kb_per_flush,))
def test_a_window_without_flushes_reads_nothing(reader):
    assert reader.read(readings(PROGRAM, flushes=0)) is None


def _synthetic(kernel_name="tpu_custom_call.1"):
    dev = "/device:TPU:0"

    def ev(plane, line, name, start, dur):
        return {"plane": plane, "line": line, "name": name, "start_ns": start,
                "dur_ns": dur}

    return [
        ev("/host:CPU", "python", "bench.window", 0.0, 10000.0),
        ev(dev, "XLA Modules", "jit_run(1)", 1000.0, 3000.0),
        ev(dev, "XLA Ops", f"%{kernel_name} = u32[4]{{0}} custom-call()",
           1000.0, 1200.0),
        ev(dev, "XLA Ops", "%fusion.3 = u32[4]{0} fusion()", 2200.0, 1800.0),
    ]


def test_kernel_time_sums_the_named_ops():
    summary = tr.reduce_events(
        _synthetic("flashql_mws_block_reduce.5")
        + [
            {"plane": "/device:TPU:0", "line": "XLA Ops",
             "name": "%flashql_popcount.2 = s32[8]{0} custom-call()",
             "start_ns": 5000.0, "dur_ns": 300.0},
        ]
    )
    r = readings(PROGRAM, flushes=2, trace=summary)
    assert kernel_device_ms.read(r) == pytest.approx(1500e-9 / 2 * 1e3)
    assert kernel_device_ms.read(readings(PROGRAM, flushes=2)) is None


def test_staged_program_without_named_kernels_fails_the_reading():
    """A traced window of a program that records its stages, with flushes
    and no ``flashql_*`` op: the kernels were renamed, which is an error,
    not a metric left out."""
    r = readings(PROGRAM, flushes=2, trace=tr.reduce_events(_synthetic()))
    with pytest.raises(LookupError, match="flashql_"):
        kernel_device_ms.read(r)
    assert kernel_device_ms.read(readings(PROGRAM, 0, r.trace)) is None


def test_build_stage_lies_inside_plan_host():
    """``build`` is part of ``dispatch``, so on the same flushes
    ``flush_build_ms`` never exceeds ``plan_host_ms``."""
    spans = [("compile", 0.0, 0.004), ("dispatch", 0.004, 0.020)]
    r = readings(dict(PROGRAM, **{"stage.build_s": 0.012}), 1, spans=spans)
    assert flush_build_ms.read(r) < plan_host_ms.read(r)


def test_recorded_named_trace():
    """A traced window of ``bmi.retention-cold`` recorded on one v5e, cut to
    four whole flushes around a full collector pass: the host annotations
    (``bench.*`` and the program's ``flashql.*`` stages), the flush
    program's module runs, the ``flashql_*`` kernel ops and the eight
    longest other ops.  The op events carry the HLO instruction's text, so
    a kernel's op name is its ``pallas_call`` name and an instance number."""
    rec = json.loads((DATA / "trace_excerpt_named.json").read_text())
    events = rec["events"]
    host = {e["name"] for e in events if not tr.is_device(e["plane"])}
    stages = {"compile", "dispatch", "build", "launch", "transfer", "wait", "copy",
              "reduce"}
    assert {f"flashql.{s}" for s in stages | {"gc"}} <= host
    ops = {tr.op_name(e["name"]) for e in events if e["line"] == tr.OPS_LINE}
    kernels = {op.rsplit(".", 1)[0] for op in ops if op.startswith("flashql_")}
    assert kernels == {"flashql_mws_block_reduce", "flashql_popcount"}
    assert all(e["name"].startswith("%") and " = " in e["name"]
               for e in events if e["line"] == tr.OPS_LINE)

    s = tr.reduce_events(events)
    flushes = rec["flushes"]
    r = readings(PROGRAM, flushes=flushes, trace=s)
    kernel = kernel_device_ms.read(r)
    named = sum(v for k, v in s.op_s.items() if k.startswith("flashql_"))
    assert kernel == pytest.approx(named / flushes * 1e3)
    assert 0 < kernel < flush_device_ms.read(r)
    # the other ops are the flush program's glue, not its kernels
    assert sum(s.op_s.values()) > named
