"""The reduction from a profiler trace to busy and idle time, device time
per flush and roofline share."""

import json
from pathlib import Path

import pytest

from bench import trace as tr
from bench.harness import Readings
from bench.metrics import device_idle_pct, flush_device_ms, flush_hbm_roofline_pct

DATA = Path(__file__).parent / "data"


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start, "dur_ns": dur}


def synthetic():
    dev = "/device:TPU:0"
    return [
        ev("/host:CPU", "python", "bench.window", 1000.0, 10000.0),
        ev("/host:CPU", "python", "bench.flush", 1500.0, 3000.0),
        ev("/host:CPU", "python", "bench.wait", 4000.0, 7000.0),
        ev(dev, "XLA Modules", "jit_run(1)", 2000.0, 2000.0),
        ev(dev, "XLA Modules", "jit_run(1)", 7000.0, 1000.0),
        ev(dev, "XLA Modules", "jit_other", 500.0, 1000.0),
        # overlapping ops count once; the op before the window is clipped
        ev(dev, "XLA Ops", "gather", 2000.0, 1500.0),
        ev(dev, "XLA Ops", "mws", 3000.0, 1000.0),
        ev(dev, "XLA Ops", "mws", 7000.0, 1000.0),
        ev(dev, "XLA Ops", "early", 500.0, 1000.0),
    ]


def readings(summary, wordlines, flushes, masks=0, page_bytes=1 << 20):
    return Readings(
        seconds=1.0, setup_s=1.0, latencies_s=[], answered_by_close=0,
        flushes=flushes, mask_answers=masks,
        counters={"wordlines_sensed": wordlines}, spans=[], compiles=0,
        page_bytes=page_bytes, peak={"hbm_bytes_per_s": 819e9}, trace=summary,
    )


def test_synthetic_trace():
    # host seconds on another clock: trace ns = t * 1e9 + 1000 -> 1500..2500
    spans = [("compile", 0.5e-6, 1.5e-6)]
    s = tr.reduce_events(synthetic(), spans, span_offset_ns=1000.0)
    assert s.window_s == pytest.approx(10000e-9)
    # busy: [1000,1500) clipped early op + [2000,4000) + [7000,8000)
    assert s.busy_s == pytest.approx(3500e-9)
    assert s.modules("jit_run") == (pytest.approx(3000e-9), 2)
    r = readings(s, wordlines=10, flushes=2)
    assert device_idle_pct.read(r) == pytest.approx(65.0)
    assert flush_device_ms.read(r) == pytest.approx(1.5e-3)
    want = 100 * 10 * (1 << 20) / 819e9 / 3000e-9
    assert flush_hbm_roofline_pct.read(r) == pytest.approx(want)
    labels = dict((round(sec * 1e9), name) for name, sec in s.gaps)
    assert labels[500] == "bench.flush/compile"  # gap 1500..2000
    assert labels[3000] == "bench.wait"  # gap 4000..7000
    bd = s.breakdown()
    assert bd["device_ops"][0] == ["mws", pytest.approx(2000e-9)]
    assert bd["idle_gaps"][0] == ["bench.wait", pytest.approx(3000e-9)]


def test_flush_module_matched_by_exact_name():
    events = synthetic() + [
        ev("/device:TPU:0", "XLA Modules", "jit_runner(2)", 9000.0, 500.0),
        ev("/device:TPU:0", "XLA Modules", "jit_run_other(3)", 9500.0, 500.0),
    ]
    s = tr.reduce_events(events)
    assert s.modules("jit_run") == (pytest.approx(3000e-9), 2)
    assert tr.module_name("jit_run(17858709383493028842)") == "jit_run"
    assert tr.module_name("jit_run") == "jit_run"


def test_missing_flush_module_fails_the_reading():
    """A traced window with flushes but no run of the flush program (a
    renamed program) is an error, not a metric left out."""
    renamed = [
        dict(e, name="jit_flush(1)") if e["name"].startswith("jit_run") else e
        for e in synthetic()
    ]
    r = readings(tr.reduce_events(renamed), wordlines=10, flushes=2)
    for reader in (flush_device_ms, flush_hbm_roofline_pct):
        with pytest.raises(LookupError, match="jit_run"):
            reader.read(r)
    assert flush_device_ms.read(readings(tr.reduce_events(renamed), 10, 0)) is None


def test_no_trace_reads_nothing():
    r = readings(None, wordlines=10, flushes=2)
    for reader in (device_idle_pct, flush_device_ms, flush_hbm_roofline_pct):
        assert reader.read(r) is None


def test_trace_without_window_or_device_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce_events([e for e in synthetic() if e["name"] != "bench.window"])
    with pytest.raises(ValueError, match="device"):
        tr.reduce_events([e for e in synthetic() if e["plane"] == "/host:CPU"])


def test_recorded_chip_trace():
    """A trace recorded on one v5e: 20 flushes of one MASK each over a
    materialized page at 2^20 rows, cut to the window's device events and
    the harness's spans."""
    rec = json.loads((DATA / "trace_excerpt.json").read_text())
    events = rec["events"]
    s = tr.reduce_events(events, [tuple(x) for x in rec["spans"]], rec["offset_ns"])
    ops = [e for e in events if e["line"] == "XLA Ops"]
    assert 0 < s.busy_s <= sum(e["dur_ns"] for e in ops) * 1e-9
    assert s.window_s == pytest.approx(0.5, abs=0.01)
    seconds, runs = s.modules(flush_device_ms.FLUSH_MODULE)
    assert runs == 20 and 0 < seconds < s.window_s
    # each flush: one mask sensing its materialized page and the valid
    # page, one 2^20-row result page of 128 KiB
    r = readings(s, wordlines=40, flushes=20, masks=20, page_bytes=1 << 17)
    assert flush_device_ms.read(r) == pytest.approx(seconds / 20 * 1e3)
    assert 0 < flush_hbm_roofline_pct.read(r) <= 100
    assert 0 < device_idle_pct.read(r) < 100
    bd = s.breakdown()
    assert all(" = " not in name for name, _ in bd["device_ops"])
    assert any("mws_block_reduce_pallas" in name for name, _ in bd["device_ops"])
    assert len(bd["idle_gaps"]) == tr.TOP
    assert all(name.startswith("bench.") for name, _ in bd["idle_gaps"])
