"""Reduction of a profiler trace to device busy time, module time and the
idle gaps, on the profiler's own clock.

A trace is first flattened to plain events — ``{"plane", "line", "name",
"start_ns", "dur_ns"}`` — by :func:`events_from_xplane`, so that the
reduction itself (:func:`reduce_events`) runs on a small recorded list in
the tests exactly as on a run's trace.

* The window is the host annotation ``bench.window`` that the harness
  opens around the measured loop.
* Device events are those on planes named ``/device:<KIND>:<n>``.  Busy
  time is the union of the intervals of the ``XLA Ops`` line of each
  device (the ``XLA Modules`` line where a plane has no op line), clipped
  to the window and averaged over the devices.
* Module time is the sum of the ``XLA Modules`` events by module name.
* An idle gap is a stretch of the window in which no device op runs.  The
  longest are named after what the host was doing at its middle: the innermost
  ``bench.*`` annotation there, refined by the scheduler's flush span
  (``compile``, ``dispatch``, ``transfer``, ``reduce``) mapped onto the
  trace's clock.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10  # entries of each list in the breakdown


def events_from_xplane(path: Path) -> list[dict]:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                out.append(
                    {
                        "plane": plane.name,
                        "line": line.name,
                        "name": e.name,
                        "start_ns": float(e.start_ns),
                        "dur_ns": float(e.duration_ns),
                    }
                )
    return out


def events_from_dir(root: Path) -> list[dict]:
    files = sorted(Path(root).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {root}")
    out: list[dict] = []
    for f in files:
        out.extend(events_from_xplane(f))
    return out


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and not plane.startswith("/device:CPU")


def op_name(text: str) -> str:
    """An op event's name is its HLO instruction; keep the instruction's
    name (``%fusion.12 = u32[...] fusion(...)`` -> ``fusion.12``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def module_name(text: str) -> str:
    """An ``XLA Modules`` event's name is the module's name and its program
    id (``jit_run(1785...)`` -> ``jit_run``)."""
    head, sep, tail = text.rpartition("(")
    return head if sep and tail[:-1].isdigit() and tail.endswith(")") else text


def union_ns(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over the devices
    devices: int
    module_s: dict[str, float] = field(default_factory=dict)
    module_n: dict[str, int] = field(default_factory=dict)
    op_s: dict[str, float] = field(default_factory=dict)
    gaps: list[tuple[str, float]] = field(default_factory=list)  # longest

    def modules(self, name: str) -> tuple[float, int]:
        """Seconds and count of the runs of the module named ``name``."""
        keys = [k for k in self.module_s if module_name(k) == name]
        return (
            sum(self.module_s[k] for k in keys),
            sum(self.module_n[k] for k in keys),
        )

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in self.gaps],
        }


def _label(mid: float, host: list[dict], spans: list[tuple]) -> str:
    """What the host was doing at trace time ``mid``."""
    inner = None
    for e in host:
        if e["start_ns"] <= mid <= e["start_ns"] + e["dur_ns"]:
            if inner is None or e["dur_ns"] < inner["dur_ns"]:
                inner = e
    label = inner["name"] if inner is not None else "outside bench spans"
    for name, a, b in spans:
        if a <= mid <= b:
            return f"{label}/{name}"
    return label


def reduce_events(
    events: list[dict],
    spans: list[tuple[str, float, float]] = (),
    span_offset_ns: float | None = None,
) -> TraceSummary:
    """Reduce flattened events over the ``bench.window`` annotation.

    ``spans`` are ``(name, t0, t1)`` host spans in seconds of another
    clock; ``span_offset_ns`` maps them onto the trace (trace ns = t *
    1e9 + offset).  Without an offset they are left out of the labels.
    """
    windows = [e for e in events if e["name"] == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    w = windows[0]
    w0, w1 = w["start_ns"], w["start_ns"] + w["dur_ns"]

    def clip(e) -> tuple[float, float] | None:
        a = max(e["start_ns"], w0)
        b = min(e["start_ns"] + e["dur_ns"], w1)
        return (a, b) if b > a else None

    by_plane: dict[str, dict[str, list[dict]]] = {}
    for e in events:
        if is_device(e["plane"]):
            by_plane.setdefault(e["plane"], {}).setdefault(
                e["line"], []
            ).append(e)
    if not by_plane:
        raise ValueError("trace has no device plane")

    module_s: dict[str, float] = {}
    module_n: dict[str, int] = {}
    op_s: dict[str, float] = {}
    busy = 0.0
    gaps_ns: list[tuple[float, float]] = []
    devices = 0
    for lines in by_plane.values():
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        if not ops:
            continue
        devices += 1
        spans_ns = [c for c in map(clip, ops) if c is not None]
        merged = union_ns(spans_ns)
        busy += sum(b - a for a, b in merged)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps_ns.extend(
            (edges[i], edges[i + 1])
            for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]
        )
        for e in lines.get(OPS_LINE, []):
            c = clip(e)
            if c is not None:
                name = op_name(e["name"])
                op_s[name] = op_s.get(name, 0.0) + (c[1] - c[0]) * 1e-9
        for e in lines.get(MODULES_LINE, []):
            c = clip(e)
            if c is not None:
                module_s[e["name"]] = module_s.get(e["name"], 0.0) + (
                    c[1] - c[0]
                ) * 1e-9
                module_n[e["name"]] = module_n.get(e["name"], 0) + 1
    if not devices:
        raise ValueError("trace has no device op or module events")

    host = [
        e
        for e in events
        if not is_device(e["plane"])
        and e["name"].startswith("bench.")
        and e["name"] != WINDOW
    ]
    mapped = (
        []
        if span_offset_ns is None
        else [
            (n, a * 1e9 + span_offset_ns, b * 1e9 + span_offset_ns)
            for n, a, b in spans
        ]
    )
    longest = sorted(gaps_ns, key=lambda g: g[0] - g[1])[:TOP]
    gaps = [
        (_label((a + b) / 2, host, mapped), (b - a) * 1e-9) for a, b in longest
    ]
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy / devices * 1e-9,
        devices=devices,
        module_s=module_s,
        module_n=module_n,
        op_s=op_s,
        gaps=gaps,
    )
