"""Order statistics of the benchmark (a copy of the program's
``repro.query.telemetry.percentile``, kept here so the yardstick cannot
move with the program)."""

from __future__ import annotations

import math


def percentile(samples, q: float) -> float | None:
    """The ``q``-th percentile (nearest rank) of ``samples``, or ``None``
    when there are none."""
    s = sorted(samples)
    if not s:
        return None
    rank = min(max(1, math.ceil(q / 100 * len(s))), len(s))  # 1-based
    return s[rank - 1]
