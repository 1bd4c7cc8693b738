"""Table generator of the ``bmi`` configuration (see ``bmi.json``).

Users with one activity bit per day (``day0`` .. ``day29``), a
``country`` and an ``age``, in uint8 straight from the seed's bytes, as
``chip_smoke.make_table`` draws them: 32 columns of 2^27 rows take 4 GiB
of host memory this way, where int64 would take 32.
"""

from __future__ import annotations

import numpy as np


def rows(cfg: dict) -> int:
    return int(cfg["users"])


def make_table(cfg: dict, rng: np.random.Generator, n: int) -> dict:
    def u8() -> np.ndarray:  # n uniform bytes
        return rng.bit_generator.random_raw(-(-n // 8)).view(np.uint8)[:n]

    p = int(cfg["activity_p_256"])
    table = {
        f"day{d}": (u8() < p).view(np.uint8) for d in range(int(cfg["days"]))
    }
    table["country"] = u8() % int(cfg["countries"])
    span = int(cfg["age_max"]) - int(cfg["age_min"]) + 1
    table["age"] = int(cfg["age_min"]) + u8() % span
    return table
