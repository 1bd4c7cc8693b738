"""The control of the comparison that decides ``correct``.

    python bench/control.py --workload <cell> --seeds s1 s2 s3 ...

The configurations state one guarantee: exact answers.  The control breaks
it the way a tempting change would — a kernel that rounds the rows down to
its tile and leaves the last word of every page out — by putting in the
program's place the reference computed without the table's last 32 rows.
For each seed it draws the requests a run of the cell draws (at the
benchmark's ``run_seconds``), answers the sampled ones both ways, and
compares them exactly as a run does; the control must come out not
correct.  A run of the benchmark never runs this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[1]
TAIL_ROWS = 32  # one uint32 word of every page


def control_reference(table: dict):
    from bench.reference import Reference, pack

    class TailDropped(Reference):
        def eq(self, column: str, value: int) -> np.ndarray:
            key = (column, value)
            got = self._eq.get(key)
            if got is None:
                bits = self.table[column] == value
                bits[-TAIL_ROWS:] = False
                got = self._eq[key] = pack(bits)
            return got

    return TailDropped(table)


def readings(name: str, seed: int, seconds: float, rows: int | None = None) -> dict:
    """The compared numbers of the control on ``seed``."""
    from bench import traffic
    from bench.harness import load_cell
    from bench.reference import Reference, same

    cell = load_cell(name)
    rng = traffic.streams(seed)
    n = rows or cell.generator.rows(cell.config)
    table = cell.generator.make_table(cell.config, rng["table"], n)
    _due, requests, keep = traffic.draw_window(cell.mix, seconds, rng)
    queries = [q for r in keep for q in requests[r]]
    wants = Reference(table).answers(queries)
    served = control_reference(table).answers(queries)
    wrong = sum(1 for got, want in zip(served, wants) if not same(got, want))
    return {
        "seed": seed,
        "wrong_answers": {"value": wrong, "limit": 0, "of": len(queries)},
        "correct": wrong <= 0,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(REPO), str(REPO / "src")]
    seconds = json.loads((REPO / "BENCHMARK.json").read_text())["run_seconds"]
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed, seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
