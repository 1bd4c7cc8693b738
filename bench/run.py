"""FlashQL's chip benchmark: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's table from the seed, has FlashQL ingest, program and
serve it on one TPU under the cell's open-loop traffic for ``--seconds``,
checks the answers against the plain numpy reference, and prints one JSON
line as the last line of standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or its per-layer
metrics with ``--trace 1``), ``device`` and, last, ``check`` — each
number compared with its limit, which also end standard error.  See
``bench/harness.py`` for what a run does and ``PERF.md`` for the cells.

Off a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(REPO), str(REPO / "src")]
    from bench.harness import BenchError, measure

    try:
        out = measure(
            args.workload,
            args.seed,
            args.seconds,
            bool(args.trace),
            t_start=T_START,
        )
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
