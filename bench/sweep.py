"""Find a cell's knee: the highest offered rate the system keeps up with.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates r1 r2 ...

Builds and warms the cell once, as a run does, then serves one open-loop
window per rate (requests per second, in the order given) and prints one
JSON line per window: offered and answered queries per second, p50 and
p95 latency, how late submissions ran, and the backlog of requests still
unanswered at the close.  A rate the system keeps up with answers what it
is offered and ends with no backlog.  Answers are not checked here; the
cell's rate in its traffic file is set from this sweep, once, by hand.
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
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(REPO), str(REPO / "src")]
    from bench import traffic
    from bench.harness import (
        CompileCounter,
        Probe,
        chips,
        enable_cache,
        load_cell,
        open_loop,
        set_up,
    )
    from bench.stats import percentile

    cell = load_cell(args.workload)
    chips(cell.chips)
    enable_cache()
    counter = CompileCounter()
    rng = traffic.streams(args.seed)
    n_rows = cell.generator.rows(cell.config)
    system, table = set_up(cell, rng, n_rows, counter, sys.stderr)
    del table  # the sweep checks no answers
    print(f"sweep: set-up {time.perf_counter() - T_START:.3f} s", file=sys.stderr)
    for rate in args.rates:
        due, requests, _keep = traffic.draw_window(
            cell.mix, args.seconds, rng, rate_per_s=rate
        )
        probe = Probe(system, counter, None)
        w = open_loop(system, due, requests, args.seconds, [], probe)
        while system.pending:  # what the drain left, before the next rate
            system.flush()
        per = len(cell.mix["queries"])
        print(
            json.dumps(
                {
                    "rate_per_s": rate,
                    "requests": len(due),
                    "offered_qps": len(due) * per / args.seconds,
                    "answered_qps": w.answered_by_close / args.seconds,
                    "p50_ms": percentile(w.latencies_s, 50) * 1e3,
                    "p95_ms": percentile(w.latencies_s, 95) * 1e3,
                    "late_p95_ms": percentile(w.late_s, 95) * 1e3,
                    "backlog_at_close": w.backlog_at_close,
                    "flushes": w.flushes,
                    "compiles": probe.compiles,
                }
            ),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
