"""Device time per flush of the fused flush program: the traced window's
``XLA Modules`` runs of the flush program, over the window's flushes."""

UNIT = "ms"
# the scheduler's fused flush program is ``jax.jit(run)``; a traced window
# with flushes and no run of a module of this name fails the run
FLUSH_MODULE = "jit_run"


def flush_runs(r) -> tuple[float, int]:
    """Seconds and count of the flush program's runs in the traced window."""
    seconds, runs = r.trace.modules(FLUSH_MODULE)
    if not runs:
        raise LookupError(
            f"no run of the flush program {FLUSH_MODULE!r} in a traced window "
            f"of {r.flushes} flushes; modules seen: {sorted(r.trace.module_s)}"
        )
    return seconds, runs


def read(r):
    if r.trace is None or not r.flushes:
        return None
    seconds, _ = flush_runs(r)
    return seconds / r.flushes * 1e3
