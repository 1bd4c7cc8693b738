"""Backend compiles (or loads from the persistent cache) inside the
window, counted by the harness's ``jax.monitoring`` listener.  The target
is 0."""

UNIT = "compiles"


def read(r):
    return r.compiles
