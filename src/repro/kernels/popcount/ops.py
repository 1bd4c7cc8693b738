"""Public jit'd API for the popcount kernel (padding + shape handling)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.platform import resolve_interpret
from repro.kernels.popcount.popcount import (
    DEFAULT_BLOCK_ROWS,
    DEFAULT_BLOCK_WORDS,
    popcount_pallas,
)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "block_words", "interpret")
)
def popcount(
    words: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_words: int = DEFAULT_BLOCK_WORDS,
    interpret: bool | None = None,
) -> jax.Array:
    """Population count per row. (W,)->() or (R, W)->(R,); zero-pads freely
    (padding words contribute 0 to the count).

    Emulation (the default off a TPU, see :mod:`repro.kernels.platform`)
    counts with plain XLA ops: the Pallas interpreter walks the grid in
    Python — milliseconds per block, which would dominate batched
    aggregation — and the XLA count is bit-identical to the kernel (the
    kernel tests assert exactly that).  A TPU dispatches the Pallas kernel.
    """
    squeeze = words.ndim == 1
    if squeeze:
        words = words[None]
    if resolve_interpret(interpret):
        out = jnp.sum(
            jax.lax.population_count(words).astype(jnp.int32), axis=-1
        )
        return out[0] if squeeze else out
    r, w = words.shape
    block_rows = min(block_rows, max(1, r))
    rp = -(-r // block_rows) * block_rows
    wp = -(-w // block_words) * block_words
    padded = jnp.pad(words, ((0, rp - r), (0, wp - w)))
    out = popcount_pallas(
        padded,
        block_rows=block_rows,
        block_words=block_words,
        interpret=False,
    )[:r]
    return out[0] if squeeze else out
