"""Public jit'd API for the k-of-N threshold kernel (padding + slicing).

Word-axis padding uses zeros; block-axis padding also uses zeros — a
padded block never conducts, so it can never count toward the threshold
(the OR-identity dual of the MWS wrappers' AND-identity padding).
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.mws.ops import _pad_to
from repro.kernels.platform import resolve_interpret
from repro.kernels.threshold.threshold import (
    DEFAULT_BLOCK_WORDS,
    threshold_pallas,
)


@functools.partial(
    jax.jit, static_argnames=("k", "block_words", "interpret")
)
def threshold_reduce(
    anded: jax.Array,
    k: int,
    *,
    block_words: int = DEFAULT_BLOCK_WORDS,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-bit ``count-of-set-rows >= k`` over an (N, W) word stack -> (W,).

    ``interpret=None`` compiles the kernel on a TPU and runs the Pallas
    interpreter elsewhere (:mod:`repro.kernels.platform`)."""
    n, w = anded.shape
    if not 1 <= k <= n:
        raise ValueError(f"threshold k={k} outside 1..{n} rows")
    bw = min(block_words, DEFAULT_BLOCK_WORDS)
    padded = _pad_to(anded, 1, bw, 0)  # word axis: zeros
    padded = _pad_to(padded, 0, 8, 0)  # block axis: zeros (never count)
    out = threshold_pallas(
        padded, k, n, block_words=bw, interpret=resolve_interpret(interpret)
    )
    return out[:w]
