"""Public jit'd API for the fused reduce+count kernel (padding + corrections).

Word-axis padding uses zeros; operand-axis padding uses the reduction
identity.  A padded column therefore reduces to 0 for AND/OR/XOR (0 & ident
= 0 since real rows are zero-padded) and to ~0 after an inverse read — the
wrapper subtracts the 32·(padded words) over-count for inverted ops.
"""

from __future__ import annotations

import functools

import jax

from repro.core.bitops import BitOp
from repro.kernels.mws.ops import _identity_word, _pad_to
from repro.kernels.mws_count.mws_count import (
    DEFAULT_BLOCK_WORDS,
    DEFAULT_FAN_IN,
    mws_count_pallas,
)
from repro.kernels.platform import resolve_interpret


@functools.partial(
    jax.jit, static_argnames=("op", "fan_in", "block_words", "interpret")
)
def mws_count(
    stack: jax.Array,
    op: BitOp,
    *,
    fan_in: int = DEFAULT_FAN_IN,
    block_words: int = DEFAULT_BLOCK_WORDS,
    interpret: bool | None = None,
) -> jax.Array:
    """Population count of the op-reduction of an (N, W) word stack -> ().

    ``interpret=None`` compiles the kernel on a TPU and runs the Pallas
    interpreter elsewhere (:mod:`repro.kernels.platform`)."""
    n, w = stack.shape
    fan_in = min(fan_in, max(8, 8 * -(-n // 8)))
    ident = _identity_word(op, stack.dtype)
    padded = _pad_to(stack, 1, block_words, 0)  # word axis: zeros
    padded = _pad_to(padded, 0, fan_in, ident)  # operand axis: identity
    count = mws_count_pallas(
        padded,
        op,
        fan_in=fan_in,
        block_words=block_words,
        interpret=resolve_interpret(interpret),
    )
    if op.inverted:
        padded_words = padded.shape[1] - w
        count = count - 32 * padded_words
    return count
