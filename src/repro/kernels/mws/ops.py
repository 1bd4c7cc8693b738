"""Public jit'd API for the MWS kernel: padding, dtype handling, serial baseline.

``mws_reduce``      — the Flash-Cosmos path (one fused pass).
``parabit_reduce``  — the ParaBit baseline (serial pairwise ops; one HBM
                      round-trip of the running result per operand), used by
                      benchmarks and as a second correctness oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bitops import BitOp
from repro.kernels.mws.mws import (
    DEFAULT_BLOCK_WORDS,
    DEFAULT_FAN_IN,
    mws_reduce_pallas,
)
from repro.kernels.platform import resolve_interpret


def _identity_word(op: BitOp, dtype) -> np.ndarray:
    iinfo = jnp.iinfo(dtype)
    if op.base is BitOp.AND:
        return np.array(iinfo.max if iinfo.min == 0 else -1, dtype=dtype)
    return np.array(0, dtype=dtype)


def _pad_to(x: jax.Array, axis: int, multiple: int, fill) -> jax.Array:
    size = x.shape[axis]
    target = -(-size // multiple) * multiple
    if target == size:
        return x
    pad_width = [(0, 0)] * x.ndim
    pad_width[axis] = (0, target - size)
    return jnp.pad(x, pad_width, constant_values=fill)


@functools.partial(
    jax.jit, static_argnames=("op", "fan_in", "block_words", "interpret")
)
def mws_reduce(
    stack: jax.Array,
    op: BitOp,
    *,
    fan_in: int = DEFAULT_FAN_IN,
    block_words: int = DEFAULT_BLOCK_WORDS,
    interpret: bool | None = None,
) -> jax.Array:
    """Bitwise ``op``-reduce over axis 0 of an (N, W) packed-word stack.

    Handles arbitrary N/W by padding the operand axis with the reduction
    identity and the word axis with zeros, then slicing the result back.
    ``interpret=None`` compiles the kernel on a TPU and runs the Pallas
    interpreter elsewhere (:mod:`repro.kernels.platform`).
    """
    if stack.ndim != 2:
        raise ValueError(f"expected (N, W) stack, got {stack.shape}")
    n, w = stack.shape
    fan_in = min(fan_in, max(8, 8 * -(-n // 8)))  # small stacks: shrink block
    ident = _identity_word(op, stack.dtype)
    padded = _pad_to(stack, 0, fan_in, ident)
    padded = _pad_to(padded, 1, block_words, ident)
    out = mws_reduce_pallas(
        padded,
        op,
        fan_in=fan_in,
        block_words=block_words,
        interpret=resolve_interpret(interpret),
    )
    return out[:w]


@functools.partial(jax.jit, static_argnames=("op",))
def parabit_reduce(stack: jax.Array, op: BitOp) -> jax.Array:
    """ParaBit baseline: serial pairwise reduction (one op per operand).

    Written as a ``lax.fori_loop`` over operands so XLA cannot fuse it into a
    single pass — each iteration reads the full running result and one operand
    and writes the full result, modelling ParaBit's one-sensing-per-operand
    data path.
    """
    base = op.base
    fn = {
        BitOp.AND: jnp.bitwise_and,
        BitOp.OR: jnp.bitwise_or,
        BitOp.XOR: jnp.bitwise_xor,
    }[base]

    def body(i, acc):
        return fn(acc, stack[i])

    out = jax.lax.fori_loop(1, stack.shape[0], body, stack[0])
    if op.inverted:
        out = ~out
    return out
