"""Pallas TPU kernel: fused multi-operand bitwise reduce + population count.

The complete BMI query ("how many users were active every day?") in ONE
kernel: the AND-reduction happens in VMEM and only a scalar count leaves —
the result bit-vector never round-trips through HBM at all.  This carries
the paper's one-sensing philosophy one level further than `kernels/mws`:
Flash-Cosmos still DMAs the result page to the host for counting (§7, BMI);
on TPU the count collapses into the same pass.

Traffic: N·W bytes in, 4 bytes out — vs (N+1)·W for reduce-then-popcount
and 3(N−1)·W+… for the serial baseline.

Grid: word-blocks outer, operand-blocks inner (same revisit-safe layout as
`kernels/mws`); a VMEM scratch block holds the running reduction, and the
(1,1) int32 output — a scalar, so it lives in SMEM (the TPU refuses scalar
stores to VMEM) — accumulates SWAR popcounts on the final operand block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitops import BitOp
from repro.kernels.mws.mws import _tree_reduce

DEFAULT_FAN_IN = 64
DEFAULT_BLOCK_WORDS = 2048

_M1 = np.uint32(0x55555555)
_M2 = np.uint32(0x33333333)
_M4 = np.uint32(0x0F0F0F0F)
_H01 = np.uint32(0x01010101)


def _swar(v):
    v = v - ((v >> 1) & _M1)
    v = (v & _M2) + ((v >> 2) & _M2)
    v = (v + (v >> 4)) & _M4
    return ((v * _H01) >> 24).astype(jnp.int32)


def _kernel(x_ref, o_ref, acc_ref, *, op: BitOp, n_op_blocks: int):
    j = pl.program_id(0)  # word-block (outer)
    i = pl.program_id(1)  # operand-block (inner; revisit-safe)
    part = _tree_reduce(x_ref[...], op.base)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = part

    @pl.when(i > 0)
    def _acc():
        fn = {
            BitOp.AND: jnp.bitwise_and,
            BitOp.OR: jnp.bitwise_or,
            BitOp.XOR: jnp.bitwise_xor,
        }[op.base]
        acc_ref[...] = fn(acc_ref[...], part)

    @pl.when(i == n_op_blocks - 1)
    def _count():
        red = acc_ref[...]
        if op.inverted:
            red = ~red
        blk_count = jnp.sum(_swar(red))

        @pl.when(j == 0)
        def _first():
            o_ref[0, 0] = blk_count

        @pl.when(j > 0)
        def _rest():
            o_ref[0, 0] = o_ref[0, 0] + blk_count


@functools.partial(
    jax.jit, static_argnames=("op", "fan_in", "block_words", "interpret")
)
def mws_count_pallas(
    stack: jax.Array,
    op: BitOp,
    *,
    fan_in: int = DEFAULT_FAN_IN,
    block_words: int = DEFAULT_BLOCK_WORDS,
    interpret: bool = True,
) -> jax.Array:
    n, w = stack.shape
    assert n % fan_in == 0 and w % block_words == 0
    out = pl.pallas_call(
        functools.partial(
            _kernel, op=op, n_op_blocks=n // fan_in
        ),
        grid=(w // block_words, n // fan_in),
        in_specs=[pl.BlockSpec((fan_in, block_words), lambda j, i: (i, j))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, block_words), jnp.uint32)],
        interpret=interpret,
        name="flashql_mws_count",
    )(stack)
    return out[0, 0]
