"""Pallas TPU kernel: population count over packed bit-planes.

Used by the BMI workload ("how many users were active every day") — the
paper leaves the bit-count on the host CPU, overlapped with the result DMA
(§7); on TPU the count is cheap enough to fuse right after the MWS reduce,
so the result vector never round-trips through HBM unpacked.

SWAR popcount (Hacker's Delight §5-1) on the VPU; per-row partial sums are
accumulated across word-blocks in an SMEM-friendly (R, 1) int32 output block
revisited along the innermost grid axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 8
DEFAULT_BLOCK_WORDS = 2048

_M1 = np.uint32(0x55555555)
_M2 = np.uint32(0x33333333)
_M4 = np.uint32(0x0F0F0F0F)
_H01 = np.uint32(0x01010101)


def _swar_popcount(v: jax.Array) -> jax.Array:
    v = v - ((v >> 1) & _M1)
    v = (v & _M2) + ((v >> 2) & _M2)
    v = (v + (v >> 4)) & _M4
    return ((v * _H01) >> 24).astype(jnp.int32)


def _popcount_kernel(x_ref, o_ref):
    j = pl.program_id(1)  # word-block index (innermost => safe revisits)
    part = jnp.sum(_swar_popcount(x_ref[...]), axis=1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = part

    @pl.when(j > 0)
    def _acc():
        o_ref[...] = o_ref[...] + part


@functools.partial(
    jax.jit, static_argnames=("block_rows", "block_words", "interpret")
)
def popcount_pallas(
    words: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_words: int = DEFAULT_BLOCK_WORDS,
    interpret: bool = True,
) -> jax.Array:
    """(R, W) uint32 -> (R,) int32, R % block_rows == 0, W % block_words == 0."""
    r, w = words.shape
    assert r % block_rows == 0 and w % block_words == 0

    out = pl.pallas_call(
        _popcount_kernel,
        grid=(r // block_rows, w // block_words),
        in_specs=[pl.BlockSpec((block_rows, block_words), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        interpret=interpret,
        name="flashql_popcount",
    )(words)
    return out[:, 0]
