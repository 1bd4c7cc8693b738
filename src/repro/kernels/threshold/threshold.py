"""Pallas TPU kernel: k-of-N threshold combine over per-block AND results.

The MCFlash-style dynamic-sensing primitive: after every activated block's
NAND strings have resolved (AND of the block's selected wordlines — the
same first stage as a plain MWS), the cross-block combine compares the
number of conducting blocks per bit position against a programmable
threshold ``k`` instead of the fixed wired-OR.  ``k == 1`` IS the MWS OR.

The per-bit counter never materializes as an integer: counts are held
**bit-sliced** across four uint32 accumulator planes (counts <= 8 blocks
fit in 4 bits), built with a ripple-carry half-adder chain — each block
row costs two vector ops per plane, all on the VPU, and the final
``count >= k`` comparator is a statically-unrolled equality fan-in over
the count planes.  One input streaming pass, one output block, no HBM
round-trip of intermediate counts.

Grid: word-blocks only — the block axis (<= 8 rows, padded with zeros,
which never conduct and never count) fits one sublane tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_WORDS = 2048
MAX_COUNT_BITS = 4  # bit-sliced counter planes; holds counts <= 15


def bitslice_threshold(anded: jax.Array, k: int, n_blocks: int) -> jax.Array:
    """``count >= k`` per bit over the rows of ``anded`` (shared logic).

    ``anded`` is a ``(rows, W)`` uint32 stack (rows beyond ``n_blocks``
    are ignored); returns the ``(1, W)`` threshold bitmap.  Pure jnp —
    the Pallas kernel body calls this on its VMEM tile and the engine's
    emulation path calls it directly, so both paths are bit-identical by
    construction.  Explicit loops only (no ``jnp.bitwise_*.reduce``).
    """
    c = [jnp.zeros_like(anded[:1]) for _ in range(MAX_COUNT_BITS)]
    for r in range(n_blocks):
        carry = anded[r : r + 1]
        for j in range(MAX_COUNT_BITS):
            t = c[j] & carry
            c[j] = c[j] ^ carry
            carry = t
    out = jnp.zeros_like(anded[:1])
    for v in range(k, n_blocks + 1):
        term = None
        for j in range(MAX_COUNT_BITS):
            plane = c[j] if (v >> j) & 1 else ~c[j]
            term = plane if term is None else term & plane
        out = out | term
    return out


def _kernel(x_ref, o_ref, *, k: int, n_blocks: int):
    o_ref[...] = bitslice_threshold(x_ref[...], k, n_blocks)


@functools.partial(
    jax.jit, static_argnames=("k", "n_blocks", "block_words", "interpret")
)
def threshold_pallas(
    anded: jax.Array,
    k: int,
    n_blocks: int,
    *,
    block_words: int = DEFAULT_BLOCK_WORDS,
    interpret: bool = True,
) -> jax.Array:
    rows, w = anded.shape
    assert n_blocks <= rows and w % block_words == 0
    out = pl.pallas_call(
        functools.partial(_kernel, k=k, n_blocks=n_blocks),
        grid=(w // block_words,),
        in_specs=[pl.BlockSpec((rows, block_words), lambda j: (0, j))],
        out_specs=pl.BlockSpec((1, block_words), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, w), jnp.uint32),
        interpret=interpret,
        name="flashql_threshold",
    )(anded)
    return out[0]
