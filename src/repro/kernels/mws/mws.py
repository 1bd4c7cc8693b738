"""Pallas TPU kernel: one-shot multi-operand bitwise reduction (MWS analogue).

Flash-Cosmos performs bitwise AND/OR of up to ~48 operands with a *single*
sensing operation instead of one sensing per operand (ParaBit).  The TPU
analogue: the serial pairwise baseline re-streams the running result through
HBM for every operand (~``3*(N-1)*W`` bytes of traffic); this kernel streams
every operand tile into VMEM exactly once and reduces it on the VPU with a
static tree, writing the result once (``(N+1)*W`` bytes).

Tiling (the "placement" analogue of the paper's same-block co-location):

* operand axis = sublane axis, blocked at ``fan_in`` rows (the VMEM analogue
  of the 48-wordline NAND-string limit).  When ``N > fan_in`` the grid walks
  operand blocks *innermost* and accumulates into the output block — exactly
  the paper's "accumulate multiple MWS results in the latches" (§6.1).
* word axis = lane axis, blocked at ``block_words`` (multiple of 128).

The inverse-read mode (NAND/NOR/XNOR) is a complement applied once, on the
final operand block — the latch-init ordering rule of §6.2 falls out of this:
an inverted read cannot be *followed* by further accumulation into the same
output, which the command planner enforces.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitops import BitOp

# VMEM budget reasoning (v5e: ~16 MiB usable VMEM/core): one input block of
# (64, 2048) uint32 = 512 KiB + (1, 2048) out + double-buffering head-room.
DEFAULT_FAN_IN = 64
DEFAULT_BLOCK_WORDS = 2048


def _tree_reduce(blk: jax.Array, base: BitOp) -> jax.Array:
    """AND/OR/XOR reduce over axis 0 via a static binary tree (Mosaic-safe)."""
    fn = {
        BitOp.AND: jnp.bitwise_and,
        BitOp.OR: jnp.bitwise_or,
        BitOp.XOR: jnp.bitwise_xor,
    }[base]
    n = blk.shape[0]
    while n > 1:
        half = n // 2
        lo = blk[:half]
        hi = blk[half : 2 * half]
        rest = blk[2 * half : n]
        blk = fn(lo, hi)
        if rest.shape[0]:
            blk = jnp.concatenate([blk, rest], axis=0)
        n = blk.shape[0]
    return blk  # (1, BW)


def _mws_kernel(x_ref, o_ref, *, op: BitOp, n_op_blocks: int):
    i = pl.program_id(1)  # operand-block index (innermost => safe revisits)
    part = _tree_reduce(x_ref[...], op.base)

    fn = {
        BitOp.AND: jnp.bitwise_and,
        BitOp.OR: jnp.bitwise_or,
        BitOp.XOR: jnp.bitwise_xor,
    }[op.base]

    @pl.when(i == 0)
    def _init():
        o_ref[...] = part

    @pl.when(i > 0)
    def _acc():
        o_ref[...] = fn(o_ref[...], part)

    if op.inverted:

        @pl.when(i == n_op_blocks - 1)
        def _invert():
            o_ref[...] = ~o_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("op", "fan_in", "block_words", "interpret"),
)
def mws_reduce_pallas(
    stack: jax.Array,
    op: BitOp,
    *,
    fan_in: int = DEFAULT_FAN_IN,
    block_words: int = DEFAULT_BLOCK_WORDS,
    interpret: bool = True,
) -> jax.Array:
    """One-shot multi-operand bitwise reduce of a padded operand stack.

    ``stack``: (N, W) packed words with N a multiple of ``fan_in`` and W a
    multiple of ``block_words`` (use :mod:`repro.kernels.mws.ops` for the
    padding/unpadding wrapper).  Returns (W,).
    """
    n, w = stack.shape
    assert n % fan_in == 0 and w % block_words == 0, (n, w, fan_in, block_words)
    n_op_blocks = n // fan_in
    n_w_blocks = w // block_words

    out = pl.pallas_call(
        functools.partial(_mws_kernel, op=op, n_op_blocks=n_op_blocks),
        grid=(n_w_blocks, n_op_blocks),
        in_specs=[
            pl.BlockSpec((fan_in, block_words), lambda j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, block_words), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, w), stack.dtype),
        interpret=interpret,
        name="flashql_mws_reduce",
    )(stack)
    return out[0]


def _and_rows(x_ref) -> jax.Array:
    """AND over the rows of an ``(n, BW)`` tile, any ``n``: whole 8-row
    sublane groups first, then the ragged tail one row at a time (the
    wordline count of a NAND block is rarely a multiple of 8)."""
    n = x_ref.shape[0]
    full = n - n % 8
    acc = None
    for i in range(0, full, 8):
        blk = x_ref[i : i + 8, :]
        acc = blk if acc is None else acc & blk
    if acc is not None:
        acc = _tree_reduce(acc, BitOp.AND)
    for i in range(full, n):
        row = x_ref[i : i + 1, :]
        acc = row if acc is None else acc & row
    return acc  # (1, BW)


def _block_kernel(x_ref, o_ref, *, or_blocks: bool):
    part = _and_rows(x_ref)
    if not or_blocks:
        o_ref[...] = part
        return
    b = pl.program_id(1)  # NAND-block index (innermost => safe revisits)

    @pl.when(b == 0)
    def _init():
        o_ref[...] = part

    @pl.when(b > 0)
    def _acc():
        o_ref[...] = o_ref[...] | part


@functools.partial(
    jax.jit, static_argnames=("or_blocks", "block_words", "interpret")
)
def mws_block_reduce_pallas(
    cube: jax.Array,
    *,
    or_blocks: bool,
    block_words: int = DEFAULT_BLOCK_WORDS,
    interpret: bool = False,
) -> jax.Array:
    """Both MWS stages over a gathered ``(blocks, wordlines, W)`` cube.

    Each grid step reads one block's ``(wordlines, block_words)`` tile —
    the whole wordline axis is one tile, so the cube is neither transposed
    nor padded — and ANDs its rows (the NAND string).  ``or_blocks``
    ORs the blocks into a ``(W,)`` result (the shared bitlines);
    otherwise the per-block ANDs come back as ``(blocks, W)`` for a
    threshold combine.  A ragged last word tile needs no padding: its
    out-of-range lanes are never written back.
    """
    k, n, w = cube.shape
    bw = min(block_words, w)  # a narrow page is one full-width tile
    grid = (pl.cdiv(w, bw), k)
    in_spec = pl.BlockSpec((None, n, bw), lambda j, b: (b, 0, j))
    if or_blocks:
        out_spec = pl.BlockSpec((1, bw), lambda j, b: (0, j))
        out_shape = jax.ShapeDtypeStruct((1, w), cube.dtype)
    else:
        out_spec = pl.BlockSpec((None, 1, bw), lambda j, b: (b, 0, j))
        out_shape = jax.ShapeDtypeStruct((k, 1, w), cube.dtype)
    out = pl.pallas_call(
        functools.partial(_block_kernel, or_blocks=or_blocks),
        grid=grid,
        in_specs=[in_spec],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="flashql_mws_block_reduce",
    )(cube)
    return out[0] if or_blocks else out[:, 0]
