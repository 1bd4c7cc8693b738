"""Per-kernel correctness: MWS fused reduce vs pure-jnp oracle.

Sweeps shapes/dtypes and asserts bit-exact equality (interpret=True executes
the kernel body on CPU; the BlockSpec tiling logic is exercised for real).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # hypothesis optional

from repro.core.bitops import BitOp, pack_bits, reduce_words, unpack_bits
from repro.core.engine import fused_block_reduce, threshold_block_reduce
from repro.kernels.mws import mws_reduce, mws_reduce_ref, parabit_reduce
from repro.core.store import IDENTITY_SLOT, ZERO_SLOT
from repro.kernels.mws.mws import mws_block_reduce_pallas, mws_sense_pallas
from repro.kernels.popcount import popcount
from repro.query.device import pad_idx, repeat_padding

ALL_OPS = list(BitOp)


def _rand_stack(rng, n, w, dtype):
    hi = int(jnp.iinfo(dtype).max)
    return jnp.array(
        rng.integers(0, hi, (n, w), dtype=np.uint64).astype(dtype)
    )


@pytest.mark.parametrize("op", ALL_OPS, ids=[o.value for o in ALL_OPS])
@pytest.mark.parametrize(
    "n,w",
    [
        (1, 1),
        (2, 128),
        (3, 200),
        (48, 2048),  # the paper's intra-block maximum (48 WLs/string)
        (64, 4096),  # one full fan-in block
        (65, 2049),  # operand + word padding paths
        (200, 300),  # multi-operand-block accumulation
    ],
)
def test_mws_matches_ref(op, n, w):
    rng = np.random.default_rng(n * 1000 + w)
    x = _rand_stack(rng, n, w, jnp.uint32)
    got = mws_reduce(x, op)
    want = mws_reduce_ref(x, op)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.uint32, jnp.int32])
@pytest.mark.parametrize("op", [BitOp.AND, BitOp.OR, BitOp.XOR, BitOp.NAND])
def test_mws_dtypes(dtype, op):
    rng = np.random.default_rng(7)
    x = _rand_stack(rng, 17, 513, dtype)
    np.testing.assert_array_equal(
        np.asarray(mws_reduce(x, op)), np.asarray(mws_reduce_ref(x, op))
    )


@pytest.mark.parametrize("op", ALL_OPS, ids=[o.value for o in ALL_OPS])
def test_parabit_matches_mws(op):
    """The serial baseline and the fused kernel must agree (paper: ParaBit
    and Flash-Cosmos compute the same function; FC is just one sensing)."""
    rng = np.random.default_rng(3)
    x = _rand_stack(rng, 31, 777, jnp.uint32)
    np.testing.assert_array_equal(
        np.asarray(parabit_reduce(x, op)), np.asarray(mws_reduce(x, op))
    )


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(1, 80),
    w=st.integers(1, 300),
    seed=st.integers(0, 2**31 - 1),
    op=st.sampled_from(ALL_OPS),
)
def test_mws_property_matches_ref(n, w, seed, op):
    rng = np.random.default_rng(seed)
    x = _rand_stack(rng, n, w, jnp.uint32)
    np.testing.assert_array_equal(
        np.asarray(mws_reduce(x, op)), np.asarray(mws_reduce_ref(x, op))
    )


@settings(max_examples=25, deadline=None)
@given(n=st.integers(2, 40), w=st.integers(1, 100), seed=st.integers(0, 2**31 - 1))
def test_de_morgan(n, w, seed):
    """(A1 + ... + An) == NOT(NOT A1 · ... · NOT An) — the paper's §6.1 trick
    for OR-inside-a-block via inverse-stored operands + NAND."""
    rng = np.random.default_rng(seed)
    x = _rand_stack(rng, n, w, jnp.uint32)
    or_direct = mws_reduce(x, BitOp.OR)
    nand_of_inverse = mws_reduce(~x, BitOp.NAND)
    np.testing.assert_array_equal(
        np.asarray(or_direct), np.asarray(nand_of_inverse)
    )


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(2, 30),
    split=st.integers(1, 29),
    w=st.integers(1, 64),
    seed=st.integers(0, 2**31 - 1),
    op=st.sampled_from([BitOp.AND, BitOp.OR, BitOp.XOR]),
)
def test_accumulation_associativity(n, split, w, seed, op):
    """Splitting an MWS into two commands + latch accumulation is lossless
    (paper §6.1: accumulate results of multiple intra-block MWS ops)."""
    split = min(split, n - 1)
    rng = np.random.default_rng(seed)
    x = _rand_stack(rng, n, w, jnp.uint32)
    whole = mws_reduce(x, op)
    parts = jnp.stack([mws_reduce(x[:split], op), mws_reduce(x[split:], op)])
    np.testing.assert_array_equal(
        np.asarray(mws_reduce(parts, op)), np.asarray(whole)
    )


@settings(max_examples=20, deadline=None)
@given(bits=st.lists(st.integers(0, 1), min_size=1, max_size=200))
def test_pack_unpack_roundtrip(bits):
    b = jnp.array(bits, dtype=jnp.uint8)
    words = pack_bits(b)
    np.testing.assert_array_equal(
        np.asarray(unpack_bits(words, len(bits))), np.asarray(b)
    )


def test_reduce_words_matches_kernel_ref():
    rng = np.random.default_rng(11)
    x = _rand_stack(rng, 9, 40, jnp.uint32)
    for op in ALL_OPS:
        np.testing.assert_array_equal(
            np.asarray(reduce_words(x, op)), np.asarray(mws_reduce_ref(x, op))
        )


@pytest.mark.parametrize(
    "k,n,w", [(1, 1, 1), (3, 5, 200), (2, 30, 4100), (4, 9, 2048), (1, 48, 300)]
)
def test_block_reduce_kernel_matches_fold(k, n, w):
    """The one-dispatch MWS kernel (AND within each block, OR across
    blocks, ragged wordline counts and word tiles) against the fold."""
    rng = np.random.default_rng(k * 100 + n * 10 + w)
    cube = _rand_stack(rng, k * n, w, jnp.uint32).reshape(k, n, w)
    cube = cube | _rand_stack(rng, k * n, w, jnp.uint32).reshape(k, n, w)
    anded = np.stack([np.asarray(reduce_words(cube[b], BitOp.AND)) for b in range(k)])
    np.testing.assert_array_equal(
        np.asarray(mws_block_reduce_pallas(cube, or_blocks=False, interpret=True)),
        anded,
    )
    np.testing.assert_array_equal(
        np.asarray(mws_block_reduce_pallas(cube, or_blocks=True, interpret=True)),
        np.asarray(reduce_words(jnp.asarray(anded), BitOp.OR)),
    )


def test_default_kernel_path_on_cpu_is_emulation():
    """With no ``interpret`` given, a program lowered for the CPU carries
    no TPU kernel and computes what the explicit emulation computes."""
    rng = np.random.default_rng(12)
    cube = _rand_stack(rng, 12, 300, jnp.uint32).reshape(3, 4, 300)
    for fn in (
        lambda c: fused_block_reduce(c, True),
        lambda c: threshold_block_reduce(c, 2, False),
        lambda c: popcount(c[0]),
    ):
        compiled = jax.jit(fn).lower(cube).compile()
        assert "tpu_custom_call" not in compiled.as_text()
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda c: fused_block_reduce(c, True))(cube)),
        np.asarray(fused_block_reduce(cube, True, interpret=True)),
    )
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda c: threshold_block_reduce(c, 2, False))(cube)),
        np.asarray(threshold_block_reduce(cube, 2, False, interpret=True)),
    )


def _sense_idx(rng, members, shape, wls, slots):
    """``members`` slot tables of ``shape`` (blocks, wordlines), each block
    holding ``wls[b]`` real slots and the store's identity padding, padded
    on to ``shape`` as plan-aware batching pads them."""
    real, width = len(wls), max(wls)
    tables = []
    for _ in range(members):
        ix = np.full((real, width), IDENTITY_SLOT, np.int32)
        for b, n in enumerate(wls):
            ix[b, :n] = rng.integers(ZERO_SLOT + 1, slots, n)
        tables.append(pad_idx(ix, shape))
    return np.stack(tables)


@pytest.mark.parametrize(
    "members,shape,wls,inverse,words,latch",
    [
        (1, (1, 1), (1,), False, 1024, False),  # one page
        (8, (1, 29), (21,), False, 2500, True),  # identity-padded long AND
        (8, (1, 3), (3,), True, 3000, False),  # inverse read, one block
        (3, (3, 4), (4, 2, 3), False, 1100, False),  # several blocks
        (2, (4, 5), (5, 1), True, 2500, True),  # zero-slot-padded blocks
        (1, (2, 2), (2, 2), True, 3 * 1024, False),  # words fill the tiles
    ],
)
def test_mws_sense_matches_gathered_fold(members, shape, wls, inverse, words, latch):
    """In-place sensing (Pallas interpreter) equals the fold of the cube
    gathered from the same store, bit for bit: AND within blocks, OR
    across blocks, identity and zero padding, inverse read, the S latch,
    and word counts that leave a tail in the last tile (sliced off)."""
    rng = np.random.default_rng(members * 1000 + words)
    slots = 9
    flat = np.array(_rand_stack(rng, slots, words, jnp.uint32))
    flat[IDENTITY_SLOT] = 0xFFFFFFFF
    flat[ZERO_SLOT] = 0
    rows = 8 * -(-words // 1024)  # whole (8, 128) tiles, one per grid row
    store = np.zeros((slots, rows * 128), np.uint32)
    store[:, :words] = flat
    store = jnp.asarray(store.reshape(slots, rows, 128))
    idx = _sense_idx(rng, members, shape, wls, slots)
    s = _rand_stack(rng, members, rows * 128, jnp.uint32).reshape(members, rows, 128)

    want = jax.vmap(
        lambda ix: fused_block_reduce(
            jnp.take(jnp.asarray(flat), ix, axis=0), inverse, interpret=True
        )
    )(jnp.asarray(idx))
    if latch:
        want = want & s.reshape(members, -1)[:, :words]
    for table in (idx, repeat_padding(idx)):  # as planned, as the runner sends
        got = mws_sense_pallas(
            jnp.asarray(table),
            store,
            s if latch else None,
            inverse=inverse,
            tile_rows=8,
            interpret=True,
        )
        assert got.shape == (members, rows, 128)
        np.testing.assert_array_equal(
            np.asarray(got).reshape(members, -1)[:, :words], np.asarray(want)
        )
