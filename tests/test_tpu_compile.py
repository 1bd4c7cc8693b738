"""Compile the served path's Pallas kernels for a TPU v5e at real widths.

Nothing runs: each program is lowered against a described ``v5e:2x2``
topology and compiled by the TPU compiler installed beside jaxlib, which
refuses what the chip would refuse (misaligned tiles, scalar stores to
VMEM, programs that do not fit HBM).  Widths are those of
``chip_smoke.py``: 2^27 rows plus the append reserve, about 4M uint32
words per page.  Every program must contain a Pallas kernel
(``tpu_custom_call``) and fit one chip's 16 GiB.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under pytest-xdist every worker
imports this file.
"""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.bitops import BitOp, num_words
from repro.core.engine import fused_block_reduce, threshold_block_reduce
from repro.kernels.mws import mws_reduce
from repro.kernels.mws_count import mws_count
from repro.kernels.popcount import popcount
from repro.kernels.threshold import threshold_reduce
from repro.query.aggregate import sliced_counts
from repro.query.device import make_flush_runner

REPO = Path(__file__).resolve().parents[1]
HBM_BYTES = 16 << 30  # one v5e chip
PLANES = 4


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SMOKE = _smoke()
W = -(-num_words(SMOKE.ROWS + SMOKE.APPEND_ROWS) // PLANES) * PLANES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to a persistent cache but
    # can never be read back without one: keep the cache out of the way
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, one_chip, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile_for_chip(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
        + m.generated_code_size_in_bytes
        - m.alias_size_in_bytes
    )
    assert total < HBM_BYTES, total
    return compiled


@pytest.mark.parametrize(
    "op,n", [(BitOp.AND, 48), (BitOp.OR, 3), (BitOp.NAND, 200)]
)
def test_mws_reduce_compiles(one_chip, op, n):
    _compile_for_chip(
        lambda x: mws_reduce(x, op, interpret=False), _spec((n, W), one_chip)
    )


@pytest.mark.parametrize("op", [BitOp.AND, BitOp.NAND])
def test_mws_count_compiles(one_chip, op):
    _compile_for_chip(
        lambda x: mws_count(x, op, interpret=False), _spec((30, W), one_chip)
    )


@pytest.mark.parametrize("shape", [(8, W), (W,), (100,)])
def test_popcount_compiles(one_chip, shape):
    _compile_for_chip(
        lambda x: popcount(x, interpret=False), _spec(shape, one_chip)
    )


def test_threshold_reduce_compiles(one_chip):
    _compile_for_chip(
        lambda x: threshold_reduce(x, 3, interpret=False),
        _spec((5, W), one_chip),
    )


# this process's default backend is the CPU, so the tests steer the served
# path onto the kernels with interpret=False; on a TPU the default is that
@pytest.mark.parametrize("shape", [(4, 1, 30, W), (8, 2, 3, W)])
def test_vmapped_block_reduce_compiles(one_chip, shape):
    _compile_for_chip(
        jax.vmap(lambda c: fused_block_reduce(c, True, interpret=False)),
        _spec(shape, one_chip),
    )


def test_vmapped_threshold_block_reduce_compiles(one_chip):
    _compile_for_chip(
        jax.vmap(lambda c: threshold_block_reduce(c, 2, False, interpret=False)),
        _spec((4, 5, 2, W), one_chip),
    )


def test_sliced_counts_compiles(one_chip):
    _compile_for_chip(
        lambda m, e: sliced_counts(m, e, interpret=False),
        _spec((4, W), one_chip),
        _spec((4, 7, W), one_chip),
    )


def test_smoke_flush_program_compiles_and_fits(one_chip):
    """The whole fused flush of ``chip_smoke.py`` — its query mix at its
    ``max_batch``, built through the scheduler at a small row count, then
    compiled with the kernel path at the smoke's page width."""
    rng = np.random.default_rng(0)
    sched = SMOKE.build(SMOKE.make_table(rng, 4096))
    sched.serve(SMOKE.smoke_queries())
    program = sched.last_program
    assert program.n_members == len(SMOKE.smoke_queries()) <= SMOKE.MAX_BATCH

    sense, reduce_sigs, _, cse = program.key
    runner = make_flush_runner((sense, reduce_sigs, W, cse), False)

    def like(a):
        return _spec(a.shape, one_chip, a.dtype)

    slots = sched.device.store.num_slots
    extras = tuple(
        None if e is None else _spec(e.shape[:-1] + (W,), one_chip)
        for e in program.extras
    )
    _compile_for_chip(
        runner,
        _spec((slots, W), one_chip),
        jax.tree.map(like, program.group_idxs),
        like(program.inv_perm),
        _spec((W,), one_chip),
        jax.tree.map(like, program.sels),
        extras,
        jax.tree.map(like, program.cse_idxs),
    )
