"""TPU-adaptation benchmarks: fused MWS kernel vs serial ParaBit baseline.

Wall-clock on this CPU container is *not* the score (kernels run in
interpret mode); the decisive metric is the modelled HBM traffic — the TPU
analogue of the paper's sensing count — plus measured interpret-mode time
as a correctness-of-trend check.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bitops import BitOp
from repro.kernels.mws import parabit_reduce
from repro.kernels.mws.mws import mws_block_reduce_pallas
from repro.kernels.platform import resolve_interpret
from repro.kernels.popcount import popcount
from repro.kernels.signcomp import compress_signs


def _time(fn, *args, reps=3):
    fn(*args).block_until_ready()  # compile+warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.tree.map(lambda a: a.block_until_ready(), out)
    return (time.perf_counter() - t0) / reps * 1e6  # µs


def hbm_traffic_model(n_operands: int, words: int, dtype_bytes: int = 4):
    """Bytes moved for fused (MWS) vs serial pairwise (ParaBit) reduce."""
    fused = (n_operands + 1) * words * dtype_bytes
    serial = 3 * (n_operands - 1) * words * dtype_bytes
    return fused, serial


def mws_vs_parabit():
    rows = []
    rng = np.random.default_rng(0)
    W = 1 << 16
    for n in (2, 4, 8, 16, 32, 48, 64):
        x = jnp.array(rng.integers(0, 2**32, (n, W), dtype=np.uint32))
        # the served MWS kernel: one NAND block of n wordlines
        t_fused = _time(
            lambda a: mws_block_reduce_pallas(
                a[None], or_blocks=True, interpret=resolve_interpret(None)
            ),
            x,
        )
        t_serial = _time(lambda a: parabit_reduce(a, BitOp.AND), x)
        fused_b, serial_b = hbm_traffic_model(n, W)
        rows.append(
            (
                f"tpu_mws.and.n={n}.traffic_ratio",
                round(serial_b / fused_b, 2),
                f"fused={fused_b>>10}KiB serial={serial_b>>10}KiB",
            )
        )
        rows.append(
            (
                f"tpu_mws.and.n={n}.interp_us",
                round(t_fused, 1),
                f"serial={t_serial:.1f}us",
            )
        )
    return rows


def fused_count_bench():
    """Fused reduce+count (one-pass BMI query): traffic model vs two-pass."""
    rows = []
    rng = np.random.default_rng(3)
    from repro.kernels.mws_count import mws_count

    W = 1 << 16
    for n in (8, 48):
        x = jnp.array(rng.integers(0, 2**32, (n, W), dtype=np.uint32))
        t = _time(lambda a: mws_count(a, BitOp.AND), x)
        fused_b = n * W * 4 + 4  # operands in, scalar out
        twopass_b = (n + 1) * W * 4 + (W * 4 + 4)  # reduce out + count in
        rows.append(
            (
                f"tpu_mws_count.n={n}.traffic_ratio",
                round(twopass_b / fused_b, 3),
                f"fused={fused_b>>10}KiB two-pass={twopass_b>>10}KiB",
            )
        )
        rows.append((f"tpu_mws_count.n={n}.interp_us", round(t, 1), ""))
    return rows


def popcount_bench():
    rng = np.random.default_rng(1)
    rows = []
    for w in (1 << 12, 1 << 16):
        x = jnp.array(rng.integers(0, 2**32, (8, w), dtype=np.uint32))
        t = _time(popcount, x)
        rows.append((f"tpu_popcount.w={w}.interp_us", round(t, 1), ""))
    return rows


def signcomp_bench():
    rng = np.random.default_rng(2)
    rows = []
    for n in (1 << 16, 1 << 20):
        g = jnp.array(rng.normal(size=(n,)).astype(np.float32))
        t_c = _time(compress_signs, g)
        packed = compress_signs(g)
        ratio = g.size * 4 / (packed.size * 4)
        rows.append(
            (
                f"tpu_signcomp.n={n}.compress_us",
                round(t_c, 1),
                f"compression={ratio:.0f}x",
            )
        )
    return rows
