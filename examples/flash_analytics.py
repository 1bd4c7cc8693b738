"""The paper's three workloads (BMI / IMS / KCS) end to end: functional
execution on the TPU engine at reduced scale + full-scale performance/energy
projection on the SSD model (the Fig. 17/18 reproduction).

Run:  PYTHONPATH=src python examples/flash_analytics.py
"""

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.engine import FlashArray
from repro.core.expr import Page, and_, or_
from repro.flashsim import (
    Platform,
    bmi_workload,
    ims_workload,
    kcs_workload,
    run_workload,
)
from repro.kernels.popcount import popcount


def bmi_demo():
    """Bitmap Index: which of 100k users were active on ALL of 60 days?"""
    rng = np.random.default_rng(1)
    users, days = 100_000, 60
    arr = FlashArray()
    names = [f"day{i}" for i in range(days)]
    arr.layout.place_colocated(names)
    daily = (rng.random((days, users)) < 0.97).astype(np.uint8)
    from repro.core.bitops import pack_bits

    for n, bits in zip(names, daily):
        arr.fc_write(n, pack_bits(jnp.asarray(bits)))
    result = arr.fc_read(and_(*map(Page, names)))
    count = int(popcount(result))
    oracle = int(daily.all(axis=0).sum())
    assert count == oracle
    print(f"BMI: {count} of {users} users active all {days} days (exact)")


def kcs_demo():
    """K-clique star: AND of adjacency vectors OR clique vector, 1 sensing."""
    rng = np.random.default_rng(2)
    vertices, k = 50_000, 12
    arr = FlashArray()
    adj_names = [f"adj{i}" for i in range(k)]
    arr.layout.place_colocated(adj_names)
    arr.layout.place_spread(["clique"])
    from repro.core.bitops import pack_bits

    adj = (rng.random((k, vertices)) < 0.9).astype(np.uint8)
    clique = np.zeros(vertices, np.uint8)
    clique[rng.choice(vertices, k, replace=False)] = 1
    for n, bits in zip(adj_names, adj):
        arr.fc_write(n, pack_bits(jnp.asarray(bits)))
    arr.fc_write("clique", pack_bits(jnp.asarray(clique)))

    expr = or_(and_(*map(Page, adj_names)), Page("clique"))
    from repro.core.planner import Planner

    plan = Planner(arr.layout).compile(expr)
    result = arr.execute(plan)
    oracle = adj.all(axis=0) | clique.astype(bool)
    from repro.core.bitops import unpack_bits

    got = np.asarray(unpack_bits(result, vertices)).astype(bool)
    assert (got == oracle).all()
    print(
        f"KCS: clique star of {int(oracle.sum())} vertices in "
        f"{plan.num_sensing_ops} sensing op(s) (exact)"
    )


def projection():
    print("\nfull-scale projection (Table-1 SSD):")
    print(f"{'workload':14s} {'OSP':>9s} {'ISP':>9s} {'ParaBit':>9s} {'FC':>9s}")
    for wl in (bmi_workload(36), ims_workload(100_000), kcs_workload(32)):
        times = [
            run_workload(wl, p).time_s
            for p in (Platform.OSP, Platform.ISP, Platform.PB, Platform.FC)
        ]
        print(
            f"{wl.name:14s} "
            + " ".join(f"{t:8.3f}s" for t in times)
            + f"   (FC speedup {times[0]/times[3]:.1f}x)"
        )


if __name__ == "__main__":
    enable_compile_cache()
    bmi_demo()
    kcs_demo()
    projection()
