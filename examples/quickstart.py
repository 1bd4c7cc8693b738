"""Quickstart: Flash-Cosmos bulk bitwise operations on the TPU engine.

Demonstrates the public API end to end:
  1. fc_write operand pages (ESP mode = guaranteed error-free compute),
  2. build a bitwise expression, let the planner compile it to MWS commands,
  3. execute with one-shot multi-operand sensing (fused Pallas kernel),
  4. compare against the ParaBit serial baseline and a CPU oracle.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.bitops import BitOp
from repro.core.engine import FlashArray, eval_expr
from repro.core.expr import Page, and_, or_
from repro.core.planner import Planner
from repro.kernels.mws import parabit_reduce
from repro.kernels.popcount import popcount


def main():
    rng = np.random.default_rng(0)
    words_per_page = 4096  # 16 KiB pages, like the paper's chips

    # --- 1. store 48 operand pages (one NAND-string's worth) -------------
    arr = FlashArray()
    logical = {}
    names = [f"day{i}" for i in range(48)]
    arr.layout.place_colocated(names)  # §6.3: co-locate AND operands
    for n in names:
        data = jnp.array(
            rng.integers(0, 2**32, (words_per_page,), dtype=np.uint32)
        )
        logical[n] = data
        arr.fc_write(n, data, esp=True)

    # --- 2./3. one-shot 48-operand AND (the BMI query core) --------------
    expr = and_(*map(Page, names))
    plan = Planner(arr.layout).compile(expr)
    print(f"48-operand AND -> {plan.num_sensing_ops} sensing operation(s)")
    result = arr.execute(plan)
    active = int(popcount(result))
    print(f"bit-count of result: {active}")

    # --- 4. verify against serial baseline + oracle ----------------------
    stack = jnp.stack([logical[n] for n in names])
    assert (result == parabit_reduce(stack, BitOp.AND)).all()
    assert (result == eval_expr(expr, logical)).all()
    print("matches ParaBit serial baseline and CPU oracle: OK")

    # --- bonus: OR via De Morgan inverse storage (one sensing too) -------
    arr2 = FlashArray()
    ors = [f"v{i}" for i in range(32)]
    arr2.layout.place_colocated(ors, inverted=True)
    for n in ors:
        logical[n] = jnp.array(
            rng.integers(0, 2**32, (words_per_page,), dtype=np.uint32)
        )
        arr2.fc_write(n, logical[n])
    plan_or = Planner(arr2.layout).compile(or_(*map(Page, ors)))
    print(f"32-operand OR  -> {plan_or.num_sensing_ops} sensing operation(s)")
    got = arr2.execute(plan_or)
    assert (got == eval_expr(or_(*map(Page, ors)), logical)).all()
    print("De Morgan inverse-storage OR: OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
