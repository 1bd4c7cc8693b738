"""Hyperdimensional computing (HDC) on the flash array, in three sensings.

HDC's three primitives map 1:1 onto in-flash bulk bitwise operations:

* **bind** (role (x) filler)  = XOR        -> one XOR read
* **bundle** (superposition)  = majority   -> ONE k-of-N threshold sensing
* **similarity** (Hamming)    = XOR + popcount -> one XOR read + kernel

The majority vote is the showpiece: bundling N hypervectors classically
needs per-bit counters over N operands, but the threshold sensing
compares the number of conducting wordlines against k = ceil((N+1)/2)
in a single staircase sense — the bundle never exists as intermediate
per-bit counts anywhere.

The demo builds a tiny item memory of role/filler hypervectors, encodes
records by binding and bundling ON DEVICE, learns class prototypes by
bundling noisy examples, then classifies unseen noisy queries by
on-device Hamming distance — every step asserted against a numpy oracle.

Run:  PYTHONPATH=src python examples/flashql_hdc.py
"""

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.bitops import pack_bits, unpack_bits
from repro.core.engine import FlashArray
from repro.core.expr import Page, Threshold, xor_
from repro.kernels.popcount import popcount

D = 4096  # hypervector dimensionality (bits)
NUM_CLASSES = 3
EXAMPLES_PER_CLASS = 7  # odd: the majority vote can never tie
NOISE = 0.15  # per-bit flip probability for examples/queries


def majority(k, names):
    """Bundle = per-bit majority: ONE k-of-N threshold sensing."""
    return Threshold(k, tuple(Page(n) for n in names))


def write_hv(arr, name, bits):
    arr.fc_write(name, pack_bits(jnp.asarray(bits)))


def read_bits(arr, expr):
    return np.asarray(unpack_bits(arr.fc_read(expr), D))


def main() -> None:
    rng = np.random.default_rng(0)
    arr = FlashArray()

    # -- item memory: random role/filler hypervectors ---------------------
    roles = {r: rng.integers(0, 2, D, np.uint8) for r in ("role0", "role1")}
    for name, bits in roles.items():
        write_hv(arr, name, bits)

    # -- bind on device: record = role (x) filler (XOR) -------------------
    filler = rng.integers(0, 2, D, np.uint8)
    write_hv(arr, "filler", filler)
    bound = read_bits(arr, xor_(Page("role0"), Page("filler")))
    np.testing.assert_array_equal(bound, roles["role0"] ^ filler)
    print(f"bind: role (x) filler XOR, D={D}, bit-exact")

    # -- learn: class prototype = on-device majority bundle ---------------
    k = (EXAMPLES_PER_CLASS + 1) // 2  # strict majority of 7 => k=4
    bases = [rng.integers(0, 2, D, np.uint8) for _ in range(NUM_CLASSES)]
    protos = []
    for c, base in enumerate(bases):
        names = []
        examples = []
        for i in range(EXAMPLES_PER_CLASS):
            flips = rng.random(D) < NOISE
            ex = base ^ flips.astype(np.uint8)
            name = f"class{c}/ex{i}"
            write_hv(arr, name, ex)
            names.append(name)
            examples.append(ex)
        proto = read_bits(arr, majority(k, names))
        want = (np.sum(examples, axis=0) >= k).astype(np.uint8)
        np.testing.assert_array_equal(proto, want)
        write_hv(arr, f"proto{c}", proto)
        protos.append(proto)
        agree = int((proto == base).sum())
        print(
            f"bundle: class {c} prototype = majority of "
            f"{EXAMPLES_PER_CLASS} noisy examples in ONE threshold "
            f"sensing ({agree}/{D} bits match the hidden base)"
        )

    # -- classify: nearest prototype by on-device Hamming distance --------
    correct = 0
    trials = 12
    for t in range(trials):
        true = int(rng.integers(0, NUM_CLASSES))
        flips = rng.random(D) < NOISE
        query = bases[true] ^ flips.astype(np.uint8)
        write_hv(arr, "query", query)
        dists = []
        for c in range(NUM_CLASSES):
            diff = arr.fc_read(xor_(Page("query"), Page(f"proto{c}")))
            dists.append(int(popcount(diff)))
            want = int((query ^ protos[c]).sum())
            assert dists[-1] == want, (c, dists[-1], want)
        pred = int(np.argmin(dists))
        correct += pred == true
    print(
        f"similarity: {correct}/{trials} noisy queries classified by "
        f"on-device XOR + popcount Hamming distance"
    )
    assert correct == trials, "HDC classification should be exact here"
    print("ok")


if __name__ == "__main__":
    enable_compile_cache()
    main()
