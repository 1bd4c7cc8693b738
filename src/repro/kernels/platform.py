"""Kernel path selection: compiled Pallas on a TPU, emulation elsewhere.

Every kernel wrapper, the engine's block reduces and ``FlashArray`` take
``interpret``.  Left at ``None`` (the default), the platform picks the
path: a TPU compiles the Pallas kernel, any other backend (the CPU test
runs) runs the emulation — the Pallas interpreter or an equivalent
plain-XLA fold.  So a TPU never executes the Pallas interpreter unless
asked to, and a CPU run never meets a TPU kernel.  ``True`` / ``False``
force emulation / the compiled kernel (tests, the described-chip compiles
in ``tests/test_tpu_compile.py``, and the cross-check in
``chip_smoke.py``).
"""

from __future__ import annotations

import jax


def resolve_interpret(
    interpret: bool | None, device: jax.Device | None = None
) -> bool:
    """``interpret`` itself when given; otherwise True (emulate) unless
    the data lives on a TPU — on ``device`` when the caller placed it,
    else on JAX's default backend."""
    if interpret is not None:
        return interpret
    platform = device.platform if device is not None else jax.default_backend()
    return platform != "tpu"

