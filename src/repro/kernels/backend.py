"""Where the Pallas kernels run: compiled on the TPU, interpreted on the CPU.

Every ``pl.pallas_call`` in this package passes ``interpret=interpret_mode()``.
There is no third path: on any other backend the kernels refuse to trace
rather than silently fall back to the interpreter.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True on the CPU backend (the tier-1 tests run the kernels in the Pallas
    interpreter there), False on the TPU (the kernels compile).  Raises on
    any other backend."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels here target the TPU (compiled) or the CPU "
        f"(interpret mode); backend {backend!r} has neither path")
