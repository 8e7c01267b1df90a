"""Pallas TPU kernels of the render hot path (PRTU CTU and VRU blend).

Where a kernel runs is decided here, from the platform alone: on the CPU
backend (tests, CI) `pallas_call` interprets the kernel; on every other
backend Mosaic compiles it. No caller chooses.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True iff Pallas kernels run in the interpreter: the CPU backend."""
    return jax.default_backend() == "cpu"
