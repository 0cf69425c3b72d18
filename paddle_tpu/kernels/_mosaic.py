"""What every Pallas TPU kernel file of this package shares.

The package turns 64-bit mode on for everyone (``paddle_tpu/__init__.py``:
Paddle's int64/float64 semantics). Mosaic refuses 64-bit indices — an
index map that yields an ``i64`` fails to legalize — so a kernel is
traced with x64 off. Interpret mode has no such restriction, and toggling
x64 inside an outer trace splits cached sub-jaxprs across dtype regimes
(i32/i64 ``func.call`` mismatch at lowering), so it is left alone there.
"""
from __future__ import annotations

import contextlib

import jax

__all__ = ["x64_off"]


def x64_off(interpret: bool):
    """Context for tracing a ``pallas_call``: 32-bit mode when the kernel
    compiles for the chip, a no-op in interpret mode."""
    return contextlib.nullcontext() if interpret else jax.enable_x64(False)
