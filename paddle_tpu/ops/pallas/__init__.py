"""Pallas TPU kernels (hot-op fast paths), and what every Pallas entry point
in the package shares about where it runs."""
from __future__ import annotations

import contextlib

import jax

from ...core.compat import enable_x64


def interpret_default() -> bool:
    """Mosaic lowers for the TPU only: with the CPU as default backend (the
    test tier) a kernel runs under the Pallas interpreter, everywhere else it
    is compiled. Entry points take ``interpret=None`` to mean this."""
    return jax.default_backend() == "cpu"


def kernel_x64_off(interpret: bool):
    """The framework enables x64 globally (paddle int64 semantics) but Mosaic
    has no i64/f64 lowering, so a compiled kernel is traced with x64 off:
    index maps and weak python scalars must stay 32-bit. The interpreter
    handles 64-bit fine, and flipping x64 inside an outer x64 trace (jit or
    shard_map around the model) has mixed i32/i64 in its grid loops before,
    so interpret mode leaves the setting alone."""
    return contextlib.nullcontext() if interpret else enable_x64(False)
