"""What every family's plain reference shares, and the harness with them: the
precisions a reference computes in, and the norm of a difference.

``"f32"`` judges a run; ``"fp8"`` is the control of the correctness check, a
precision below the configurations' bfloat16 that a later PR could be tempted
by. A family's reference sends the operands of every linear layer and of its
head through ``operands`` (or ``linear``) and is then both.

``"fp8"``: operands rounded to float8 e4m3 (one scale per tensor,
straight-through gradient). It is never used to judge a run. (An int8
control, per-token activations and per-output-channel weights, read no
further from float32 on the chip than the bfloat16 program itself, PERF.md
section 2, and is gone.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def fake_fp8(x):
    """float8 e4m3 (3 mantissa bits) with one scale per tensor that puts its
    largest magnitude at the format's maximum, 448."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
    return x + jax.lax.stop_gradient(q - x)


def operands(x, w, mode):
    if mode == "fp8":
        return fake_fp8(x), fake_fp8(w)
    if mode != "f32":
        raise ValueError(f"unknown precision mode {mode!r}; know 'f32' and 'fp8'")
    return x, w


def linear(x, w, b, mode):
    x, w = operands(x, w, mode)
    return jnp.matmul(x, w, precision=HI) + b


@jax.jit
def diff_norm(a, b):
    """||a - b|| in float32: a leaf's change since the first step, for the
    reference's state and for the program's alike (one compiled program a
    shape)."""
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32))))
