"""Weight-only int8 matmul kernel (registry: ``int8_matmul``).

The serving engine's int8 path (``serving/int8.py``) stores weights as
``{int8 q, f32 absmax scale}`` and dequantizes the WHOLE tensor to the
compute dtype before every dense matmul — for the LM head that is a full
``(V, d)`` f32 materialization per decode step just to read one row's
logits. This kernel fuses the dequant into the matmul: the int8 weight
streams into VMEM one ``block_n`` column-tile at a time, is dequantized
in-register with the exact ``(q.astype(f32) * (scale / 127)).astype(dtype)``
expression ``dequantize_tree`` uses, and is consumed immediately — 4x less
weight traffic (int8 vs f32), no full-size dequant buffer.

Because the per-tile dequant expression and the ``dot_general`` dims match
the dense path op-for-op, the output is **bit-identical** to
dequantize-then-matmul on the CPU tier (interpret mode); ``block_n`` only
changes the program count, never the accumulation order within a tile's dot.

``transpose_w=True`` is the GPT tied head (``rows @ wte.T``, weight stored
``(N, K)``); ``False`` is the Llama head (``rows @ head_w``, ``(K, N)``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..pallas import interpret_default, kernel_x64_off
from .registry import register_kernel, resolve_config

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

__all__ = ["int8_matmul", "int8_matmul_key"]


def _pick_bn(limit: int, n: int) -> int:
    """Largest of (limit, 512, 256, 128) that tiles N; N itself if none do
    (mirrors flash's ``_pick_block`` degrade-don't-fail contract)."""
    for b in (limit, 512, 256, 128):
        if b <= n and n % b == 0:
            return b
    return n


def int8_matmul_key(M, K, N, transpose_w, dtype) -> tuple:
    """Shape bucket: M (the decode batch) rounded up to a power of two; K/N
    are weight dims and exact."""
    m = 1
    while m < int(M):
        m *= 2
    return (m, int(K), int(N), bool(transpose_w), str(jnp.dtype(dtype)))


def _int8_kernel(scale_ref, x_ref, w_ref, o_ref, *, transpose_w):
    # the exact dequant expression from serving/int8.py dequantize_tree —
    # required for bit-identity with the dense path
    wd = (w_ref[...].astype(jnp.float32)
          * (scale_ref[0] / 127.0)).astype(x_ref.dtype)
    dims = ((((1,), (1,)), ((), ())) if transpose_w
            else (((1,), (0,)), ((), ())))
    # accumulate in f32 and round once: the MXU has no narrower accumulator
    # (Mosaic: "Expected matmul acc to be 32-bit")
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], wd, dims, preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def int8_matmul(x, qw, scale, transpose_w=True, config=None, interpret=None):
    """``x @ dequant(qw).T`` (transpose_w) or ``x @ dequant(qw)``.

    x: (..., K) activations; qw: int8 ``(N, K)`` if transpose_w else
    ``(K, N)``; scale: scalar f32 absmax. Leading dims of x are flattened
    into the row dim and restored on return.
    """
    if not _HAS_PALLAS:
        raise RuntimeError("pallas unavailable")
    if interpret is None:
        interpret = interpret_default()
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    N = qw.shape[0] if transpose_w else qw.shape[1]
    if config is None:
        config = resolve_config(
            "int8_matmul", int8_matmul_key(M, K, N, transpose_w, x.dtype))
    bn = _pick_bn(int(config.get("block_n", 512)), N)
    wspec = (pl.BlockSpec((bn, K), lambda i: (i, 0)) if transpose_w
             else pl.BlockSpec((K, bn), lambda i: (0, i)))
    with kernel_x64_off(interpret):
        out = pl.pallas_call(
            functools.partial(_int8_kernel, transpose_w=transpose_w),
            grid=(N // bn,),
            in_specs=[
                pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
                pl.BlockSpec((M, K), lambda i: (0, 0)),
                wspec,
            ],
            out_specs=pl.BlockSpec((M, bn), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
            interpret=interpret,
        )(jnp.asarray(scale, jnp.float32).reshape(1), x2, qw)
    return out.reshape(*lead, N)


# -- registry ----------------------------------------------------------------

def _valid(config, key):
    # _pick_bn degrades any block_n, so every declared choice traces; still
    # skip tiles wider than the weight
    return int(config["block_n"]) <= key[2] or key[2] < 128


def _runner(key):
    import numpy as np

    M, K, N, transpose_w, dtype = key
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(M, K), dtype)
    w = rng.randn(*((N, K) if transpose_w else (K, N))).astype(np.float32)
    scale = jnp.asarray(np.abs(w).max(), jnp.float32)
    qw = jnp.asarray(
        np.clip(np.round(w / (np.asarray(scale) / 127.0)), -127, 127),
        jnp.int8)

    def make(config):
        fn = jax.jit(functools.partial(
            int8_matmul, transpose_w=transpose_w, config=config))
        return lambda: fn(x, qw, scale)

    return make


register_kernel(
    "int8_matmul",
    defaults={"block_n": 512},
    space={"block_n": (128, 256, 512, 1024, 2048)},
    runner=_runner,
    valid=_valid,
)
