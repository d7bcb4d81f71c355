"""Attention functionals.

Parity+: the reference only has fused_attention (C++
``paddle/fluid/operators/fused/fused_attention_op.cc`` / ``fmha_ref.h``); we
provide the same capability as a functional that XLA fuses, plus a
flash-attention entry point that routes to the Pallas TPU kernel when
available (paddle_tpu/ops/pallas/flash_attention.py).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.dispatch import as_tensor, eager_call
from ...ops.pallas import interpret_default


def _flash_eligible(q, k, is_causal, attn_mask, dropout_p, training):
    if not is_causal or attn_mask is not None:
        return False
    if dropout_p and training:
        return False
    d = q.shape[-1]
    if d % 8 != 0 or d > 256:
        return False
    if q.shape[1] < 512 or k.shape[1] % 128 != 0:
        return False  # short sequences: XLA's fused exact path measured faster
    # The backward kernels keep one full (T, D) operand pair resident in VMEM
    # (K/V for dq, Q/dO for dkv); bound it so jit-compile can't die on a
    # Mosaic allocation error with no fallback (~16 MB VMEM on v5e).
    esize = 2 if q.dtype in ("bfloat16", jnp.bfloat16) else 4
    if k.shape[1] * d * esize > 4 * 1024 * 1024:
        return False
    # under the interpreter (the CPU tier) the kernel is orders slower than
    # XLA's exact attention
    return not interpret_default()


def _flash(q, k, v):
    """The causal Pallas flash call. GSPMD cannot partition a Mosaic custom
    call ("Mosaic kernels cannot be automatically partitioned"), so under a
    train step compiled over a device mesh (``distributed.mesh.partitioned_over``)
    the call is a ``shard_map`` island, as ring attention is: batch over 'dp',
    heads over 'mp', each device running the kernel on its own slice. An axis
    the enclosing code already mapped by hand, or that does not divide its
    dimension, is left out of the specs (the kernel then sees that dimension
    whole)."""
    from ...distributed.mesh import partitioned_mesh
    from ...ops.pallas.flash_attention import flash_attention_array, flash_attention_tpu

    mesh = partitioned_mesh()
    if mesh is None or mesh.size == 1:
        return flash_attention_tpu(q, k, v, causal=True)

    from jax.sharding import PartitionSpec as P

    from ...distributed.collective import _axis_bound
    from ...distributed.mesh import shard_map_compat

    # the axes no enclosing map took by hand
    free = frozenset(a for a in mesh.axis_names if not _axis_bound(a))

    def axis(name, dim):
        n = mesh.shape.get(name, 1)
        return name if n > 1 and dim % n == 0 and name in free else None

    dp, mp = axis("dp", q.shape[0]), axis("mp", q.shape[2])
    if dp is None and mp is None:
        return flash_attention_tpu(q, k, v, causal=True)
    spec = P(dp, None, mp, None)
    shard_map, check = shard_map_compat()
    if len(free) < len(mesh.axis_names):
        # inside a map that took some axes (the engine's step, manual over
        # 'dp' and not 'mp'): map EVERY axis it left, over the mesh of that
        # map (Mosaic refuses a call while any axis of the mesh is GSPMD's)
        over = {"axis_names": free}
    else:
        over = {"mesh": mesh}
    fn = shard_map(
        lambda a, b, c: flash_attention_array(a, b, c, causal=True),
        in_specs=(spec, spec, spec), out_specs=spec, **over, **check,
    )
    return eager_call("flash_attention_spmd", fn, [q, k, v])


def scaled_dot_product_attention(
    query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False, training=True,
    name=None, impl=None
):
    """q,k,v: (B, T, H, D) — paddle convention. Returns (B, T, H, D).

    Causal/no-mask/no-dropout calls route to the Pallas flash kernel
    (blockwise online softmax, no T×T materialization); everything else uses
    the XLA fused formulation. ``impl``: None (auto) | "exact" (never flash)
    | "flash" (force the Pallas kernel; raises if the call is ineligible).
    A call the kernel was chosen for and then refuses is an error, not a
    reason to run the exact path unseen.
    """
    q, k, v = as_tensor(query), as_tensor(key), as_tensor(value)
    if impl == "flash":
        if not is_causal or attn_mask is not None or (dropout_p and training):
            raise ValueError(
                "impl='flash' requires is_causal=True, no attn_mask, no dropout"
            )
        return _flash(q, k, v)
    if impl is None and _flash_eligible(q, k, is_causal, attn_mask, dropout_p, training):
        return _flash(q, k, v)
    inputs = [q, k, v]
    has_mask = attn_mask is not None
    if has_mask:
        inputs.append(as_tensor(attn_mask))
    use_dropout = bool(dropout_p) and training
    if use_dropout:
        # keep-mask as a data input (same pattern as functional.dropout — a
        # closure-captured key would recompile the dispatch cache every step)
        from ...core import random as random_state
        from ...core.tensor import Tensor

        shape = (q.shape[0], q.shape[2], q.shape[1], k.shape[1])
        keep = jax.random.bernoulli(random_state.next_key(), 1.0 - float(dropout_p), shape)
        inputs.append(Tensor(keep))

    def fn(q, k, v, *rest, is_causal=False, has_mask=False, dropout_p=0.0):
        # (B, T, H, D) → (B, H, T, D)
        qh = jnp.swapaxes(q, 1, 2)
        kh = jnp.swapaxes(k, 1, 2)
        vh = jnp.swapaxes(v, 1, 2)
        scale = 1.0 / math.sqrt(qh.shape[-1])
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
        idx = 0
        if has_mask:
            scores = scores + rest[idx]
            idx += 1
        if is_causal:
            tq, tk = scores.shape[-2], scores.shape[-1]
            causal = jnp.tril(jnp.ones((tq, tk), bool))
            scores = jnp.where(causal, scores, jnp.asarray(-1e30, scores.dtype))
        probs = jax.nn.softmax(scores, axis=-1)
        if dropout_p:
            probs = probs * rest[idx].astype(probs.dtype) / (1.0 - dropout_p)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
        return jnp.swapaxes(out, 1, 2)

    return eager_call(
        "scaled_dot_product_attention", fn, inputs,
        {"is_causal": is_causal, "has_mask": has_mask,
         "dropout_p": float(dropout_p) if use_dropout else 0.0},
    )


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False, name=None):
    """Flash attention — same routing as scaled_dot_product_attention (one
    eligibility gate: Pallas kernel when it wins, XLA exact otherwise)."""
    return scaled_dot_product_attention(query, key, value, is_causal=causal, dropout_p=dropout), None
