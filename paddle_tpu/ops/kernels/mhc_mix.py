"""The mixing maps of a manifold-constrained hyper-connection (kernel
``mhc_mix``; nothing to tune, so not in the registry): from a token's ``n (2 + n)`` pre-activations to ``H_pre =
sigmoid``, ``H_post = 2 sigmoid`` and ``H_res``, the clipped exponential made
doubly stochastic by Sinkhorn's column-then-row normalisation, in float32.

Twenty rounds over an ``n x n`` matrix a token are some six hundred tiny
reductions and divisions a call in XLA, sixteen calls a decode step of an
eight-layer model; here they are ONE kernel: the tokens lie along the lanes,
each of the matrix's ``n^2`` entries is a row of the block, a column sum is
``n - 1`` additions of rows, and nothing leaves VMEM between the rounds.

The plain form is ``models/mla_moe.sinkhorn_plain``; the two agree to float32
rounding (tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..pallas import interpret_default, kernel_x64_off

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

__all__ = ["mhc_mix"]

LANES = 128


def _mix_kernel(z_ref, o_ref, *, n, iters, eps, lo, hi):
    row = lambda r: z_ref[pl.ds(r, 1), :]
    for r in range(n):
        o_ref[pl.ds(r, 1), :] = jax.nn.sigmoid(row(r))
        o_ref[pl.ds(n + r, 1), :] = 2.0 * jax.nn.sigmoid(row(n + r))
    m = [[jnp.exp(jnp.clip(row(2 * n + i * n + j), lo, hi)) for j in range(n)]
         for i in range(n)]

    def one_round(_, m):
        m = [list(r) for r in m]
        for j in range(n):  # columns
            s = functools.reduce(lambda a, b: a + b, [m[i][j] for i in range(n)]) + eps
            for i in range(n):
                m[i][j] = m[i][j] / s
        for i in range(n):  # rows
            s = functools.reduce(lambda a, b: a + b, m[i]) + eps
            m[i] = [v / s for v in m[i]]
        return tuple(tuple(r) for r in m)

    m = jax.lax.fori_loop(0, iters, one_round, tuple(tuple(r) for r in m))
    for i in range(n):
        for j in range(n):
            o_ref[pl.ds(2 * n + i * n + j, 1), :] = m[i][j]


@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "clamp",
                                             "interpret"))
def _mix_call(z, *, n, iters, eps, clamp, interpret):
    lead, K = z.shape[:-1], n * (2 + n)
    zt = z.reshape(-1, K).T.astype(jnp.float32)  # (K, tokens): tokens on lanes
    T = zt.shape[1]
    tl = min(512, -(-T // LANES) * LANES)
    Tp, Kp = -(-T // tl) * tl, -(-K // 8) * 8
    zt = jnp.pad(zt, ((0, Kp - K), (0, Tp - T)))
    with kernel_x64_off(interpret):
        out = pl.pallas_call(
            functools.partial(_mix_kernel, n=n, iters=iters, eps=eps,
                              lo=clamp[0], hi=clamp[1]),
            name="mhc_mix",
            grid=(Tp // tl,),
            in_specs=[pl.BlockSpec((Kp, tl), lambda t: (0, t))],
            out_specs=pl.BlockSpec((Kp, tl), lambda t: (0, t)),
            out_shape=jax.ShapeDtypeStruct((Kp, Tp), jnp.float32),
            interpret=interpret,
        )(zt)
    out = out[:K, :T].T
    return (out[:, :n].reshape(lead + (n,)),
            out[:, n:2 * n].reshape(lead + (n,)),
            out[:, 2 * n:].reshape(lead + (n, n)))


def mhc_mix(z, n, iters, eps, clamp, interpret=None):
    """``(H_pre (..., n), H_post (..., n), H_res (..., n, n))`` float32 from
    the maps' pre-activations ``z`` (..., n (2 + n)): ``[pre | post | res row
    by row]``, the scalars and biases already applied."""
    if not _HAS_PALLAS:
        raise RuntimeError("pallas unavailable")
    if interpret is None:
        interpret = interpret_default()
    return _mix_call(z, n=int(n), iters=int(iters), eps=float(eps),
                     clamp=(float(clamp[0]), float(clamp[1])),
                     interpret=bool(interpret))

