"""The recurrence of a selective state-space layer (Mamba-1, arXiv:2312.00752;
kernels ``selective_scan`` and ``state_update``; nothing to tune, so not in
the registry). A token ``t`` of a row moves the layer's state ``S`` (state
size ``N`` x channels ``d_i``, float32) and reads it:

    S_t = exp(Delta_t A) * S_{t-1} + B_t (Delta_t c_t)^T
    y_t = S_t^T C_t + D * c_t

with ``Delta_t, c_t`` (d_i), ``B_t, C_t`` (N), ``A`` (N, d_i) negative, ``D``
(d_i). The channels lie along the lanes and the ``N`` state rows along the
sublanes, so a state is ``N / 8`` whole vector registers a 128 channels and
nothing is padded (``(d_i, N)`` would pad 16 lanes to 128).

- ``selective_scan``: a whole prompt, from the zero state. The grid is (row,
  channel chunk, token chunk); the token chunks of one (row, channel chunk)
  run in order and carry ``S`` in VMEM, a loop over the chunk's tokens inside.
  A position whose ``Delta`` is 0 leaves the state as it was (``exp(0) = 1``,
  nothing added), which is how the caller stops a padded row at its true
  length. Returns every ``y_t`` and the last state.
- ``state_update``: one token a row against the state POOL ``(layers, slots,
  N, d_i)``, updated in place: the layer and each row's slot arrive by scalar
  prefetch and pick the block, so no ``pool[layer, slots]`` is gathered or
  scattered.

``B_t`` and ``C_t`` multiply along the sublanes. Mosaic has no cheap way from
a lane vector to a sublane vector, so the caller's ``(.., N)`` is broadcast
over one 128-lane tile outside the kernel (``(.., N, 128)``, 8 KB a token) and
repeated along the lanes inside, which is a placement of registers.

The plain forms are ``selective_scan_plain`` (a ``lax.scan`` over the tokens)
and ``state_update_plain``; kernel and plain form agree to float32 rounding
(tests/test_phi4flash.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..pallas import interpret_default, kernel_x64_off

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

__all__ = ["selective_scan", "selective_scan_plain", "state_update",
           "state_update_plain"]

LANES = 128
F32 = jnp.float32


# -- the plain forms -----------------------------------------------------------

def _one_token(S, dt, c, Bm, Cm, A, D):
    """One step of the recurrence for a batch of rows: ``S`` (B, N, d_i),
    ``dt``/``c`` (B, d_i), ``Bm``/``Cm`` (B, N). Returns ``(S, y)``."""
    S = jnp.exp(dt[:, None, :] * A) * S + Bm[:, :, None] * (dt * c)[:, None, :]
    return S, jnp.sum(S * Cm[:, :, None], axis=1) + D * c


def selective_scan_plain(dt, c, Bm, Cm, A, D):
    """``dt``/``c`` (B, T, d_i), ``Bm``/``Cm`` (B, T, N), ``A`` (N, d_i), ``D``
    (d_i), all float32, from the zero state. Returns ``(y (B, T, d_i), S (B,
    N, d_i))``."""
    def body(S, xs):
        S, y = _one_token(S, *xs, A, D)
        return S, y

    S0 = jnp.zeros((dt.shape[0],) + A.shape, F32)
    S, y = lax.scan(body, S0, tuple(jnp.swapaxes(a, 0, 1)
                                    for a in (dt, c, Bm, Cm)))
    return jnp.swapaxes(y, 0, 1), S


def state_update_plain(pool, layer, slots, dt, c, Bm, Cm, A, D):
    """One token a row against ``pool`` (layers, slots, N, d_i): ``dt``/``c``
    (B, d_i), ``Bm``/``Cm`` (B, N). Returns ``(pool, y (B, d_i))``."""
    S, y = _one_token(pool[layer, slots], dt, c, Bm, Cm, A, D)
    return pool.at[layer, slots].set(S), y


# -- the kernels ---------------------------------------------------------------

def _lanes(tile, width):
    """A (N, 128) tile repeated along the lanes to (N, width)."""
    return tile if width == tile.shape[1] else jnp.concatenate(
        [tile] * (width // tile.shape[1]), axis=1)


def _over_a_tile(x):
    """(.., N) -> (.., N, lanes of one tile): what the kernels take of B, C."""
    return jnp.broadcast_to(x[..., None], x.shape + (LANES,))


def _scan_kernel(dt_ref, c_ref, b_ref, cm_ref, a_ref, d_ref, y_ref, s_ref,
                 state, *, tokens):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    A, D = a_ref[...], d_ref[...]
    width = A.shape[1]

    def body(i, S):
        dt, c = dt_ref[pl.ds(i, 1), :], c_ref[pl.ds(i, 1), :]
        S = jnp.exp(dt * A) * S + _lanes(b_ref[i], width) * (dt * c)
        y_ref[pl.ds(i, 1), :] = jnp.sum(
            S * _lanes(cm_ref[i], width), axis=0, keepdims=True) + D * c
        return S

    S = lax.fori_loop(0, tokens, body, state[...])
    state[...] = S

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        s_ref[...] = S


def _chunk(n, most):
    """The largest divisor of ``n`` that is at most ``most`` and a multiple
    of a lane tile, or ``n`` itself where it has none (the interpreter's
    small sizes)."""
    for c in range(min(most, n) // LANES * LANES, 0, -LANES):
        if n % c == 0:
            return c
    return n


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_call(dt, c, Bm, Cm, A, D, *, interpret):
    B, T, d_i = dt.shape
    N = A.shape[0]
    dc, tc = _chunk(d_i, 512), min(T, 128)
    if T % tc:
        raise ValueError(f"selective_scan: {T} tokens are not whole chunks of {tc}")
    seq = lambda b, j, t: (b, t, j)
    tile = lambda b, j, t: (b, t, 0, 0)
    with kernel_x64_off(interpret):
        return pl.pallas_call(
            functools.partial(_scan_kernel, tokens=tc),
            name="selective_scan",
            grid=(B, d_i // dc, T // tc),
            in_specs=[pl.BlockSpec((None, tc, dc), seq),
                      pl.BlockSpec((None, tc, dc), seq),
                      pl.BlockSpec((None, tc, N, LANES), tile),
                      pl.BlockSpec((None, tc, N, LANES), tile),
                      pl.BlockSpec((N, dc), lambda b, j, t: (0, j)),
                      pl.BlockSpec((1, dc), lambda b, j, t: (0, j))],
            out_specs=[pl.BlockSpec((None, tc, dc), seq),
                       pl.BlockSpec((None, N, dc), lambda b, j, t: (b, 0, j))],
            out_shape=[jax.ShapeDtypeStruct((B, T, d_i), F32),
                       jax.ShapeDtypeStruct((B, N, d_i), F32)],
            scratch_shapes=[pltpu.VMEM((N, dc), F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(dt, c, _over_a_tile(Bm), _over_a_tile(Cm), A, D.reshape(1, d_i))


def selective_scan(dt, c, Bm, Cm, A, D, interpret=None):
    """``selective_scan_plain`` as one kernel: the same operands (float32),
    the same ``(y, S)``. ``T`` is a multiple of 128 or less than it."""
    if not _HAS_PALLAS:
        raise RuntimeError("pallas unavailable")
    if interpret is None:
        interpret = interpret_default()
    return tuple(_scan_call(dt.astype(F32), c.astype(F32), Bm.astype(F32),
                            Cm.astype(F32), A.astype(F32), D.astype(F32),
                            interpret=bool(interpret)))


def _update_kernel(layer_ref, slots_ref, pool_ref, dt_ref, c_ref, b_ref,
                   cm_ref, a_ref, d_ref, out_ref, y_ref):
    A, dt, c = a_ref[...], dt_ref[...], c_ref[...]
    width = A.shape[1]
    S = jnp.exp(dt * A) * pool_ref[...] + _lanes(b_ref[...], width) * (dt * c)
    out_ref[...] = S
    y_ref[...] = jnp.sum(S * _lanes(cm_ref[...], width), axis=0,
                         keepdims=True) + d_ref[...] * c


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_call(pool, layer, slots, dt, c, Bm, Cm, A, D, *, interpret):
    B, d_i = dt.shape
    N = A.shape[0]
    state = lambda b, layer, slots: (layer[0], slots[b], 0, 0)
    row = lambda b, *_: (b, 0, 0)
    whole = lambda b, *_: (0, 0)
    with kernel_x64_off(interpret):
        pool, y = pl.pallas_call(
            _update_kernel,
            name="state_update",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B,),
                in_specs=[pl.BlockSpec((None, None, N, d_i), state),
                          pl.BlockSpec((None, 1, d_i), row),
                          pl.BlockSpec((None, 1, d_i), row),
                          pl.BlockSpec((None, N, LANES), row),
                          pl.BlockSpec((None, N, LANES), row),
                          pl.BlockSpec((N, d_i), whole),
                          pl.BlockSpec((1, d_i), whole)],
                out_specs=[pl.BlockSpec((None, None, N, d_i), state),
                           pl.BlockSpec((None, 1, d_i), row)],
            ),
            out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                       jax.ShapeDtypeStruct((B, 1, d_i), F32)],
            # the pool is updated where it lies (operand 2, after the two
            # prefetched scalars)
            input_output_aliases={2: 0},
            # rows that pad a bucket share the trash slot: in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(layer, slots, pool, dt[:, None], c[:, None], _over_a_tile(Bm),
          _over_a_tile(Cm), A, D.reshape(1, d_i))
    return pool, y[:, 0]


def state_update(pool, layer, slots, dt, c, Bm, Cm, A, D, interpret=None):
    """``state_update_plain`` as one kernel over the pool in place: ``pool``
    (layers, slots, N, d_i) float32, ``layer`` an int or int32 scalar,
    ``slots`` (B,) int32."""
    if not _HAS_PALLAS:
        raise RuntimeError("pallas unavailable")
    if interpret is None:
        interpret = interpret_default()
    return tuple(_update_call(
        pool, jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(slots, jnp.int32), dt.astype(F32), c.astype(F32),
        Bm.astype(F32), Cm.astype(F32), A.astype(F32), D.astype(F32),
        interpret=bool(interpret)))
