"""Forward attention of whole prompts over a BAND of keys (registry:
``window_flash``): causal, grouped, with an optional lower bound ``window`` (a
query at ``p`` sees ``p'`` with ``0 <= p - p' < window``), for serving's
prefill. Forward only: nothing is kept for a backward pass, and
``ops/pallas/flash_attention.py``, the training kernel, is not touched.

What Mosaic is given:

- ``q`` and the result as the projections leave and take them, ``(B, T, H
  D)``, and ``k`` / ``v`` ``(B, T, G D)``: a grid step is (row, K/V head,
  block of ``block_q`` query positions) and takes the ``H / G`` query heads of
  its K/V head as lane slices of ONE block (the grouped index map: a K/V head
  is read once for the eight heads it serves, and nothing is repeated or
  transposed in HBM). The heads are stacked into one ``(H / G x block_q, D)``
  matrix in VMEM, so every product has ``H / G`` times the block's rows (512
  at eight heads a K/V head and the default block);
- K and V of one (row, K/V head) RESIDENT in VMEM, ``(T, D)`` each (2 MB at
  8,192 positions of 128 in bfloat16): their block index does not change
  with the query block, so each is copied once a head, and the kernel walks
  the key blocks of its band in a ``fori_loop`` over VMEM with the online
  softmax of ``flash_attention._fwd_kernel`` (running max, sum and
  accumulator in float32, probabilities rounded to the values' dtype for the
  second product). A streamed K/V axis in the grid would need an index map
  clamped to the band, which Mosaic does not pipeline
  (``flash_attention._kv_index_map``: 2.8 x slower); :func:`window_flash_takes`
  says up to which ``T`` the two fit;
- the key blocks wholly OUTSIDE the band are never visited (the loop's bounds:
  from the block that holds ``p - window + 1`` of the block's first query to
  the block that holds its last query), the blocks wholly INSIDE take no mask,
  and only the edge blocks (the window's low edge, the diagonal) compare
  positions: three loops over one body;
- true lengths by row (scalar prefetch): a block of queries wholly past
  ``lens`` does no work and gives zeros. Keys past ``lens`` need no mask: they
  lie behind every real query.

The plain form is ``models/afmoe.prompt_attention_plain`` (blocks of query
rows, float32 scores of one block). The tests hold the two within the
products' own rounding.

Tunables: ``block_q`` (query positions a grid step, times ``H / G`` rows a
product) and ``block_k`` (keys a loop turn). On the v5e at 1 x 8,192 x 32 heads
of 128 (PERF.md, PR 45): 64 x 512 reads 3.59 ms a window layer and 5.52 ms a
full one, 128 x 512 3.81 / 5.93, 256 x 256 4.08 / 6.71: the narrower the block
of queries, the less of the diagonal's and the low edge's blocks is masked
work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..pallas import interpret_default, kernel_x64_off
from .registry import register_kernel, resolve_config

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

__all__ = ["window_flash", "window_flash_key", "window_flash_takes"]

# a masked score: far below any real one, and finite (``exp(_MASK - m)`` is an
# exact 0 once a row has seen a real score)
_MASK = -0.7 * float(np.finfo(np.float32).max)
# K and V of one (row, K/V head) stay in VMEM whole, double-buffered by the
# pipeline: 4 x this beside the scores
_RESIDENT_BYTES = 8 * 2 ** 20


def window_flash_key(B, T, H, G, D, window, dtype) -> tuple:
    return (int(B), int(T), int(H), int(G), int(D), int(window or 0),
            str(jnp.dtype(dtype)))


def window_flash_takes(T, D, dtype, interpret=None) -> bool:
    """Whether the kernel takes prompts of ``T`` positions at head width
    ``D``: Mosaic slices heads out of a line in multiples of 128 lanes, and K
    and V of one head have to fit VMEM whole (16,384 positions of 128 in
    bfloat16). The interpreter takes anything."""
    if interpret is None:
        interpret = interpret_default()
    return bool(interpret) or (
        int(D) % 128 == 0 and int(T) * int(D) * jnp.dtype(dtype).itemsize <= _RESIDENT_BYTES // 2)


def _kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, *, bq, bk, rep, D, window, scale):
    b, qi = pl.program_id(0), pl.program_id(2)
    qs = qi * bq
    qe = qs + bq - 1
    R = rep * bq
    # key blocks [lo, hi) hold the band of this block's queries; [in_lo,
    # in_hi) of them lie inside it for EVERY query of the block
    hi = qe // bk + 1
    in_hi = (qs + 1) // bk
    if window is None:
        lo = in_lo = jnp.int32(0)
    else:
        lo = jnp.maximum(qs - window + 1, 0) // bk
        in_lo = jnp.minimum((jnp.maximum(qe - window + 1, 0) + bk - 1) // bk, hi)
    in_hi = jnp.clip(in_hi, in_lo, hi)
    live = qs < lens_ref[b]  # a block wholly past the row's length: no turn
    lo, in_lo, in_hi, hi = (jnp.where(live, x, 0) for x in (lo, in_lo, in_hi, hi))

    # the query heads of this K/V head, stacked: row r * bq + i is head r at
    # position qs + i
    q = jnp.concatenate([q_ref[:, r * D:(r + 1) * D] for r in range(rep)], axis=0)
    qpos = qs + jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) % bq

    def turn(masked):
        def body(j, carry):
            m, l, acc = carry
            at = pl.multiple_of(j * bk, bk)
            s = jax.lax.dot_general(
                q, k_ref[pl.ds(at, bk), :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (R, bk), 1)
                sees = kpos <= qpos
                if window is not None:
                    sees = sees & (qpos - kpos < window)
                s = jnp.where(sees, s, _MASK)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            e = jnp.exp(s - m_new)
            v = v_ref[pl.ds(at, bk), :]
            acc = alpha * acc + jax.lax.dot_general(
                e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, alpha * l + e.sum(axis=1, keepdims=True), acc

        return body

    carry = (jnp.full((R, 1), _MASK, jnp.float32), jnp.zeros((R, 1), jnp.float32),
             jnp.zeros((R, D), jnp.float32))
    carry = jax.lax.fori_loop(lo, in_lo, turn(True), carry)      # the low edge
    carry = jax.lax.fori_loop(in_lo, in_hi, turn(False), carry)  # inside the band
    _, l, acc = jax.lax.fori_loop(in_hi, hi, turn(True), carry)  # the diagonal
    out = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)
    for r in range(rep):
        o_ref[:, r * D:(r + 1) * D] = out[r * bq:(r + 1) * bq]


@functools.partial(jax.jit, static_argnames=("heads", "window", "bq", "bk", "interpret"))
def _call(q, k, v, lens, *, heads, window, bq, bk, interpret):
    B, T, HD = q.shape
    D = HD // heads
    G = k.shape[2] // D
    rep = heads // G
    kern = functools.partial(_kernel, bq=bq, bk=bk, rep=rep, D=D, window=window,
                             scale=float(D) ** -0.5)
    with kernel_x64_off(interpret):
        return pl.pallas_call(
            kern,
            name="window_flash",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B, G, T // bq),
                in_specs=[
                    pl.BlockSpec((None, bq, rep * D), lambda b, g, i, lens: (b, i, g)),
                    pl.BlockSpec((None, T, D), lambda b, g, i, lens: (b, 0, g)),
                    pl.BlockSpec((None, T, D), lambda b, g, i, lens: (b, 0, g)),
                ],
                out_specs=pl.BlockSpec((None, bq, rep * D),
                                       lambda b, g, i, lens: (b, i, g)),
            ),
            out_shape=jax.ShapeDtypeStruct((B, T, HD), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel"),
                vmem_limit_bytes=96 * 2 ** 20),
            interpret=interpret,
        )(lens, q, k, v)


def window_flash(q, k, v, lens, *, heads, window=None, config=None, interpret=None):
    """Causal grouped attention of whole prompts.

    q: (B, T, H D), ``heads`` = H query heads side by side; k / v: (B, T, G
    D), K/V head ``g`` serving query heads ``g H / G .. (g + 1) H / G - 1``;
    lens: (B,) int32 true lengths (rows of queries past them come back as
    zeros or as finite garbage, and are the caller's padding); ``window``: a
    query at ``p`` sees ``p'`` with ``0 <= p - p' < window`` (None: every ``p'
    <= p``). Scores are scaled by ``D ** -0.5``. Returns (B, T, H D) in
    ``q``'s dtype: ``afmoe.prompt_attention_plain`` within the products'
    rounding."""
    if not _HAS_PALLAS:
        raise RuntimeError("pallas unavailable")
    if interpret is None:
        interpret = interpret_default()
    B, T, HD = q.shape
    D = HD // int(heads)
    G = k.shape[2] // D
    if not window_flash_takes(T, D, q.dtype, interpret):
        raise ValueError(
            f"window_flash: {T} positions of {D}-wide heads in {q.dtype}: Mosaic takes "
            "head widths that are multiples of 128 and K/V of one head resident in "
            "VMEM (window_flash_takes); use afmoe.prompt_attention_plain")
    if config is None:
        config = resolve_config(
            "window_flash", window_flash_key(B, T, heads, G, D, window, q.dtype))
    # blocks are powers of two no longer than the prompt, which is padded to
    # whole blocks of both (a serving bucket is 16 x a power of two already)
    fit = lambda n: min(int(n), 1 << (max(T, 1) - 1).bit_length())
    bq, bk = fit(config.get("block_q", 64)), fit(config.get("block_k", 512))
    Tp = -(-T // max(bq, bk)) * max(bq, bk)
    if Tp != T:
        pad = lambda x: jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0)))
        q, k, v = pad(q), pad(k), pad(v)
    o = _call(q, k, v, jnp.asarray(lens, jnp.int32), heads=int(heads),
              window=None if window is None else int(window), bq=bq, bk=bk,
              interpret=bool(interpret))
    return o[:, :T]


def _runner(key):
    """One prompt a row at its full length, seeded."""
    B, T, H, G, D, window, dtype = key
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, T, H * D), dtype)
    k = jnp.asarray(rng.randn(B, T, G * D), dtype)
    v = jnp.asarray(rng.randn(B, T, G * D), dtype)
    lens = jnp.full((B,), T, jnp.int32)

    def make(config):
        fn = jax.jit(functools.partial(window_flash, heads=H, window=window or None,
                                       config=config))
        return lambda: fn(q, k, v, lens)

    return make


register_kernel(
    "window_flash",
    defaults={"block_q": 64, "block_k": 512},
    space={"block_q": (64, 128, 256), "block_k": (256, 512, 1024)},
    runner=_runner,
)
