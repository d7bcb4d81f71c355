"""Attention of one prefill CALL against a row's context in sequence order
(registry: ``mla_prefill_attention``): ``T`` query positions of a row that
start at ``starts`` see the ``starts`` positions the cache already holds and
their own, causally. Forward only, for serving's chunked prefill of the
latent-attention arch in the EXPANDED form of its mathematics: keys and values
of every head up-projected from the cached latent rows (a K/V head a query
head, a key ``[k_nope | k_rope | 0]`` of 256 lanes, a value of 128). The
ABSORBED form (the queries carried through the key up-projection against ONE
K/V head, the cached row itself) was measured through this kernel and lost by
2.4-2.6 x (PERF.md, PR 47); it is not kept.

The design is ``window_flash``'s (its docstring has what Mosaic is given and
why), with two differences: queries and keys have positions of their own
(``Tq`` queries at ``starts + i`` against ``S`` keys at ``0 .. S - 1``), and
key and value widths differ.

- ``q`` and the result as the projections leave and take them, ``(B, T, H
  Dk)`` and ``(B, T, H Dv)``; ``k`` ``(B, S, H Dk)``, ``v`` ``(B, S, H Dv)``.
  A grid step is (row, head, block of ``block_q`` queries), a head a lane
  slice of its line;
- K and V of one (row, head) RESIDENT in VMEM, ``(S, Dk)`` and ``(S,
  Dv)`` (16.8 + 8.4 MB at 32,768 positions in bfloat16, double-buffered by
  the pipeline): their block index does not change with the query block, so
  each is copied once a head, and the kernel walks the key blocks a query
  block can see in a ``fori_loop`` over VMEM with an online softmax (running
  max, sum and accumulator in float32, probabilities rounded to the values'
  dtype for the second product);
- the key blocks past the block's last query are never visited (the loop's
  bound, from ``starts`` by scalar prefetch), those wholly at or before its
  first query take no mask, and only the diagonal's blocks compare
  positions: two loops over one body. No (queries x context) tensor exists
  anywhere: a block of scores is ``block_q`` by ``block_k`` float32 in
  VMEM;
- true lengths by row (scalar prefetch): a block of queries wholly past
  ``lens`` does no work and gives zeros. Keys behind a row's last real query
  need no mask of their own (they lie behind every real query) but have to
  be FINITE: a probability of exactly 0 times a NaN is a NaN.

The plain form is ``models/mla_moe.attend_call_plain`` (blocks of query rows,
float32 scores of one block). The tests hold the two within the products' own
rounding.

Tunables: ``block_q`` (query positions a grid step) and ``block_k`` (keys a
loop turn). On the v5e, a call of 8,192 queries x 16 heads, milliseconds at 0
/ 8,192 / 16,384 positions cached (PERF.md, PR 47): 512 x 512 3.65 / 9.14 /
14.83, 512 x 1,024 3.69 / 8.91 / 14.33, 256 x 512 3.81 / 9.42 / 15.03, 1,024
x 512 3.78 / 9.63 / 15.50, 512 x 256 4.87 / 13.02 / 21.22; the absorbed form
(one K/V head of 640 / 512 lanes under 16 stacked heads) 8.83 at 0 and 24.08
at 8,192.
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

__all__ = ["mla_prefill_attention", "mla_prefill_attention_key",
           "mla_prefill_attention_takes"]

# a masked score: far below any real one, and finite (``exp(_MASK - m)`` is an
# exact 0 once a row has seen a real score)
_MASK = -0.7 * float(np.finfo(np.float32).max)
# K and V of one (row, head), double-buffered by the pipeline, beside the
# scores of a block under ``vmem_limit_bytes``
_RESIDENT_BYTES = 72 * 2 ** 20
_VMEM_LIMIT = 100 * 2 ** 20


def mla_prefill_attention_key(B, T, S, H, Dk, Dv, dtype) -> tuple:
    return (int(B), int(T), int(S), int(H), int(Dk), int(Dv), str(jnp.dtype(dtype)))


def mla_prefill_attention_takes(S, Dk, Dv, dtype, interpret=None) -> bool:
    """Whether the kernel takes a context of ``S`` positions at key and value
    widths ``Dk`` / ``Dv``: Mosaic slices heads out of a line in multiples of
    128 lanes, and K and V of one head have to fit VMEM whole, twice. The
    interpreter takes anything."""
    if interpret is None:
        interpret = interpret_default()
    resident = 2 * int(S) * (int(Dk) + int(Dv)) * jnp.dtype(dtype).itemsize
    return bool(interpret) or (
        int(Dk) % 128 == 0 and int(Dv) % 128 == 0 and resident <= _RESIDENT_BYTES)


def _kernel(starts_ref, lens_ref, q_ref, k_ref, v_ref, o_ref, *, bq, bk, S,
            scale):
    b, qi = pl.program_id(0), pl.program_id(2)
    start = starts_ref[b]
    qs = qi * bq
    # key blocks [0, hi) hold what this block's queries see; [0, in_hi) of
    # them lie at or before its FIRST query: every query of the block sees
    # every key of theirs
    hi = jnp.minimum((start + qs + bq - 1) // bk + 1, S // bk)
    in_hi = jnp.minimum((start + qs + 1) // bk, hi)
    live = qs < lens_ref[b]  # a block wholly past the row's length: no turn
    hi, in_hi = jnp.where(live, hi, 0), jnp.where(live, in_hi, 0)

    q = q_ref[...]
    qpos = start + qs + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    def turn(masked):
        def body(j, carry):
            m, l, acc = carry
            at = pl.multiple_of(j * bk, bk)
            s = jax.lax.dot_general(
                q, k_ref[pl.ds(at, bk), :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                s = jnp.where(kpos <= qpos, s, _MASK)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            e = jnp.exp(s - m_new)
            v = v_ref[pl.ds(at, bk), :]
            acc = alpha * acc + jax.lax.dot_general(
                e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, alpha * l + e.sum(axis=1, keepdims=True), acc

        return body

    carry = (jnp.full((bq, 1), _MASK, jnp.float32), jnp.zeros((bq, 1), jnp.float32),
             jnp.zeros((bq, v_ref.shape[1]), jnp.float32))
    carry = jax.lax.fori_loop(0, in_hi, turn(False), carry)      # the context
    _, l, acc = jax.lax.fori_loop(in_hi, hi, turn(True), carry)  # the diagonal
    o_ref[...] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "scale", "bq", "bk",
                                             "interpret"))
def _call(q, k, v, starts, lens, *, heads, scale, bq, bk, interpret):
    B, T, HDk = q.shape
    S = k.shape[1]
    Dk, Dv = HDk // heads, v.shape[2] // heads
    kern = functools.partial(_kernel, bq=bq, bk=bk, S=S, scale=scale)
    with kernel_x64_off(interpret):
        return pl.pallas_call(
            kern,
            name="mla_prefill_attention",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B, heads, T // bq),
                in_specs=[
                    pl.BlockSpec((None, bq, Dk), lambda b, h, i, *_: (b, i, h)),
                    pl.BlockSpec((None, S, Dk), lambda b, h, i, *_: (b, 0, h)),
                    pl.BlockSpec((None, S, Dv), lambda b, h, i, *_: (b, 0, h)),
                ],
                out_specs=pl.BlockSpec((None, bq, Dv), lambda b, h, i, *_: (b, i, h)),
            ),
            out_shape=jax.ShapeDtypeStruct((B, T, heads * Dv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(starts, lens, q, k, v)


def mla_prefill_attention(q, k, v, starts, lens, *, heads, scale, config=None,
                          interpret=None):
    """Causal attention of a call's queries against a context in sequence
    order.

    q: (B, T, H Dk), ``heads`` = H query heads side by side, row ``b``'s at
    positions ``starts[b] + 0 .. T - 1``; k: (B, S, H Dk), v: (B, S, H Dv),
    a K/V head a query head at positions ``0 .. S - 1``, every row FINITE; starts,
    lens: (B,) int32, the positions a row has cached and its real queries
    (rows of queries past ``lens`` come back as zeros or as finite garbage,
    and are the caller's padding). ``scale`` multiplies the scores. Returns
    (B, T, H Dv) in ``q``'s dtype: ``mla_moe.attend_call_plain`` within the
    products' rounding."""
    if not _HAS_PALLAS:
        raise RuntimeError("pallas unavailable")
    if interpret is None:
        interpret = interpret_default()
    B, T, HDk = q.shape
    S = k.shape[1]
    heads = int(heads)
    Dk, Dv = HDk // heads, v.shape[2] // heads
    if not mla_prefill_attention_takes(S, Dk, Dv, q.dtype, interpret):
        raise ValueError(
            f"mla_prefill_attention: a context of {S} positions at widths {Dk} / {Dv} "
            f"in {q.dtype}: Mosaic takes widths that are multiples of 128 and K/V of "
            "one head resident in VMEM (mla_prefill_attention_takes); use "
            "mla_moe.attend_call_plain")
    if config is None:
        config = resolve_config(
            "mla_prefill_attention",
            mla_prefill_attention_key(B, T, S, heads, Dk, Dv, q.dtype))
    # blocks are powers of two no longer than what they cut, which is padded
    # to whole blocks (a serving bucket is 16 x a power of two already)
    fit = lambda n, of: min(int(n), 1 << (max(of, 1) - 1).bit_length())
    bq = fit(config.get("block_q", 512), T)
    bk = fit(config.get("block_k", 1024), S)
    Tp, Sp = -(-T // bq) * bq, -(-S // bk) * bk
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0)))
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0)))
    o = _call(q, k, v, jnp.asarray(starts, jnp.int32), jnp.asarray(lens, jnp.int32),
              heads=heads, scale=float(scale), bq=bq, bk=bk,
              interpret=bool(interpret))
    return o[:, :T]


def _runner(key):
    """One call a row, its context half cached, seeded."""
    B, T, S, H, Dk, Dv, dtype = key
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, T, H * Dk), dtype)
    k = jnp.asarray(rng.randn(B, S, H * Dk), dtype)
    v = jnp.asarray(rng.randn(B, S, H * Dv), dtype)
    starts = jnp.full((B,), max(S - T, 0) // 2, jnp.int32)
    lens = jnp.full((B,), T, jnp.int32)

    def make(config):
        fn = jax.jit(functools.partial(mla_prefill_attention, heads=H,
                                       scale=Dk ** -0.5, config=config))
        return lambda: fn(q, k, v, starts, lens)

    return make


register_kernel(
    "mla_prefill_attention",
    defaults={"block_q": 512, "block_k": 1024},
    space={"block_q": (256, 512, 1024), "block_k": (256, 512, 1024)},
    runner=_runner,
)
