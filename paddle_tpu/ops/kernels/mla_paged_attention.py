"""Block-table decode attention over the paged LATENT pool (registry:
``mla_paged_attention``): latent attention in its absorbed form.

The pool ``(L, NB, BS, W)`` holds one row a cached token a layer: the normed
latent (``R`` numbers), the rotary key, zeros up to ``W``, a multiple of
Mosaic's 128-lane tile (576 -> 640). That one row is the KEY of every head
(the query arrives already carried through the key up-projection, zero over
the padding) and, in its first ``R`` lanes, the VALUE of every head. So a
row's context crosses from HBM once for all heads: two dots a chunk, ``(H, W)
x (C BS, W)^T`` and the probabilities against the chunk's leading ``R`` lanes.

The design is ``paged_attention``'s (its docstring has what Mosaic is given
and why): the pool whole in ``memory_space=ANY``, layer / tables / positions
by scalar prefetch, only the row's LIVE blocks copied, into a double-buffered
VMEM chunk, the next chunk's copies (of the next row too) started before the
current one is waited for, an online softmax in float32, one batch row a grid
step. One pool instead of two and no KV groups: every column of a chunk is a
token of every head. A dead row (``pos = 0``, table at the trash block) costs
one block.

The copy schedule of a chunk (PR 43). A copy is one block, 16 tokens: 20 KB,
25 ns of the chip's HBM. Timed alone at the serving cell's shape (PERF.md
section 6), the copies themselves hide behind the dots (a chunk's copies land
0.2 us + 24 ns a block after their start, whoever issues them); what stands
BESIDE the dots, on the one instruction stream, is the scalar work of
starting a copy (a table entry, the address arithmetic, two bounds checks)
and of waiting for it, a block at a time. So a FULL chunk (all ``C`` blocks
live: every chunk of a row but its last) is started as straight-line code
(the loop's body unrolled ``C`` times when the kernel is lowered, so that the
scheduler packs the ``C`` table entries, addresses and descriptors as it
likes) and is waited for ONCE: a DMA semaphore counts bytes, so one wait on
a descriptor whose destination is the whole ``buf.at[slot]`` takes the ``C``
blocks' bytes off ``sems.at[slot]``. One chunk at most is in flight a slot,
so the count is that chunk's alone, also across the grid step that hands the
slot to the next row. A PARTIAL chunk (a row's last, ``live < C``) keeps the
two loops of dynamic length, one start and one wait a live block; what it
leaves unwritten of the buffer holds an earlier chunk's rows or the zeros of
the first grid step, finite either way, and is masked. The copies, their
order and the arithmetic are the same in both: at one ``blocks_per_chunk``
the outputs are bit for bit those of the schedule with a loop a block
(``tests/test_mla_paged_attention.py`` keeps that body).

Contract with the plain form (``models/mla_moe.attend_absorbed_plain``, the
gather of the row's table): the caller scatters the step's fresh row into the
pool BEFORE the call; probabilities are rounded to the pool's dtype before
the second dot, as the gather's einsum rounds them; the online softmax sums
in chunk order, so agreement is within ``2e-5 * max|plain| + 2e-6`` for
float32 inputs (tests), not bit for bit.

Tunable: ``blocks_per_chunk`` (C). The dots of a chunk are one dependent
chain (scores, row maximum, exponentials, the second dot, the carry), about
0.3 us however many tokens it holds, so a wider chunk is cheaper a block: 16
since PR 43.
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

__all__ = ["mla_paged_attention", "mla_paged_attention_key",
           "blocks_per_chunk", "chunk_counts"]

_MASK = -0.7 * float(np.finfo(np.float32).max)


def mla_paged_attention_key(B, MB, BS, H, W, R, dtype) -> tuple:
    return (int(B), int(MB), int(BS), int(H), int(W), int(R),
            str(jnp.dtype(dtype)))


def blocks_per_chunk(key, config=None) -> int:
    """The ``C`` a call of shape ``key`` (``mla_paged_attention_key``) copies
    and multiplies at a time: the registry's, at most a row's table."""
    if config is None:
        config = resolve_config("mla_paged_attention", key)
    return max(1, min(int(config.get("blocks_per_chunk", 16)), key[1]))


def chunk_counts(pos, BS, C) -> dict:
    """What the kernel's copy schedule does, a layer, with rows that write
    positions ``pos`` (the live rows of a step: a row that pads the bucket
    costs one block and is the caller's to leave out), on the host:
    ``latent_blocks`` copied, ``latent_chunks`` they come in and
    ``latent_full_chunks``, those of them started as straight-line code and
    waited for once."""
    blocks = np.asarray(pos, np.int64) // BS + 1
    return {"latent_blocks": int(blocks.sum()),
            "latent_chunks": int((-(-blocks // C)).sum()),
            "latent_full_chunks": int((blocks // C).sum())}


def _mla_kernel(layer_ref, tables_ref, pos_ref, q_ref, pool_ref, o_ref, buf,
                sems, slot_ref, *, B, MB, BS, C, R, scale):
    b = pl.program_id(0)
    layer = layer_ref[0]

    def live_blocks(b, c):
        return jnp.clip(pos_ref[b] // BS + 1 - c * C, 0, C)

    def block_copy(b, c, slot, j):
        bid = tables_ref[b * MB + c * C + j]
        return pltpu.make_async_copy(pool_ref.at[layer, bid], buf.at[slot, j],
                                     sems.at[slot])

    def for_blocks(n, do, unroll=False):
        def body(j, carry):
            do(j)
            return carry

        jax.lax.fori_loop(0, n, body, 0, unroll=unroll)

    def start(b, c, slot):
        live = live_blocks(b, c)
        issue = lambda j: block_copy(b, c, slot, j).start()

        @pl.when(live == C)
        def _():
            # a full chunk: straight-line code. ONE traced body, unrolled C
            # times when it is lowered: a decode program's set-up is tracing
            for_blocks(C, issue, unroll=True)

        @pl.when(live < C)
        def _():
            for_blocks(live, issue)

    def wait(b, c, slot):
        live = live_blocks(b, c)

        @pl.when(live == C)
        def _():
            # the semaphore counts bytes: ONE wait for the chunk's C blocks
            # (the descriptor is only its destination's size; nothing starts it)
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sems.at[slot]).wait()

        @pl.when(live < C)
        def _():
            for_blocks(live, lambda j: block_copy(b, c, slot, j).wait())

    @pl.when(b == 0)
    def _():
        # a partial chunk leaves blocks of the buffer unwritten; their
        # probabilities are an exact 0, which only a finite value keeps at 0
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        start(0, 0, 0)

    H, W = q_ref.shape[1], q_ref.shape[2]
    N = C * BS
    pos = pos_ref[b]
    n_chunks = (pos // BS + C) // C  # ceil((pos // BS + 1) / C)
    q = q_ref[0]
    tok = jax.lax.broadcasted_iota(jnp.int32, (H, N), 1)

    def chunk_body(c, carry):
        slot, m, l, acc = carry
        nxt = 1 - slot

        # what is multiplied next: this row's next chunk, or the next row's
        # first (ONE site: the kernel is lowered for every decode bucket)
        last = c + 1 >= n_chunks

        @pl.when(jnp.logical_or(jnp.logical_not(last), b + 1 < B))
        def _():
            start(jnp.where(last, b + 1, b), jnp.where(last, 0, c + 1), nxt)

        wait(b, c, slot)
        kv = buf[slot].reshape(N, W)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(tok + c * N <= pos, s, _MASK)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        e = jnp.exp(s - m_new)
        l = alpha * l + e.sum(axis=1, keepdims=True)
        acc = alpha * acc + jax.lax.dot_general(
            e.astype(kv.dtype), kv[:, :R], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return nxt, m_new, l, acc

    # the buffer in flight is carried from one row (grid step) to the next
    slot, _, l, acc = jax.lax.fori_loop(
        0, n_chunks, chunk_body,
        (slot_ref[0], jnp.full((H, 1), _MASK, jnp.float32),
         jnp.zeros((H, 1), jnp.float32), jnp.zeros((H, R), jnp.float32)))
    slot_ref[0] = slot
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("R", "scale", "C", "interpret"))
def _mla_call(q, pool, layer, tables, pos, *, R, scale, C, interpret):
    B, H, W = q.shape
    L, NB, BS, _ = pool.shape
    MB = tables.shape[1]
    # queries padded to whole sublane tiles of the widest dtype Mosaic packs
    Hp = -(-H // 16) * 16
    qp = jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0))) if Hp != H else q
    kern = functools.partial(_mla_kernel, B=B, MB=MB, BS=BS, C=C, R=R,
                             scale=scale)
    with kernel_x64_off(interpret):
        out = pl.pallas_call(
            kern,
            name="mla_paged_attention",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(B,),
                in_specs=[
                    pl.BlockSpec((1, Hp, W), lambda b, *_: (b, 0, 0)),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec((1, Hp, R), lambda b, *_: (b, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, C, BS, W), pool.dtype),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SMEM((1,), jnp.int32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((B, Hp, R), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(layer, tables.reshape(B * MB), pos, qp, pool)
    return out[:, :H]


def mla_paged_attention(q, pool, layer, tables, pos, latent_width, scale,
                        config=None, interpret=None):
    """One decode step's latent attention of layer ``layer``.

    q: (B, H, W) absorbed queries (zero over the row's padding); pool:
    (L, NB, BS, W), the WHOLE pool; tables: (B, MB) int32; pos: (B,) int32
    write positions; ``latent_width`` R: the leading lanes of a row that are
    its value; ``scale``: the softmax scale. Returns (B, H, R): the
    probability-weighted latent of each head, before the value
    up-projection."""
    if not _HAS_PALLAS:
        raise RuntimeError("pallas unavailable")
    if interpret is None:
        interpret = interpret_default()
    B, H, W = q.shape
    BS, MB = pool.shape[2], tables.shape[1]
    if not interpret and (W % 128 or latent_width % 128):
        raise ValueError(
            f"mla_paged_attention: Mosaic takes rows and values in whole "
            f"128-lane tiles, not {W} and {latent_width}")
    C = blocks_per_chunk(
        mla_paged_attention_key(B, MB, BS, H, W, latent_width, q.dtype), config)
    return _mla_call(
        q, pool, jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(tables, jnp.int32), jnp.asarray(pos, jnp.int32),
        R=int(latent_width), scale=float(scale), C=C,
        interpret=bool(interpret))


def _runner(key):
    """Synthetic pool/tables at the bucketed shape for measured search: row
    ``b`` holds ``1 + b % MB`` live blocks."""
    B, MB, BS, H, W, R, dtype = key
    rng = np.random.RandomState(0)
    NB = max(B * MB + 1, 2)
    pool = jnp.asarray(rng.randn(1, NB, BS, W), dtype)
    tables = np.zeros((B, MB), np.int32)
    pos = np.zeros((B,), np.int32)
    for b in range(B):
        n_live = 1 + (b % MB)
        pos[b] = n_live * BS - 1
        tables[b, :n_live] = 1 + b * MB + np.arange(n_live)
    tables, pos = jnp.asarray(tables), jnp.asarray(pos)
    q = jnp.asarray(rng.randn(B, H, W), dtype)

    def make(config):
        fn = jax.jit(functools.partial(
            mla_paged_attention, latent_width=R, scale=W ** -0.5, config=config))
        return lambda: fn(q, pool, 0, tables, pos)

    return make


register_kernel(
    "mla_paged_attention",
    # 16 since PR 43 (8 until then): measured on the chip at the serving
    # cell's shape, PERF.md section 6
    defaults={"blocks_per_chunk": 16},
    space={"blocks_per_chunk": (4, 8, 16)},
    runner=_runner,
)
