"""Block-table decode attention over the paged KV pool (registry:
``paged_attention``).

One decode step's attention read, straight from the serving engine's 5-D
pools ``(L, NB, BS, KV, D)`` by block table. Nothing is gathered: for each
batch row the kernel copies only the blocks the row has LIVE
(``pos // BS + 1`` of them; table columns behind that, which hold the trash
block, are never touched) from HBM into a double-buffered VMEM chunk and
folds each chunk into an online softmax, so VMEM holds two chunks whatever
the context length and the work follows ``pos``, not the table's width. A
dead row (``pos = 0``, table at the trash block) costs one block.

What Mosaic is given (ROADMAP S2a lists what it refused of the kernel this
one replaces):

- the pools whole, in ``memory_space=ANY``, and the layer index as a
  run-time scalar: no ``kpool[li]`` slice is formed (XLA:TPU copies it) and
  every layer of a step calls the same jitted kernel, traced and lowered
  once a program;
- layer, block tables and positions by scalar prefetch
  (``PrefetchScalarGridSpec``), the tables flattened to one dimension;
- a block of one layer is one contiguous ``BS * KV * D`` slab; the pool is
  viewed ``(L, NB, BS * KV, D)`` so that a chunk of ``C`` blocks lands in
  VMEM as a plain ``(C * BS * KV, D)`` matrix. The next chunk's copies (of
  the same row, or of the next row's first chunk, across grid steps too) are
  started before the current chunk is waited for, up to ``2 C`` in flight;
- two 2-D dots a chunk with float32 accumulation: all ``H`` queries of the
  row against every ``(token, kv head)`` line of the chunk, ``(H, D) x
  (C BS KV, D)^T``, then the probabilities against V. A query only keeps the
  columns of its own KV group (``column % KV == head // rep``, a constant
  mask handed in as ``tok``), so grouped heads (``rep > 1``) share their
  group's K/V read and the K/V stream crosses the MXU once, as it would
  head by head; the wasted columns cost MXU rows that a decode step has to
  spare. Running max, sum and accumulator are float32.

Contract with the gather path (``models/generation.py build_paged_decode``,
which stays as the plain reference): the caller scatters the step's fresh
K/V into the pool BEFORE the call, every live position is attended, K/V and
queries keep their dtype, softmax statistics and accumulation are float32.
Bit-identity with the gather path is NOT promised: the online softmax sums
in chunk order and divides once at the end. The tolerance the tests hold is
``|kernel - gather| <= 2e-5 * max|gather| + 2e-6`` for float32 inputs
(observed: a few float32 ulp), and equal greedy token streams on the seeded
tiny models. With bfloat16 inputs the probabilities are rounded to bfloat16
before the second dot, as the gather path's einsum rounds them.

Tunable (kernel registry): ``blocks_per_chunk`` (C). One batch row a grid
step: copies are prefetched across rows whether they share a grid step or
not, and on the chip 8 rows a step read the same time as 1 (PERF.md, PR 25),
so there is no rows-per-program knob.

Head width: Mosaic takes a block's slab only when a K/V line fills whole
128-lane rows (``D % 128 == 0``, :func:`mosaic_takes`). It sees a narrower
pool padded to its 128-lane tiling and refuses the copy ("Slice shape along
dimension 3 must be aligned to tiling (128), but is 64"); packing ``128 //
D`` lines a row would take another pool layout, which prefill, CoW and the
snapshots are written against. For those widths the engine keeps the gather
step (``models/generation.py paged_kernel_default``). The interpreter runs
any width.

Tensor-parallel: inside ``build_tp_paged_decode``'s ``shard_map`` body the
call sees the chip's local KV-head shard (``KV / tp`` heads, ``H / tp``
queries, the same ``rep``); attention is independent per KV group, so the
local call is a smaller instance of the same contract and the one
all-gather of head outputs stays in the caller.
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

__all__ = ["paged_attention_rows", "paged_attention_key", "mosaic_takes"]

# a masked score: far below any real one, and finite, so a row of padding
# queries (every column masked) still has a finite softmax
_MASK = -0.7 * float(np.finfo(np.float32).max)
_NO_TOKEN = 2 ** 30  # ``tok`` of a column outside the query's KV group


def paged_attention_key(B, MB, BS, KV, rep, D, dtype) -> tuple:
    """Shape-bucket key. B arrives pre-bucketed (the engine's decode bucket)
    and MB is the engine-wide table width, so the key is exact."""
    return (int(B), int(MB), int(BS), int(KV), int(rep), int(D),
            str(jnp.dtype(dtype)))


def mosaic_takes(head_dim) -> bool:
    """Whether Mosaic compiles the kernel for this head width (the module
    docstring says why): 128, 256, 384 do; 32, 64, 80, 96 do not."""
    return int(head_dim) % 128 == 0


def _paged_kernel(layer_ref, tables_ref, pos_ref, q_ref, tok_ref, kpool_ref,
                  vpool_ref, o_ref, kbuf, vbuf, sems, slot_ref, *, B, MB, BS,
                  C, scale):
    b = pl.program_id(0)
    layer = layer_ref[0]

    def for_live_blocks(b, c, slot, do):
        """``do(K copy, V copy)`` for each LIVE block of chunk ``c`` of row
        ``b`` into buffer ``slot`` (a loop, not ``C`` unrolled branches: the
        kernel is lowered for every decode bucket, and its size is set-up
        time); the same descriptors start and wait."""
        live = jnp.clip(pos_ref[b] // BS + 1 - c * C, 0, C)

        def body(j, carry):
            bid = tables_ref[b * MB + c * C + j]
            do(pltpu.make_async_copy(kpool_ref.at[layer, bid],
                                     kbuf.at[slot, j], sems.at[slot, 0]),
               pltpu.make_async_copy(vpool_ref.at[layer, bid],
                                     vbuf.at[slot, j], sems.at[slot, 1]))
            return carry

        jax.lax.fori_loop(0, live, body, 0)

    def start(b, c, slot):
        for_live_blocks(b, c, slot, lambda kc, vc: (kc.start(), vc.start()))

    def wait(b, c, slot):
        for_live_blocks(b, c, slot, lambda kc, vc: (kc.wait(), vc.wait()))

    @pl.when(b == 0)
    def _():
        # a partial chunk leaves blocks of the buffer unwritten; their
        # probabilities are an exact 0, which only a finite V keeps at 0
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        start(0, 0, 0)

    Hp, D = q_ref.shape[1], q_ref.shape[2]
    N = kbuf.shape[1] * kbuf.shape[2]
    pos = pos_ref[b]
    n_chunks = (pos // BS + C) // C  # ceil((pos // BS + 1) / C)
    q = q_ref[0]

    def chunk_body(c, carry):
        slot, m, l, acc = carry
        nxt = 1 - slot

        @pl.when(c + 1 < n_chunks)
        def _():
            start(b, c + 1, nxt)

        @pl.when(jnp.logical_and(c + 1 >= n_chunks, b + 1 < B))
        def _():
            start(b + 1, 0, nxt)

        wait(b, c, slot)
        k = kbuf[slot].reshape(N, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(tok_ref[...] + c * (C * BS) <= pos, s, _MASK)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        e = jnp.exp(s - m_new)
        l = alpha * l + e.sum(axis=1, keepdims=True)
        v = vbuf[slot].reshape(N, D)
        acc = alpha * acc + jax.lax.dot_general(
            e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return nxt, m_new, l, acc

    # the buffer in flight is carried from one row (grid step) to the next
    slot, _, l, acc = jax.lax.fori_loop(
        0, n_chunks, chunk_body,
        (slot_ref[0], jnp.full((Hp, 1), _MASK, jnp.float32),
         jnp.zeros((Hp, 1), jnp.float32), jnp.zeros((Hp, D), jnp.float32)))
    slot_ref[0] = slot
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _group_tokens(Hp, H, KV, BS, C):
    """``tok[h, n]``: the token (within a chunk) of K/V line ``n = t * KV +
    g`` if ``g`` is query ``h``'s KV group, else ``_NO_TOKEN``; rows ``>= H``
    are padding queries and match nothing."""
    n = np.arange(C * BS * KV)
    h = np.arange(Hp)[:, None]
    own = (n[None, :] % KV == h // (H // KV)) & (h < H)
    return np.where(own, n[None, :] // KV, _NO_TOKEN).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("C", "interpret", "scale",
                                             "kv_heads"))
def _paged_call(q, kpool, vpool, layer, tables, pos, *, C, interpret,
                scale=None, kv_heads=None):
    B, H, D = q.shape
    if kv_heads is None:
        L, NB, BS, KV, _ = kpool.shape
    else:  # the pool as the kernel reads it: a block is one slab of lines
        (L, NB, lines, _), KV = kpool.shape, kv_heads
        BS = lines // KV
    MB = tables.shape[1]
    # queries padded to whole sublane tiles of the widest dtype Mosaic packs
    Hp = -(-H // 16) * 16
    qp = jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0))) if Hp != H else q
    tok = jnp.asarray(_group_tokens(Hp, H, KV, BS, C))
    kern = functools.partial(_paged_kernel, B=B, MB=MB, BS=BS, C=C,
                             scale=float(1.0 / np.sqrt(D)) if scale is None
                             else scale)
    with kernel_x64_off(interpret):
        out = pl.pallas_call(
            kern,
            name="paged_attention",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(B,),
                in_specs=[
                    pl.BlockSpec((1, Hp, D), lambda b, *_: (b, 0, 0)),
                    pl.BlockSpec((Hp, C * BS * KV), lambda b, *_: (0, 0)),
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec((1, Hp, D), lambda b, *_: (b, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, C, BS * KV, D), kpool.dtype),
                    pltpu.VMEM((2, C, BS * KV, D), vpool.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.SMEM((1,), jnp.int32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((B, Hp, D), q.dtype),
            # the buffer in flight is carried from one grid step to the next
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(layer, tables.reshape(B * MB), pos, qp, tok,
          kpool.reshape(L, NB, BS * KV, D), vpool.reshape(L, NB, BS * KV, D))
    return out[:, :H].reshape(B, H * D)


def paged_attention_rows(q, kpool, vpool, layer, tables, pos, config=None,
                         interpret=None, scale=None, kv_heads=None):
    """One decode step's attention read of layer ``layer`` over the paged
    pool.

    q: (B, H, D), one fresh-token query per batch row (its K/V already
    scattered into the pool at the row's write slot); kpool/vpool:
    (L, NB, BS, KV, D), the WHOLE pools; layer: int or int32 scalar; tables:
    (B, MB) int32 per-row block tables (dead columns at the trash block);
    pos: (B,) int32 per-row write positions. Returns (B, H*D),
    ``_grouped_attention``'s reshaped output within the module's tolerance.
    ``scale``: what the scores are multiplied by, ``D ** -0.5`` unless given
    (differential attention pads its queries to twice the width their scores
    are scaled by). ``kv_heads``: the pools are 4-D, ``(L, NB, BS * KV,
    D)``, a block one slab of ``(token, kv head)`` lines as the kernel copies
    it. (A 5-D pool is viewed so; where ``KV`` is not whole sublane tiles,
    10 heads of bfloat16, that view is a copy of the pool a call, so such an
    arch holds its pools 4-D.)
    """
    if not _HAS_PALLAS:
        raise RuntimeError("pallas unavailable")
    if interpret is None:
        interpret = interpret_default()
    B, H, D = q.shape
    if kv_heads is None:
        BS, KV = kpool.shape[2], kpool.shape[3]
    else:
        BS, KV = kpool.shape[2] // int(kv_heads), int(kv_heads)
    MB = tables.shape[1]
    if config is None:
        config = resolve_config(
            "paged_attention",
            paged_attention_key(B, MB, BS, KV, H // KV, D, q.dtype))
    if not interpret and not mosaic_takes(D):
        raise ValueError(
            f"paged_attention: Mosaic takes head widths that are multiples "
            f"of 128, not {D}; build the gather step "
            f"(generation.paged_kernel_default chooses)")
    C = max(1, min(int(config.get("blocks_per_chunk", 8)), MB))
    return _paged_call(
        q, kpool, vpool, jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(tables, jnp.int32), jnp.asarray(pos, jnp.int32),
        C=C, interpret=bool(interpret),
        scale=None if scale is None else float(scale),
        kv_heads=None if kv_heads is None else KV)


# -- registry ----------------------------------------------------------------

def _runner(key):
    """Synthetic pool/tables at the bucketed shape for measured search: row
    ``b`` holds ``1 + b % MB`` live blocks, so the rows are ragged."""
    B, MB, BS, KV, rep, D, dtype = key
    rng = np.random.RandomState(0)
    NB = max(B * MB + 1, 2)
    kpool = jnp.asarray(rng.randn(1, NB, BS, KV, D), dtype)
    vpool = jnp.asarray(rng.randn(1, NB, BS, KV, D), dtype)
    tables = np.zeros((B, MB), np.int32)
    pos = np.zeros((B,), np.int32)
    for b in range(B):
        n_live = 1 + (b % MB)
        pos[b] = n_live * BS - 1
        tables[b, :n_live] = 1 + b * MB + np.arange(n_live)
    tables, pos = jnp.asarray(tables), jnp.asarray(pos)
    q = jnp.asarray(rng.randn(B, KV * rep, D), dtype)

    def make(config):
        fn = jax.jit(functools.partial(paged_attention_rows, config=config))
        return lambda: fn(q, kpool, vpool, 0, tables, pos)

    return make


register_kernel(
    "paged_attention",
    defaults={"blocks_per_chunk": 8},
    space={"blocks_per_chunk": (4, 8, 16)},
    runner=_runner,
)
