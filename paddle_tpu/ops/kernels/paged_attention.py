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

The copy schedule of a chunk (PR 46; ``mla_paged_attention`` has had it for
its one pool since PR 43, and its docstring has the reasons). A block is two
copies, its K slab and its V slab. Timed alone at the shapes the serving
cells run (PERF.md section 6, PR 46), half of a block's time at 4 K/V heads
(98.6 ns at 8 blocks a chunk: 47 the dots, 52 the rest) was neither its
bytes nor its dots but what stands BESIDE the dots on the one instruction
stream: the scalar work of starting a copy (a table entry, the address
arithmetic, two bounds checks) and of waiting for it, twice a
block. So a FULL chunk (all ``C`` blocks live: every chunk of a row but its
last) has its ``2 C`` copies started as straight-line code (ONE traced loop
body, unrolled when the kernel is lowered, at ONE site in the chunk loop
that starts either the row's next chunk or the next row's first, plus the
first grid step's start) and is waited for ONCE a pool: a DMA semaphore
counts bytes, so a wait on a descriptor whose destination is the whole
``kbuf.at[slot]`` takes the ``C`` blocks' bytes off ``sems.at[slot, 0]``,
and ``vbuf``'s off ``sems.at[slot, 1]``. One chunk at most is in flight a
slot, so the counts are that chunk's alone, also across the grid step that
hands the slot to the next row. A PARTIAL chunk (a row's last, ``live <
C``) keeps the two loops of dynamic length, a K and a V start and a K and a
V wait a live block. The copies, their order and the arithmetic are the
same in both: at one ``blocks_per_chunk`` the outputs are bit for bit those
of the schedule with a loop a block (``tests/test_paged_kernel.py`` keeps
that body). Where a block is large (10 pairs: 2 x 40 KB) the copies
themselves set the pace (scattered slabs move at 690 GB/s, 84% of the HBM
peak) and the schedule changes nothing. :func:`chunk_counts` says on the
host how many of a step's chunks came whole.

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

Tunable (kernel registry): ``blocks_per_chunk`` (C). The dots of a chunk are
one dependent chain (scores, row maximum, exponentials, the second dot, the
carry) that costs a fixed part however many tokens the chunk holds, so a
wider chunk is cheaper a block, until the columns of a row's last, partial
chunk cost more than the chain saves: since PR 46 (8 until then) the
registry's 0 leaves the choice to :func:`blocks_per_chunk`, the widest chunk
of at most 640 KB a pool, which is what the chip measured as fastest at the
serving cells' shapes: 32 blocks of 16 KB (4 K/V heads of 128 in bfloat16),
16 of 40 KB (10 pairs), 8 of 64 KB (16 heads); VMEM holds four buffers of
``C`` blocks, 2 to 2.5 MB. One
batch row a grid step: copies are prefetched across rows whether they share
a grid step or not, and on the chip 8 rows a step read the same time as 1
(PERF.md, PR 25), so there is no rows-per-program knob.

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
from .registry import get_kernel, register_kernel, resolve_config

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

__all__ = ["paged_attention_rows", "paged_attention_key", "mosaic_takes",
           "blocks_per_chunk", "chunk_counts"]

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


# what a chunk of one pool may hold where the registry leaves the choice to
# the shape: measured on the chip (PERF.md section 6, PR 46), the kernel
# alone is fastest at 32 blocks of 16 KB, 16 of 40 KB and 8 of 64 KB a chunk
_CHUNK_BYTES = 640 * 1024


def blocks_per_chunk(key, config=None) -> int:
    """The ``C`` a call of shape ``key`` (``paged_attention_key``) copies and
    multiplies at a time, at most a row's table: the registry's, and where
    that is 0 the widest of its space whose chunk of one pool is at most
    ``_CHUNK_BYTES``. A chunk's dots are one dependent chain with a fixed
    cost, so a wider chunk is cheaper a block until its lines (``C * BS *
    KV``, every one a column of both dots whether its block is live or not)
    cost more than the chain saves and the last, partial chunk of a row
    pays for the whole width."""
    if config is None:
        config = resolve_config("paged_attention", key)
    C = int(config.get("blocks_per_chunk", 0))
    if not C:
        _, _, BS, KV, _, D, dtype = key
        block = BS * KV * D * jnp.dtype(dtype).itemsize
        C = max([c for c in get_kernel("paged_attention").space["blocks_per_chunk"]
                 if c * block <= _CHUNK_BYTES], default=1)
    return max(1, min(C, key[1]))


def chunk_counts(pos, BS, C) -> dict:
    """What the kernel's copy schedule does in ONE call with rows that write
    positions ``pos`` (the live rows of a step: a row that pads the bucket
    costs one block and is the caller's to leave out), on the host:
    ``paged_blocks`` copied from each pool, ``paged_chunks`` they come in and
    ``paged_full_chunks``, those of them started as straight-line code and
    waited for once a pool."""
    blocks = np.asarray(pos, np.int64) // BS + 1
    return {"paged_blocks": int(blocks.sum()),
            "paged_chunks": int((-(-blocks // C)).sum()),
            "paged_full_chunks": int((blocks // C).sum())}


def _paged_kernel(layer_ref, tables_ref, pos_ref, q_ref, tok_ref, kpool_ref,
                  vpool_ref, o_ref, kbuf, vbuf, sems, slot_ref, *, B, MB, BS,
                  C, scale):
    b = pl.program_id(0)
    layer = layer_ref[0]

    def live_blocks(b, c):
        return jnp.clip(pos_ref[b] // BS + 1 - c * C, 0, C)

    def block_copies(b, c, slot, j):
        """The K and the V copy of block ``j`` of chunk ``c`` of row ``b``
        into buffer ``slot``; the same descriptors start and wait."""
        bid = tables_ref[b * MB + c * C + j]
        return (pltpu.make_async_copy(kpool_ref.at[layer, bid],
                                      kbuf.at[slot, j], sems.at[slot, 0]),
                pltpu.make_async_copy(vpool_ref.at[layer, bid],
                                      vbuf.at[slot, j], sems.at[slot, 1]))

    def for_blocks(n, do, unroll=False):
        def body(j, carry):
            do(j)
            return carry

        jax.lax.fori_loop(0, n, body, 0, unroll=unroll)

    def start(b, c, slot):
        live = live_blocks(b, c)

        def issue(j):
            kc, vc = block_copies(b, c, slot, j)
            kc.start()
            vc.start()

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
            # a semaphore counts bytes: ONE wait a pool for the chunk's C
            # blocks (the descriptor is only its destination's size; nothing
            # starts it)
            pltpu.make_async_copy(kbuf.at[slot], kbuf.at[slot],
                                  sems.at[slot, 0]).wait()
            pltpu.make_async_copy(vbuf.at[slot], vbuf.at[slot],
                                  sems.at[slot, 1]).wait()

        @pl.when(live < C)
        def _():
            def await_block(j):
                kc, vc = block_copies(b, c, slot, j)
                kc.wait()
                vc.wait()

            for_blocks(live, await_block)

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

        # what is multiplied next: this row's next chunk, or the next row's
        # first (ONE site: the kernel is lowered for every decode bucket)
        last = c + 1 >= n_chunks

        @pl.when(jnp.logical_or(jnp.logical_not(last), b + 1 < B))
        def _():
            start(jnp.where(last, b + 1, b), jnp.where(last, 0, c + 1), nxt)

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
    if not interpret and not mosaic_takes(D):
        raise ValueError(
            f"paged_attention: Mosaic takes head widths that are multiples "
            f"of 128, not {D}; build the gather step "
            f"(generation.paged_kernel_default chooses)")
    C = blocks_per_chunk(
        paged_attention_key(B, MB, BS, KV, H // KV, D, q.dtype), config)
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
    # 0 since PR 46 (8 until then): by the bytes of a block
    # (``blocks_per_chunk``)
    defaults={"blocks_per_chunk": 0},
    space={"blocks_per_chunk": (4, 8, 16, 32)},
    runner=_runner,
)
