"""Routed experts' gated MLPs over the experts a layer holds (registry:
``moe_experts``): a grouped matrix product that reads, of the stacked expert
weights ``(E, d, f)`` / ``(E, f, d)``, the experts some token chose and no
others, each once per tile of its tokens.

No capacity: every (token, choice) pair is computed, whatever the imbalance.
The pairs are grouped by expert and laid out in row tiles of ``tm`` rows, each
expert's group padded up to whole tiles (at most ``E`` tiles of padding), so
that a tile belongs to ONE expert. The kernel's grid is (row tiles, slices of
``f``): a scalar-prefetched map names each tile's expert, and the weight
blocks' index maps follow it, so consecutive tiles of one expert and the
unused tiles behind the last used one (mapped onto the block already
resident, their compute skipped) start no copy. What is read from HBM is
therefore ``touched experts x 3 d f`` plus the rows themselves: at decode, a
few dozen rows that hit k experts each, that is the experts hit (26 of 64 at
8 rows, 56 at 32); at prefill each expert's matrices pass once per ``tm`` of
its tokens, so once for all but the hottest.

Per tile: ``h = silu(x Wg[e]) * (x Wu[e])`` slice by slice of ``f``, ``y +=
h Wd[e]`` accumulated in float32 and rounded to the activations' dtype once
a (token, choice) pair. The gates are applied where the ``k`` partial
results of a token are summed, in float32, the sum rounded once. Padding rows
are zeros and their results are never read.

How a pair's row gets back to its token (the "combine") follows the tile
width, which the rule below derives from the pairs:

- 16-row tiles (a decode batch): the tiles leave the kernel as tiles and the
  program gathers EVERY pair's row out of them (a pair without an expert
  reads the last row and is masked), converts, gates and sums: a few
  hundred rows, nothing to win.
- tiles of ``_COPY_TILE`` rows or more (256: every prefill program; PR 48):
  no tile leaves as a tile. A finished tile is packed into 32-bit words
  (``_pack``: two bfloat16 columns a word, since one row of a packed dtype
  is nothing a copy can address) and laid out a row an (8, 128) tile of
  VMEM, 4 KB contiguous at ``d`` 2048; each row some pair went to is then
  copied by a DMA of its own to row ``choice * N + token`` of ``k`` planes
  of ``(N, d)`` in HBM, by the ``src`` map the layout builds anyway. The
  schedule is the block-table reads' (``paged_attention``): a full tile's
  copies are started as straight-line code and waited for ONCE, in the
  next tile's epilogue, after they flew under its products; a group's last,
  partial tile starts and awaits its rows in a loop. A row no pair went to
  (a group's padding) and a pair no held expert took (a padding token, an
  expert held on another chip: ``slot == E``) start NO copy: where a chip
  holds 16 of a router's 128 experts, an eighth of the pairs move.
  ``moe_combine`` then reads the ``k`` planes once, ``_COMBINE_ROWS`` tokens
  a step: unpacks (two shifts: a bfloat16 is the upper half of its
  float32), masks what no copy wrote to an exact zero whatever lies there,
  gates and sums in float32 and writes ``(N, d)``. No float32 array of the
  pairs exists in HBM (the gathered form's ``(N, k, d)`` float32, its ``k``
  padded to 8 sublanes, was written and read back: 537 MB a call at 8,192
  x 6 x 2,048), and a row the kernel produced crosses HBM once more, not
  three times. A width whose rows cannot be copied (``_rows_copy``: not
  whole 128-lane rows of words) keeps the gathered form at any tile.

The plain form is ``models/mla_moe.experts_plain`` (dense and masked). The
tests hold the two within the matmul's own rounding.

Tunables: ``rows_per_tile`` (tm: 16 for a decode batch, 256 from 512 pairs
on) and ``f_slice``.
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

__all__ = ["moe_experts", "moe_experts_key", "tile_layout"]

I32 = jnp.int32
# from this many rows a tile on, a tile's products (22 us of the MXU at 256
# x 2048 x 1408) are long enough to fly its rows' copies under the next's
_COPY_TILE = 128
_COMBINE_ROWS = 64  # tokens a step of ``moe_combine``: k x 64 rows of 4 KB


def moe_experts_key(pairs, E, d, f, dtype) -> tuple:
    return (int(pairs), int(E), int(d), int(f), str(jnp.dtype(dtype)))


def tile_layout(slot, E, tm):
    """Where each (token, choice) pair goes. ``slot`` (A,) int32 in [0, E],
    ``E`` meaning "no expert here". Returns ``(dest (A,), tile_expert (NT,),
    n_tiles ())``: the pair's row in the tiled layout (``NT * tm`` for a pair
    without an expert: the first row past the tiles), each tile's expert
    (tiles past ``n_tiles`` repeat the last used one), and how many tiles are
    used. ``NT = ceil(A / tm) + E`` bounds them whatever the routing."""
    A = slot.shape[0]
    NT = -(-A // tm) + E
    # a pair's rank within its expert's group, in the pairs' own order: a
    # running count by expert (no sort: XLA:TPU compiles one slowly)
    mine = slot[:, None] == jnp.arange(E, dtype=I32)[None, :]
    seen = jnp.cumsum(mine.astype(I32), axis=0)
    counts = seen[-1]
    rank = jnp.sum(jnp.where(mine, seen, 0), axis=1) - 1
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles).astype(I32)
    n_tiles = tile_end[-1]
    row0 = (tile_end - tiles) * tm                      # first row of a group
    dest = jnp.where(slot < E, row0[jnp.minimum(slot, E - 1)] + rank, NT * tm)
    t = jnp.arange(NT, dtype=I32)
    te = jnp.searchsorted(tile_end, jnp.minimum(t, jnp.maximum(n_tiles - 1, 0)),
                          side="right").astype(I32)
    return dest, jnp.minimum(te, E - 1), n_tiles.reshape(1)


def _tile_products(x_ref, wg_ref, wu_ref, wd_ref, acc, j):
    """One slice of ``f`` of a tile's gated MLP, accumulated in ``acc``."""
    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
    acc[...] += jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)


def _experts_kernel(te_ref, nt_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc,
                    *, nf):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i < nt_ref[0])
    def _():
        _tile_products(x_ref, wg_ref, wu_ref, wd_ref, acc, j)

        @pl.when(j == nf - 1)
        def _():
            o_ref[...] = acc[...].astype(o_ref.dtype)


def _rows_copy(d, dtype) -> bool:
    """Whether a result row of width ``d`` can leave the kernel by a copy of
    its own: whole 128-lane rows of 32-bit words (two bfloat16 a word)."""
    dtype = jnp.dtype(dtype)
    return (dtype.itemsize == 4 and d % 128 == 0) or (
        dtype == jnp.bfloat16 and d % 256 == 0)


def _pack(y, dtype):
    """A tile's float32 results ``(tm, d)`` as the 32-bit words that leave
    the kernel: rounded to ``dtype``, here and nowhere else; of bfloat16,
    columns ``c`` and ``c + d / 2`` share a word (a bfloat16 is the upper
    half of its float32)."""
    if jnp.dtype(dtype).itemsize == 4:
        return y.astype(dtype)
    bits = jax.lax.bitcast_convert_type(
        y.astype(jnp.bfloat16).astype(jnp.float32), jnp.uint32)
    h = y.shape[1] // 2
    return bits[:, h:] | (bits[:, :h] >> 16)


def _unpack(w, dtype):
    """``_pack``'s words as float32: the column halves ``[lo, hi]`` of
    bfloat16 (two shifts, no conversion), the one array of a 32-bit dtype."""
    if jnp.dtype(dtype).itemsize == 4:
        return [w.astype(jnp.float32)]
    f32 = functools.partial(jax.lax.bitcast_convert_type,
                            new_dtype=jnp.float32)
    return [f32(w << 16), f32(w & jnp.uint32(0xFFFF0000))]


def _scatter_kernel(te_ref, nt_ref, cnt_ref, x_ref, wg_ref, wu_ref, wd_ref,
                    to_ref, planes_ref, acc, obuf, sem, *, nf, NT, tm, dtype,
                    straight):
    """``_experts_kernel`` whose tiles do not leave as tiles: each row a pair
    went to is copied to that pair's place in ``planes_ref``. ``straight``:
    a full tile's starts are unrolled (for Mosaic; the interpreter's program
    would only grow by it)."""
    i, j = pl.program_id(0), pl.program_id(1)
    nt = nt_ref[0]

    def for_rows(n, do, unroll=False):
        def body(r, carry):
            do(r)
            return carry

        jax.lax.fori_loop(0, n, body, 0, unroll=unroll)

    def start_row(r):
        pltpu.make_async_copy(obuf.at[pl.ds(r, 1)],
                              planes_ref.at[pl.ds(to_ref[0, r], 1)], sem).start()

    def start_rows(t):
        """Tile ``t``'s rows leave ``obuf``: a group's rows come first in its
        last tile, so ``cnt_ref[t]`` says which; a full tile's starts are
        straight-line code (ONE traced body, unrolled when lowered)."""
        cnt = cnt_ref[t]

        @pl.when(cnt == tm)
        def _():
            for_rows(tm, start_row, unroll=straight)

        @pl.when(cnt < tm)
        def _():
            for_rows(cnt, start_row)

    def wait_rows(t):
        """Tile ``t``'s copies have landed. A semaphore counts bytes: a full
        tile's 256 are taken off it by ONE wait on a descriptor of the whole
        buffer (nothing starts it), where the buffer's bytes ARE its rows'
        bytes: a row of whole (8, 128) tiles. A row of 14 lane-rows (``d``
        3,584) lies in 16, a descriptor of the buffer counts all 16 and the
        wait would never end: there every row is awaited."""
        cnt = cnt_ref[t]
        whole = obuf.shape[1] % 8 == 0

        def each():
            for_rows(cnt, lambda r: pltpu.make_async_copy(
                obuf.at[pl.ds(0, 1)], obuf.at[pl.ds(0, 1)], sem).wait())

        if whole:
            pl.when(cnt == tm)(
                lambda: pltpu.make_async_copy(obuf, obuf, sem).wait())
        pl.when(cnt < tm if whole else cnt > 0)(each)

    @pl.when(i < nt)
    def _():
        _tile_products(x_ref, wg_ref, wu_ref, wd_ref, acc, j)

        @pl.when(j == nf - 1)
        def _():
            @pl.when(i > 0)
            def _():  # they flew under this tile's products
                wait_rows(i - 1)

            # a row an (8, 128) tile of its own: what a copy can address
            obuf[...] = _pack(acc[...], dtype).reshape(obuf.shape)
            start_rows(i)

    @pl.when((i == NT - 1) & (j == nf - 1) & (nt > 0))
    def _():
        wait_rows(nt - 1)


def _combine_kernel(slot_ref, g_ref, p_ref, o_ref, *, E, dtype):
    """``rows`` tokens' k pair rows, one plane a choice, unpacked, masked,
    gated and summed in float32, rounded once."""
    took = slot_ref[...] < E
    g = g_ref[...]
    rows, k = took.shape
    acc = None
    for c in range(k):
        m, gc = took[:, c:c + 1], g[:, c:c + 1]
        hs = [jnp.where(m, h, 0.0) * gc
              for h in _unpack(p_ref[c].reshape(rows, -1), dtype)]
        acc = hs if acc is None else [a + h for a, h in zip(acc, hs)]
    o_ref[...] = jnp.concatenate(acc, axis=-1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tf", "interpret"))
def _experts_call(x, slot, gates, wg, wu, wd, layer=None, *, tm, tf, interpret):
    N, d = x.shape
    k = slot.shape[1]
    E, _, f = wg.shape[-3:]
    nf = f // tf
    A = N * k
    flat = slot.reshape(A).astype(I32)
    dest, te, nt = tile_layout(flat, E, tm)
    if layer is not None:
        # the stacks of several layers, viewed as one run of experts: a
        # tile's expert is counted from its layer's first
        te = te + layer.astype(I32) * E
        wg, wu, wd = (w.reshape((-1,) + w.shape[-2:]) for w in (wg, wu, wd))
    NT = te.shape[0]
    # the rows, tile by tile: each row of the layout GATHERS its pair's token
    # (a scatter of whole rows compiles and runs slower); a row no pair went
    # to reads the zero row appended to the tokens
    src = jnp.full((NT * tm + 1,), A, I32).at[dest].set(jnp.arange(A, dtype=I32))
    xs = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[
        jnp.where(src < A, src // k, N)[:NT * tm]]

    def used(i, nt):  # tiles past the last used one stay on its blocks
        return jnp.maximum(jnp.minimum(i, nt[0] - 1), 0)

    def f_at(i, j, nt):
        return jnp.where(i < nt[0], j, nf - 1)

    def tiles(kernel, maps, operands, in_specs, out_specs, out_shape, scratch):
        """The grid of (row tiles, slices of ``f``) over ``kernel``, behind
        the scalar-prefetched ``maps`` (each tile's expert and the count of
        tiles first)."""
        with kernel_x64_off(interpret):
            return pl.pallas_call(
                kernel,
                name=f"moe_experts_t{tm}",
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=len(maps),
                    grid=(NT, nf),
                    in_specs=[
                        pl.BlockSpec((tm, d), lambda i, j, te, nt, *_: (used(i, nt), 0)),
                        pl.BlockSpec((None, d, tf),
                                     lambda i, j, te, nt, *_: (te[i], 0, f_at(i, j, nt))),
                        pl.BlockSpec((None, d, tf),
                                     lambda i, j, te, nt, *_: (te[i], 0, f_at(i, j, nt))),
                        pl.BlockSpec((None, tf, d),
                                     lambda i, j, te, nt, *_: (te[i], f_at(i, j, nt), 0)),
                    ] + in_specs,
                    out_specs=out_specs,
                    scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)] + scratch,
                ),
                out_shape=out_shape,
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary", "arbitrary"),
                    vmem_limit_bytes=96 * 2 ** 20),
                interpret=interpret,
            )(*maps, xs, wg, wu, wd, *operands)

    if tm < _COPY_TILE or not _rows_copy(d, x.dtype):
        # the tiles leave as tiles and every pair's row is gathered back: a
        # pair without an expert reads the last row and is masked
        ys = tiles(functools.partial(_experts_kernel, nf=nf), (te, nt), (), [],
                   pl.BlockSpec((tm, d), lambda i, j, te, nt: (used(i, nt), 0)),
                   jax.ShapeDtypeStruct((NT * tm, d), x.dtype), [])
        dest = dest.reshape(N, k)
        took = (slot < E)[..., None]
        y = jnp.where(took, ys[jnp.minimum(dest, NT * tm - 1)].astype(jnp.float32), 0.0)
        return jnp.sum(y * gates[..., None], axis=1).astype(x.dtype)

    # each row a pair went to leaves the kernel for row ``choice * N +
    # token`` of k planes of (N, d) in 32-bit words, a row an (8, 128) tile
    # or several; a place no copy wrote is never unmasked
    words = jnp.uint32 if x.dtype == jnp.bfloat16 else x.dtype
    lanes = (d * jnp.dtype(x.dtype).itemsize // 4) // 128
    src = src[:NT * tm]
    to = jnp.where(src < A, (src % k) * N + src // k, 0).reshape(NT, 1, tm)
    cnt = jnp.sum((src < A).reshape(NT, tm), axis=1, dtype=I32)
    planes = tiles(
        functools.partial(_scatter_kernel, nf=nf, NT=NT, tm=tm, dtype=x.dtype,
                          straight=not interpret),
        (te, nt, cnt), (to,),
        [pl.BlockSpec((None, 1, tm),
                      lambda i, j, te, nt, cnt: (used(i, nt), 0, 0),
                      memory_space=pltpu.SMEM)],
        pl.BlockSpec(memory_space=pl.ANY),
        jax.ShapeDtypeStruct((k * N, lanes, 128), words),
        [pltpu.VMEM((tm, lanes, 128), words), pltpu.SemaphoreType.DMA(())])
    rows = min(N, _COMBINE_ROWS)
    with kernel_x64_off(interpret):
        return pl.pallas_call(
            functools.partial(_combine_kernel, E=E, dtype=x.dtype),
            name="moe_combine",
            grid=(pl.cdiv(N, rows),),
            in_specs=[pl.BlockSpec((rows, k), lambda i: (i, 0)),
                      pl.BlockSpec((rows, k), lambda i: (i, 0)),
                      pl.BlockSpec((k, rows, lanes, 128), lambda i: (0, i, 0, 0))],
            out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((N, d), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=96 * 2 ** 20),
            interpret=interpret,
        )(slot.astype(I32), gates, planes.reshape(k, N, lanes, 128))


def moe_experts(x, slot, gates, gate_w, up_w, down_w, config=None,
                interpret=None, layer=None):
    """``sum_k gates[n, k] * Expert_{slot[n, k]}(x[n])`` over the held experts.

    x: (N, d); slot: (N, k) int32, an index into the held experts or their
    count ``E`` for "none" (a padding token, an expert held elsewhere);
    gates: (N, k) float32; gate_w / up_w: (E, d, f); down_w: (E, f, d).
    ``layer``: the weights are the stacks of several layers, (L, E, d, f) /
    (L, E, f, d), and this int32 scalar (traced inside a scan over layers)
    says whose experts to take: the stacks go to the kernel whole and no
    ``gate_w[layer]`` is formed (XLA:TPU would copy it for the call).
    Returns (N, d) in ``x``'s dtype."""
    if not _HAS_PALLAS:
        raise RuntimeError("pallas unavailable")
    if interpret is None:
        interpret = interpret_default()
    N, d = x.shape
    E, _, f = gate_w.shape[-3:]
    pairs = N * slot.shape[1]
    if config is None:
        config = resolve_config("moe_experts",
                                moe_experts_key(pairs, E, d, f, x.dtype))
    tm = int(config.get("rows_per_tile") or (16 if pairs < 512 else 256))
    tf = int(config.get("f_slice") or 512)
    tf = tf if f % tf == 0 else f
    return _experts_call(x, slot, gates, gate_w, up_w, down_w,
                         None if layer is None else jnp.asarray(layer, I32),
                         tm=tm, tf=tf, interpret=bool(interpret))


def _runner(key):
    """Synthetic tokens choosing 4 of the experts uniformly."""
    import numpy as np

    pairs, E, d, f, dtype = key
    rng = np.random.RandomState(0)
    k = min(4, E)
    N = max(pairs // k, 1)
    x = jnp.asarray(rng.randn(N, d), dtype)
    slot = jnp.asarray(np.stack([rng.permutation(E)[:k] for _ in range(N)]), I32)
    gates = jnp.full((N, k), 1.0 / k, jnp.float32)
    ws = [jnp.asarray(rng.randn(*s) * 0.02, dtype)
          for s in ((E, d, f), (E, d, f), (E, f, d))]

    def make(config):
        fn = jax.jit(functools.partial(moe_experts, config=config))
        return lambda: fn(x, slot, gates, *ws)

    return make


register_kernel(
    "moe_experts",
    defaults={"rows_per_tile": 0, "f_slice": 512},
    space={"rows_per_tile": (0, 16, 128, 256), "f_slice": (256, 512, 1024)},
    runner=_runner,
)
