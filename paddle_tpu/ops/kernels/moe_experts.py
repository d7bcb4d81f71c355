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
h Wd[e]`` accumulated in float32; the gates are applied outside, in float32,
where the ``k`` partial results of a token are summed. Padding rows are
zeros and their results are never read.

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


def _experts_kernel(te_ref, nt_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc,
                    *, nf):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i < nt_ref[0])
    def _():
        @pl.when(j == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        acc[...] += jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)

        @pl.when(j == nf - 1)
        def _():
            o_ref[...] = acc[...].astype(o_ref.dtype)


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

    with kernel_x64_off(interpret):
        ys = pl.pallas_call(
            functools.partial(_experts_kernel, nf=nf),
            name=f"moe_experts_t{tm}",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(NT, nf),
                in_specs=[
                    pl.BlockSpec((tm, d), lambda i, j, te, nt: (used(i, nt), 0)),
                    pl.BlockSpec((None, d, tf),
                                 lambda i, j, te, nt: (te[i], 0, f_at(i, j, nt))),
                    pl.BlockSpec((None, d, tf),
                                 lambda i, j, te, nt: (te[i], 0, f_at(i, j, nt))),
                    pl.BlockSpec((None, tf, d),
                                 lambda i, j, te, nt: (te[i], f_at(i, j, nt), 0)),
                ],
                out_specs=pl.BlockSpec((tm, d),
                                       lambda i, j, te, nt: (used(i, nt), 0)),
                scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((NT * tm, d), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=96 * 2 ** 20),
            interpret=interpret,
        )(te, nt, xs, wg, wu, wd)
    dest = dest.reshape(N, k)
    took = (slot < E)[..., None]
    y = jnp.where(took, ys[jnp.minimum(dest, NT * tm - 1)].astype(jnp.float32), 0.0)
    return jnp.sum(y * gates[..., None], axis=1).astype(x.dtype)


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
