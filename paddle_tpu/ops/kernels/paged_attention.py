"""Block-table-aware paged-attention decode kernel (registry: ``paged_attention``).

The serving engine's gather-based decode (``models/generation.py
build_paged_decode``) materializes every row's context DENSE in HBM —
``kpool[li][tables].reshape(B, T_pad, KV, D)`` per layer per step — then
attends over the padding behind each row's live mask. This kernel reads K/V
**directly from the PagePool blocks**: per grid step it DMAs exactly the
blocks named by that row's block table into VMEM scratch, bounds the score
loop at the row's LIVE block count (``pos // block_size + 1`` — no
trash-block padding attend), and runs the grouped-GQA attention math in the
same op order as the dense reference, so the output is **bit-identical** to
the gather path (pinned on the CPU tier via Pallas interpret mode, where
both paths execute the same XLA backend ops).

Contract vs the gather path: the caller scatters this step's fresh K/V into
the pool BEFORE the kernel reads it (the reference overwrites the gathered
context at ``pos`` in-context — same values, same slot). Trash blocks ARE
copied (matching the reference's gather of them) so dead context stays
finite; their scores are never computed and their softmax weights are an
exact 0.0, so they contribute exactly nothing — also matching the reference.

Tunables: ``rows_per_program`` amortizes per-program overhead over several
batch rows; ``score_mode`` picks the live-bounded per-block score loop
(``"live"``) or one whole-context dot (``"full"`` — the reference's exact
gemm shape, more FLOPs, fewer loop iterations). Both verified bit-identical
at every engine-reachable shape: the engine's ``block_size`` is a multiple
of 8, which keeps each per-block score gemm's output width on the CPU SIMD
grain so chunked and full-width dots round identically (at a hypothetical
block_size of 4 the Eigen kernels pick different vector strategies and the
live path drifts by a ulp — ``"full"`` is exact at ANY shape).

bf16-on-TPU note: the surrounding model runs its score einsum under the
global ``jax_default_matmul_precision`` while Mosaic uses the MXU's native
bf16×bf16→f32; the bit-identity pin is the f32 CPU tier, TPU bf16 parity is
numeric (same contract as the flash kernel).

Tensor-parallel note: under ``FLAGS_serve_tp`` the engine calls this kernel
INSIDE the per-device shard_map body with the local KV-head shard — q is
``(B, KV_local*rep, D)``, the pools are the chip's ``kv_heads/tp`` slice,
and the block tables are the replicated host truth. Attention is
independent per KV group, so the kernel needs no axis awareness: the local
call is exactly a smaller-KV instance of the same contract, and the tp
boundary (one all_gather of the per-head outputs) lives in the caller.
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

__all__ = ["paged_attention_rows", "paged_attention_key"]


def paged_attention_key(B, MB, BS, KV, rep, D, dtype) -> tuple:
    """Shape-bucket key. B and MB arrive pre-bucketed (the engine's decode
    bucket and power-of-two gather width), so the key is exact."""
    return (int(B), int(MB), int(BS), int(KV), int(rep), int(D),
            str(jnp.dtype(dtype)))


def _attend_one_row(q, kc, vc, pos, *, KV, rep, D, BS, MB, score_mode):
    """The per-row attention math, mirroring ``_grouped_attention``'s op
    sequence exactly so the CPU interpret path is bit-identical to the dense
    reference. The size-1 query axis is KEPT in the einsum specs
    (``qgrd,kgd->grqk``): dropping it changes jnp.einsum's contraction
    lowering at rep=1 and costs a ulp vs the batched reference."""
    T_pad = MB * BS
    scale = jnp.asarray(1.0 / np.sqrt(D), q.dtype)
    live = jnp.arange(T_pad, dtype=jnp.int32) <= pos
    q = q.reshape(1, KV, rep, D)  # (q=1, g, r, d)
    if score_mode == "live":
        # per-block scores bounded at the row's live block count; dead
        # columns stay at the exact -inf the reference's mask produces
        n_live = pos // BS + 1
        s0 = jnp.where(jnp.zeros((KV, rep, 1, T_pad), bool),
                       jnp.zeros((KV, rep, 1, T_pad), q.dtype), -jnp.inf)

        def body(j, s):
            kb = jax.lax.dynamic_slice_in_dim(kc, j * BS, BS, axis=0)
            sb = jnp.einsum("qgrd,kgd->grqk", q, kb) * scale
            return jax.lax.dynamic_update_slice_in_dim(s, sb, j * BS, axis=3)

        s = jax.lax.fori_loop(0, n_live, body, s0)
        s = jnp.where(live[None, None, None, :], s, -jnp.inf)
    else:  # "full": one dot over the whole padded context (reference shape)
        s = jnp.einsum("qgrd,kgd->grqk", q, kc) * scale
        s = jnp.where(live[None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("grqk,kgd->qgrd", p, vc)  # (1, KV, rep, D)


def _paged_kernel(tables_ref, pos_ref, q_ref, kpool_ref, vpool_ref, o_ref,
                  ctx_k, ctx_v, sem, *, KV, rep, D, BS, MB, R, score_mode):
    H = KV * rep
    T_pad = MB * BS
    for r in range(R):
        # copy the row's blocks (trash included — keeps dead context finite,
        # matching the gather) from the HBM pool into VMEM scratch
        for j in range(MB):
            bid = tables_ref[r, j]
            pltpu.make_async_copy(kpool_ref.at[bid], ctx_k.at[j], sem).start()
            pltpu.make_async_copy(kpool_ref.at[bid], ctx_k.at[j], sem).wait()
            pltpu.make_async_copy(vpool_ref.at[bid], ctx_v.at[j], sem).start()
            pltpu.make_async_copy(vpool_ref.at[bid], ctx_v.at[j], sem).wait()
        q = q_ref[r].reshape(KV, rep, D)
        o = _attend_one_row(
            q, ctx_k[:].reshape(T_pad, KV, D), ctx_v[:].reshape(T_pad, KV, D),
            pos_ref[r], KV=KV, rep=rep, D=D, BS=BS, MB=MB,
            score_mode=score_mode)
        o_ref[r] = o.reshape(H * D)


def paged_attention_rows(q, kpool, vpool, tables, pos, config=None,
                         interpret=None):
    """One decode step's attention read over the paged pool.

    q: (B, H, D) — one fresh-token query per batch row (its K/V already
    scattered into the pool at the row's write slot); kpool/vpool:
    (NB, BS, KV, D) — ONE layer's pool; tables: (B, MB) int32 per-row block
    tables (dead columns at the trash block); pos: (B,) int32 per-row write
    positions. Returns (B, H*D) — ``_grouped_attention``'s reshaped output.
    """
    if not _HAS_PALLAS:
        raise RuntimeError("pallas unavailable")
    if interpret is None:
        interpret = interpret_default()
    B, H, D = q.shape
    NB, BS, KV, _ = kpool.shape
    MB = tables.shape[1]
    rep = H // KV
    if config is None:
        config = resolve_config(
            "paged_attention", paged_attention_key(B, MB, BS, KV, rep, D,
                                                   q.dtype))
    R = int(config.get("rows_per_program", 1))
    if B % R:
        R = 1
    score_mode = str(config.get("score_mode", "live"))
    kern = functools.partial(
        _paged_kernel, KV=KV, rep=rep, D=D, BS=BS, MB=MB, R=R,
        score_mode=score_mode)
    with kernel_x64_off(interpret):
        return pl.pallas_call(
            kern,
            grid=(B // R,),
            in_specs=[
                pl.BlockSpec((R, MB), lambda b: (b, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((R,), lambda b: (b,), memory_space=pltpu.SMEM),
                pl.BlockSpec((R, H * D), lambda b: (b, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((R, H * D), lambda b: (b, 0)),
            out_shape=jax.ShapeDtypeStruct((B, H * D), q.dtype),
            scratch_shapes=[
                pltpu.VMEM((MB, BS, KV, D), q.dtype),
                pltpu.VMEM((MB, BS, KV, D), q.dtype),
                pltpu.SemaphoreType.DMA,
            ],
            interpret=interpret,
        )(jnp.asarray(tables, jnp.int32).reshape(B, MB),
          jnp.asarray(pos, jnp.int32), q.reshape(B, H * D), kpool, vpool)


# -- registry ----------------------------------------------------------------

def _valid(config, key):
    B = key[0]
    return B % int(config["rows_per_program"]) == 0


def _runner(key):
    """Synthetic pool/tables at the bucketed shape for measured search."""
    B, MB, BS, KV, rep, D, dtype = key
    rng = np.random.RandomState(0)
    NB = max(B * MB + 1, 2)
    kpool = jnp.asarray(rng.randn(NB, BS, KV, D), dtype)
    vpool = jnp.asarray(rng.randn(NB, BS, KV, D), dtype)
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
        return lambda: fn(q, kpool, vpool, tables, pos)

    return make


register_kernel(
    "paged_attention",
    defaults={"rows_per_program": 1, "score_mode": "live"},
    space={"rows_per_program": (1, 2, 4), "score_mode": ("live", "full")},
    runner=_runner,
    valid=_valid,
)
