"""Flash attention — Pallas TPU kernel.

Replaces the reference's fused attention CUDA kernels
(``paddle/fluid/operators/fused/fused_attention_op.cu``, ``fmha_ref.h``) with
a TPU-native blockwise kernel: Q blocks stream over K/V blocks held in VMEM,
softmax is accumulated online (running max + sum), the T×T score matrix never
reaches HBM. Forward stores the logsumexp so the backward recomputes
probabilities row-block-wise.

Layout: q, k, v are (B, T, H, D) paddle-convention; kernel operates on
(B*H, T, D). D must be ≤ 256 and a multiple of 8 for clean tiling; T must be
a multiple of the block size (the functional pads otherwise).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from . import interpret_default, kernel_x64_off

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

_NEG_INF = -1e30

# The framework pins jax_default_matmul_precision="highest" (fp32 parity for
# f32 tests); Mosaic rejects fp32 contract precision on bf16 operands, and the
# MXU's native mode is bf16×bf16→f32 anyway. For f32 inputs keep HIGHEST
# (true fp32 passes — the pre-rework accuracy); dtype is known at trace time.
def _prec(dtype):
    return (
        jax.lax.Precision.HIGHEST
        if dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *, block_k: int, causal: bool, scale: float, n_kv: int, kv_len: int):
    # STREAMED K/V: grid is (BH, n_q, n_kv) with the kv dim innermost, so K/V
    # arrive one (1, BK, D) block at a time (Pallas double-buffers the fetch
    # under the previous block's compute) and VMEM never holds (T, D) — this
    # is what makes 32k+ sequences fit. Running max / sum / output accumulate
    # in VMEM scratch across the kv steps of one q block.
    # q_ref: (1, BQ, D); k_ref/v_ref: (1, BK, D); o_ref: (1, BQ, D);
    # lse_ref: (1, 1, BQ) — lse rides the LANE axis ((T, 1) single-lane VMEM
    # blocks crash Mosaic at T=8192; (1, T) tiles fine)
    iq = pl.program_id(1)
    ikv = pl.program_id(2)
    bq = q_ref.shape[1]
    _PREC = _prec(q_ref.dtype)

    @pl.when(ikv == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    # causal: block is live iff some q_pos >= some k_pos, i.e. the block's
    # max q_pos reaches its min k_pos. Dead blocks skip COMPUTE only — the
    # sweep still fetches them (affine index maps keep the DMA pipelined;
    # see _kv_index_map).
    live = ((iq + 1) * bq > ikv * block_k) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]  # (BQ, D) — keep input dtype: MXU does bf16×bf16→f32
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        m, l = m_sc[:], l_sc[:]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
        ) * jnp.float32(scale)  # (BQ, BK) f32 accum
        if causal or kv_len < n_kv * block_k:
            q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            k_pos = ikv * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            valid = k_pos < kv_len  # zero-padded keys must not attend
            if causal:
                valid = valid & (q_pos >= k_pos)
            s = jnp.where(valid, s, jnp.float32(_NEG_INF))
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        m_sc[:] = m_new
        l_sc[:] = l * alpha + jnp.sum(p, axis=1)
        acc_sc[:] = acc_sc[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        l_safe = jnp.maximum(l_sc[:], jnp.float32(1e-30))
        o_ref[0] = (acc_sc[:] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, :] = m_sc[:] + jnp.log(l_safe)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, kv_len):
    # q: (BH, T, D)
    with kernel_x64_off(interpret):
        return _flash_fwd_inner(q, k, v, causal, block_q, block_k, interpret, kv_len)


def _kv_index_map():
    """K/V block index for grid step (b, iq, ikv) of the streamed kernels.

    Deliberately AFFINE (plain sweep) even for causal: clamping dead ikv to
    the last live block (to skip their fetch) makes the map non-affine, which
    disables Mosaic's pipelined double-buffering — measured 2.8x SLOWER at
    32k than sweeping every block and skipping only the compute (pl.when in
    the kernels). Dead-block DMA is cheap; a serialized pipeline is not."""
    return lambda b, iq, ikv: (b, ikv, 0)


# K/V (and the dkv pass's Q/dO) stay whole-T VMEM-resident up to this byte
# budget; beyond it the streamed-grid kernels take over (see kernel comments)
_RESIDENT_BYTES = 8 * 1024 * 1024


def _resident_ok(t_side: int, d: int, dtype) -> bool:
    return 2 * t_side * d * jnp.dtype(dtype).itemsize <= _RESIDENT_BYTES


# -- MULTI-ROW resident kernels (A/B: LOSES — kept behind a flag) ------------
# Hypothesis (round 5): per-program overhead at short T (each (b·h, q-block)
# program runs ~2 small (BQ,BK)·D matmuls) capped the kernel at ~27 TF/s,
# since the same matmul chain hits ~95 TF/s with 8 chunks per program at
# T=4096. These kernels batch ROWS (b·h pairs) per program to amortize it.
# MEASURED A/B at (B=8,H=16,T=1024,D=64) bf16, 24-layer chain, v5e:
#   single-row  fwd 1.118 ms/layer   fwd+bwd 1.998 ms/layer
#   rows=8/4    fwd 1.250 ms/layer   fwd+bwd 2.148 ms/layer   <- LOSES ~7%
#   (also tried: chunk-outer/rows-inner with one fori per program: 1.41-1.53;
#    static-unrolled row loop: 0.98; native (B,T,H·D) two-pass layout: 2.09)
# The per-program-overhead theory did not survive contact: the win at long T
# comes from fori steady-state, which row batching does not create. Flag kept
# so the A/B is reproducible.
_MULTI_ROW = False

def _pick_rows(bh: int, t: int, d: int, dtype, arrays: int, budget=10 * 1024 * 1024) -> int:
    """Rows per program: largest R | bh with `arrays` resident (T, D) buffers
    (double-buffered) under the VMEM budget."""
    es = jnp.dtype(dtype).itemsize
    for r in (8, 4, 2):
        if bh % r == 0 and arrays * r * t * d * es * 2 <= budget:
            return r
    return 1


def _fwd_kernel_multi(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int, causal: bool, scale: float, t_kv: int, kv_len: int, rows: int):
    # q/o: (R, BQ, D); k/v: (R, T, D); lse: (R, 1, BQ)
    iq = pl.program_id(1)
    bq = q_ref.shape[1]
    d = q_ref.shape[2]
    _PREC = _prec(q_ref.dtype)
    n_kb = t_kv // block_k
    if causal and bq == block_k:
        last_kb = jnp.minimum(iq + 1, n_kb)
    else:
        last_kb = n_kb

    def row(r, _):
        q = q_ref[r]  # (BQ, D)

        def body(kb, carry):
            m, l, acc = carry
            k_blk = k_ref[r, pl.ds(kb * block_k, block_k), :]
            v_blk = v_ref[r, pl.ds(kb * block_k, block_k), :]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
            ) * jnp.float32(scale)
            if causal or kv_len < t_kv:
                q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
                k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
                valid = k_pos < kv_len
                if causal:
                    valid = valid & (q_pos >= k_pos)
                s = jnp.where(valid, s, jnp.float32(_NEG_INF))
            m_new = jnp.maximum(m, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=1)
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_PREC,
            )
            return m_new, l, acc

        m, l, acc = jax.lax.fori_loop(
            0, last_kb, body,
            (jnp.full((bq,), _NEG_INF, jnp.float32), jnp.zeros((bq,), jnp.float32),
             jnp.zeros((bq, d), jnp.float32)),
        )
        l_safe = jnp.maximum(l, jnp.float32(1e-30))
        o_ref[r] = (acc / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[r, 0, :] = m + jnp.log(l_safe)
        return 0

    jax.lax.fori_loop(0, rows, row, 0)


def _dq_kernel_multi(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, block_k: int, causal: bool, scale: float, t_kv: int, kv_len: int, rows: int):
    # q/do/dq: (R, BQ, D); k/v: (R, T, D); lse/delta: (R, 1, BQ)
    iq = pl.program_id(1)
    bq = q_ref.shape[1]
    d = q_ref.shape[2]
    _PREC = _prec(q_ref.dtype)
    n_kb = t_kv // block_k
    if causal and bq == block_k:
        last_kb = jnp.minimum(iq + 1, n_kb)
    else:
        last_kb = n_kb

    def row(r, _):
        q = q_ref[r]
        do = do_ref[r]
        lse = lse_ref[r, 0, :]
        delta = delta_ref[r, 0, :]

        def body(kb, acc):
            k_blk = k_ref[r, pl.ds(kb * block_k, block_k), :]
            v_blk = v_ref[r, pl.ds(kb * block_k, block_k), :]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
            ) * jnp.float32(scale)
            if causal or kv_len < t_kv:
                q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
                k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
                valid = k_pos < kv_len
                if causal:
                    valid = valid & (q_pos >= k_pos)
                s = jnp.where(valid, s, jnp.float32(_NEG_INF))
            p = jnp.exp(s - lse[:, None])
            dp = jax.lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
            )
            ds = p * (dp - delta[:, None])
            return acc + jax.lax.dot_general(
                ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_PREC,
            )

        acc = jax.lax.fori_loop(0, last_kb, body, jnp.zeros((bq, d), jnp.float32))
        dq_ref[r] = (acc * jnp.float32(scale)).astype(dq_ref.dtype)
        return 0

    jax.lax.fori_loop(0, rows, row, 0)


def _dkv_kernel_multi(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, block_q: int, causal: bool, scale: float, t_q: int, kv_len: int, rows: int):
    # k/v/dk/dv: (R, BK, D); q/do: (R, T, D); lse/delta: (R, 1, T)
    ik = pl.program_id(1)
    bk = k_ref.shape[1]
    d = k_ref.shape[2]
    _PREC = _prec(k_ref.dtype)
    n_qb = t_q // block_q
    first_qb = ik if (causal and bk == block_q) else 0

    def row(r, _):
        k_blk = k_ref[r]  # (BK, D)
        v_blk = v_ref[r]

        def body(qb, carry):
            dk, dv = carry
            qq = q_ref[r, pl.ds(qb * block_q, block_q), :]
            do = do_ref[r, pl.ds(qb * block_q, block_q), :]
            lse = lse_ref[r, 0, pl.ds(qb * block_q, block_q)]
            delta = delta_ref[r, 0, pl.ds(qb * block_q, block_q)]
            s = jax.lax.dot_general(
                qq, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
            ) * jnp.float32(scale)
            q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
            k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
            valid = k_pos < kv_len
            if causal:
                valid = valid & (q_pos >= k_pos)
            s = jnp.where(valid, s, jnp.float32(_NEG_INF))
            p = jnp.exp(s - lse[:, None])
            dv = dv + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_PREC,
            )
            dp = jax.lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
            )
            ds = p * (dp - delta[:, None]) * jnp.float32(scale)
            dk = dk + jax.lax.dot_general(
                ds.astype(qq.dtype), qq, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_PREC,
            )
            return dk, dv

        dk, dv = jax.lax.fori_loop(
            first_qb, n_qb, body,
            (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)),
        )
        dk_ref[r] = dk.astype(dk_ref.dtype)
        dv_ref[r] = dv.astype(dv_ref.dtype)
        return 0

    jax.lax.fori_loop(0, rows, row, 0)


def _flash_fwd_inner(q, k, v, causal, block_q, block_k, interpret, kv_len):
    bh, t, d = q.shape
    t_kv = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    n_kv = t_kv // block_k

    if _resident_ok(t_kv, d, k.dtype):
        rows = _pick_rows(bh, t_kv, d, k.dtype, arrays=2)  # K+V resident
        if _MULTI_ROW and rows > 1 and t == t_kv:
            out, lse = pl.pallas_call(
                functools.partial(
                    _fwd_kernel_multi, block_k=block_k, causal=causal,
                    scale=scale, t_kv=t_kv, kv_len=kv_len, rows=rows,
                ),
                name="flash_fwd",
                grid=(bh // rows, t // block_q),
                in_specs=[
                    pl.BlockSpec((rows, block_q, d), lambda b, i: (b, i, 0)),
                    pl.BlockSpec((rows, t_kv, d), lambda b, i: (b, 0, 0)),
                    pl.BlockSpec((rows, t_kv, d), lambda b, i: (b, 0, 0)),
                ],
                out_specs=[
                    pl.BlockSpec((rows, block_q, d), lambda b, i: (b, i, 0)),
                    pl.BlockSpec((rows, 1, block_q), lambda b, i: (b, 0, i)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((bh, t, d), q.dtype),
                    jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
                ],
                interpret=interpret,
            )(q, k, v)
            return out, lse
        out, lse = pl.pallas_call(
            functools.partial(
                _fwd_kernel_resident, block_k=block_k, causal=causal,
                scale=scale, t_kv=t_kv, kv_len=kv_len,
            ),
            name="flash_fwd",
            grid=(bh, t // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, t_kv, d), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, t_kv, d), lambda b, i: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, t, d), q.dtype),
                jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
            ],
            interpret=interpret,
        )(q, k, v)
        return out, lse

    grid = (bh, t // block_q, n_kv)
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, causal=causal, scale=scale, n_kv=n_kv,
        kv_len=kv_len,
    )
    kv_map = _kv_index_map()
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse




# -- RESIDENT-K/V kernels (short/medium sequences) ---------------------------
# Whole K/V (or Q/dO for the dkv pass) stays VMEM-resident across the block
# loop: fetched once per (batch*head) row and reused by every q block. For
# sequences that fit (the common <=8k training case) this beats the streamed
# grid by avoiding the per-q-block re-stream of the whole K/V prefix
# (measured 2.5x at 8k); the streamed kernels above exist for the lengths
# where (T, D) simply cannot sit in VMEM (32k+).

def _fwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int, causal: bool, scale: float, t_kv: int, kv_len: int):
    # q_ref: (1, BQ, D); k_ref/v_ref: (1, T, D); o_ref: (1, BQ, D); lse_ref: (1, 1, BQ)
    # lse/delta ride the LANE axis: a (T, 1) single-lane VMEM block crashes
    # the Mosaic compiler at T=8192 (one f32 per 8x128 tile); (1, T) tiles fine
    iq = pl.program_id(1)
    bq = q_ref.shape[1]
    d = q_ref.shape[2]
    q = q_ref[0]  # (BQ, D) — keep input dtype: MXU does bf16×bf16→f32
    _PREC = _prec(q.dtype)

    m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)

    n_kb = t_kv // block_k

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
        ) * jnp.float32(scale)  # (BQ, BK) f32 accum
        if causal or kv_len < t_kv:
            q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            valid = k_pos < kv_len  # zero-padded keys must not attend
            if causal:
                valid = valid & (q_pos >= k_pos)
            s = jnp.where(valid, s, jnp.float32(_NEG_INF))
        m_blk = jnp.max(s, axis=1)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )
        return m_new, l_new, acc_new

    if causal and bq == block_k:
        # equal q/k blocks: q block iq attends k blocks 0..iq (no division —
        # in-kernel int64 promotion breaks the Mosaic lowering under x64)
        last_kb = jnp.minimum(iq + 1, n_kb)
    else:
        last_kb = n_kb
    m, l, acc = jax.lax.fori_loop(0, last_kb, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, jnp.float32(1e-30))
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0, :] = m + jnp.log(l_safe)


def _dq_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, block_k: int, causal: bool, scale: float, t_kv: int, kv_len: int):
    # q/do/dq: (1, BQ, D); k/v: (1, T, D); lse/delta: (1, 1, BQ)
    iq = pl.program_id(1)
    bq = q_ref.shape[1]
    q = q_ref[0]  # (BQ, D)
    _PREC = _prec(q.dtype)
    do = do_ref[0]
    lse = lse_ref[0, 0, :]
    delta = delta_ref[0, 0, :]
    n_kb = t_kv // block_k

    def body(kb, acc):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
        ) * jnp.float32(scale)  # (BQ, BK)
        if causal or kv_len < t_kv:
            q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            valid = k_pos < kv_len  # zero-padded keys must not attend
            if causal:
                valid = valid & (q_pos >= k_pos)
            s = jnp.where(valid, s, jnp.float32(_NEG_INF))
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
        )  # (BQ, BK)
        ds = p * (dp - delta[:, None])
        return acc + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )

    if causal and bq == block_k:
        last_kb = jnp.minimum(iq + 1, n_kb)
    else:
        last_kb = n_kb
    acc = jax.lax.fori_loop(0, last_kb, body, jnp.zeros((bq, q_ref.shape[2]), jnp.float32))
    dq_ref[0] = (acc * jnp.float32(scale)).astype(dq_ref.dtype)


def _dkv_kernel_resident(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, block_q: int, causal: bool, scale: float, t_q: int, kv_len: int):
    # k/v/dk/dv: (1, BK, D); q/do: (1, T, D); lse/delta: (1, 1, T)
    ik = pl.program_id(1)
    bk = k_ref.shape[1]
    d = k_ref.shape[2]
    k_blk = k_ref[0]  # (BK, D)
    _PREC = _prec(k_blk.dtype)
    v_blk = v_ref[0]
    n_qb = t_q // block_q

    def body(qb, carry):
        dk, dv = carry
        qq = q_ref[0, pl.ds(qb * block_q, block_q), :]
        do = do_ref[0, pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(qb * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(qb * block_q, block_q)]
        s = jax.lax.dot_general(
            qq, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
        ) * jnp.float32(scale)  # (BQ, BK)
        q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
        k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
        valid = k_pos < kv_len  # zero-padded keys contribute nothing
        if causal:
            valid = valid & (q_pos >= k_pos)
        s = jnp.where(valid, s, jnp.float32(_NEG_INF))
        p = jnp.exp(s - lse[:, None])  # (BQ, BK)
        dv = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )  # (BK, D)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
        )  # (BQ, BK)
        ds = p * (dp - delta[:, None]) * jnp.float32(scale)
        dk = dk + jax.lax.dot_general(
            ds.astype(qq.dtype), qq, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )  # (BK, D)
        return dk, dv

    if causal and bk == block_q:
        first_qb = ik  # q blocks strictly before this k block are fully masked
    else:
        first_qb = 0
    dk, dv = jax.lax.fori_loop(
        first_qb, n_qb, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)),
    )
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# -- NATIVE-LAYOUT (B, T, H·D) resident kernels -------------------------------
# The (B,T,H,D)→(B·H,T,D) swapaxes around the BH kernels are real per-layer
# HBM transposes in a model (each layer has its own k/v — XLA cannot hoist
# them the way a k/v-reusing microbenchmark lets it). These kernels read the
# contiguous (B, T, H·D) view (a FREE reshape of the paddle layout — exactly
# what the QKV projection emits) with `hp` heads per program so the lane
# width hp·D tiles the 128-lane axis (hp=2 for D=64). Softmax is two-pass
# against a VMEM score scratch: pass A writes score chunks and the true row
# max, pass B does exp exactly once — no per-chunk accumulator rescaling.

def _fwd_kernel_hd(q_ref, k_ref, v_ref, o_ref, lse_ref, s_sc, *, block_k: int, causal: bool, scale: float, t_kv: int, kv_len: int, d: int, hp: int):
    # q/o: (1, BQ, hp·D); k/v: (1, T, hp·D); lse: (1, 1, hp, BQ); s_sc: (BQ, T) f32
    iq = pl.program_id(2)
    bq = q_ref.shape[1]
    _PREC = _prec(q_ref.dtype)
    n_kb = t_kv // block_k
    if causal and bq == block_k:
        last_kb = jnp.minimum(iq + 1, n_kb)
    else:
        last_kb = n_kb

    for hi in range(hp):
        q = q_ref[0, :, hi * d:(hi + 1) * d]  # (BQ, D)

        def pass_a(kb, m, _q=q):
            k_blk = k_ref[0, pl.ds(kb * block_k, block_k), hi * d:(hi + 1) * d]
            s = jax.lax.dot_general(
                _q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
            ) * jnp.float32(scale)  # (BQ, BK)
            if causal or kv_len < t_kv:
                q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
                k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
                valid = k_pos < kv_len
                if causal:
                    valid = valid & (q_pos >= k_pos)
                s = jnp.where(valid, s, jnp.float32(_NEG_INF))
            s_sc[:, pl.ds(kb * block_k, block_k)] = s
            return jnp.maximum(m, jnp.max(s, axis=1))

        m = jax.lax.fori_loop(0, last_kb, pass_a, jnp.full((bq,), _NEG_INF, jnp.float32))

        def pass_b(kb, carry):
            l, acc = carry
            v_blk = v_ref[0, pl.ds(kb * block_k, block_k), hi * d:(hi + 1) * d]
            p = jnp.exp(s_sc[:, pl.ds(kb * block_k, block_k)] - m[:, None])
            l = l + jnp.sum(p, axis=1)
            acc = acc + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_PREC,
            )
            return l, acc

        l, acc = jax.lax.fori_loop(
            0, last_kb, pass_b,
            (jnp.zeros((bq,), jnp.float32), jnp.zeros((bq, d), jnp.float32)),
        )
        l_safe = jnp.maximum(l, jnp.float32(1e-30))
        o_ref[0, :, hi * d:(hi + 1) * d] = (acc / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, hi, :] = m + jnp.log(l_safe)


def _dq_kernel_hd(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, block_k: int, causal: bool, scale: float, t_kv: int, kv_len: int, d: int, hp: int):
    # q/do/dq: (1, BQ, hp·D); k/v: (1, T, hp·D); lse/delta: (1, 1, hp, BQ)
    iq = pl.program_id(2)
    bq = q_ref.shape[1]
    _PREC = _prec(q_ref.dtype)
    n_kb = t_kv // block_k
    if causal and bq == block_k:
        last_kb = jnp.minimum(iq + 1, n_kb)
    else:
        last_kb = n_kb

    for hi in range(hp):
        q = q_ref[0, :, hi * d:(hi + 1) * d]
        do = do_ref[0, :, hi * d:(hi + 1) * d]
        lse = lse_ref[0, 0, hi, :]
        delta = delta_ref[0, 0, hi, :]

        def body(kb, acc, _q=q, _do=do, _lse=lse, _delta=delta):
            k_blk = k_ref[0, pl.ds(kb * block_k, block_k), hi * d:(hi + 1) * d]
            v_blk = v_ref[0, pl.ds(kb * block_k, block_k), hi * d:(hi + 1) * d]
            s = jax.lax.dot_general(
                _q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
            ) * jnp.float32(scale)
            if causal or kv_len < t_kv:
                q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
                k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
                valid = k_pos < kv_len
                if causal:
                    valid = valid & (q_pos >= k_pos)
                s = jnp.where(valid, s, jnp.float32(_NEG_INF))
            p = jnp.exp(s - _lse[:, None])
            dp = jax.lax.dot_general(
                _do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
            )
            ds = p * (dp - _delta[:, None])
            return acc + jax.lax.dot_general(
                ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_PREC,
            )

        acc = jax.lax.fori_loop(0, last_kb, body, jnp.zeros((bq, d), jnp.float32))
        dq_ref[0, :, hi * d:(hi + 1) * d] = (acc * jnp.float32(scale)).astype(dq_ref.dtype)


def _dkv_kernel_hd(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, block_q: int, causal: bool, scale: float, t_q: int, kv_len: int, d: int, hp: int):
    # k/v/dk/dv: (1, BK, hp·D); q/do: (1, T, hp·D); lse/delta: (1, 1, hp, T)
    ik = pl.program_id(2)
    bk = k_ref.shape[1]
    _PREC = _prec(k_ref.dtype)
    n_qb = t_q // block_q
    first_qb = ik if (causal and bk == block_q) else 0

    for hi in range(hp):
        k_blk = k_ref[0, :, hi * d:(hi + 1) * d]  # (BK, D)
        v_blk = v_ref[0, :, hi * d:(hi + 1) * d]

        def body(qb, carry, _k=k_blk, _v=v_blk):
            dk, dv = carry
            qq = q_ref[0, pl.ds(qb * block_q, block_q), hi * d:(hi + 1) * d]
            do = do_ref[0, pl.ds(qb * block_q, block_q), hi * d:(hi + 1) * d]
            lse = lse_ref[0, 0, hi, pl.ds(qb * block_q, block_q)]
            delta = delta_ref[0, 0, hi, pl.ds(qb * block_q, block_q)]
            s = jax.lax.dot_general(
                qq, _k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
            ) * jnp.float32(scale)  # (BQ, BK)
            q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
            k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
            valid = k_pos < kv_len
            if causal:
                valid = valid & (q_pos >= k_pos)
            s = jnp.where(valid, s, jnp.float32(_NEG_INF))
            p = jnp.exp(s - lse[:, None])
            dv = dv + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_PREC,
            )
            dp = jax.lax.dot_general(
                do, _v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
            )
            ds = p * (dp - delta[:, None]) * jnp.float32(scale)
            dk = dk + jax.lax.dot_general(
                ds.astype(qq.dtype), qq, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_PREC,
            )
            return dk, dv

        dk, dv = jax.lax.fori_loop(
            first_qb, n_qb, body,
            (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)),
        )
        dk_ref[0, :, hi * d:(hi + 1) * d] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, hi * d:(hi + 1) * d] = dv.astype(dv_ref.dtype)


def _flash_hd_fwd_inner(q, k, v, causal, block_q, block_k, interpret, kv_len, d, hp):
    b, t, hd = q.shape
    t_kv = k.shape[1]
    g = hd // (hp * d)
    w = hp * d
    scale = 1.0 / math.sqrt(d)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel_hd, block_k=block_k, causal=causal, scale=scale,
            t_kv=t_kv, kv_len=kv_len, d=d, hp=hp,
        ),
        name="flash_fwd",
        grid=(b, g, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, w), lambda bb, gg, i: (bb, i, gg)),
            pl.BlockSpec((1, t_kv, w), lambda bb, gg, i: (bb, 0, gg)),
            pl.BlockSpec((1, t_kv, w), lambda bb, gg, i: (bb, 0, gg)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, w), lambda bb, gg, i: (bb, i, gg)),
            pl.BlockSpec((1, 1, hp, block_q), lambda bb, gg, i: (bb, gg, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, hd), q.dtype),
            jax.ShapeDtypeStruct((b, g, hp, t), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, t_kv), jnp.float32)],
        interpret=interpret,
    )(q, k, v)
    return out, lse


def _flash_hd_bwd_inner(q, k, v, out, lse, do, causal, block_q, block_k, interpret, kv_len, d, hp):
    b, t, hd = q.shape
    t_kv = k.shape[1]
    h = hd // d
    g = h // hp
    w = hp * d
    scale = 1.0 / math.sqrt(d)
    # delta_i = dO_i · O_i per head, laid out (B, G, hp, T): rows on lanes
    delta = jnp.transpose(
        jnp.sum(
            (do.astype(jnp.float32) * out.astype(jnp.float32)).reshape(b, t, g, hp, d),
            axis=-1,
        ),
        (0, 2, 3, 1),
    )
    dq = pl.pallas_call(
        functools.partial(_dq_kernel_hd, block_k=block_k, causal=causal, scale=scale, t_kv=t_kv, kv_len=kv_len, d=d, hp=hp),
        name="flash_dq",
        grid=(b, g, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, w), lambda bb, gg, i: (bb, i, gg)),
            pl.BlockSpec((1, t_kv, w), lambda bb, gg, i: (bb, 0, gg)),
            pl.BlockSpec((1, t_kv, w), lambda bb, gg, i: (bb, 0, gg)),
            pl.BlockSpec((1, block_q, w), lambda bb, gg, i: (bb, i, gg)),
            pl.BlockSpec((1, 1, hp, block_q), lambda bb, gg, i: (bb, gg, 0, i)),
            pl.BlockSpec((1, 1, hp, block_q), lambda bb, gg, i: (bb, gg, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, w), lambda bb, gg, i: (bb, i, gg)),
        out_shape=jax.ShapeDtypeStruct((b, t, hd), q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel_hd, block_q=block_q, causal=causal, scale=scale, t_q=t, kv_len=kv_len, d=d, hp=hp),
        name="flash_dkv",
        grid=(b, g, t_kv // block_k),
        in_specs=[
            pl.BlockSpec((1, block_k, w), lambda bb, gg, j: (bb, j, gg)),
            pl.BlockSpec((1, block_k, w), lambda bb, gg, j: (bb, j, gg)),
            pl.BlockSpec((1, t, w), lambda bb, gg, j: (bb, 0, gg)),
            pl.BlockSpec((1, t, w), lambda bb, gg, j: (bb, 0, gg)),
            pl.BlockSpec((1, 1, hp, t), lambda bb, gg, j: (bb, gg, 0, 0)),
            pl.BlockSpec((1, 1, hp, t), lambda bb, gg, j: (bb, gg, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, w), lambda bb, gg, j: (bb, j, gg)),
            pl.BlockSpec((1, block_k, w), lambda bb, gg, j: (bb, j, gg)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t_kv, hd), k.dtype),
            jax.ShapeDtypeStruct((b, t_kv, hd), v.dtype),
        ],
        interpret=interpret,
    )(k, v, q, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_hd(q, k, v, causal, block_q, block_k, interpret, kv_len, d, hp):
    with kernel_x64_off(interpret):
        out, _ = _flash_hd_fwd_inner(q, k, v, causal, block_q, block_k, interpret, kv_len, d, hp)
    return out


def _flash_hd_vjp_fwd(q, k, v, causal, block_q, block_k, interpret, kv_len, d, hp):
    with kernel_x64_off(interpret):
        out, lse = _flash_hd_fwd_inner(q, k, v, causal, block_q, block_k, interpret, kv_len, d, hp)
    return out, (q, k, v, out, lse)


def _flash_hd_vjp_bwd(causal, block_q, block_k, interpret, kv_len, d, hp, res, do):
    q, k, v, out, lse = res
    with kernel_x64_off(interpret):
        return _flash_hd_bwd_inner(q, k, v, out, lse, do, causal, block_q, block_k, interpret, kv_len, d, hp)


_flash_hd.defvjp(_flash_hd_vjp_fwd, _flash_hd_vjp_bwd)


def _hd_heads_per_program(h: int, d: int):
    """Heads per program so the lane width hp·D tiles 128 lanes; None if the
    native-layout path can't tile this head shape."""
    if d % 128 == 0:
        return 1
    if 128 % d == 0 and h % (128 // d) == 0:
        return 128 // d
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, interpret, kv_len):
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret, kv_len)
    return out


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, interpret, kv_len):
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k, interpret, kv_len)
    return out, (q, k, v, out, lse)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_sc, *, block_k: int, causal: bool, scale: float, n_kv: int, kv_len: int):
    # STREAMED K/V, grid (BH, n_q, n_kv): q/do/dq: (1, BQ, D);
    # k/v: (1, BK, D); lse/delta: (1, 1, BQ); dq accumulates in scratch.
    iq = pl.program_id(1)
    ikv = pl.program_id(2)
    bq = q_ref.shape[1]
    _PREC = _prec(q_ref.dtype)

    @pl.when(ikv == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)

    live = ((iq + 1) * bq > ikv * block_k) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]  # (BQ, D)
        do = do_ref[0]
        lse = lse_ref[0, 0, :]
        delta = delta_ref[0, 0, :]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
        ) * jnp.float32(scale)  # (BQ, BK)
        if causal or kv_len < n_kv * block_k:
            q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            k_pos = ikv * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            valid = k_pos < kv_len  # zero-padded keys must not attend
            if causal:
                valid = valid & (q_pos >= k_pos)
            s = jnp.where(valid, s, jnp.float32(_NEG_INF))
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
        )  # (BQ, BK)
        ds = p * (dp - delta[:, None])
        acc_sc[:] = acc_sc[:] + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        dq_ref[0] = (acc_sc[:] * jnp.float32(scale)).astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_sc, dv_sc, *, block_q: int, causal: bool, scale: float, n_q: int, kv_len: int):
    # STREAMED Q/dO, grid (BH, n_kv, n_q): k/v/dk/dv: (1, BK, D);
    # q/do: (1, BQ, D); lse/delta: (1, 1, BQ); dk/dv accumulate in scratch.
    ik = pl.program_id(1)
    iqb = pl.program_id(2)
    bk = k_ref.shape[1]
    _PREC = _prec(k_ref.dtype)

    @pl.when(iqb == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    live = ((iqb + 1) * block_q > ik * bk) if causal else True

    @pl.when(live)
    def _compute():
        k_blk = k_ref[0]  # (BK, D)
        v_blk = v_ref[0]
        qq = q_ref[0]  # (BQ, D)
        do = do_ref[0]
        lse = lse_ref[0, 0, :]
        delta = delta_ref[0, 0, :]
        s = jax.lax.dot_general(
            qq, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
        ) * jnp.float32(scale)  # (BQ, BK)
        q_pos = iqb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
        k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
        valid = k_pos < kv_len  # zero-padded keys contribute nothing
        if causal:
            valid = valid & (q_pos >= k_pos)
        s = jnp.where(valid, s, jnp.float32(_NEG_INF))
        p = jnp.exp(s - lse[:, None])  # (BQ, BK)
        dv_sc[:] = dv_sc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )  # (BK, D)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
        )  # (BQ, BK)
        ds = p * (dp - delta[:, None]) * jnp.float32(scale)
        dk_sc[:] = dk_sc[:] + jax.lax.dot_general(
            ds.astype(qq.dtype), qq, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )  # (BK, D)

    @pl.when(iqb == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _q_index_map(lane: bool = False):
    """Q/dO (lane=False) or lse/delta (lane=True: the block rides the lane
    axis) index for grid step (b, ik, iqb) of the dkv pass. Affine for the
    same pipelining reason as _kv_index_map; dead blocks skip compute only."""

    def imap(b, ik, iqb):
        return (b, 0, iqb) if lane else (b, iqb, 0)

    return imap


# A/B: MERGED backward LOSES — kept behind _MERGED_BWD for reproducibility.
# Measured at (B=2,H=16,T=8192,D=64) bf16, 24-layer chain, v5e:
#   two-kernel bwd (dq + dkv): fwd+bwd 12.64 ms/layer
#   merged single-sweep bwd:   fwd+bwd 16.94 ms/layer   <- LOSES 34%
# The saved score/dp recompute (2 of 7 matmuls) is outweighed by the
# per-iteration read-modify-write of the persistent (T, D) f32 dq scratch.
_MERGED_BWD = False


def _dfused_kernel_resident(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dq_ref, dq_sc, *, block_q: int, causal: bool, scale: float, t_q: int, kv_len: int, n_kv: int):
    # MERGED backward: one sweep computes dq, dk, dv — the separate dq pass's
    # score and dp recomputes (2 of the 7 backward matmuls, plus one of the
    # two exp passes) disappear. Grid (BH, n_kv): dk/dv are per-block
    # outputs; dq accumulates in a PERSISTENT f32 VMEM scratch across the
    # consecutive ik steps of one row and is written once at ik == n_kv-1
    # (the dq output block is the full (1, T, D) row, revisited across ik).
    # k/v/dk/dv: (1, BK, D); q/do: (1, T, D); lse/delta: (1, 1, T);
    # dq: (1, T, D); dq_sc: (T, D) f32.
    ik = pl.program_id(1)
    bk = k_ref.shape[1]
    d = k_ref.shape[2]
    k_blk = k_ref[0]  # (BK, D)
    _PREC = _prec(k_blk.dtype)
    v_blk = v_ref[0]
    n_qb = t_q // block_q

    @pl.when(ik == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    def body(qb, carry):
        dk, dv = carry
        qq = q_ref[0, pl.ds(qb * block_q, block_q), :]
        do = do_ref[0, pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(qb * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(qb * block_q, block_q)]
        s = jax.lax.dot_general(
            qq, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
        ) * jnp.float32(scale)  # (BQ, BK)
        q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
        k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
        valid = k_pos < kv_len
        if causal:
            valid = valid & (q_pos >= k_pos)
        s = jnp.where(valid, s, jnp.float32(_NEG_INF))
        p = jnp.exp(s - lse[:, None])  # (BQ, BK)
        dv = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )  # (BK, D)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_PREC
        )  # (BQ, BK)
        ds = p * (dp - delta[:, None])  # unscaled; scale folded at the writes
        dsb = ds.astype(qq.dtype)
        dk = dk + jax.lax.dot_general(
            dsb, qq, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_PREC,
        )  # (BK, D)
        dq_sc[pl.ds(qb * block_q, block_q), :] = (
            dq_sc[pl.ds(qb * block_q, block_q), :]
            + jax.lax.dot_general(
                dsb, k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_PREC,
            )
        )
        return dk, dv

    first_qb = ik if (causal and bk == block_q) else 0
    dk, dv = jax.lax.fori_loop(
        first_qb, n_qb, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)),
    )
    dk_ref[0] = (dk * jnp.float32(scale)).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(ik == n_kv - 1)
    def _finalize():
        dq_ref[0] = (dq_sc[:] * jnp.float32(scale)).astype(dq_ref.dtype)


def _flash_bwd_inner(q, k, v, out, lse, do, causal, block_q, block_k, interpret, kv_len):
    bh, t, d = q.shape
    t_kv = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    n_kv = t_kv // block_k
    n_q = t // block_q
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, None, :]  # (BH, 1, T)

    if _resident_ok(max(t, t_kv), d, q.dtype):
        # merged single-sweep backward: needs q/do resident + a (T, D) f32
        # dq accumulator scratch + k/v blocks; square self-attention only
        # (causal block skip + the dq row write assume t == t_kv)
        if (_MERGED_BWD and t == t_kv and block_q == block_k
                and t * d * 4 <= 4 * 1024 * 1024):
            dk, dv, dq = pl.pallas_call(
                functools.partial(
                    _dfused_kernel_resident, block_q=block_q, causal=causal,
                    scale=scale, t_q=t, kv_len=kv_len, n_kv=n_kv,
                ),
                name="flash_bwd_fused",
                grid=(bh, n_kv),
                in_specs=[
                    pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
                    pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
                    pl.BlockSpec((1, t, d), lambda b, j: (b, 0, 0)),
                    pl.BlockSpec((1, t, d), lambda b, j: (b, 0, 0)),
                    pl.BlockSpec((1, 1, t), lambda b, j: (b, 0, 0)),
                    pl.BlockSpec((1, 1, t), lambda b, j: (b, 0, 0)),
                ],
                out_specs=[
                    pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
                    pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
                    pl.BlockSpec((1, t, d), lambda b, j: (b, 0, 0)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((bh, t_kv, d), k.dtype),
                    jax.ShapeDtypeStruct((bh, t_kv, d), v.dtype),
                    jax.ShapeDtypeStruct((bh, t, d), q.dtype),
                ],
                scratch_shapes=[pltpu.VMEM((t, d), jnp.float32)],
                interpret=interpret,
            )(k, v, q, do, lse, delta)
            return dq, dk, dv
        # Both bwd kernels stream 4 (T,D)-class operands + 2 lse rows and
        # carry several live (BQ,BK) f32 temporaries, so they get a tighter
        # row cap than the fwd: rows=8 measured 20 KB over the 16 MB
        # scoped-vmem limit at T=1024/D=64; rows=4 fits.
        rows = 1
        if _MULTI_ROW:
            rows = _pick_rows(bh, max(t, t_kv), d, q.dtype, arrays=2)
            while rows > 4:  # bwd hard cap: 8 rows = 16.02M scoped vmem (OOM)
                rows //= 2
        if _MULTI_ROW and rows > 1 and t == t_kv:
            dq = pl.pallas_call(
                functools.partial(_dq_kernel_multi, block_k=block_k, causal=causal, scale=scale, t_kv=t_kv, kv_len=kv_len, rows=rows),
                name="flash_dq",
                grid=(bh // rows, n_q),
                in_specs=[
                    pl.BlockSpec((rows, block_q, d), lambda b, i: (b, i, 0)),
                    pl.BlockSpec((rows, t_kv, d), lambda b, i: (b, 0, 0)),
                    pl.BlockSpec((rows, t_kv, d), lambda b, i: (b, 0, 0)),
                    pl.BlockSpec((rows, block_q, d), lambda b, i: (b, i, 0)),
                    pl.BlockSpec((rows, 1, block_q), lambda b, i: (b, 0, i)),
                    pl.BlockSpec((rows, 1, block_q), lambda b, i: (b, 0, i)),
                ],
                out_specs=pl.BlockSpec((rows, block_q, d), lambda b, i: (b, i, 0)),
                out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
                interpret=interpret,
            )(q, k, v, do, lse, delta)
            dk, dv = pl.pallas_call(
                functools.partial(_dkv_kernel_multi, block_q=block_q, causal=causal, scale=scale, t_q=t, kv_len=kv_len, rows=rows),
                name="flash_dkv",
                grid=(bh // rows, n_kv),
                in_specs=[
                    pl.BlockSpec((rows, block_k, d), lambda b, j: (b, j, 0)),
                    pl.BlockSpec((rows, block_k, d), lambda b, j: (b, j, 0)),
                    pl.BlockSpec((rows, t, d), lambda b, j: (b, 0, 0)),
                    pl.BlockSpec((rows, t, d), lambda b, j: (b, 0, 0)),
                    pl.BlockSpec((rows, 1, t), lambda b, j: (b, 0, 0)),
                    pl.BlockSpec((rows, 1, t), lambda b, j: (b, 0, 0)),
                ],
                out_specs=[
                    pl.BlockSpec((rows, block_k, d), lambda b, j: (b, j, 0)),
                    pl.BlockSpec((rows, block_k, d), lambda b, j: (b, j, 0)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((bh, t_kv, d), k.dtype),
                    jax.ShapeDtypeStruct((bh, t_kv, d), v.dtype),
                ],
                interpret=interpret,
            )(k, v, q, do, lse, delta)
            return dq, dk, dv
        dq = pl.pallas_call(
            functools.partial(_dq_kernel_resident, block_k=block_k, causal=causal, scale=scale, t_kv=t_kv, kv_len=kv_len),
            name="flash_dq",
            grid=(bh, n_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, t_kv, d), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, t_kv, d), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
                pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            interpret=interpret,
        )(q, k, v, do, lse, delta)

        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel_resident, block_q=block_q, causal=causal, scale=scale, t_q=t, kv_len=kv_len),
            name="flash_dkv",
            grid=(bh, n_kv),
            in_specs=[
                pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
                pl.BlockSpec((1, t, d), lambda b, j: (b, 0, 0)),
                pl.BlockSpec((1, t, d), lambda b, j: (b, 0, 0)),
                pl.BlockSpec((1, 1, t), lambda b, j: (b, 0, 0)),
                pl.BlockSpec((1, 1, t), lambda b, j: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, t_kv, d), k.dtype),
                jax.ShapeDtypeStruct((bh, t_kv, d), v.dtype),
            ],
            interpret=interpret,
        )(k, v, q, do, lse, delta)
        return dq, dk, dv

    kv_map = _kv_index_map()
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_k=block_k, causal=causal, scale=scale, n_kv=n_kv, kv_len=kv_len),
        name="flash_dq",
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    q_map = _q_index_map()
    q_map_lane = _q_index_map(lane=True)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, causal=causal, scale=scale, n_q=n_q, kv_len=kv_len),
        name="flash_dkv",
        grid=(bh, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_q), q_map_lane),
            pl.BlockSpec((1, 1, block_q), q_map_lane),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_kv, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t_kv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(k, v, q, do, lse, delta)
    return dq, dk, dv


def _flash_vjp_bwd(causal, block_q, block_k, interpret, kv_len, res, do):
    # Pallas backward: recompute p = exp(q·kᵀ·scale − lse) block-wise in VMEM.
    # Two kernels — dq streams K/V blocks per query block; dk/dv streams Q/dO
    # blocks per key block (causal lower bound skips fully-masked blocks).
    # No (BQ,T) score block or (n_q,BH,T,D) intermediate ever reaches HBM.
    q, k, v, out, lse = res
    with kernel_x64_off(interpret):
        return _flash_bwd_inner(q, k, v, out, lse, do, causal, block_q, block_k, interpret, kv_len)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _pick_block(limit, t):
    # Largest power-of-two block ≤ limit that divides t — avoids zero-padding
    # (a 512-block on T=640 would pad ~60% wasted FLOPs). 512 measured fastest
    # on v5e (vs 128: 1.55× at T=1024, 3.2× at T=8192).
    for b in (limit, 256, 128):
        if b <= limit and t % b == 0 and b % 8 == 0:
            return b
    return 128  # no aligned divisor: 128 block + zero-padding


def flash_attention_array(q, k, v, causal=False, block_q=None, block_k=None, interpret=None):
    """Pure-array flash attention. q,k,v: (B, T, H, D) → (B, T, H, D).

    ``block_q``/``block_k`` default to the kernel registry's resolved config
    (``ops/kernels``: the pinned 512/512 defaults with autotune off, a tuned
    winner otherwise); explicit values bypass the registry. Either way the
    requested blocks flow through ``_pick_block``'s divisibility degrade
    exactly as before the registry existed."""
    if not _HAS_PALLAS:
        raise RuntimeError("pallas unavailable")
    if interpret is None:
        interpret = interpret_default()
    # mixed q/k/v dtypes (e.g. one operand silently upcast to f32 upstream)
    # would pair HIGHEST precision with bf16 operands inside the kernel,
    # which Mosaic rejects — unify on q's dtype
    if k.dtype != q.dtype:
        k = k.astype(q.dtype)
    if v.dtype != q.dtype:
        v = v.astype(q.dtype)
    b, t, h, d = q.shape
    t_kv = k.shape[1]
    if block_q is None or block_k is None:
        from ..kernels import flash_attention_key, resolve_config

        cfg = resolve_config(
            "flash_attention",
            flash_attention_key(b, h, t, t_kv, d, q.dtype, causal))
        block_q = int(cfg["block_q"]) if block_q is None else block_q
        block_k = int(cfg["block_k"]) if block_k is None else block_k
    block_q = _pick_block(min(block_q, t), t)
    block_k = _pick_block(min(block_k, t_kv), t_kv)

    # native-layout path: no (B,T,H,D)→(BH,T,D) transpose round-trips (real
    # per-layer HBM passes in a model); scores scratch caps VMEM
    hp = _hd_heads_per_program(h, d)
    if (
        hp is not None
        and t == t_kv  # dkv holds full-length-t q/do resident: square only
        and t % block_q == 0 and t_kv % block_k == 0
        and _resident_ok(t_kv, hp * d, k.dtype)
        and block_q * t_kv * 4 <= 4 * 1024 * 1024
    ):
        out = _flash_hd(
            q.reshape(b, t, h * d), k.reshape(b, t_kv, h * d),
            v.reshape(b, t_kv, h * d), causal, block_q, block_k, interpret,
            t_kv, d, hp,
        )
        return out.reshape(b, t, h, d)

    def to_bh(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, x.shape[1], d)

    pad_q = (-t) % block_q
    pad_k = (-t_kv) % block_k
    qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)
    if pad_q:
        qb = jnp.pad(qb, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # padded keys are masked inside the kernels via kv_len (k_pos >=
        # kv_len contributes -inf scores), so any T_kv works non-causally too
        kb = jnp.pad(kb, ((0, 0), (0, pad_k), (0, 0)))
        vb = jnp.pad(vb, ((0, 0), (0, pad_k), (0, 0)))
    out = _flash(qb, kb, vb, causal, block_q, block_k, interpret, t_kv)
    if pad_q:
        out = out[:, :t]
    return jnp.swapaxes(out.reshape(b, h, t, d), 1, 2)


def flash_attention_tpu(q, k, v, causal=False):
    """Tensor-level wrapper used by nn.functional.flash_attention."""
    from ...core.dispatch import eager_call

    return eager_call(
        "flash_attention",
        lambda qa, ka, va: flash_attention_array(qa, ka, va, causal=causal),
        [q, k, v],
    )
