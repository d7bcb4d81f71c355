"""``ops/kernels/mla_paged_attention`` alone, under the Pallas interpreter:
against its plain form (``models/mla_moe.attend_absorbed_plain``, the gather
of the row's table) within the contract's ``2e-5 * max|plain| + 2e-6``, and,
at one ``blocks_per_chunk``, bit for bit against the schedule it had until
PR 43 (one start and one wait a live block, each from a loop of its own),
whose kernel body is kept below as the plain form of the copy schedule: the
new one issues and awaits the same copies, so the same sums in the same order.
"""
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import paddle_tpu  # noqa: F401
from paddle_tpu.models import mla_moe as M
from paddle_tpu.ops.kernels import mla_paged_attention as K

H, W, R, BS, MB, L = 4, 128, 64, 8, 40, 2
SCALE = 0.11
CFG = types.SimpleNamespace(kv_lora_rank=R)


def _case(ctx, seed=0, dtype=jnp.float32):
    """Rows whose contexts are ``ctx`` tokens long (0: a dead row, ``pos`` 0
    and its table at the trash block), their blocks drawn without order from
    a pool of random rows; layer 1 of two."""
    rng = np.random.default_rng(seed)
    B = len(ctx)
    need = [max(-(-c // BS), 0) for c in ctx]
    NB = 1 + sum(need) + 3
    pool = jnp.asarray(rng.normal(size=(L, NB, BS, W)), dtype)
    free = list(1 + rng.permutation(NB - 1))
    tables = np.zeros((B, MB), np.int32)
    for b, n in enumerate(need):
        tables[b, :n] = [free.pop() for _ in range(n)]
    pos = np.asarray([max(c - 1, 0) for c in ctx], np.int32)
    q = jnp.asarray(rng.normal(size=(B, H, W)), dtype)
    return q, pool, jnp.asarray(tables), jnp.asarray(pos)


def _kernel(case, C):
    q, pool, tables, pos = case
    return K.mla_paged_attention(q, pool, 1, tables, pos, R, SCALE,
                                 config={"blocks_per_chunk": C}, interpret=True)


def _plain(case):
    q, pool, tables, pos = case
    return M.attend_absorbed_plain(CFG, q, pool, 1, tables, pos, SCALE)


def close(a, b, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    tol = 2e-5 * np.abs(b).max() + 2e-6
    assert np.abs(a - b).max() <= tol, (what, np.abs(a - b).max(), tol)


def _contexts(C):
    """Where a row's context ends, in tokens, against a chunk of ``C`` blocks
    of ``BS`` tokens."""
    N = C * BS
    return {
        "in_a_chunks_first_block": [N + 3, 2 * N + BS],
        "on_a_chunks_last_token": [N, 2 * N],
        "one_token_past_a_chunk": [N + 1, 2 * N + 1],
        "dead_row_between_live_rows": [N + 5, 0, 2 * N - 1],
        "full_chunk_row_then_one_block_row": [N, 3, N, BS],
        "first_row_dead": [0, N + BS + 1],
        "under_one_chunk": [1, BS - 1, BS + 1],
    }


@pytest.mark.parametrize("C", [4, 8, 16])
@pytest.mark.parametrize("rows", list(_contexts(1)))
def test_equals_the_plain_gather(C, rows):
    case = _case(_contexts(C)[rows], seed=C)
    close(_kernel(case, C), _plain(case), rows)


def test_a_dead_row_reads_its_one_trash_block():
    """``pos`` 0 with the table at block 0: the softmax of one token is 1, so
    the row's output is the trash block's first row, whatever lies around."""
    case = _case([0, 2 * BS, 0])
    out = np.asarray(_kernel(case, 4))
    first = np.asarray(case[1])[1, 0, 0, :R]
    for b in (0, 2):
        np.testing.assert_allclose(out[b], np.broadcast_to(first, (H, R)),
                                   rtol=1e-6, atol=1e-6)


def test_bfloat16_pool_rounds_the_probabilities_as_the_gather_does():
    case = _case([3 * 4 * BS + 2, 0, 4 * BS], seed=5, dtype=jnp.bfloat16)
    out, ref = _kernel(case, 4), _plain(case)
    assert out.dtype == jnp.bfloat16
    # one rounding of the output to bfloat16 on each side
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2)


# -- the schedule until PR 43, as the plain form of the copy schedule ------------
_MASK = K._MASK


def _parent_kernel(layer_ref, tables_ref, pos_ref, q_ref, pool_ref, o_ref, buf,
                   sems, slot_ref, *, B, MB, BS, C, R, scale):
    b = pl.program_id(0)
    layer = layer_ref[0]

    def for_live_blocks(b, c, slot, do):
        live = jnp.clip(pos_ref[b] // BS + 1 - c * C, 0, C)

        def body(j, carry):
            bid = tables_ref[b * MB + c * C + j]
            do(pltpu.make_async_copy(pool_ref.at[layer, bid], buf.at[slot, j],
                                     sems.at[slot]))
            return carry

        jax.lax.fori_loop(0, live, body, 0)

    def start(b, c, slot):
        for_live_blocks(b, c, slot, lambda cp: cp.start())

    def wait(b, c, slot):
        for_live_blocks(b, c, slot, lambda cp: cp.wait())

    @pl.when(b == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        start(0, 0, 0)

    H, W = q_ref.shape[1], q_ref.shape[2]
    N = C * BS
    pos = pos_ref[b]
    n_chunks = (pos // BS + C) // C
    q = q_ref[0]
    tok = jax.lax.broadcasted_iota(jnp.int32, (H, N), 1)

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

    slot, _, l, acc = jax.lax.fori_loop(
        0, n_chunks, chunk_body,
        (slot_ref[0], jnp.full((H, 1), _MASK, jnp.float32),
         jnp.zeros((H, 1), jnp.float32), jnp.zeros((H, R), jnp.float32)))
    slot_ref[0] = slot
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_equals_the_parents_schedule_bit_for_bit(monkeypatch, dtype):
    """Every kind of row at once (full chunks alone, a partial chunk behind
    full ones, one block, dead, the slot handed on at a full and at a partial
    chunk) at one ``blocks_per_chunk``: the same bits from both schedules."""
    C = 4
    N = C * BS
    ctx = [N, 3, 0, 2 * N + 1, 3 * N, BS, 0, 0, N - 1, 2 * N + BS + 2, 1]
    case = _case(ctx, seed=9, dtype=dtype)
    new = np.asarray(_kernel(case, C))
    K._mla_call.clear_cache()
    monkeypatch.setattr(K, "_mla_kernel", _parent_kernel)
    try:
        old = np.asarray(_kernel(case, C))
    finally:
        K._mla_call.clear_cache()
    assert np.array_equal(new, old)


# -- the host's count of what the kernel copies -----------------------------------
@pytest.mark.parametrize("C", [4, 8, 16])
def test_chunk_counts_are_what_the_positions_give_by_hand(C):
    N = C * BS
    # context (tokens) -> (blocks, chunks, full chunks), by hand
    rows = {1: (1, 1, 0), BS: (1, 1, 0), BS + 1: (2, 1, 0),
            N - 1: (C, 1, 1), N: (C, 1, 1), N + 1: (C + 1, 2, 1),
            3 * N: (3 * C, 3, 3), 3 * N + BS + 1: (3 * C + 2, 4, 3)}
    for ctx, (blocks, chunks, full) in rows.items():
        got = K.chunk_counts(np.asarray([ctx - 1]), BS, C)
        assert got == {"latent_blocks": blocks, "latent_chunks": chunks,
                       "latent_full_chunks": full}, ctx
    pos = np.asarray([c - 1 for c in rows])
    total = K.chunk_counts(pos, BS, C)
    assert total == {
        "latent_blocks": sum(v[0] for v in rows.values()),
        "latent_chunks": sum(v[1] for v in rows.values()),
        "latent_full_chunks": sum(v[2] for v in rows.values())}
    assert K.chunk_counts(np.zeros((0,), np.int32), BS, C) == {
        "latent_blocks": 0, "latent_chunks": 0, "latent_full_chunks": 0}
