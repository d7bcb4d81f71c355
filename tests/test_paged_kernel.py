"""Paged-attention decode kernel + int8 head kernel: the serving kernels.

- ``ops/kernels/paged_attention`` reads K/V straight from the PagePool
  blocks through the block table, only the blocks a row has live, and must
  agree with the gather path (``build_paged_decode``, the plain reference)
  within the float32 tolerance its docstring states: at the kernel level
  against the same ``_grouped_attention`` math over ragged tables, at the
  builder level (``build_paged_decode_kernel`` vs ``build_paged_decode``,
  GPT and Llama/GQA), and engine end to end with EQUAL greedy token streams
  on the seeded tiny models (prefix cache on and off). The engine chooses
  the builder by backend (``generation.paged_kernel_default``); the tests
  patch that one function. CPU runs the kernel in Pallas interpret mode.
- ``ops/kernels/int8_matmul`` (weight-only int8 head matmul behind
  ``FLAGS_serve_int8_kernel``) must match the dequantize-then-matmul it
  replaces bitwise, and the engine's int8 path must produce identical
  tokens with the kernel on or off.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.models.generation as G
from paddle_tpu import profiler
from paddle_tpu.framework import flags
from paddle_tpu.ops import kernels as K
from paddle_tpu.serving import Engine
from paddle_tpu.serving.pool import TRASH_BLOCK
from serving_util import ENGINE_KW, make_prompts, paged_kernel, tiny_gpt

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402


def _close(out, ref):
    """The kernel's stated float32 tolerance against the gather path."""
    out, ref = np.asarray(out), np.asarray(ref)
    tol = 2e-5 * np.abs(ref).max() + 2e-6
    return np.abs(out - ref).max() <= tol


def _ref_paged(q, kpool, vpool, layer, tables, pos):
    """The gather path's read: gather context via the block table, then
    dense grouped attention over live positions."""
    B, H, D = q.shape
    _, NB, BS, KV, _ = kpool.shape
    T_pad = tables.shape[1] * BS
    kc = kpool[layer, tables].reshape(B, T_pad, KV, D)
    vc = vpool[layer, tables].reshape(B, T_pad, KV, D)
    live = jnp.arange(T_pad)[None, :] <= pos[:, None]
    o = G._grouped_attention(q[:, None], kc, vc,
                             live[:, None, None, None, :], H // KV)
    return o.reshape(B, H * D)


def _disjoint_tables(rng, B, MB, NB):
    """Per-row disjoint block ids, as PagePool guarantees (duplicate ids
    would make the fresh-KV scatter order compilation-dependent)."""
    perm = rng.permutation(np.arange(1, NB))[: B * MB]
    return jnp.asarray(perm.reshape(B, MB).astype(np.int32))


# ragged batches the engine produces; MB = 5 blocks of 8 tokens a row
_MB, _BS = 5, 8
_RAGGED = {
    # live tokens a row (0 = a dead row: pos 0, every column at the trash)
    "one_block": [1, 5, 8],
    "at_max_blocks": [40, 33, 40],
    "not_a_power_of_two": [17, 24, 23, 9, 20],
    "dead_row_on_trash": [12, 0, 30, 0],
    "prefix_shared_block": [19, 27, 11],
}


def _ragged_batch(case, rng, NB):
    lens = _RAGGED[case]
    B = len(lens)
    tables = np.full((B, _MB), TRASH_BLOCK, np.int32)
    pos = np.zeros((B,), np.int32)
    free = list(rng.permutation(np.arange(1, NB)))
    for b, n in enumerate(lens):
        if n:
            nb = -(-n // _BS)
            tables[b, :nb] = [free.pop() for _ in range(nb)]
            pos[b] = n - 1
    if case == "prefix_shared_block":
        # rows 0 and 1 read the same first block (a cached prompt head)
        tables[1, 0] = tables[0, 0]
    return tables, pos


class TestPagedKernelAgainstGather:
    @pytest.mark.parametrize("heads", [(4, 4), (8, 2)],
                             ids=["mha", "gqa_rep4"])
    def test_kernel_matches_gather_reference(self, heads):
        H, KV = heads
        B, D, BS, MB, NB, L = 4, 16, 8, 4, 64, 3
        rng = np.random.RandomState(1)
        kpool = jnp.asarray(rng.randn(L, NB, BS, KV, D), jnp.float32)
        vpool = jnp.asarray(rng.randn(L, NB, BS, KV, D), jnp.float32)
        tables = jnp.asarray(rng.randint(1, NB, size=(B, MB)), jnp.int32)
        pos = jnp.asarray([3, 8, 17, 31], jnp.int32)
        q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
        for layer in (0, 2):
            ref = _ref_paged(q, kpool, vpool, layer, tables, pos)
            for chunk in (1, 3, 8):
                out = K.paged_attention_rows(
                    q, kpool, vpool, layer, tables, pos,
                    config={"blocks_per_chunk": chunk})
                assert _close(out, ref), (layer, chunk)

    @pytest.mark.parametrize("rep", [1, 4], ids=["rep1", "rep4"])
    @pytest.mark.parametrize("case", sorted(_RAGGED))
    def test_ragged_tables(self, case, rep):
        """Only a row's live blocks are read: whatever sits behind them in
        the table (here a block of NaN) reaches no output."""
        KV, D, NB, L = 2, 16, 32, 2
        H = KV * rep
        rng = np.random.RandomState(5)
        tables, pos = _ragged_batch(case, rng, NB - 1)
        kpool = rng.randn(L, NB, _BS, KV, D).astype(np.float32)
        vpool = rng.randn(L, NB, _BS, KV, D).astype(np.float32)
        q = jnp.asarray(rng.randn(len(pos), H, D), jnp.float32)
        ref = _ref_paged(q, jnp.asarray(kpool), jnp.asarray(vpool), 1,
                         jnp.asarray(tables), jnp.asarray(pos))
        # columns behind the live count point at a poisoned block: the
        # gather would read it (and mask it); the kernel must not touch it
        poisoned = tables.copy()
        for b, p_ in enumerate(pos):
            poisoned[b, p_ // _BS + 1:] = NB - 1
        kpool[:, NB - 1] = np.nan
        vpool[:, NB - 1] = np.nan
        for chunk in (2, 8):
            out = K.paged_attention_rows(
                q, jnp.asarray(kpool), jnp.asarray(vpool), 1,
                jnp.asarray(poisoned), jnp.asarray(pos),
                config={"blocks_per_chunk": chunk})
            assert _close(out, ref), (case, rep, chunk)

    @pytest.mark.parametrize("which", ["gpt", "llama_gqa"])
    def test_builder_within_tolerance_of_gather_builder(self, which):
        if which == "gpt":
            _, arch, params, _ = G.gpt_decode_state(tiny_gpt(seed=0))
            vocab = 211
        else:
            from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

            paddle.seed(0)
            m = LlamaForCausalLM(llama_tiny(num_kv_heads=2))
            m.eval()
            _, arch, params, _ = G.llama_decode_state(m)
            vocab = m.model.config.vocab_size
        B, BS, MB, NB = 4, 8, 4, 64
        L, KV, D = len(params["layers"]), arch["kv_heads"], arch["head_dim"]
        rng = np.random.RandomState(1)
        kpool = jnp.asarray(rng.randn(L, NB, BS, KV, D), jnp.float32)
        vpool = jnp.asarray(rng.randn(L, NB, BS, KV, D), jnp.float32)
        tables = _disjoint_tables(rng, B, MB, NB)
        pos = jnp.asarray([3, 8, 17, 30], jnp.int32)
        toks = jnp.asarray(rng.randint(0, vocab, (B,)), jnp.int32)
        temps = jnp.asarray([0.0, 0.7, 0.0, 1.1], jnp.float32)
        key = jax.random.PRNGKey(7)

        ref = jax.jit(G.build_paged_decode(arch, B, BS, MB))
        ker = jax.jit(G.build_paged_decode_kernel(arch, B, BS, MB))
        r = ref(params, kpool, vpool, tables, pos, toks, temps, key)
        k = ker(params, kpool, vpool, tables, pos, toks, temps, key)
        assert _close(k[0], r[0]) and _close(k[1], r[1])
        assert np.array_equal(np.asarray(k[2]), np.asarray(r[2]))


# -- the copy schedule (PR 46) ------------------------------------------------
# The schedule the kernel had until PR 46 (one K and one V start a live block
# from a loop, one wait each from a second loop, the next chunk started at two
# sites), kept as the plain form of the copy schedule: the new one issues and
# awaits the same copies, so the same sums in the same order.
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from paddle_tpu.ops.kernels import paged_attention as PA  # noqa: E402


def _parent_kernel(layer_ref, tables_ref, pos_ref, q_ref, tok_ref, kpool_ref,
                   vpool_ref, o_ref, kbuf, vbuf, sems, slot_ref, *, B, MB, BS,
                   C, scale):
    b = pl.program_id(0)
    layer = layer_ref[0]

    def for_live_blocks(b, c, slot, do):
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
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        start(0, 0, 0)

    Hp, D = q_ref.shape[1], q_ref.shape[2]
    N = kbuf.shape[1] * kbuf.shape[2]
    pos = pos_ref[b]
    n_chunks = (pos // BS + C) // C
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
        s = jnp.where(tok_ref[...] + c * (C * BS) <= pos, s, PA._MASK)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        e = jnp.exp(s - m_new)
        l = alpha * l + e.sum(axis=1, keepdims=True)
        v = vbuf[slot].reshape(N, D)
        acc = alpha * acc + jax.lax.dot_general(
            e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return nxt, m_new, l, acc

    slot, _, l, acc = jax.lax.fori_loop(
        0, n_chunks, chunk_body,
        (slot_ref[0], jnp.full((Hp, 1), PA._MASK, jnp.float32),
         jnp.zeros((Hp, 1), jnp.float32), jnp.zeros((Hp, D), jnp.float32)))
    slot_ref[0] = slot
    o_ref[0] = (acc / l).astype(o_ref.dtype)


_SC, _SBS, _SMB = 4, 8, 14  # blocks a chunk, tokens a block, table width
_SN = _SC * _SBS            # tokens a chunk
# contexts a row, in tokens (0: a dead row, pos 0 and its table at the trash)
_SCHEDULE_ROWS = {
    "a_row_of_whole_chunks": [3 * _SN],
    "a_full_chunk_then_one_block": [_SN + 1, 2 * _SN + _SBS],
    "partial_only": [_SBS + 1, 3, _SN - 1],
    "dead_rows_between_live_ones": [_SN + 5, 0, 2 * _SN - 1, 0, 0, 3],
    # the slot handed to the next row at a full chunk and at a partial one,
    # the next row's first chunk full and partial
    "prefetch_across_a_row_boundary": [_SN, 3, 2 * _SN, _SN, _SBS, _SN + 2],
}


def _schedule_case(ctx, dtype, pools, seed=0):
    """Rows of ``ctx`` tokens, their blocks drawn without order from pools of
    random lines; 2 KV heads x 2 queries each, layer 1 of two; ``pools``:
    "5d" ``(L, NB, BS, KV, D)`` or "4d" ``(L, NB, BS * KV, D)`` with
    ``kv_heads``."""
    rng = np.random.default_rng(seed)
    KV, rep, D, L = 2, 2, 16, 2
    need = [-(-c // _SBS) for c in ctx]
    NB, MB = 1 + sum(need) + 3, max(_SMB, max(need) + 1)
    shape = (L, NB, _SBS, KV, D) if pools == "5d" else (L, NB, _SBS * KV, D)
    kpool = jnp.asarray(rng.normal(size=shape), dtype)
    vpool = jnp.asarray(rng.normal(size=shape), dtype)
    free = list(1 + rng.permutation(NB - 1))
    tables = np.full((len(ctx), MB), TRASH_BLOCK, np.int32)
    for b, n in enumerate(need):
        tables[b, :n] = [free.pop() for _ in range(n)]
    pos = np.asarray([max(c - 1, 0) for c in ctx], np.int32)
    q = jnp.asarray(rng.normal(size=(len(ctx), KV * rep, D)), dtype)
    kw = {} if pools == "5d" else {"kv_heads": KV}
    return (q, kpool, vpool, 1, jnp.asarray(tables), jnp.asarray(pos)), kw


class TestCopySchedule:
    """A full chunk's ``2 C`` copies are started as straight-line code and
    waited for once a pool; a partial chunk keeps a loop a block. The copies
    and the arithmetic are the parent's."""

    @pytest.fixture(autouse=True, scope="class")
    def _drop_the_executables(self):
        """Every case compiles two interpreted kernels; a test process that
        keeps some hundred XLA:CPU executables alive runs out of room for
        code (PERF.md, PR 45 (6)), so they go when the class is done."""
        yield
        jax.clear_caches()

    # every kind of row as 5-D float32 pools and as 4-D bfloat16 pools with
    # ``kv_heads``; the other two pairings on the row that has every site
    @pytest.mark.parametrize("rows,pools,dtype", [
        *[(rows, pools, dtype) for rows in _SCHEDULE_ROWS for pools, dtype in
          (("5d", "float32"), ("4d_kv_heads", "bfloat16"))],
        ("prefetch_across_a_row_boundary", "5d", "bfloat16"),
        ("prefetch_across_a_row_boundary", "4d_kv_heads", "float32")])
    def test_equals_the_parents_schedule_bit_for_bit(self, monkeypatch, rows,
                                                     pools, dtype):
        args, kw = _schedule_case(_SCHEDULE_ROWS[rows], jnp.dtype(dtype),
                                  pools[:2])
        run = lambda: np.asarray(K.paged_attention_rows(
            *args, config={"blocks_per_chunk": _SC}, interpret=True, **kw))
        new = run()
        PA._paged_call.clear_cache()
        monkeypatch.setattr(PA, "_paged_kernel", _parent_kernel)
        try:
            old = run()
        finally:
            PA._paged_call.clear_cache()
        assert new.dtype == old.dtype and np.array_equal(new, old)
        assert np.isfinite(np.asarray(new, np.float32)).all()

    @pytest.mark.parametrize("rows", list(_SCHEDULE_ROWS))
    def test_within_tolerance_of_the_gather_at_the_default_chunk(self, rows):
        """No ``config``: the registry's ``blocks_per_chunk``, the chunk the
        engine's programs resolve, over the same kinds of row with every
        length a multiple of the test chunk so that the default's chunks
        fill."""
        C = PA.blocks_per_chunk(PA.paged_attention_key(
            8, 4096, _SBS, 2, 2, 16, jnp.float32))
        ctx = [c * C // _SC for c in _SCHEDULE_ROWS[rows]]
        args, _ = _schedule_case(ctx, jnp.float32, "5d")
        out = K.paged_attention_rows(*args, interpret=True)
        assert _close(out, _ref_paged(*args))


# -- the chunk a shape gets (PR 46) ----------------------------------------------
@pytest.mark.parametrize("key,C", [
    # (B, table, BS, KV, queries a KV head, D, dtype): what the cells run, and
    # what the chip measured as fastest for each (PERF.md section 6, PR 46)
    pytest.param((32, 512, 16, 4, 8, 128, "bfloat16"), 32, id="trinity_full_16KB"),
    pytest.param((32, 128, 16, 4, 8, 128, "bfloat16"), 32, id="trinity_rings"),
    pytest.param((64, 128, 16, 4, 8, 128, "bfloat16"), 32, id="packed_16KB"),
    pytest.param((64, 128, 16, 10, 4, 128, "bfloat16"), 16, id="hybrid_paged_40KB"),
    pytest.param((64, 32, 16, 10, 4, 128, "bfloat16"), 16, id="hybrid_rings"),
    pytest.param((4, 128, 16, 16, 1, 128, "bfloat16"), 8, id="dense_64KB"),
    pytest.param((32, 512, 16, 4, 8, 128, "float32"), 16, id="float32_doubles_a_block"),
    pytest.param((32, 512, 16, 16, 1, 128, "float32"), 4, id="the_spaces_narrowest"),
    pytest.param((32, 512, 16, 64, 1, 256, "float32"), 1, id="a_block_over_the_limit"),
    pytest.param((8, 5, 16, 4, 8, 128, "bfloat16"), 5, id="at_most_the_table")])
def test_the_chunk_follows_a_blocks_bytes(key, C):
    """No flag, no arch's name: the widest chunk of the registry's space that
    holds at most 640 KB of one pool, at most the row's table; a ``config``
    that names a width wins."""
    assert PA.blocks_per_chunk(key) == C
    assert PA.blocks_per_chunk(key, {"blocks_per_chunk": 2}) == 2
    assert PA.blocks_per_chunk(key, {"blocks_per_chunk": 64}) == min(64, key[1])


# -- the host's count of what the kernel copies --------------------------------
@pytest.mark.parametrize("C", [4, 8, 16, 32])
def test_chunk_counts_are_what_the_positions_give_by_hand(C):
    BS = 16
    N = C * BS
    # context (tokens) -> (blocks, chunks, full chunks), by hand
    rows = {1: (1, 1, 0), BS: (1, 1, 0), BS + 1: (2, 1, 0),
            N - 1: (C, 1, 1), N: (C, 1, 1), N + 1: (C + 1, 2, 1),
            3 * N: (3 * C, 3, 3), 3 * N + BS + 1: (3 * C + 2, 4, 3)}
    names = ("paged_blocks", "paged_chunks", "paged_full_chunks")
    for ctx, by_hand in rows.items():
        got = PA.chunk_counts(np.asarray([ctx - 1]), BS, C)
        assert got == dict(zip(names, by_hand)), ctx
    total = PA.chunk_counts(np.asarray([c - 1 for c in rows]), BS, C)
    assert total == {n: sum(v[i] for v in rows.values())
                     for i, n in enumerate(names)}
    # an empty step (no live row) copies nothing
    assert PA.chunk_counts(np.zeros((0,), np.int32), BS, C) == dict.fromkeys(names, 0)


def _run_engine(prompt_seed=3, n=4, max_new=8, kernel=False, **fl):
    """Token outputs of a fresh tiny-GPT engine under flag overrides, its
    decode program built with the kernel step or the gather step."""
    old = {k: flags._FLAGS.get(k) for k in fl}
    flags._FLAGS.update(fl)
    try:
        with paged_kernel(kernel), Engine(tiny_gpt(seed=0),
                                          **ENGINE_KW) as eng:
            prompts = make_prompts(n, np.random.RandomState(prompt_seed))
            handles = [eng.submit(p, max_new_tokens=max_new, temperature=0.0)
                       for p in prompts]
            return [h.result(timeout=300) for h in handles]
    finally:
        for k, v in old.items():
            if v is None:
                flags._FLAGS.pop(k, None)
            else:
                flags._FLAGS[k] = v


class TestEnginePagedKernel:
    @pytest.mark.parametrize("prefix_cache", [False, True],
                             ids=["plain", "prefix_cache"])
    def test_engine_tokens_identical_with_kernel(self, prefix_cache):
        base = _run_engine(kernel=False,
                           FLAGS_serve_prefix_cache=prefix_cache)
        kern = _run_engine(kernel=True,
                           FLAGS_serve_prefix_cache=prefix_cache)
        assert base == kern

    def test_engine_actually_builds_kernel_step(self, monkeypatch):
        """The choice must really swap the decode builder (no hidden
        fallback to the gather), and on the CPU tier it is the gather."""
        called = {"n": 0}
        real = G.build_paged_decode_kernel

        def spy(*a, **k):
            called["n"] += 1
            return real(*a, **k)

        monkeypatch.setattr(G, "build_paged_decode_kernel", spy)
        _, arch, _, _ = G.gpt_decode_state(tiny_gpt(seed=0))
        assert G.paged_kernel_default(arch) is False  # this tier interprets
        c0 = profiler.counters().get("serve_decode_blocks_read", 0)
        base = _run_engine()  # the backend's own choice
        assert called["n"] == 0
        # a gathering step reads bucket x width blocks, not the live ones
        assert profiler.counters().get("serve_decode_blocks_read", 0) == c0
        out = _run_engine(kernel=True)
        assert called["n"] >= 1
        assert out == base

    def test_one_full_width_program_a_bucket_and_blocks_counted(self):
        """The kernel step takes the table ``_max_blocks`` wide, so a bucket
        has ONE decode program however long its rows grow (no
        ``_gather_width`` regrowth), and ``decode_build``'s ``blocks_live``
        / ``serve_decode_blocks_read`` count what the tables hold."""
        from paddle_tpu.profiler import spans

        rows, held = [], []
        spans.add_span_observer(rows.append)
        c0 = profiler.counters().get("serve_decode_blocks_read", 0)
        try:
            with paged_kernel(True), Engine(tiny_gpt(seed=0),
                                            **ENGINE_KW) as eng:
                real = eng._decode_build

                def build(k=0):
                    built = real(k)
                    if built is not None:
                        held.append(int(np.count_nonzero(
                            built[4] != TRASH_BLOCK)))
                        assert built[4].shape[1] == eng._max_blocks
                    return built

                eng._decode_build = build
                rng = np.random.RandomState(21)
                # a short stream first, then one that crosses every power
                # of two of blocks up to the sequence limit
                eng.submit(rng.randint(0, 211, (4,)).tolist(),
                           max_new_tokens=4).result(timeout=600)
                eng.submit(rng.randint(0, 211, (10,)).tolist(),
                           max_new_tokens=100).result(timeout=600)
                decode_keys = [k for k in eng._fns if k[0] == "decode"]
                assert decode_keys == [("decode", 1, eng._max_blocks)]
                assert eng._decode_mb == {}
        finally:
            spans.remove_span_observer(rows.append)
        live = [sp.attrs["blocks_live"] for sp in rows
                if sp.name == "decode_build" and "bucket" in sp.attrs]
        assert live == held and len(live) >= 100
        assert max(live) == -(-(10 + 100 - 1) // ENGINE_KW["block_size"])
        c1 = profiler.counters().get("serve_decode_blocks_read", 0)
        assert c1 - c0 == sum(held)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel_step", "gather_step"])
def test_dense_archs_decode_spans_carry_the_copy_schedule(kernel):
    """An arch that caches K and V per head reads them through the kernel
    where the engine built the kernel step, a call a layer (two here): a
    table of 4 blocks of 8 tokens, chunks of 4; the gather step has no
    chunks and says nothing."""
    from paddle_tpu.profiler import spans

    seen = []
    spans.add_span_observer(seen.append)
    try:
        with paged_kernel(kernel), Engine(
                tiny_gpt(seed=0), **{**ENGINE_KW, "max_seq_len": 32,
                                     "decode_buckets": (4,)}) as eng:
            eng.submit(list(range(22)), max_new_tokens=6).result(timeout=600)
    finally:
        spans.remove_span_observer(seen.append)
    steps = [sp.attrs for sp in seen if sp.name == "decode_step" and sp.attrs["ahead"]]
    assert len(steps) == 4 and all(a["rows"] == 1 for a in steps)
    if not kernel:
        assert not any(k.startswith("paged_") for a in steps for k in a)
        return
    assert [(a["paged_blocks"], a["paged_chunks"], a["paged_full_chunks"])
            for a in steps] == [(6, 2, 0), (6, 2, 0), (8, 2, 2), (8, 2, 2)]


def _gpt_of_width(head_dim, seed=0):
    """A two-head, two-layer GPT whose heads are ``head_dim`` wide."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    paddle.seed(seed)
    m = GPTForPretraining(GPTConfig(
        vocab_size=211, hidden_size=2 * head_dim, num_layers=2, num_heads=2,
        max_position_embeddings=128, hidden_dropout=0.0,
        attention_dropout=0.0))
    m.eval()
    return m


class TestChosenByHeadWidth:
    """Mosaic takes the kernel only where a K/V line fills whole 128-lane
    rows (``tests/test_tpu_lowering.py`` holds the compiler's own answer);
    the chooser sees the arch, so a narrower model keeps the gather step on
    the chip instead of failing to build its decode program."""

    @pytest.mark.parametrize("head_dim,takes", [
        (32, False), (64, False), (80, False), (96, False), (128, True),
        (256, True)])
    def test_chooser_follows_backend_and_head_width(self, head_dim, takes):
        arch = {"head_dim": head_dim, "kv_heads": 2}
        assert G.paged_kernel_default(arch, mosaic=True) is takes
        assert G.paged_kernel_default(arch, mosaic=False) is False
        assert G.paged_kernel_default(arch) is False  # this tier interprets

    def test_kernel_names_the_rule_where_mosaic_would_refuse(self):
        z = jnp.zeros((2, 4, 8, 2, 64), jnp.float32)
        with pytest.raises(ValueError, match="multiples of 128, not 64"):
            K.paged_attention_rows(
                jnp.zeros((1, 2, 64)), z, z, 0, jnp.zeros((1, 2), jnp.int32),
                jnp.zeros((1,), jnp.int32), interpret=False)

    @pytest.mark.parametrize("head_dim,kernel", [(64, False), (128, True)],
                             ids=["d64_gathers", "d128_kernel"])
    def test_engine_built_under_the_chips_rule_serves(self, head_dim, kernel,
                                                      monkeypatch):
        built = {"kernel": 0, "gather": 0}
        for name, real in (("kernel", G.build_paged_decode_kernel),
                           ("gather", G.build_paged_decode)):
            def spy(*a, _name=name, _real=real, **k):
                built[_name] += 1
                return _real(*a, **k)

            monkeypatch.setattr(
                G, "build_paged_decode_kernel" if name == "kernel"
                else "build_paged_decode", spy)
        prompts = make_prompts(3, np.random.RandomState(11))
        outs = {}
        for mode in (False, "mosaic"):
            with paged_kernel(mode), Engine(_gpt_of_width(head_dim),
                                            **ENGINE_KW) as eng:
                assert eng._paged_kernel is (kernel and mode == "mosaic")
                outs[mode] = [
                    eng.submit(p, max_new_tokens=6, temperature=0.0)
                    .result(timeout=600) for p in prompts]
        assert outs["mosaic"] == outs[False]
        assert all(len(o) == len(p) + 6
                   for o, p in zip(outs["mosaic"], prompts))
        assert (built["kernel"] > 0) is kernel and built["gather"] > 0


class TestInt8Kernel:
    def test_int8_matmul_bitwise_vs_dequant_matmul(self):
        rng = np.random.RandomState(2)
        w = rng.randn(64, 32).astype(np.float32)
        scale = jnp.asarray(np.abs(w).max(), jnp.float32)
        qw = jnp.asarray(
            np.clip(np.round(w / (np.asarray(scale) / 127.0)), -127, 127),
            jnp.int8)
        wd = (qw.astype(jnp.float32) * (scale / 127.0)).astype(jnp.float32)
        x = jnp.asarray(rng.randn(3, 32), jnp.float32)
        out_t = K.int8_matmul(x, qw, scale, transpose_w=True,
                              config={"block_n": 512})
        assert np.array_equal(np.asarray(out_t), np.asarray(x @ wd.T))
        out_n = K.int8_matmul(x, qw.T, scale, transpose_w=False,
                              config={"block_n": 512})
        assert np.array_equal(np.asarray(out_n), np.asarray(x @ wd.T))

    def test_attach_int8_head_grafts_quantized_head(self):
        from paddle_tpu.serving.int8 import (
            attach_int8_head, dequantize_tree, quantize_params,
        )

        _, _, params, _ = G.gpt_decode_state(tiny_gpt(seed=0))
        tagged = quantize_params(params)
        dense = dequantize_tree(tagged, jnp.float32)
        grafted = attach_int8_head(dense, tagged)
        assert grafted["head_q"]["q"].dtype == jnp.int8
        assert "head_q" not in dense  # original tree untouched
        # un-quantized tree passes through unchanged
        assert attach_int8_head(params, params) is params

    def test_engine_int8_tokens_identical_with_kernel(self, monkeypatch):
        import paddle_tpu.ops.kernels as KM

        calls = {"n": 0}
        real = KM.int8_matmul

        def spy(*a, **k):
            calls["n"] += 1
            return real(*a, **k)

        monkeypatch.setattr(KM, "int8_matmul", spy)
        base = _run_engine(FLAGS_serve_int8=True,
                           FLAGS_serve_int8_kernel=False)
        assert calls["n"] == 0  # kernel off: head stays on the dense matmul
        kern = _run_engine(FLAGS_serve_int8=True,
                           FLAGS_serve_int8_kernel=True)
        assert calls["n"] >= 1  # kernel on: the head traced through it
        assert base == kern
        both = _run_engine(kernel=True, FLAGS_serve_int8=True,
                           FLAGS_serve_int8_kernel=True)
        assert base == both
