"""A prompt of the latent-attention arch prefilled in CALLS against the paged
latent cache (``models/mla_moe.expand_context`` / ``attend_call``,
``ops/kernels/mla_prefill_attention``, ``generation.build_paged_tail_prefill``,
the engine's ``prefill_chunk``) against the monolithic prefill and against the
family's plain reference (``benchmark/reference/kimivl.py``: float32
``jax.numpy``, sharing no code with the program), at a small size on the CPU
with seeded weights. Tolerances as in ``tests/test_mla_moe.py``: float32 on
both sides, so what is left is the order of the sums.
"""
import importlib.util
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
import paddle_tpu.models.generation as G
from paddle_tpu.models import mla_moe as M
from paddle_tpu.models.mla_moe import MLAMoEConfig, MLAMoEForCausalLM
from paddle_tpu.ops.kernels.mla_prefill_attention import mla_prefill_attention
from paddle_tpu.profiler import counters, spans
from paddle_tpu.serving import Engine

REPO = pathlib.Path(__file__).parent.parent


def _family():
    spec = importlib.util.spec_from_file_location(
        "kimivl_family_under_test", REPO / "benchmark/families/kimivl.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAM = _family()
PUBLISHED = json.loads((REPO / "benchmark/configs/kimi-vl-a3b-7l.json").read_text())
TINY = {**PUBLISHED, **FAM.REHEARSE}


def close(a, b, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    tol = 2e-5 * np.abs(b).max() + 2e-6
    assert np.abs(a - b).max() <= tol, (what, np.abs(a - b).max(), tol)


@pytest.fixture(scope="module")
def tiny():
    from benchmark import weights as W

    w = W.make_weights(TINY, 3, FAM.leaf_specs(TINY))
    net, _ = FAM.build(TINY, w)
    net.eval()
    return net, w


# -- (a) calls of several sizes against the pool ------------------------------------
@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernels"])
def test_calls_equal_the_monolithic_prefill_and_the_reference(tiny, kernels):
    """A block-aligned prompt of 9 blocks prefilled in calls of 32, 16, 16 and
    8 positions (a call boundary inside the prompt at every size, the last
    call ONE block; each call in a bucket of its own size or wider, padded):
    every call's last logits are the reference's at that position, the pool
    holds the rows the monolithic prefill writes, the experts' counts add up
    to its counts, and three decode steps behind it give the reference's
    logits."""
    net, w = tiny
    _, arch, params, _ = G.mla_moe_decode_state(net, kernels)
    bs, MB, n = 8, 16, 72
    ids = np.random.default_rng(1).integers(0, TINY["vocab_size"], n + 3).astype(np.int32)
    ref = np.asarray(FAM.reference.forward_logits(TINY, w, ids[None], "f32")[0])
    L, W_ = TINY["num_hidden_layers"], arch["cache"][0][0]
    empty = jnp.zeros((L, 32, bs, W_), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8, 9, 10] + [0] * 6], jnp.int32)
    mono = jax.jit(G.build_paged_prefill(arch, 1, 128, bs, MB))
    whole = np.zeros((1, 128), np.int32)
    whole[0, :n] = ids[:n]
    pool_m, logits_m, counts_m = mono(params, jnp.asarray(whole), jnp.asarray([n]),
                                      tables, empty)
    close(logits_m[0], ref[n - 1], "monolithic prefill")

    pool, counts = empty, 0
    tails = {}
    for a, b, T in ((0, 32, 32), (32, 48, 16), (48, 64, 32), (64, 72, 8)):
        if T not in tails:
            tails[T] = jax.jit(G.build_paged_tail_prefill(arch, 1, T, bs, MB))
        x = np.zeros((1, T), np.int32)
        x[0, :b - a] = ids[a:b]
        pool, logits, c = tails[T](params, jnp.asarray(x), jnp.asarray([a]),
                                   jnp.asarray([b - a]), tables, pool)
        counts = counts + np.asarray(c)
        close(logits[0], ref[b - 1], f"call {a}..{b} in a bucket of {T}")
    held = np.arange(1, 10)
    rows = lambda p: np.asarray(p)[:, held].reshape(L, -1, W_)[:, :n]
    close(rows(pool), rows(pool_m), "the pool's rows")
    assert np.array_equal(counts, np.asarray(counts_m))

    for t in range(n, n + 3):
        X = arch["embed"](params, jnp.asarray(ids[None, t:t + 1]), None)
        pos = jnp.asarray([t], jnp.int32)
        for li, lw in enumerate(params["layers"]):
            X, (pool,), _ = arch["decode_layer"](
                lw, X, (pool,), li, tables, pos, tables[:, t // bs], pos % bs,
                jnp.asarray([True]))
        close(arch["head"](params, X[:, -1])[0], ref[t], f"decode, position {t}")


def test_two_rows_of_one_call_keep_their_own_starts(tiny):
    """Two rows a call, one half cached and one fresh, of different lengths:
    each row's logits are those of the row alone."""
    net, w = tiny
    _, arch, params, _ = G.mla_moe_decode_state(net, False)
    bs, MB = 8, 8
    rng = np.random.default_rng(2)
    a, b = (rng.integers(0, TINY["vocab_size"], n).astype(np.int32) for n in (40, 21))
    ref = [np.asarray(FAM.reference.forward_logits(TINY, w, x[None], "f32")[0]) for x in (a, b)]
    pool = jnp.zeros((TINY["num_hidden_layers"], 16, bs, arch["cache"][0][0]), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4, 5, 0, 0, 0], [6, 7, 8, 0, 0, 0, 0, 0]], jnp.int32)
    tail = jax.jit(G.build_paged_tail_prefill(arch, 2, 24, bs, MB))
    x = np.zeros((2, 24), np.int32)
    x[0], x[1, :21] = a[:24], b
    pool, logits, _ = tail(params, jnp.asarray(x), jnp.asarray([0, 0]),
                           jnp.asarray([24, 21]), tables, pool)
    close(logits[1], ref[1][20], "the fresh row")
    x = np.zeros((2, 24), np.int32)
    x[0, :16] = a[24:]
    # the second row pads the call: its table is unmapped, it reaches no expert
    pool, logits, c = tail(params, jnp.asarray(x), jnp.asarray([24, 0]),
                           jnp.asarray([16, 1]), tables.at[1].set(0), pool)
    close(logits[0], ref[0][39], "the row with 24 positions cached")
    assert int(np.asarray(c).sum()) == 16 * 2 * TINY["num_experts_per_tok"]


def _expand_rows_plain(cfg, w, latent):
    """``expand_rows`` as it stood until PR 49 pinned its results' layout:
    the same arithmetic with no word about a layout, its plain form."""
    B, T = latent.shape[:2]
    H, r, nope = cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    up = (latent[..., :r] @ w["kv_b"]).reshape(B, T, H, -1)
    shared = jnp.broadcast_to(latent[:, :, None, r:], (B, T, H, latent.shape[-1] - r))
    k = jnp.concatenate([up[..., :nope], shared], axis=-1)
    return k.reshape(B, T, -1), up[..., nope:].reshape(B, T, -1)


def test_expand_context_fills_its_scratch_turn_by_turn(tiny, monkeypatch):
    """Two rows whose longer one ends inside the SECOND of the scratch's three
    turns: the two turns the loop runs hold, position for position,
    ``expand_rows`` of the rows their tables name (the positions up to each
    row's end among them), bit for bit what the function gave before its
    results' layout was pinned, and the third turn is the scratch's zeros."""
    monkeypatch.setattr(M, "EXPAND_ROWS", 16)
    net, _ = tiny
    cfg = net.config
    _, _, params, _ = G.mla_moe_decode_state(net, False)
    w, layer, bs, B = params["layers"][1], 1, 8, 2
    rng = np.random.default_rng(5)
    pool = jnp.asarray(rng.standard_normal((3, 16, bs, cfg.cache_row)), jnp.float32)
    width, scratch = M.context_scratch(cfg, B, bs, 5, pool.dtype)
    rows = M.expand_turn(bs, width) * bs
    assert (width, rows, scratch[0].shape[1]) == (6, 16, 48)
    tables = np.zeros((B, width), np.int32)
    tables[0, :3], tables[1, :2] = [3, 9, 4], [7, 1]
    ends = jnp.asarray([21, 12], jnp.int32)
    K, V = jax.jit(lambda pool, tables, ends: M.expand_context(
        cfg, w, pool, layer, tables, ends, scratch))(pool, jnp.asarray(tables), ends)
    assert K.shape == (B, 48, 4 * (16 + 96)) and V.shape == (B, 48, 4 * 16)
    plain = jax.jit(lambda latent: _expand_rows_plain(cfg, w, latent))
    for turn in range(2):
        latent = pool[layer][tables[:, 2 * turn:2 * turn + 2]].reshape(B, rows, -1)
        k, v = plain(latent)
        at = slice(turn * rows, (turn + 1) * rows)
        np.testing.assert_array_equal(np.asarray(K[:, at]), np.asarray(k))
        np.testing.assert_array_equal(np.asarray(V[:, at]), np.asarray(v))
        for got, want in zip(M.expand_rows(cfg, w, latent), (k, v)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # a key is [k_nope | the row's lanes behind the latent] a head
    np.testing.assert_array_equal(
        np.asarray(K[0, 20]).reshape(4, -1)[:, 16:],
        np.broadcast_to(np.asarray(pool[layer, 4, 4, cfg.kv_lora_rank:]), (4, 96)))
    assert not np.asarray(K[:, 2 * rows:]).any() and not np.asarray(V[:, 2 * rows:]).any()


# -- (b) the kernel against its plain form ------------------------------------------
@pytest.mark.parametrize("H,Dk,Dv", [
    pytest.param(4, 24, 16, id="four_heads"),
    pytest.param(2, 40, 32, id="two_wider_heads")])
@pytest.mark.parametrize("starts,lens", [
    pytest.param([0, 0], [40, 13], id="start_0_ragged"),
    pytest.param([48, 16], [40, 29], id="cached_ragged"),
    pytest.param([88, 0], [1, 40], id="one_query_at_the_tables_end")])
def test_kernel_equals_its_plain_form(H, Dk, Dv, starts, lens):
    """Interpreted, with blocks that cut the call and the context (16 x 32):
    ragged true lengths, nothing cached and something cached, the real queries
    alone compared (a padded query's row is the caller's padding)."""
    rng = np.random.default_rng(0)
    B, T, S = 2, 40, 128
    q = jnp.asarray(rng.normal(0, 1, (B, T, H * Dk)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, S, H * Dk)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, S, H * Dv)), jnp.float32)
    starts, lens = jnp.asarray(starts, jnp.int32), jnp.asarray(lens, jnp.int32)
    got = mla_prefill_attention(q, k, v, starts, lens, heads=H, scale=0.2,
                                config={"block_q": 16, "block_k": 32}, interpret=True)
    want = M.attend_call_plain(q, k, v, starts, H, 0.2, block=16)
    assert got.shape == want.shape == (B, T, H * Dv)
    for b in range(B):
        n = int(lens[b])
        close(got[b, :n], want[b, :n], f"row {b}")
        # and against the definition: every key at or before the query
        qh = np.asarray(q[b, :n]).reshape(n, H, Dk)
        s = np.einsum("qhd,khd->hqk", qh, np.asarray(k[b]).reshape(S, H, Dk)) * 0.2
        s = np.where(np.arange(S)[None, :] <= int(starts[b]) + np.arange(n)[:, None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        o = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True),
                      np.asarray(v[b]).reshape(S, H, Dv))
        close(want[b, :n], o.reshape(n, H * Dv), f"row {b}, by the definition")


def test_kernel_skips_a_block_of_queries_past_the_length():
    """A block of queries wholly past ``lens`` does no work and gives zeros,
    whatever the context holds behind the row's end (finite)."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(0, 1, (1, 64, 2 * 16)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (1, 64, 2 * 16)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (1, 64, 2 * 8)), jnp.float32)
    got = mla_prefill_attention(q, k, v, jnp.asarray([0]), jnp.asarray([20]), heads=2,
                                scale=0.25, interpret=True,
                                config={"block_q": 16, "block_k": 16})
    assert not np.asarray(got[0, 32:]).any() and np.asarray(got[0, :20]).any()


# -- (c) the committed configuration's keys -----------------------------------------
def test_the_committed_configuration_switches_the_mechanisms_by_its_keys():
    cfg = MLAMoEConfig.from_dict(PUBLISHED)
    assert [cfg.is_expert_layer(i) for i in range(cfg.num_hidden_layers)] == \
        [False] + [True] * 6
    shapes = {k: s for k, s, _ in MLAMoEForCausalLM.parameter_specs(cfg)}
    # a direct query projection, no low-rank path
    assert shapes["model.layers.0.attn.q.weight"] == (2048, 16 * 192)
    assert not any(".q_a" in k or ".q_b" in k for k in shapes)
    # two shared experts are ONE gated MLP of width 2,816
    assert shapes["model.layers.1.mlp.shared.gate.weight"] == (2048, 2816)
    assert shapes["model.layers.1.mlp.shared.down.weight"] == (2816, 2048)
    assert shapes["model.layers.0.mlp.gate.weight"] == (2048, 11264)
    assert shapes["model.layers.6.mlp.experts.gate"] == (64, 2048, 1408)
    assert shapes["lm_head.weight"] == (2048, 163840)
    # one residual stream, plain rotary frequencies, no YaRN
    assert cfg.hc_mult == 1 and not any("_hc." in k for k in shapes)
    inv, amp, scale = M.rope_tables(cfg)
    np.testing.assert_allclose(inv, 800000.0 ** (-np.arange(0, 64, 2) / 64))
    assert amp == 1.0 and scale == 192 ** -0.5
    assert (cfg.rms_norm_eps, cfg.num_experts_per_tok, cfg.routed_scaling_factor) == \
        (1e-5, 6, 2.446)
    assert (cfg.latent_width, cfg.cache_row) == (576, 640)


# -- (d) through the engine -------------------------------------------------------------
def _engine(net, **kw):
    return Engine(net, **{"block_size": 8, "num_blocks": 64, "max_batch": 8,
                          "max_seq_len": 128, **kw})


@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernels"])
def test_the_engine_serves_prompts_in_calls(tiny, monkeypatch, kernels):
    """``prefill_chunk`` 32, two rows a call: prompts under a chunk keep the
    monolithic program, longer ones are fed in calls of ONE shape (the last
    padded), and every served token is the reference's best at its position;
    the experts' table counts every fed position once a choice; the calls'
    spans say what they fed and read."""
    net, w = tiny
    real = G.mla_moe_decode_state
    monkeypatch.setattr(G, "mla_moe_decode_state", lambda m, k=None: real(m, kernels))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
               for n in (5, 40, 64, 72, 33)]
    before = dict(counters())
    seen = []
    spans.add_span_observer(seen.append)
    try:
        with _engine(net, prefill_chunk=32, prefill_batch=2) as eng:
            outs = [h.result(timeout=600) for h in
                    [eng.submit(p, max_new_tokens=6) for p in prompts]]
            stats = eng.stats()
    finally:
        spans.remove_span_observer(seen.append)
    for p, out in zip(prompts, outs):
        ref = FAM.reference.forward_logits(TINY, w, np.asarray(out[:-1])[None], "f32")[0, len(p) - 1:]
        toks = jnp.asarray(out[len(p):])
        gap = ref.max(-1) - jnp.take_along_axis(ref, toks[:, None], -1)[:, 0]
        assert float(gap.max()) <= 1e-5, len(p)
    tokens = sum(len(p) for p in prompts) + 5 * len(prompts)
    assert np.asarray(stats["expert_tokens"]).sum() == \
        tokens * FAM.expert_layers(TINY) * TINY["num_experts_per_tok"]
    calls = [sp.attrs for sp in seen if sp.name == "prefill" and sp.attrs.get("chunked")]
    assert calls and all(a["bucket_t"] == 32 for a in calls)  # one shape
    fed = sum(len(p) for p in prompts if len(p) > 32)
    assert sum(a["feed"] for a in calls) == fed
    assert all(a["context_tokens"] == a["start"] + a["feed"] and a["latent_blocks_read"] > 0
               and a["attended_pairs"] > 0 for a in calls)
    assert [a["calls_left"] for a in calls][-1] == 0
    moved = lambda k: counters().get(k, 0) - before.get(k, 0)
    assert moved("serve_prefill_chunks") == len(calls)
    assert moved("serve_prefill_context_tokens") == sum(a["context_tokens"] for a in calls)
    # one tail program, whatever the remainders were
    assert sum(k[0] == "prefill_tail" for k in
               [tuple([r["kind"]] + r["bucket"]) for r in stats["programs"]]) == 1
    steps = [sp.attrs for sp in seen if sp.name == "decode_step" and "rows" in sp.attrs]
    assert steps and all(a["context_tokens"] >= a["rows"] for a in steps)


def test_attended_pairs_are_the_familys_count(tiny):
    """A call's ``attended_pairs`` by hand: 40 positions fed as 32 + 8: 32 x
    33 / 2 = 528, then 8 x 32 + 8 x 9 / 2 = 292."""
    assert (FAM.call_pairs(0, 32), FAM.call_pairs(32, 8)) == (528, 292)
    seen = []
    spans.add_span_observer(seen.append)
    try:
        with _engine(tiny[0], prefill_chunk=32) as eng:
            eng.submit(np.arange(40, dtype=np.int32), max_new_tokens=2).result(timeout=600)
    finally:
        spans.remove_span_observer(seen.append)
    calls = [sp.attrs for sp in seen if sp.name == "prefill" and sp.attrs.get("chunked")]
    assert [(a["start"], a["feed"], a["attended_pairs"], a["calls_left"]) for a in calls] == \
        [(0, 32, 528, 1), (32, 8, 292, 0)]


@pytest.mark.parametrize("kw,path", [
    ({"tp": 2}, "tp"), ({"int8": True}, "int8"),
    ({"spec_k": 2}, "speculative verify"),
    ({"prefix_cache": True}, "the prefix index"),
    ({"prefix_cache": True, "prefill_chunk": 16}, "the prefix index")])
def test_what_is_still_refused_is_named(tiny, kw, path):
    with pytest.raises(NotImplementedError) as e:
        _engine(tiny[0], **kw)
    assert "mla_moe" in str(e.value) and path in str(e.value)
    assert "tail prefill" not in str(e.value)  # the tail program exists


def test_snapshots_are_still_refused_with_chunked_prefill_on(tiny):
    with _engine(tiny[0], prefill_chunk=16) as eng:
        for call in (eng.snapshot, eng.handoff, lambda: eng.adopt({})):
            with pytest.raises(NotImplementedError, match="mla_moe.*snapshots"):
                call()


@pytest.mark.parametrize("chunk", [32, 0], ids=["in_a_call", "refused_at_submit"])
def test_a_prompt_past_what_the_whole_prompt_program_holds(tiny, monkeypatch, chunk):
    """The arch says how long a prompt its whole-prompt program takes
    (``prompt_max``: the (H, T, T) float32 scores under
    ``WHOLE_SCORES_BYTES``; 16 positions here). With ``prefill_chunk`` set a
    longer prompt goes through the tail program though it is under a chunk,
    and serves the tokens of an engine that holds it whole; without, it is
    refused at ``submit`` by name."""
    net, _ = tiny
    prompt = np.random.default_rng(4).integers(0, TINY["vocab_size"], 24).astype(np.int32)
    with _engine(net) as eng:
        want = eng.submit(prompt, max_new_tokens=5).result(timeout=600)
    monkeypatch.setattr(M, "WHOLE_SCORES_BYTES", 4 * TINY["num_attention_heads"] * 16 * 16)
    assert M.whole_prompt_max(MLAMoEConfig.from_dict(TINY)) == 16
    seen = []
    spans.add_span_observer(seen.append)
    try:
        with _engine(net, prefill_chunk=chunk) as eng:
            if not chunk:
                with pytest.raises(ValueError, match="at most 16 tokens.*prefill_chunk"):
                    eng.submit(prompt, max_new_tokens=5)
                return
            assert eng.submit(prompt, max_new_tokens=5).result(timeout=600) == want
            eng.submit(prompt[:16], max_new_tokens=2).result(timeout=600)
    finally:
        spans.remove_span_observer(seen.append)
    fills = [sp.attrs for sp in seen if sp.name == "prefill"]
    assert [(a.get("chunked", False), a.get("feed")) for a in fills] == \
        [(True, 24), (False, None)]


def test_rows_in_mid_prefill_hold_their_batch_slots(tiny):
    """More long prompts at once than the engine has rows: a sequence whose
    prompt is still being fed holds a batch slot from its admission, so the
    live rows never pass ``max_batch`` when the calls land (before PR 47 the
    admission counted the running rows alone, and a landing past the widest
    decode bucket ended the engine)."""
    net, _ = tiny
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, TINY["vocab_size"], 70).astype(np.int32) for _ in range(6)]
    seen = []
    spans.add_span_observer(seen.append)
    try:
        with _engine(net, max_batch=2, prefill_chunk=16, decode_buckets=(1, 2)) as eng:
            handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
            outs = [h.result(timeout=600) for h in handles]
    finally:
        spans.remove_span_observer(seen.append)
    assert [len(o) for o in outs] == [82] * 6
    assert max(sp.attrs["rows"] for sp in seen
               if sp.name == "decode_step" and "rows" in sp.attrs) <= 2
