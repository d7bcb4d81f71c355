"""The latent-attention / routed-expert / hyper-connection decoder
(``paddle_tpu.models.mla_moe``) against its plain reference
(``benchmark/reference/xing4.py``: float32 ``jax.numpy``, written from the
published equations and sharing no code with the program), at a small size on
the CPU with seeded weights.

Tolerances. Everything here runs in float32 on both sides, so what is left
between program and reference is the order of the sums: ``TOL`` = 2e-5 of the
largest reference value + 2e-6, the bound ``paged_attention``'s tests hold
(observed: a few float32 ulp, 3e-7). A mechanism left out, an iteration cut or
an expert dropped moves the result by 1e-2 and more, which the tests that
perturb the reference show.
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
from paddle_tpu.profiler import counters
from paddle_tpu.serving import Engine

REPO = pathlib.Path(__file__).parent.parent


def _family():
    spec = importlib.util.spec_from_file_location(
        "xing4_family_under_test", REPO / "benchmark/families/xing4.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAM = _family()
PUBLISHED = json.loads((REPO / "benchmark/configs/xing4-29b-a4b-8l.json").read_text())
TINY = {**PUBLISHED, **FAM.REHEARSE}


def close(a, b, what=""):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    tol = 2e-5 * np.abs(b).max() + 2e-6
    assert np.abs(a - b).max() <= tol, (what, np.abs(a - b).max(), tol)


def build(cfg, seed=3):
    """(model, leaves) of a configuration dict, seeded as the benchmark seeds."""
    from benchmark import weights as W

    w = W.make_weights(cfg, seed, FAM.leaf_specs(cfg))
    net, _ = FAM.build(cfg, w)
    net.eval()
    return net, w


def layer_leaves(w, i):
    p = f"h{i}."
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


@pytest.fixture(scope="module")
def tiny():
    return build(TINY)


# -- (a) the hyper-connection alone ---------------------------------------------
def _wrap_pair(iters):
    cfg = {**TINY, "hc_sinkhorn_iters": iters}
    rng = np.random.default_rng(0)
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    hc = {"phi": jnp.asarray(rng.normal(0, 0.02, (n * d, n * (2 + n))), jnp.float32),
          "alpha": jnp.asarray(1 + rng.normal(0, 0.02, 3), jnp.float32),
          "bias": jnp.asarray(rng.normal(0, 0.02, n * (2 + n)), jnp.float32)}
    g = jnp.asarray(1 + rng.normal(0, 0.02, d), jnp.float32)
    X = jnp.asarray(rng.normal(0, 1, (7, n, d)), jnp.float32)
    fn = lambda u: jnp.tanh(u) * 0.5
    return cfg, hc, g, X, fn


@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernel"])
def test_mhc_wrap_equals_reference(kernels):
    from benchmark.reference import xing4 as R

    cfg, hc, g, X, fn = _wrap_pair(20)
    got = M.mhc_wrap(MLAMoEConfig.from_dict(cfg), hc, X, g, fn, kernels=kernels)
    close(got, R.wrap(cfg, hc, g, X, fn, "f32"), "mhc wrap")


def test_twenty_sinkhorn_rounds_are_not_one():
    """Cutting the iterations must fail the tolerance the wrap is held to:
    with the ``alpha`` leaves seeded around 1 the residual map's entries
    spread by about +-2 before the Sinkhorn, and one round leaves its columns
    off 1 by percents."""
    from benchmark.reference import xing4 as R

    cfg, hc, g, X, fn = _wrap_pair(20)
    full = np.asarray(R.wrap(cfg, hc, g, X, fn, "f32"))
    once = np.asarray(M.mhc_wrap(
        MLAMoEConfig.from_dict({**cfg, "hc_sinkhorn_iters": 1}), hc, X, g, fn))
    assert np.abs(once - full).max() > 100 * (2e-5 * np.abs(full).max() + 2e-6)
    z = jnp.asarray(np.random.default_rng(1).normal(0, 2, (5, 24)), jnp.float32)
    res = np.asarray(M.sinkhorn_plain(z, 4, 20, 1e-6, (-30.0, 30.0))[2])
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-3)


# -- (b) prefill, then decode through the paged latent cache ----------------------
def _serve(net, prompts, new, **kw):
    with Engine(net, block_size=8, num_blocks=64, max_batch=8, max_seq_len=128,
                **kw) as eng:
        handles = [eng.submit(p, max_new_tokens=new) for p in prompts]
        outs = [h.result(timeout=600) for h in handles]
        return outs, eng.stats()


def _gaps(cfg, w, prompt, out):
    """How far each served token's reference logit lies below the best."""
    ref = FAM.reference.forward_logits(cfg, w, np.asarray(out[:-1])[None], "f32")[0, len(prompt) - 1:]
    toks = jnp.asarray(out[len(prompt):])
    return np.asarray(ref.max(-1) - jnp.take_along_axis(ref, toks[:, None], -1)[:, 0])


@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernels"])
def test_serving_equals_the_reference_forward(tiny, monkeypatch, kernels):
    """Prompts prefilled in the expanded form, then decoded in the absorbed
    form through the paged latent pool: every served token is the reference's
    best at its position (float32: the gap is an exact 0 unless two logits
    tie to rounding), with the plain forms and with the three kernels (under
    the interpreter here)."""
    net, w = tiny
    real = G.mla_moe_decode_state
    monkeypatch.setattr(G, "mla_moe_decode_state",
                        lambda m, k=None: real(m, kernels))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, TINY["vocab_size"], n).astype(np.int32)
               for n in (5, 17, 30)]
    outs, stats = _serve(net, prompts, 6)
    for p, out in zip(prompts, outs):
        assert len(out) == len(p) + 6
        assert _gaps(TINY, w, p, out).max() <= 1e-5
    # two expert layers, k = 2: every real token counted once a choice, the
    # padding of the buckets nowhere
    tokens = sum(len(p) for p in prompts) + 5 * len(prompts)
    assert np.asarray(stats["expert_tokens"]).sum() == tokens * 2 * 2


def test_expanded_and_absorbed_forms_agree(tiny):
    """The same numbers in another order: logits of the last prompt token by
    prefill (expanded) against the same position reached by decode steps
    (absorbed, through the pool)."""
    net, w = tiny
    rng = np.random.default_rng(1)
    ids = rng.integers(0, TINY["vocab_size"], 24).astype(np.int32)
    full = np.asarray(net(ids[None])._data[0])       # expanded, every position
    close(full, FAM.reference.forward_logits(TINY, w, ids[None], "f32")[0], "prefill")
    _, arch, params, _ = G.mla_moe_decode_state(net, False)
    bs, nb, cut = 8, 8, 16
    pool = jnp.zeros((TINY["num_hidden_layers"], nb, bs, arch["cache"][0][0]), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    pre = G.build_paged_prefill(arch, 1, cut, bs, 4)
    pool, logits, _ = pre(params, jnp.asarray(ids[None, :cut]), jnp.asarray([cut]), tables, pool)
    close(logits[0], full[cut - 1], "prefill's last row")
    for t in range(cut, 24):
        X = arch["embed"](params, jnp.asarray(ids[None, t:t + 1]), None)
        pos = jnp.asarray([t], jnp.int32)
        for li, lw in enumerate(params["layers"]):
            X, (pool,), _ = arch["decode_layer"](
                lw, X, (pool,), li, tables, pos, tables[:, t // bs], pos % bs,
                jnp.asarray([True]))
        close(arch["head"](params, X[:, -1])[0], full[t], f"absorbed, position {t}")


# -- (c) (d) (f) the expert layer ---------------------------------------------------
def _expert_case(cfg, w, i=2, tokens=19, seed=5):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 1, (tokens, cfg["hidden_size"])), jnp.float32)
    return x, layer_leaves(w, i)


def _program_layer(cfg, leaves, **over):
    pc = MLAMoEConfig.from_dict({**cfg, **over})
    held = list(pc.experts_held)
    w = {"router": leaves["mlp.router.w"], "e_bias": leaves["mlp.router.e_bias"],
         "experts_gate": leaves["mlp.experts.gate"][jnp.asarray(held)],
         "experts_up": leaves["mlp.experts.up"][jnp.asarray(held)],
         "experts_down": leaves["mlp.experts.down"][jnp.asarray(held)],
         "shared_gate": leaves["mlp.shared.gate.w"], "shared_up": leaves["mlp.shared.up.w"],
         "shared_down": leaves["mlp.shared.down.w"]}
    return pc, w


@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernel"])
def test_every_token_to_the_same_experts_drops_none(tiny, kernels):
    """An ``e_bias`` that sends every token to the same k experts: the worst
    imbalance there is. Nothing is dropped (no capacity), the counts say so,
    and the result is the reference's."""
    from benchmark.reference import xing4 as R

    _, w = tiny
    x, leaves = _expert_case(TINY, w)
    bias = jnp.zeros((TINY["n_routed_experts"],), jnp.float32).at[jnp.asarray([1, 6])].set(10.0)
    leaves = {**leaves, "mlp.router.e_bias": bias}
    pc, pw = _program_layer(TINY, leaves)
    y, counts = M.moe_ffn(pc, pw, x, jnp.ones((x.shape[0],), bool), kernels)
    assert np.asarray(counts).tolist() == [0, 19, 0, 0, 0, 0, 19, 0]
    close(y, R.expert_ffn(TINY, leaves, x, "f32"), "all tokens to two experts")


@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernel"])
def test_two_shares_of_the_experts_add_up(tiny, kernels):
    """Two chips' shares (experts 0-3 and 4-7 held), the shared expert counted
    once, add up to the uncut layer, in the program and against the
    reference's uncut layer."""
    from benchmark.reference import xing4 as R

    _, w = tiny
    x, leaves = _expert_case(TINY, w)
    live = jnp.ones((x.shape[0],), bool)
    parts = []
    for held in ((0, 1, 2, 3), (4, 5, 6, 7)):
        pc, pw = _program_layer(TINY, leaves, held_experts=held)
        y, counts = M.moe_ffn(pc, pw, x, live, kernels)
        assert int(counts.sum()) == x.shape[0] * TINY["num_experts_per_tok"]  # routed over ALL
        parts.append(np.asarray(y, np.float64))
        ref = R.expert_ffn(TINY, {**leaves, **{k: leaves[k][jnp.asarray(held)] for k in
                                              ("mlp.experts.gate", "mlp.experts.up", "mlp.experts.down")}},
                           x, "f32", held=held)
        close(y, ref, f"share {held}")
    shared = np.asarray(M.gated_mlp(x, leaves["mlp.shared.gate.w"], leaves["mlp.shared.up.w"],
                                    leaves["mlp.shared.down.w"]), np.float64)
    close(parts[0] + parts[1] - shared, R.expert_ffn(TINY, leaves, x, "f32"), "sum of shares")


@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernel"])
def test_padding_reaches_no_expert_and_no_counter(tiny, kernels):
    _, w = tiny
    x, leaves = _expert_case(TINY, w, tokens=12)
    pc, pw = _program_layer(TINY, leaves)
    live = jnp.asarray([True] * 5 + [False] * 7)
    y, counts = M.moe_ffn(pc, pw, x, live, kernels)
    y5, counts5 = M.moe_ffn(pc, pw, x[:5], live[:5], kernels)
    assert int(counts.sum()) == 5 * TINY["num_experts_per_tok"]
    assert np.array_equal(np.asarray(counts), np.asarray(counts5))
    close(y[:5], y5, "live rows")
    # a padded token's row is the shared expert's alone: no routed expert ran it
    shared = M.gated_mlp(x[5:], pw["shared_gate"], pw["shared_up"], pw["shared_down"])
    close(y[5:], shared, "padding rows")


def test_padded_bucket_rows_are_not_counted(tiny):
    """Through the engine: one live row in a decode bucket, prompts shorter
    than their prefill bucket; ``serve_expert_assignments`` moves by the real
    tokens alone."""
    from paddle_tpu.profiler import spans

    net, _ = tiny
    before = counters().get("serve_expert_assignments", 0)
    prompt = np.arange(5, dtype=np.int32)
    seen = []
    spans.add_span_observer(seen.append)
    try:
        outs, stats = _serve(net, [prompt], 4, decode_buckets=(4, 8))
    finally:
        spans.remove_span_observer(seen.append)
    real = (5 + 3) * 2 * 2   # tokens x expert layers x k
    assert counters()["serve_expert_assignments"] - before == real
    assert np.asarray(stats["expert_tokens"]).sum() == real
    # the same on the spans of the programs that routed: one prefill, three
    # decode steps of one live row (2 expert layers x k = 4 assignments, 4
    # distinct experts or fewer, none taken twice by one token)
    routed = [sp.attrs for sp in seen if "expert_assignments" in sp.attrs]
    assert sorted(sp.name for sp in seen if "expert_assignments" in sp.attrs) == \
        ["decode_step"] * 3 + ["prefill"]
    assert sum(a["expert_assignments"] for a in routed) == real
    # (the loop runs one step ahead: the first decode_step only enqueues,
    # and the counts ride the span their step lands in)
    steps = [sp.attrs for sp in seen if sp.name == "decode_step"
             and "expert_assignments" in sp.attrs]
    assert len(steps) == 3 and all(a["expert_assignments"] == 4 and a["experts_touched"] == 4
               and a["expert_tokens_max"] == 1 for a in steps)
    # the table stays the engine's: nothing is left in the process's counters
    assert not any(k.startswith("serve_expert_tokens") for k in counters())


@pytest.mark.parametrize("length,by_hand", [
    # the three decode steps write positions length, length + 1, length + 2;
    # blocks of 4 tokens, chunks of 16 blocks: (blocks, chunks, full chunks)
    pytest.param(5, [(2, 1, 0)] * 3, id="under_one_chunk"),
    pytest.param(62, [(16, 1, 1), (16, 1, 1), (17, 2, 1)], id="over_a_chunks_edge"),
    pytest.param(70, [(18, 2, 1), (18, 2, 1), (19, 2, 1)], id="two_chunks"),
    pytest.param(70, None, id="plain_form_has_no_schedule")])
def test_decode_spans_carry_the_latent_reads_copy_schedule(tiny, monkeypatch,
                                                           length, by_hand):
    """``latent_blocks`` / ``latent_chunks`` / ``latent_full_chunks`` of a
    ``decode_step`` span are what the landing step's positions give by hand
    for the chunk the kernel resolved (the registry's 16 blocks, of a table
    of 32); the three rows that pad the bucket of 4 (a block each in the
    kernel) are not counted; the plain gather has no chunks and says
    nothing."""
    from paddle_tpu.profiler import spans

    net, _ = tiny
    real = G.mla_moe_decode_state
    monkeypatch.setattr(G, "mla_moe_decode_state",
                        lambda m, k=None: real(m, by_hand is not None))
    prompt = np.arange(length, dtype=np.int32) % TINY["vocab_size"]
    seen = []
    spans.add_span_observer(seen.append)
    try:
        with Engine(net, block_size=4, num_blocks=64, max_batch=8,
                    max_seq_len=128, decode_buckets=(4, 8)) as eng:
            eng.submit(prompt, max_new_tokens=4).result(timeout=600)
    finally:
        spans.remove_span_observer(seen.append)
    landed = [sp.attrs for sp in seen if sp.name == "decode_step"
              and "expert_assignments" in sp.attrs]
    assert len(landed) == 3 and all(a["rows"] == 1 and a["bucket"] == 4
                                    for a in landed)
    # the latent arch's span is as it was: the grouped-head read's counters
    # (``paged_*``, PR 46) are of the archs that call that kernel
    assert not any(k.startswith("paged_") for a in landed for k in a)
    if by_hand is None:
        assert not any(k.startswith("latent_") for a in landed for k in a)
        return
    assert [(a["latent_blocks"], a["latent_chunks"], a["latent_full_chunks"])
            for a in landed] == by_hand


# -- (e) the keys switch the mechanisms ---------------------------------------------
@pytest.mark.parametrize("over", [
    pytest.param({"hc_mult": 1}, id="hc_mult_1"),
    pytest.param({"q_lora_rank": None}, id="q_lora_rank_null"),
    pytest.param({"hc_mult": 1, "q_lora_rank": None, "rope_scaling": None,
                  "n_routed_experts": 0, "tie_word_embeddings": True}, id="plain_decoder")])
def test_keys_switch_mechanisms_by_the_same_code(over):
    cfg = {**TINY, **over}
    net, w = build(cfg)
    ids = np.random.default_rng(2).integers(0, cfg["vocab_size"], (1, 20))
    close(net(ids)._data, FAM.reference.forward_logits(cfg, w, ids, "f32"), str(over))
    if cfg["hc_mult"] == 1:
        assert not any("_hc." in k for k in net.state_dict())
        # and the wrap IS the pre-norm residual
        X = jnp.asarray(np.random.default_rng(3).normal(0, 1, (4, 1, 8)), jnp.float32)
        g = jnp.ones((8,), jnp.float32)
        got = M.mhc_wrap(MLAMoEConfig(hc_mult=1), None, X, g, jnp.tanh)
        close(got[:, 0], X[:, 0] + jnp.tanh(M.rms(X[:, 0], g, 1e-6)), "x + f(norm(x))")


def test_unknown_mechanisms_are_refused_by_name():
    with pytest.raises(NotImplementedError, match="group-limited"):
        MLAMoEConfig(n_routed_experts=8, n_group=4, topk_group=2)
    with pytest.raises(NotImplementedError, match="softmax"):
        MLAMoEConfig(n_routed_experts=8, scoring_func="softmax")
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        MLAMoEConfig(rope_scaling={"type": "linear", "factor": 2})
    with pytest.raises(ValueError, match="differ"):
        MLAMoEForCausalLM(MLAMoEConfig.from_dict(TINY), weights={"model.norm.weight": 1})


# -- (g) unsupported paths raise, naming the arch and the path ------------------------
@pytest.mark.parametrize("kw,path", [
    ({"tp": 2}, "tp"), ({"int8": True}, "int8"),
    ({"spec_k": 2}, "speculative verify"),
    # the tail program exists since PR 47 (``prefill_chunk`` is served:
    # tests/test_mla_moe_chunked.py); of the prefix cache the index is owed
    ({"prefix_cache": True}, "the prefix index"),
    ({"prefix_cache": True, "prefill_chunk": 16}, "the prefix index")])
def test_unsupported_engine_paths_raise_at_construction(tiny, kw, path):
    with pytest.raises(NotImplementedError) as e:
        Engine(tiny[0], block_size=8, num_blocks=16, max_batch=4, max_seq_len=64, **kw)
    assert "mla_moe" in str(e.value) and path in str(e.value)


def test_unsupported_calls_raise_at_the_call(tiny):
    net, _ = tiny
    with Engine(net, block_size=8, num_blocks=16, max_batch=4, max_seq_len=64) as eng:
        for call in (eng.snapshot, eng.handoff, lambda: eng.adopt({})):
            with pytest.raises(NotImplementedError, match="mla_moe.*snapshots"):
                call()
        assert eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2).result(timeout=300)
    with pytest.raises(NotImplementedError, match="dense decode loop and beam search"):
        net.generate(np.zeros((1, 4), np.int64))


def test_a_decoder_without_experts_or_streams_is_served_too():
    """The same class with the routing and the streams switched off by its
    keys (latent attention alone): served through the same programs, with no
    expert table in ``stats()``, its tokens the forward pass's own."""
    cfg = MLAMoEConfig(vocab_size=97, hidden_size=32, num_hidden_layers=2,
                       num_attention_heads=2, kv_lora_rank=16, qk_nope_head_dim=8,
                       qk_rope_head_dim=8, v_head_dim=8, intermediate_size=48,
                       max_position_embeddings=64)
    net = MLAMoEForCausalLM(cfg)
    net.eval()
    prompt = np.arange(5, dtype=np.int32)
    with Engine(net, block_size=8, num_blocks=16, max_batch=4, max_seq_len=64) as eng:
        out = eng.submit(prompt, max_new_tokens=4).result(timeout=300)
        assert "expert_tokens" not in eng.stats()
    logits = np.asarray(net(np.asarray(out[:-1])[None])._data)[0]
    assert logits[4:].argmax(-1).tolist() == out[5:]
