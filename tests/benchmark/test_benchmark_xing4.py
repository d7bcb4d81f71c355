"""The ``xing4`` family's files: the counts its readers divide by against values
worked by hand at the published widths of Xing4.0-29B-A4B (d 3584, 32 heads,
latent 512 + 64, 64 experts of 1024, 8 of 40 layers), the five readers on
hand-made facts, the rule by which its reference gives a verdict, and the
cell's chip-free rehearsal. ``BENCHMARK.json`` lists the configuration, the
cell and the five metrics since PR 27, appended, and no file of the harness
was edited for them."""
import json
import types

import pytest

from benchmark import run
from benchmark.manifest import Manifest

CELL = "serve-xing4-longanswer-pinned"


@pytest.fixture(scope="module")
def M():
    return Manifest()


@pytest.fixture(scope="module")
def CFG(M):
    return M.config("xing4-29b-a4b-8l")


@pytest.fixture(scope="module")
def FAM(M):
    return M.family("xing4")


@pytest.fixture(scope="module")
def PEAKS(M):
    return M.peaks("TPU v5 lite")


def test_manifest_is_sound_and_states_the_cut(M, CFG, FAM):
    assert M.validate() == []
    entry = next(c for c in M.data["configs"] if c["name"] == "xing4-29b-a4b-8l")
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert CFG["published"] == {"num_hidden_layers": 40, "max_position_embeddings": 262144}
    assert len(entry["source"]) < 200 and "deployment" in CFG
    assert {"final_hidden", "hc_eps", "rotary_layout", "multi_token_prediction",
            "initializer"} <= set(CFG["assumed"])
    cell = M.workload(CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert [m["name"] for m in M.metrics_of(CELL, "end_to_end")] == \
        ["token_gap_p50_ms", "setup_s"]
    mine = {m["name"] for m in M.metrics_of(CELL, "per_layer")}
    assert {"experts_touched_per_layer", "expert_load_max_over_mean",
            "moe_decode_hbm_roofline", "expert_ffn_roofline",
            "latent_attention_roofline"} <= mine
    # the dense model's share lists its own cell since PR 32: a static count
    # cannot follow a routing, and moe_decode_hbm_roofline is the reading here
    assert "decode_hbm_roofline" not in mine and not hasattr(FAM, "weight_bytes")
    # appended behind what the manifest held at PR 27 (later PRs append behind
    # these in turn, so nothing here says "last")
    after = lambda names, mine, before: names.index(mine) == names.index(before) + 1
    assert after([c["name"] for c in M.data["configs"]], "xing4-29b-a4b-8l",
                 "gpt3-6p7b-4chip")
    assert after([w["name"] for w in M.data["workloads"]], CELL, "train-hybrid-4chip")
    metrics = [m["name"] for m in M.data["per_layer"]]
    at = metrics.index("experts_touched_per_layer")
    assert metrics[at:at + 5] == [
        "experts_touched_per_layer", "expert_load_max_over_mean",
        "moe_decode_hbm_roofline", "expert_ffn_roofline", "latent_attention_roofline"]
    # the load is ISSUE 32's: four fifths of the knee the file states as a
    # number, ramp at 1.5 x that, the order of the arrivals pinned
    traffic = M.traffic(cell["traffic"])
    assert traffic["rate_per_s"] == round(0.8 * traffic["knee_per_s"], 1) == 3.8
    assert traffic["ramp_rate_per_s"] == pytest.approx(1.5 * traffic["rate_per_s"])
    assert isinstance(traffic["order_seed"], int)
    assert (traffic["prompt_len"]["hi"], traffic["output_len"]["hi"]) == (768, 1024)


def test_counts_at_the_published_widths(CFG, FAM):
    # attention: q_a 3584x768 + its norm 768 + q_b 768x6144 + kv_a 3584x576
    # + its norm 512 + kv_b 512x8192 + o 4096x3584
    attn = 2_752_512 + 768 + 4_718_592 + 2_064_384 + 512 + 4_194_304 + 14_680_064
    assert FAM.attention_params(CFG) == attn == 28_411_136
    assert FAM.expert_bytes(CFG) == 3 * 3584 * 1024 * 2 == 22_020_096
    assert FAM.latent_bytes_per_token(CFG) == (512 + 64) * 2 == 1152
    assert FAM.cache_row(CFG) == 640                        # 576 padded to 5 lane tiles
    assert FAM.cache_bytes_per_context_token(CFG) == 8 * 640 * 2 == 10_240
    assert FAM.expert_layers(CFG) == 6
    # every step: 8 x (attention + two hyper-connections + two norms), six
    # routers with e_bias and shared experts, two dense FFNs, final norm + head
    hc = 2 * (4 * 3584 * 24 + 3 + 24)                       # 688,182
    layer = attn + hc + 2 * 3584                            # 29,106,486
    moe = 3584 * 64 + 64 + 3 * 3584 * 1024                  # 11,239,488
    dense = 3 * 3584 * 9216                                 # 99,090,432
    head = 3584 + 3584 * 131072                             # 469,765,632
    params = 8 * layer + 6 * moe + 2 * dense + head
    assert params == 968_235_312
    assert FAM.dense_bytes_per_step(CFG) == 2 * params == 1_936_470_624
    # and the whole model: the above, the embedding, 6 x 64 experts
    leaves = sum(int(__import__("numpy").prod(s)) for _, s, _ in FAM.leaf_specs(CFG))
    assert leaves == params + 131072 * 3584 + 6 * 64 * 3 * 3584 * 1024 == 5_665_855_792


def _facts(CFG, FAM, PEAKS, touched=300.0, rows=30.0, step_ms=12.0, steps=2):
    """Two decode steps of ``step_ms`` on the device, 8 latent-attention and 6
    expert calls each, 30 rows that each got one token at a context of 500."""
    ops, mods, t = [], [], 1_000_000
    for s in range(steps):
        mods.append([f"jit_step({s})", t, int(step_ms * 1e6)])
        for k in range(8):
            ops.append([f"%mla_paged_attention.{k} = bf16[32,32,512]{{2,1,0}} custom-call(...)",
                        t + k * 1000, 40_000])
        for k in range(6):
            ops.append([f"%moe_experts_t16.{k} = bf16[1152,3584]{{1,0}} custom-call(...)",
                        t + 500_000 + k * 1_700_000, 1_600_000])
        t += int(step_ms * 1e6) + 3_000_000
    red = {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}}, "host": [],
           "sync_ns": 0, "t0_ns": 0, "t1_ns": t, "host_window": (10.0, 20.0)}
    spans = types.SimpleNamespace(named=lambda name, *a: [
        ("decode_step", 1_000_000 + i, 2_000_000 + i, 1,
         {"rows": rows, "experts_touched": touched}) for i in range(steps)])
    served = [types.SimpleNamespace(index=0, stamps=[9.0, 12.0, 15.0, 25.0])] * 30
    sched = types.SimpleNamespace(prompt_len=[499])
    return {"trace": red, "spans": spans, "served": served, "schedule": sched,
            "config": CFG, "family": FAM, "peaks": PEAKS,
            "span_window_ns": (0, 10**12)}


def test_device_trace_readers_on_hand_made_facts(M, CFG, FAM, PEAKS):
    run_ = _facts(CFG, FAM, PEAKS)
    f = FAM.decode_trace_facts(run_)
    # tokens 1 and 2 of each of 30 streams fell inside the traced stretch
    assert f["steps"] == 2 and f["step_ns"] == 24_000_000 and f["touched"] == 300
    assert f["context_tokens"] == 30 * (500 + 501)
    need = 2 * (1_936_470_624 + 300 * 22_020_096) + 30_030 * 8 * 1152
    assert M.reader("moe_decode_hbm_roofline")(run_) == pytest.approx(
        100 * need / 819e9 / 0.024)                        # 86.9%
    per_call = 300 / 6 * 22_020_096 + 2 * 120 * 3584 * 2
    assert M.reader("expert_ffn_roofline")(run_) == pytest.approx(
        100 * per_call / 819e9 / 1.6e-3)                   # 84.2%
    # each of the 16 calls reads one layer's rows of one step's contexts
    mla = 16 * (30_030 / 2 * 1152 + 30 * 32 * (576 + 512) * 2)
    assert M.reader("latent_attention_roofline")(run_) == pytest.approx(
        100 * mla / 819e9 / (16 * 40e-6))                  # 59.2%
    assert M.reader("experts_touched_per_layer")(run_) == 50.0
    # a share over 100% is a fault of a count or of the time, and raises
    with pytest.raises(ValueError, match="moe_decode_hbm_roofline"):
        M.reader("moe_decode_hbm_roofline")(_facts(CFG, FAM, PEAKS, step_ms=5.0))
    # the parent's spans carry no experts_touched: every reader finds nothing
    old = _facts(CFG, FAM, PEAKS)
    old["spans"] = types.SimpleNamespace(named=lambda name, *a: [
        ("decode_step", 1_000_001, 2_000_000, 1, {"rows": 30})])
    for name in ("moe_decode_hbm_roofline", "expert_ffn_roofline",
                 "latent_attention_roofline", "experts_touched_per_layer"):
        assert M.reader(name)(old) is None, name
    assert M.reader("moe_decode_hbm_roofline")(dict(run_, trace=None)) is None


def test_expert_load_reader_reads_the_window_s_decode_steps(M, CFG, FAM):
    read = M.reader("expert_load_max_over_mean")
    step = lambda **attrs: ("decode_step", 5, 9, 1, dict(rows=30, **attrs))
    spans = lambda rows: types.SimpleNamespace(named=lambda name, *a: rows)
    run_ = {"config": CFG, "family": FAM, "span_window_ns": (0, 10)}
    # the parent's spans carry no expert_assignments: nothing to read
    assert read(dict(run_, spans=spans([step()]))) is None
    assert read(dict(run_, spans=None)) is None
    # 6 layers x 64 experts: 720 assignments are 1.875 a pair; the busiest
    # took 6 in one step and 3 in the other
    rows = [step(expert_tokens_max=6, expert_assignments=720),
            step(expert_tokens_max=3, expert_assignments=720)]
    assert read(dict(run_, spans=spans(rows))) == pytest.approx((3.2 + 1.6) / 2)


def test_the_cell_rehearses(M, capsys):
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "1.5",
                   "--trace", "1", "--rehearse"])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert "check: served_logit_gap = 0 " in out     # float32 on both sides
    for name in ("experts_touched_per_layer", "expert_load_max_over_mean",
                 "decode_rows_mean", "decode_step_ms", "prefill_step_ms"):
        assert f"reader: {name} read something" in out, name
    for name in ("moe_decode_hbm_roofline", "expert_ffn_roofline",
                 "latent_attention_roofline"):
        assert f"reader: {name} found nothing to read" in out, name
    assert "reader: decode_hbm_roofline" not in out   # the dense cell's alone
    assert "reference: a verdict at " in out


def test_reference_precisions_differ(CFG, FAM):
    import numpy as np

    from benchmark import weights as W

    cfg = {**CFG, **FAM.REHEARSE}
    w = W.make_weights(cfg, 11, FAM.leaf_specs(cfg))
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (1, 32))
    R = FAM.reference
    f32 = np.asarray(R.forward_logits(cfg, w, ids, "f32"))
    assert f32.shape == (1, 32, cfg["vocab_size"])
    assert np.array_equal(f32, np.asarray(R.forward_logits(cfg, w, ids, "f32")))
    fp8 = np.asarray(FAM.forward_logits(cfg, w, ids, "fp8"))
    assert np.abs(fp8 - f32).max() > 1e-2
    with pytest.raises(ValueError, match="unknown precision"):
        FAM.forward_logits(cfg, w, ids, "bf16")


def test_reference_gives_a_verdict_only_where_its_routing_is_decided(CFG, FAM, capsys):
    import numpy as np

    from benchmark import weights as W

    R = FAM.reference
    cfg = {**CFG, **FAM.REHEARSE}
    w = W.make_weights(cfg, 11, FAM.leaf_specs(cfg))
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], (1, 48))
    whole, margin = (np.asarray(a) for a in R.forward(cfg, w, ids, "f32"))
    assert margin.shape == (1, 48) and (margin > 0).all() and np.isfinite(margin).all()
    # a position's margin is the narrowest of its expert layers', each the
    # k-th largest of score + e_bias less the next: one layer by hand
    x = np.random.default_rng(2).standard_normal((5, cfg["hidden_size"])).astype(np.float32)
    lw = {k[3:]: v for k, v in w.items() if k.startswith("h2.mlp.router")}
    sc = 1 / (1 + np.exp(-x @ np.asarray(lw["mlp.router.w"], np.float32))) \
        + np.asarray(lw["mlp.router.e_bias"], np.float32)
    top = -np.sort(-sc, axis=-1)
    k = cfg["num_experts_per_tok"]
    assert np.allclose(R.routing_margin(cfg, lw, x, "f32"), top[:, k - 1] - top[:, k], atol=1e-6)
    # above the median margin: half the rows all zeros, the others untouched
    cut = float(np.median(margin))
    held, m2 = (np.asarray(a) for a in R.forward(cfg, w, ids, "f32", min_margin=cut))
    assert np.array_equal(m2, margin)
    decided = margin[0] >= cut
    assert 0 < decided.sum() < 48
    assert np.array_equal(held[0, decided], whole[0, decided])
    assert not held[0, ~decided].any()
    # the family judges by ROUTER_MARGIN and says how many positions it judged;
    # the control's logits are whole, and so is a model's that routes nothing
    judged = np.asarray(FAM.forward_logits(cfg, w, ids, "f32"))
    n = int((margin >= FAM.ROUTER_MARGIN).sum())
    assert f"reference: a verdict at {n} of 48 positions" in capsys.readouterr().out
    assert (judged[0].any(axis=-1) == (margin[0] >= FAM.ROUTER_MARGIN)).all()
    assert np.asarray(FAM.forward_logits(cfg, w, ids, "fp8"))[0].any(axis=-1).all()
    dense = {**cfg, "n_routed_experts": 0, "n_shared_experts": 0}
    wd = W.make_weights(dense, 11, FAM.leaf_specs(dense))
    _, md = R.forward(dense, wd, ids[:, :16], "f32", min_margin=1.0)
    assert np.isinf(np.asarray(md)).all()
