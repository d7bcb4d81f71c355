"""The ``lfm2`` family's files: the counts its readers divide by against
values worked by hand at the published widths of LFM2-24B-A2B (d 2048, 32
query heads on 8 key/value heads of 64, dense FFN 11776, 64 experts of 1536 of
which a token takes 4, vocabulary 65,536 tied, three convolution taps) in the
cell's cut of ten layers, the five readers on hand-made facts, the two
precisions of its reference, and the cell's chip-free rehearsal.
``BENCHMARK.json`` lists the configuration, the cell and the five metrics
since PR 38, appended, and no file of the harness was edited for them."""
import json
import types

import numpy as np
import pytest

from benchmark import run
from benchmark.manifest import Manifest

CELL = "serve-lfm2-toolturn-pinned"
CONFIG = "lfm2-24b-a2b-10l"
METRICS = ["lfm2_decode_hbm_mfu_pct", "lfm2_expert_ffn_roofline",
           "packed_kv_attention_roofline", "lfm2_experts_touched_per_layer",
           "paged_kv_tokens_per_step"]


@pytest.fixture(scope="module")
def M():
    return Manifest()


@pytest.fixture(scope="module")
def CFG(M):
    return M.config(CONFIG)


@pytest.fixture(scope="module")
def FAM(M):
    return M.family("lfm2")


@pytest.fixture(scope="module")
def PEAKS(M):
    return M.peaks("TPU v5 lite")


def test_manifest_is_sound_and_states_the_cut(M, CFG, FAM):
    assert M.validate() == []
    entry = next(c for c in M.data["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers", "layer_types"]
    assert entry["source"] == CFG["source"] and len(entry["source"]) < 200
    # every key of the catalog row's config at its published value but the two cut
    published = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
                 "intermediate_size": 11776, "max_position_embeddings": 128000,
                 "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
                 "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
                 "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
                 "num_key_value_heads": 8,
                 "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
                 "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
    assert {k: CFG[k] for k in published} == published
    whole = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9 \
        + ["full_attention", "conv"]
    assert CFG["num_hidden_layers"] == 10 and CFG["layer_types"] == whole[:10]
    assert CFG["published"]["num_hidden_layers"] == 40 == len(whole)
    assert CFG["published"]["max_position_embeddings"] == 128000
    assert CFG["dtype"] == "bfloat16" and "four-stage pipeline" in CFG["deployment"]
    assert {"tie_word_embeddings", "in_proj_order", "convolution", "qk_norm",
            "rotary_layout", "router", "gate_epsilon", "initializer", "state",
            "reference_verdict"} <= set(CFG["assumed"])
    cell = M.workload(CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, "toolturn-pinned")
    assert [m["name"] for m in M.metrics_of(CELL, "end_to_end")] == \
        ["token_gap_p50_ms", "setup_s"]
    mine = {m["name"] for m in M.metrics_of(CELL, "per_layer")}
    assert set(METRICS) <= mine
    # the serving metrics that list no cells are this cell's too; the other
    # families' shares list their own cells
    assert {"decode_step_ms", "prefill_step_ms", "decode_rows_mean", "token_gap_ms.p95",
            "device_idle_pct.serve", "decode_host_ms.build", "decode_host_ms.dispatch",
            "decode_host_ms.readback", "decode_host_ms.land", "schedule_self_ms",
            "serve_tokens_per_s", "compiles_in_window.serve"} <= mine
    assert not {"decode_hbm_roofline", "moe_decode_hbm_roofline", "expert_ffn_roofline",
                "hybrid_decode_hbm_mfu_pct", "diff_attention_roofline"} & mine
    assert not hasattr(FAM, "weight_bytes")
    for m in M.data["per_layer"]:
        if m["name"] in METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "token_gap_p50_ms"
    gap = next(m for m in M.data["end_to_end"] if m["name"] == "token_gap_p50_ms")
    assert CELL in gap["workloads"]


def test_the_traffic_is_the_issue_s(M):
    traffic = M.traffic("toolturn-pinned")
    assert traffic["kind"] == "open_loop" and traffic["arrivals"] == "exponential"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 640, "sigma": 0.7,
                                     "lo": 128, "hi": 1408}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 160, "sigma": 0.6,
                                     "lo": 32, "hi": 384}
    assert (traffic["round"], traffic["temperature"], traffic["ramp_s"]) == (16, 0.0, 12.0)
    assert isinstance(traffic["order_seed"], int) and "shared_prefix" not in traffic
    # four fifths of the knee the file states as a number, the ramp at 1.5 x
    assert traffic["rate_per_s"] == round(0.8 * traffic["knee_per_s"], 1)
    assert traffic["ramp_rate_per_s"] == pytest.approx(1.5 * traffic["rate_per_s"])
    # the longest context and what the warm-up adds to it fit the engine's 2,048
    assert traffic["prompt_len"]["hi"] + traffic["output_len"]["hi"] + 2 * 64 + 8 <= 2048
    # the longest answer at a slow 50 ms a token ends inside the drain
    assert traffic["drain_s"] >= 384 * 0.05
    cell = json.loads((M.root / "cells" / f"{CELL}.json").read_text())
    assert (cell["check_requests"], cell["trace_s"]) == (24, 4.0)
    assert set(cell["limits"]) == {"served_logit_gap"}


def test_counts_at_the_published_widths(CFG, FAM):
    # an operator and an FFN by kind (ISSUE 38's arithmetic)
    conv = 2048 * 6144 + 2048 * 3 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    dense = 3 * 2048 * 11776
    expert = 3 * 2048 * 1536
    routed = 64 * expert + 2048 * 64 + 64
    assert (conv, attn, dense, expert, routed) == \
        (16_783_360, 10_485_888, 72_351_744, 9_437_184, 604_110_912)
    norms = 2 * 2048
    # layers 0-1: conv + dense; 2-9: [attention, conv, conv, conv] twice, routed
    total = 2 * (conv + dense + norms) + 2 * attn + 6 * conv + 8 * (routed + norms) \
        + 65536 * 2048 + 2048
    assert FAM.param_count(CFG) == total == 5_267_090_176
    assert FAM.unrolled_layers(CFG) == [0, 1] and FAM.expert_layers(CFG) == 8
    names = [n for n, _, _ in FAM.leaf_specs(CFG)]
    assert "body.0.attn.qkv.w" in names and "body.3.conv.conv.w" in names
    assert dict((n, s) for n, s, _ in FAM.leaf_specs(CFG))["body.1.mlp.experts.gate"] == \
        (2, 64, 2048, 1536)
    # two attention layers cache K and V of 8 heads of 64 in bfloat16
    assert FAM.kv_bytes_per_token(CFG) == 2 * 8 * 64 * 2 == 2048
    assert FAM.cache_bytes_per_context_token(CFG) == 2 * 2048 == 4096
    assert FAM.expert_bytes(CFG) == 3 * 2048 * 1536 * 2 == 18_874_368
    # eight convolution layers keep two inputs of 2048 a row
    assert FAM.conv_state_bytes_per_row(CFG) == 8 * 2 * 2048 * 2 == 65_536
    # what every step reads: everything but the routed experts' matrices
    assert FAM.dense_bytes_per_step(CFG) == 2 * (total - 8 * 64 * expert) == 870_503_936
    # a decode step at 30 rows of 900 tokens that touch 54.8 experts a layer
    assert FAM.decode_step_bytes(CFG, 30, 8 * 54.8, 27_000) == pytest.approx(
        870_503_936 + 8 * 54.8 * 18_874_368 + 27_000 * 4096 + 2 * 30 * 65_536)
    assert FAM.decode_step_bytes(CFG, 30, 8 * 54.8, 27_000) == pytest.approx(9.2595e9, rel=1e-4)
    # one block-table read: K and V of the live tokens, 32 queries padded to
    # 128 in and their results out
    assert FAM.paged_read_bytes(CFG, 30, 27_000) == 27_000 * 2048 + 30 * 2 * 32 * 128 * 2


def _facts(CFG, FAM, PEAKS, step_ms=13.0, steps=2, rows=30, ctx=27_000, touched=438,
           attrs=True):
    """Two decode steps of ``step_ms`` on the device (eight expert calls of
    1.3 ms and two block-table reads of 120 us each) inside a traced window."""
    ops, mods, t = [], [], 1_000_000
    for s in range(steps):
        mods.append([f"jit_step({s})", t, int(step_ms * 1e6)])
        for k in range(8):
            ops.append([f"%moe_experts_t16.{k} = bf16[1280,2048]{{1,0}} custom-call(...)",
                        t + k * 1_400_000, 1_300_000])
        for k in range(2):
            ops.append([f"%paged_attention.{k} = bf16[64,32,128]{{2,1,0}} custom-call(...)",
                        t + 11_500_000 + k * 200_000, 120_000])
        t += int(step_ms * 1e6) + 1_000_000
    red = {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}}, "host": [],
           "sync_ns": 0, "t0_ns": 0, "t1_ns": t, "host_window": (10.0, 20.0)}
    step = {"rows": rows, "bucket": 32}
    if attrs:
        step.update(paged_kv_tokens=ctx, state_rows=rows, experts_touched=touched,
                    expert_tokens_max=6)
    named = {"decode_step": [("decode_step", 1_000_000 + i, 2_000_000 + i, 1, step)
                             for i in range(steps)]}
    spans = types.SimpleNamespace(named=lambda name, *a: named.get(name, []))
    sched = types.SimpleNamespace(prompt_len=np.zeros(1, int))
    return {"trace": red, "spans": spans, "config": CFG, "family": FAM, "peaks": PEAKS,
            "span_window_ns": (0, 10**12), "served": [], "schedule": sched}


def test_readers_on_hand_made_facts(M, CFG, FAM, PEAKS):
    run_ = _facts(CFG, FAM, PEAKS)
    f = FAM.decode_trace_facts(run_)
    assert (f["steps"], f["step_ns"], f["rows"], f["touched"]) == (2, 26_000_000, 30, 438)
    assert FAM.span_mean(run_, "paged_kv_tokens", traced=True) == 27_000
    # the whole step: 870.5 MB + 438 experts + 27,000 tokens + 30 rows' states,
    # twice, over 26 ms of the 819 GB/s peak
    step = 870_503_936 + 438 * 18_874_368 + 27_000 * 4096 + 2 * 30 * 65_536
    assert M.reader("lfm2_decode_hbm_mfu_pct")(run_) == pytest.approx(
        100 * 2 * step / 819e9 / 0.026)                                # 86.9%
    # 16 calls, each 438 / 8 experts and 120 pairs' rows in and out, 1.3 ms each
    call = 438 / 8 * 18_874_368 + 2 * 30 * 4 * 2048 * 2
    assert M.reader("lfm2_expert_ffn_roofline")(run_) == pytest.approx(
        100 * call / 819e9 / 1.3e-3)                                   # 97.1%
    # 4 calls of 27,000 tokens' K and V and 30 rows' padded queries, 120 us each
    read = 27_000 * 2048 + 30 * 2 * 32 * 128 * 2
    assert M.reader("packed_kv_attention_roofline")(run_) == pytest.approx(
        100 * read / 819e9 / 120e-6)                                   # 56.8%
    assert M.reader("lfm2_experts_touched_per_layer")(run_) == 438 / 8
    assert M.reader("paged_kv_tokens_per_step")(run_) == 27_000
    # a share over 100% is a fault of a count or of the time, and raises
    with pytest.raises(ValueError, match="lfm2_decode_hbm_mfu_pct"):
        M.reader("lfm2_decode_hbm_mfu_pct")(_facts(CFG, FAM, PEAKS, step_ms=11.0))


def test_readers_find_nothing_in_a_program_that_lacks_the_arch(M, CFG, FAM, PEAKS):
    """Spans without the new attributes, another family, or no trace: every
    reader returns None, none raises."""
    old = _facts(CFG, FAM, PEAKS, attrs=False)
    for name in METRICS:
        assert M.reader(name)(old) is None, name
    for fam in ("gpt", "xing4", "phi4flash"):
        other = dict(_facts(CFG, FAM, PEAKS), family=M.family(fam))
        for name in METRICS[:4]:  # the fifth reads the spans whatever the family
            assert M.reader(name)(other) is None, (name, fam)
    run_ = _facts(CFG, FAM, PEAKS)
    for name in METRICS[:3]:
        assert M.reader(name)(dict(run_, trace=None)) is None
    for name in METRICS[3:]:
        assert M.reader(name)(dict(run_, spans=None)) is None


def test_the_cell_rehearses(M, capsys):
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "1.5",
                   "--trace", "1", "--rehearse"])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert "check: served_logit_gap = 0 " in out     # float32 on both sides
    for name in ("lfm2_experts_touched_per_layer", "paged_kv_tokens_per_step",
                 "decode_rows_mean", "decode_step_ms", "prefill_step_ms",
                 "decode_host_ms.dispatch"):
        assert f"reader: {name} read something" in out, name
    # device-trace readers: no chip here (``test_readers_on_hand_made_facts``
    # holds that each of them reads, and what)
    for name in METRICS[:3]:
        assert f"reader: {name} found nothing to read" in out, name
    for name in ("decode_hbm_roofline", "moe_decode_hbm_roofline", "expert_ffn_roofline",
                 "shared_kv_tokens_per_step"):
        assert f"reader: {name} " not in out          # the other families' cells'


def test_reference_precisions_differ_and_the_verdict_is_withheld(CFG, FAM):
    from benchmark import weights as W

    cfg = {**CFG, **FAM.REHEARSE}
    w = W.make_weights(cfg, 11, FAM.leaf_specs(cfg))
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 24))
    whole = np.asarray(FAM.reference.forward_logits(cfg, w, ids, "f32"))
    assert whole.shape == (2, 24, cfg["vocab_size"])
    assert np.array_equal(whole, np.asarray(FAM.reference.forward_logits(cfg, w, ids, "f32")))
    fp8 = np.asarray(FAM.forward_logits(cfg, w, ids, "fp8"))
    assert np.abs(fp8 - whole).max() > 1e-3
    with pytest.raises(ValueError, match="unknown precision"):
        FAM.forward_logits(cfg, w, ids, "int4")
    # causal in every layer: what lies behind a position does not reach it
    longer = np.concatenate([ids, ids[:, :5]], axis=1)
    more = np.asarray(FAM.reference.forward_logits(cfg, w, longer, "f32"))
    assert np.abs(more[:, :24] - whole).max() <= 1e-5 * np.abs(whole).max()
    # the judge withholds its verdict (a row of zeros) exactly where a router
    # margin is under the family's, and says the rest as the whole reference
    _, margin = FAM.reference.forward(cfg, w, ids, "f32")
    judged = np.asarray(FAM.forward_logits(cfg, w, ids, "f32"))
    keep = np.asarray(margin) >= FAM.ROUTER_MARGIN
    assert 0 < keep.sum() < keep.size or not keep.any()
    assert not judged[~keep].any() and np.array_equal(judged[keep], whole[keep])
