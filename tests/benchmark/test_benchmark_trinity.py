"""The ``afmoe`` family's files: the counts its readers divide by against
values worked by hand at the published widths of Trinity-Mini (d 2048, 32
query heads on 4 key/value heads of 128, a window of 2,048, dense FFN 6144,
128 experts of 1024 of which a token takes 8 and one shared, vocabulary
200,192 untied) in the cell's cut (one of eight chips that share each layer:
16 experts held, 25,024 rows of the vocabulary, 16 of 32 layers), the seven
readers and the accepted ``experts_touched_per_layer`` on hand-made facts, the two precisions of its reference, and the
cell's chip-free rehearsal. ``BENCHMARK.json`` lists the configuration, the
cell and the seven metrics since PR 45, appended in this order (the cell is
appended to ``experts_touched_per_layer``'s ``workloads`` too), and no file of
the harness was edited for them."""
import json
import types

import numpy as np
import pytest

from benchmark import run
from benchmark.manifest import Manifest

CELL = "serve-trinity-shortlong-pinned"
CONFIG = "trinity-mini-16l-ep8"
METRICS = ["trinity_decode_hbm_mfu_pct", "gqa_paged_attention_roofline",
           "window_flash_roofline", "trinity_expert_ffn_roofline",
           "trinity_window_tokens_per_step", "trinity_full_kv_tokens_per_step",
           "trinity_long_prefill_step_ms"]
# the accepted reader of the routed cells: this cell is appended to its list
TOUCHED = "experts_touched_per_layer"
S, F = "sliding_attention", "full_attention"


@pytest.fixture(scope="module")
def M():
    return Manifest()


@pytest.fixture(scope="module")
def CFG(M):
    return M.config(CONFIG)


@pytest.fixture(scope="module")
def FAM(M):
    return M.family("afmoe")


@pytest.fixture(scope="module")
def PEAKS(M):
    return M.peaks("TPU v5 lite")


def test_manifest_is_sound_and_lists_what_this_family_added_in_order(M):
    """Present, and in this order (not "last": a later PR appends behind)."""
    assert M.validate() == []
    assert CONFIG in [c["name"] for c in M.data["configs"]]
    assert CELL in [w["name"] for w in M.data["workloads"]]
    names = [m["name"] for m in M.data["per_layer"]]
    at = [names.index(n) for n in METRICS]
    assert at == list(range(at[0], at[0] + len(METRICS)))
    for m in M.data["per_layer"]:
        if m["name"] in METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "token_gap_p50_ms"
            assert (m["unit"] == "%") == (m["name"].endswith("_roofline") or "mfu" in m["name"])
    gap = next(m for m in M.data["end_to_end"] if m["name"] == "token_gap_p50_ms")
    assert CELL in gap["workloads"]
    cell = M.workload(CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, "shortlong-pinned")
    assert [m["name"] for m in M.metrics_of(CELL, "end_to_end")] == \
        ["token_gap_p50_ms", "setup_s"]
    mine = {m["name"] for m in M.metrics_of(CELL, "per_layer")}
    assert set(METRICS) | {TOUCHED} <= mine
    touched = next(m for m in M.data["per_layer"] if m["name"] == TOUCHED)
    assert touched["workloads"][-1] == CELL and len(touched["workloads"]) == 2
    # the serving metrics that list no cells are this cell's too; the other
    # families' shares list their own cells
    assert {"decode_step_ms", "prefill_step_ms", "decode_rows_mean", "token_gap_ms.p95",
            "device_idle_pct.serve", "decode_host_ms.dispatch", "schedule_self_ms",
            "serve_tokens_per_s", "compiles_in_window.serve"} <= mine
    assert not {"decode_hbm_roofline", "moe_decode_hbm_roofline", "expert_ffn_roofline",
                "hybrid_decode_hbm_mfu_pct", "lfm2_decode_hbm_mfu_pct",
                "window_tokens_per_step", "paged_kv_tokens_per_step"} & mine


def test_the_configuration_states_the_cut(M, CFG, FAM):
    entry = next(c for c in M.data["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == CFG["reduced"] == \
        ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    assert all(k in CFG for k in entry["reduced"])
    assert entry["source"] == CFG["source"] and len(entry["source"]) < 200
    # every key of the catalog row's config at its published value but the four cut
    published = {"global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
                 "hidden_size": 2048, "intermediate_size": 6144, "load_balance_coeff": 0.001,
                 "max_position_embeddings": 131072, "model_type": "afmoe",
                 "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
                 "num_attention_heads": 32, "num_dense_layers": 2, "num_expert_groups": 1,
                 "num_experts_per_tok": 8, "num_key_value_heads": 4, "num_limited_groups": 1,
                 "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
                 "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
                 "score_func": "sigmoid", "sliding_window": 2048,
                 "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True}
    assert {k: CFG[k] for k in published} == published
    assert CFG["num_hidden_layers"] == 16 and CFG["layer_types"] == [S, S, S, F] * 4
    assert (CFG["num_experts"], CFG["vocab_size"]) == (16, 25024) == (128 // 8, 200192 // 8)
    assert CFG["published"]["num_experts"] == 128 == FAM.router_width(CFG)
    assert (CFG["published"]["num_hidden_layers"], CFG["published"]["vocab_size"],
            CFG["published"]["max_position_embeddings"]) == (32, 200192, 131072)
    assert CFG["dtype"] == "bfloat16" and "one v5e-8 host" in CFG["deployment"]
    assert "second stage" in CFG["deployment"] and "idle" in CFG["deployment"]
    assert f"{FAM.param_count(CFG):,}" in CFG["deployment"]
    assert {"initializer_range", "embedding_scale", "norms", "qk_norm", "rotary_layout",
            "window", "attention_gate", "fused_qkvg", "router", "gate_epsilon",
            "held_experts", "state", "reference_verdict"} <= set(CFG["assumed"])


def test_the_traffic_is_the_issue_s(M):
    traffic = M.traffic("shortlong-pinned")
    assert traffic["kind"] == "open_loop" and traffic["arrivals"] == "exponential"
    assert traffic["engine"] == {"max_seq_len": 8192, "max_batch": 64, "prefill_batch": 1,
                                 "decode_buckets": [32, 48, 64]}
    assert traffic["prompt_len"] == {"dist": "mixture", "parts": [
        {"weight": 0.7, "dist": "lognormal", "median": 512, "sigma": 0.6, "lo": 128, "hi": 1024},
        {"weight": 0.3, "dist": "lognormal", "median": 5632, "sigma": 0.2, "lo": 4608,
         "hi": 6656}]}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 192, "sigma": 0.6,
                                     "lo": 64, "hi": 512}
    assert (traffic["round"], traffic["temperature"], traffic["ramp_s"],
            traffic["order_seed"]) == (20, 0.0, 12.0, 45)
    assert "shared_prefix" not in traffic
    # four fifths of the knee the file states as a number, the ramp at 1.5 x
    # that, as ISSUE 45 fixed them and every pinned cell has them
    assert traffic["rate_per_s"] == round(0.8 * traffic["knee_per_s"], 1)
    assert traffic["ramp_rate_per_s"] == pytest.approx(1.5 * traffic["rate_per_s"])
    # the longest context is 3.5 windows, and what the warm-up adds to it fits
    assert 6656 + 512 == 7168 == 3.5 * 2048 and 7168 + 2 * 64 + 8 <= 8192
    assert traffic["drain_s"] >= 512 * 0.05
    # a round holds 14 short and 6 long; the short mode falls into four
    # prefill buckets, the long one into one
    from benchmark import generator, serve_job

    sched = generator.build_schedule(traffic, 51.0, 7, 25024)
    assert serve_job.prefill_buckets(sched.prompt_len, 16) == [128, 256, 512, 1024, 8192]
    n = len(sched.prompt_len)
    assert abs(int((sched.prompt_len > 2048).sum()) - 0.3 * n) <= 1
    assert serve_job.longest_of(sched)[1] <= 7168
    cell = json.loads((M.root / "cells" / f"{CELL}.json").read_text())
    assert cell["check_requests"] == 16 and set(cell["limits"]) == {"served_logit_gap"}
    # the check pads long contexts to three lengths past 2,048
    assert {serve_job.padded_len(x) for x in (4672, 5632, 6656, 7167)} == {5120, 6144, 7168}


def test_counts_at_the_published_widths(CFG, FAM):
    # a layer by kind (ISSUE 45's arithmetic)
    attn = 2048 * 9216 + 4096 * 2048 + 2 * 128
    dense = 3 * 2048 * 6144
    expert = 3 * 2048 * 1024
    routed = 16 * expert + 2048 * 128 + 128 + expert          # + the shared expert
    norms = 4 * 2048
    assert (attn, dense, expert, routed) == (27_263_232, 37_748_736, 6_291_456, 107_217_024)
    table = 25024 * 2048
    total = 2 * (attn + dense + norms) + 14 * (attn + routed + norms) + 2 * table + 2048
    assert FAM.param_count(CFG) == total == 2_115_378_944     # 4.23 GB of bfloat16
    assert FAM.unrolled_layers(CFG) == [0, 1, 14, 15] and FAM.expert_layers(CFG) == 14
    assert (FAM.layers_of(CFG, S), FAM.layers_of(CFG, F)) == (12, 4)
    shapes = dict((n, s) for n, s, _ in FAM.leaf_specs(CFG))
    assert shapes["h3.stack.mlp.experts.gate"] == (3, 16, 2048, 1024)
    assert shapes["h3.stack.mlp.router.w"] == (3, 2048, 128)
    assert shapes["h14.mlp.shared.down.w"] == (1024, 2048) and shapes["head.w"] == (2048, 25024)
    # K and V of 4 heads of 128 in bfloat16; four full layers grow with the context
    assert FAM.kv_bytes_per_token(CFG) == 2 * 4 * 128 * 2 == 2048
    assert FAM.cache_bytes_per_context_token(CFG) == 4 * 2048 == 8192
    # twelve rings of 2,048 a row slot: 50 MB, 3.27 GB for 64 + 1 slots
    assert FAM.window_bytes_per_row(CFG) == 12 * 2048 * 2048 == 50_331_648
    assert FAM.expert_bytes(CFG) == 2 * expert == 12_582_912
    # what every step reads: everything but the held experts' matrices and the
    # embedding table, of which it reads the fed rows
    assert FAM.dense_bytes_per_step(CFG, 48) == \
        2 * (total - 14 * 16 * expert - table + 48 * 2048) == 1_309_883_904
    # a decode step at 48 rows, 15 of 16 experts a layer, 90,000 tokens of
    # context of which 60,000 lie inside the windows
    assert FAM.decode_step_bytes(CFG, 48, 14 * 15, 90_000, 60_000) == pytest.approx(
        1_309_883_904 + 210 * 12_582_912 + 90_000 * 8192 + 60_000 * 12 * 2048)
    assert FAM.decode_step_bytes(CFG, 48, 14 * 15, 90_000, 60_000) == pytest.approx(
        6.1642e9, rel=1e-4)
    # one block-table read: K and V of the tokens seen, 32 queries of 128 in and out
    assert FAM.paged_read_bytes(CFG, 48, 90_000) == 90_000 * 2048 + 48 * 2 * 32 * 128 * 2
    # a prompt of 5,632: the band of a window layer and the triangle of a full one
    band, tri = 2048 * 2049 // 2 + 3584 * 2048, 5632 * 5633 // 2
    assert FAM.band_flops(CFG, band) == 4 * 128 * 32 * band == pytest.approx(1.546e11, rel=1e-3)
    assert FAM.band_flops(CFG, tri) == pytest.approx(2.599e11, rel=1e-3)
    assert FAM.band_bytes(CFG, 5632) == 5632 * 2 * 36 * 128 * 2


def _facts(CFG, FAM, PEAKS, step_ms=12.0, steps=2, rows=48, ctx=90_000, win=60_000,
           touched=210, pairs=600, attrs=True, flash_us=1500.0):
    """Two decode steps of ``step_ms`` on the device (fourteen expert calls of
    250 us, sixteen block-table reads of 250 us) and one long prefill (sixteen
    calls of the prompt kernel) inside a traced window."""
    ops, mods, t = [], [], 1_000_000
    for s in range(steps):
        mods.append([f"jit_step({s})", t, int(step_ms * 1e6)])
        for k in range(14):
            ops.append([f"%moe_experts_t16.{k} = bf16[768,2048]{{1,0}} custom-call(...)",
                        t + k * 300_000, 250_000])
        for k in range(16):
            ops.append([f"%paged_attention.{k} = bf16[64,4096]{{1,0}} custom-call(...)",
                        t + 5_000_000 + k * 300_000, 250_000])
        t += int(step_ms * 1e6) + 1_000_000
    fill0 = t
    mods.append(["jit_prefill(7)", t, 200_000_000])
    for k in range(16):
        ops.append([f"%window_flash.{k} = bf16[1,8192,4096]{{2,1,0}} custom-call(...)",
                    t + k * 10_000_000, int(flash_us * 1000)])
    # a prefill's expert calls carry the name the 64-row decode bucket's do and
    # are left out: they start outside every jit_step
    ops.append(["%moe_experts_t256.1 = bf16[69632,2048]{1,0} custom-call(...)",
                t + 170_000_000, 3_000_000])
    t += 201_000_000
    red = {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}}, "host": [],
           "sync_ns": 0, "t0_ns": 0, "t1_ns": t, "host_window": (10.0, 20.0)}
    step = {"rows": rows, "bucket": 64}
    fill = {"rows": 1, "bucket_t": 8192, "bucket_b": 1, "prompt_tokens": 5632}
    if attrs:
        step.update(paged_kv_tokens=ctx, window_tokens=win,
                    experts_touched=touched, expert_assignments=pairs, expert_tokens_max=9)
        fill.update(band_tokens_window=2048 * 2049 // 2 + 3584 * 2048,
                    band_tokens_full=5632 * 5633 // 2)
    named = {"decode_step": [("decode_step", 1_000_000 + i, 2_000_000 + i, 1, step)
                             for i in range(steps)],
             "prefill": [("prefill", fill0 - 1000, fill0 + 200_500_000, 1, fill),
                         ("prefill", fill0 + 200_600_000, fill0 + 200_900_000, 1,
                          dict(fill, bucket_t=512, prompt_tokens=400, band_tokens_window=80_200,
                               band_tokens_full=80_200) if attrs else {"bucket_t": 512})]}
    spans = types.SimpleNamespace(named=lambda name, *a: named.get(name, []))
    sched = types.SimpleNamespace(prompt_len=np.zeros(1, int))
    return {"trace": red, "spans": spans, "config": CFG, "family": FAM, "peaks": PEAKS,
            "span_window_ns": (0, 10**12), "served": [], "schedule": sched}


def test_readers_on_hand_made_facts(M, CFG, FAM, PEAKS):
    run_ = _facts(CFG, FAM, PEAKS)
    f = FAM.decode_trace_facts(run_)
    assert (f["steps"], f["step_ns"], f["rows"], f["touched"]) == (2, 24_000_000, 48, 210)
    # the whole step: 1.31 GB outside the experts + 210 experts + 90,000 tokens
    # of four full layers + 60,000 of twelve rings, twice, over 24 ms of 819 GB/s
    step = 1_309_883_904 + 210 * 12_582_912 + 90_000 * 8192 + 60_000 * 24_576
    assert M.reader("trinity_decode_hbm_mfu_pct")(run_) == pytest.approx(
        100 * 2 * step / 819e9 / 0.024)                                     # 62.7%
    # 32 calls of 250 us: a step's four reads of 90,000 tokens, twelve of 60,000
    io = 48 * 2 * 32 * 128 * 2
    reads = 4 * (90_000 * 2048 + io) + 12 * (60_000 * 2048 + io)
    assert M.reader("gqa_paged_attention_roofline")(run_) == pytest.approx(
        100 * 2 * reads / 819e9 / (32 * 250e-6))                            # 67.9%
    # 28 calls, each 15 experts and 600 / 14 pairs' rows in and out, 250 us each
    call = 210 / 14 * 12_582_912 + 2 * 600 / 14 * 2048 * 2
    assert M.reader("trinity_expert_ffn_roofline")(run_) == pytest.approx(
        100 * call / 819e9 / 250e-6)                                        # 92.3%
    # the long prefill alone (the short one's span holds no kernel call here):
    # twelve bands and four triangles of a prompt of 5,632 in 16 x 1.5 ms
    need = 12 * 4 * 128 * 32 * (2048 * 2049 // 2 + 3584 * 2048) + 4 * 4 * 128 * 32 * (5632 * 5633 // 2)
    assert M.reader("window_flash_roofline")(run_) == pytest.approx(
        100 * need / 197e12 / (16 * 1500e-6))                               # 61.2%
    assert M.reader(TOUCHED)(run_) == 210 / 14   # of the 16 held
    assert M.reader("trinity_window_tokens_per_step")(run_) == 60_000
    assert M.reader("trinity_full_kv_tokens_per_step")(run_) == 90_000
    assert M.reader("trinity_long_prefill_step_ms")(run_) == pytest.approx(200.501)
    # a share over 100% is a fault of a count or of the time, and raises
    with pytest.raises(ValueError, match="trinity_decode_hbm_mfu_pct"):
        M.reader("trinity_decode_hbm_mfu_pct")(_facts(CFG, FAM, PEAKS, step_ms=7.0))
    with pytest.raises(ValueError, match="window_flash_roofline"):
        M.reader("window_flash_roofline")(_facts(CFG, FAM, PEAKS, flash_us=500.0))


def test_readers_find_nothing_in_a_program_that_lacks_the_arch(M, CFG, FAM, PEAKS):
    """Spans without the new attributes, another family, or no trace: every
    reader returns None, none raises."""
    old = _facts(CFG, FAM, PEAKS, attrs=False)
    for name in METRICS[:6] + [TOUCHED]:  # the last times the prefill spans any program has
        assert M.reader(name)(old) is None, name
    for fam in ("gpt", "xing4", "phi4flash", "lfm2"):
        other = dict(_facts(CFG, FAM, PEAKS), family=M.family(fam))
        for name in METRICS:
            assert M.reader(name)(other) is None, (name, fam)
    run_ = _facts(CFG, FAM, PEAKS)
    for name in METRICS[:4]:
        assert M.reader(name)(dict(run_, trace=None)) is None
    for name in METRICS[4:] + [TOUCHED]:
        assert M.reader(name)(dict(run_, spans=None)) is None


def test_the_cell_rehearses(M, capsys):
    """``python3 -m benchmark.run --workload serve-trinity-shortlong-pinned
    --seed 7 --seconds 2 --trace 1 --rehearse``: exit code 0, correct, and the
    check holds requests past the rehearsal's window of 8."""
    rc = run.main(["--workload", CELL, "--seed", "7", "--seconds", "2",
                   "--trace", "1", "--rehearse"])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert "check: served_logit_gap = 0 " in out     # float32 on both sides
    assert "note: engine_max_seq_len = 256" in out   # the rehearsal's positions
    # every checked request is longer than the window of 8: the reference is
    # run at 256 positions a request, and its contexts reach 120
    assert line["counts"]["prompt_tokens"] / line["counts"]["requests"] > 8
    for name in METRICS[4:] + [TOUCHED, "decode_rows_mean", "decode_step_ms", "prefill_step_ms",
                               "decode_host_ms.dispatch"]:
        assert f"reader: {name} read something" in out, name
    # device-trace readers: no chip here (``test_readers_on_hand_made_facts``
    # holds that each of them reads, and what)
    for name in METRICS[:4]:
        assert f"reader: {name} found nothing to read" in out, name
    for name in ("decode_hbm_roofline", "moe_decode_hbm_roofline", "expert_ffn_roofline",
                 "shared_kv_tokens_per_step", "window_tokens_per_step",
                 "paged_kv_tokens_per_step"):
        assert f"reader: {name} " not in out          # the other families' cells'


def test_reference_precisions_differ_and_the_verdict_is_withheld(CFG, FAM):
    from benchmark import weights as W

    cfg = {**CFG, **FAM.REHEARSE, "initializer_range": 0.125}
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
    # the judge withholds its verdict (a row of zeros) exactly where a held
    # expert's margin is under the family's, and says the rest as the whole
    # reference
    _, margin = FAM.reference.forward(cfg, w, ids, "f32")
    judged = np.asarray(FAM.forward_logits(cfg, w, ids, "f32"))
    keep = np.asarray(margin) >= FAM.ROUTER_MARGIN
    assert 0 < keep.sum() < keep.size
    assert not judged[~keep].any() and np.array_equal(judged[keep], whole[keep])
