"""The ``kimivl`` family's files: the manifest with the configuration, the cell
and the five metrics PR 47 appended (no file of the harness was edited for
them), the counts its readers divide by against values worked by hand at the
published widths of Kimi-VL-A3B's decoder (d 2,048, 16 heads, latent 512 + 64,
64 experts of 1,408 at 6 a token, two shared, 7 of 27 layers), the readers on
hand-made facts, the reference's logits that are never whole, and the cell's
chip-free rehearsal."""
import json
import types

import numpy as np
import pytest

from benchmark import run
from benchmark.manifest import Manifest

CELL, CONFIG = "serve-kimivl-longdoc-pinned", "kimi-vl-a3b-7l"
METRICS = ["latent_prefill_attention_roofline", "kimivl_decode_hbm_mfu_pct",
           "kimivl_prefill_call_ms", "kimivl_prefill_ms_per_s",
           "kimivl_context_tokens_per_step"]
SHARED = ["latent_attention_roofline", "expert_ffn_roofline", "expert_load_max_over_mean"]


@pytest.fixture(scope="module")
def M():
    return Manifest()


@pytest.fixture(scope="module")
def CFG(M):
    return M.config(CONFIG)


@pytest.fixture(scope="module")
def FAM(M):
    return M.family("kimivl")


@pytest.fixture(scope="module")
def PEAKS(M):
    return M.peaks("TPU v5 lite")


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(path)]
    except OSError:
        return None
    return next(r for r in rows if r["name"] == "Kimi-VL-A3B-Instruct")


def test_manifest_is_sound_and_states_the_cut(M, CFG):
    assert M.validate() == []
    entry = M.data["configs"][-1]
    assert entry["name"] == CONFIG and entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["published"] == {"num_hidden_layers": 27} and CFG["num_hidden_layers"] == 7
    assert entry["source"] == CFG["source"] and len(entry["source"]) < 200
    for word in ("four-stage", "7 + 7 + 7 + 6", "vision tower", "NOT held", "encoder chip",
                 "final norm", "Fewer layers a chip"):
        assert word in CFG["deployment"], word
    assert {"rotary_layout", "router", "shared_experts", "initializer", "state",
            "reference_verdict"} <= set(CFG["assumed"])
    # every key of the catalog's row at its published value but the depth
    row = _catalog_row()
    if row is not None:
        assert entry["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if CFG.get(k, "absent") != v}
        assert differ == {"num_hidden_layers"}
    # the published widths, whatever the catalog file says on this machine
    assert (CFG["hidden_size"], CFG["num_attention_heads"], CFG["kv_lora_rank"],
            CFG["q_lora_rank"], CFG["qk_nope_head_dim"], CFG["qk_rope_head_dim"],
            CFG["v_head_dim"], CFG["intermediate_size"], CFG["moe_intermediate_size"],
            CFG["n_routed_experts"], CFG["num_experts_per_tok"], CFG["n_shared_experts"],
            CFG["vocab_size"], CFG["rope_scaling"], CFG["rope_theta"]) == \
        (2048, 16, 512, None, 128, 64, 128, 11264, 1408, 64, 6, 2, 163840, None, 800000)


def test_the_cell_and_its_metrics_are_appended(M):
    cell = M.data["workloads"][-1]
    assert cell == M.workload(CELL) and cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, "longdoc-pinned")
    assert "memory_peak_bytes" in cell["why"]
    assert [m["name"] for m in M.metrics_of(CELL, "end_to_end")] == \
        ["token_gap_p50_ms", "setup_s"]
    assert [m["name"] for m in M.data["per_layer"]][-5:] == METRICS
    for m in M.data["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and m["moves"] == "token_gap_p50_ms"
        assert (m["unit"] == "%") == (m["name"].endswith("_roofline") or "mfu" in m["name"])
    by_name = {m["name"]: m for m in M.data["per_layer"]}
    for name in SHARED:  # the shared kernels' readers: both latent cells
        assert by_name[name]["workloads"] == ["serve-xing4-longanswer-pinned", CELL]
    # the whole-step share is not doubled, and the touched experts' list is
    # another family's test's to hold
    assert CELL not in by_name["moe_decode_hbm_roofline"]["workloads"]
    assert CELL not in by_name["experts_touched_per_layer"]["workloads"]
    mine = {m["name"] for m in M.metrics_of(CELL, "per_layer")}
    assert set(METRICS) | set(SHARED) <= mine
    assert {"decode_step_ms", "prefill_step_ms", "decode_rows_mean", "token_gap_ms.p95",
            "device_idle_pct.serve", "serve_tokens_per_s", "compiles_in_window.serve"} <= mine


def test_the_traffic_file_is_the_issue_s(M):
    t = M.traffic("longdoc-pinned")
    assert t["engine"] == {"max_seq_len": 32768, "prefill_chunk": 8192, "prefill_batch": 1,
                           "max_batch": 32, "decode_buckets": [8, 16, 32]}
    assert t["prompt_len"] == {"dist": "lognormal", "median": 12288, "sigma": 0.35,
                               "lo": 9216, "hi": 24576}
    assert t["output_len"] == {"dist": "lognormal", "median": 128, "sigma": 0.6,
                               "lo": 32, "hi": 384}
    assert (t["order_seed"], t["round"], t["temperature"], t["ramp_s"], t["drain_s"]) == \
        (47, 16, 0.0, 12.0, 90.0)
    assert t["rate_per_s"] == round(0.8 * t["knee_per_s"], 1) > 0
    assert t["ramp_rate_per_s"] == pytest.approx(1.5 * t["rate_per_s"])
    # every prompt of the mix is two or three calls of the chunk
    from benchmark import generator

    longest, ctx = generator.longest(t, 51)
    assert longest == 24576 and ctx <= 24576 + 384
    assert M.cell(CELL)["check_requests"] >= 4


def test_counts_at_the_published_widths(CFG, FAM):
    # attention: q 2048 x 3072, kv_a 2048 x 576, the latent's norm 512,
    # kv_b 512 x 4096, o 2048 x 2048
    attn = 6_291_456 + 1_179_648 + 512 + 2_097_152 + 4_194_304
    assert FAM.attention_params(CFG) == attn == 13_763_072
    assert FAM.expert_bytes(CFG) == 3 * 2048 * 1408 * 2 == 17_301_504
    assert FAM.latent_bytes_per_token(CFG) == (512 + 64) * 2 == 1152
    assert FAM.cache_row(CFG) == 640                         # 576 padded to 5 lane tiles
    assert FAM.cache_bytes_per_context_token(CFG) == 7 * 640 * 2 == 8960
    assert FAM.expert_layers(CFG) == 6
    # every step: 7 x (attention + two norms), six routers with e_bias and
    # the shared MLP of width 2,816, one dense FFN, final norm + head
    layer = attn + 2 * 2048                                  # 13,767,168
    moe = 2048 * 64 + 64 + 3 * 2048 * 2816                   # 17,432,640
    dense = 3 * 2048 * 11264                                 # 69,206,016
    head = 2048 + 2048 * 163840                              # 335,546,368
    every_step = 7 * layer + 6 * moe + dense + head
    assert every_step == 605_718_400
    assert FAM.dense_bytes_per_step(CFG) == 2 * every_step
    # the whole model: the above, the embedding, 6 x 64 experts: 8.53 GB
    experts = 6 * 64 * 3 * 2048 * 1408
    assert FAM.param_count(CFG) == every_step + 163840 * 2048 + experts == 4_263_151_488
    assert round(2 * FAM.param_count(CFG) / 1e9, 2) == 8.53
    # a decode step of rows that hit 300 experts over a context of 200,000
    assert FAM.decode_step_bytes(CFG, 300, 200_000) == \
        2 * every_step + 300 * 17_301_504 + 200_000 * 7 * 1152
    # a prefill call: the live pairs of a row and the expanded form's products
    assert FAM.call_pairs(0, 8192) == 8192 * 8193 // 2 == 33_558_528
    assert FAM.call_pairs(16384, 8192) == 8192 * 16384 + 33_558_528 == 167_776_256
    assert FAM.call_pairs(8192, 1024) == 1024 * 8192 + 1024 * 1025 // 2 == 8_913_408
    assert FAM.call_attention_flops(CFG, 1) == 16 * (192 + 128) * 2 == 10_240
    # a prompt of 24,576 positions in three calls, a layer: 3.09 TFLOP
    pairs = sum(FAM.call_pairs(s, 8192) for s in (0, 8192, 16384))
    assert pairs == 24576 * 24577 // 2
    assert round(FAM.call_attention_flops(CFG, pairs) / 1e12, 2) == 3.09


def _facts(CFG, FAM, PEAKS, kernel_us=9000.0, step_ms=10.0):
    """A traced window of 100 ms: two prefill calls of one row (8,192 fed at
    0 cached, then 4,096 fed at 8,192 cached, padded to the same program),
    seven kernel calls each, and two decode steps of 10 rows at a context of
    13,000 each that hit 200 experts."""
    ops, mods = [], []
    calls = [(1_000_000, 30_000_000, 0, 8192), (40_000_000, 25_000_000, 8192, 4096)]
    for t, dur, _, _ in calls:
        mods.append([f"jit_prefill({t})", t + 100_000, dur])
        for k in range(7):
            ops.append([f"%mla_prefill_attention.{k} = bf16[1,8192,2048]{{2,1,0}} custom-call(...)",
                        t + 200_000 + k * 3_000_000, int(kernel_us * 1000)])
    for s in range(2):
        t = 70_000_000 + s * 12_000_000
        mods.append([f"jit_step({s})", t, int(step_ms * 1e6)])
        for k in range(7):
            ops.append([f"%mla_paged_attention.{k} = bf16[16,16,512]{{2,1,0}} custom-call(...)",
                        t + k * 1000, 400_000])
    red = {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}}, "host": [],
           "sync_ns": 0, "t0_ns": 0, "t1_ns": 100_000_000, "host_window": (10.0, 20.0)}
    # the first call's span closes behind its dispatch (``calls_left`` 1), the
    # second's after the read-back of its logits
    rows = [("prefill", t, t + (500_000 if left else dur + 1_000_000), 1,
             {"chunked": True, "rows": 1, "start": s, "feed": f, "bucket_t": 8192,
              "calls_left": left, "context_tokens": s + f,
              "attended_pairs": FAM.call_pairs(s, f)})
            for (t, dur, s, f), left in zip(calls, (1, 0))]
    rows += [("decode_step", 70_000_000 + i * 12_000_000, 70_500_000 + i * 12_000_000, 1,
              {"rows": 10, "experts_touched": 200, "context_tokens": 130_000 + 10 * i})
             for i in range(2)]
    spans = types.SimpleNamespace(
        named=lambda name, *a: [r for r in rows if r[0] == name])
    served = [types.SimpleNamespace(index=0, stamps=[9.0, 12.0, 15.0, 25.0])] * 10
    sched = types.SimpleNamespace(prompt_len=[12_999])
    return {"trace": red, "spans": spans, "served": served, "schedule": sched,
            "config": CFG, "family": FAM, "peaks": PEAKS, "span_window_ns": (0, 10 ** 12),
            "traffic": {"engine": {"prefill_chunk": 8192}}}


def test_readers_on_hand_made_facts(M, CFG, FAM, PEAKS):
    run_ = _facts(CFG, FAM, PEAKS)
    # 14 kernel calls of 9 ms; a layer of the first call is 33,558,528 pairs,
    # of the second 4,096 x 8,192 + 4,096 x 4,097 / 2 = 41,945,088
    need = 7 * 10_240 * (33_558_528 + 41_945_088)
    assert M.reader("latent_prefill_attention_roofline")(run_) == pytest.approx(
        100 * need / 197e12 / (14 * 9e-3))                 # 21.8%
    # the full call alone (the second fed half a chunk)
    assert M.reader("kimivl_prefill_call_ms")(run_) == pytest.approx(30.0)
    assert M.reader("kimivl_prefill_ms_per_s")(run_) == pytest.approx(55.0 / 0.1)
    assert M.reader("kimivl_context_tokens_per_step")(run_) == pytest.approx(130_005)
    # tokens 1 and 2 of each of 10 streams fell inside the traced stretch
    f = FAM.decode_trace_facts(run_)
    assert (f["steps"], f["step_ns"], f["touched"]) == (2, 20_000_000, 200)
    assert f["context_tokens"] == 10 * (13_000 + 13_001)
    step = 2 * 605_718_400 + 200 * 17_301_504 + 130_005 * 7 * 1152
    assert M.reader("kimivl_decode_hbm_mfu_pct")(run_) == pytest.approx(
        100 * 2 * step / 819e9 / 0.020)                    # 34.9%
    # the shared kernels' reader takes this family's counts too
    mla = 14 * (260_010 / 2 * 1152 + 10 * 16 * (576 + 512) * 2)
    assert M.reader("latent_attention_roofline")(run_) == pytest.approx(
        100 * mla / 819e9 / (14 * 400e-6))
    # a share over 100% is a fault of a count or of the time, and raises
    with pytest.raises(ValueError, match="latent_prefill_attention_roofline"):
        M.reader("latent_prefill_attention_roofline")(_facts(CFG, FAM, PEAKS, kernel_us=1500.0))
    with pytest.raises(ValueError, match="kimivl_decode_hbm_mfu_pct"):
        M.reader("kimivl_decode_hbm_mfu_pct")(_facts(CFG, FAM, PEAKS, step_ms=3.0))


def test_the_share_of_gaps_behind_a_call_by_hand(M, CFG, FAM, PEAKS, capsys):
    """Four scheduler passes, a prefill call in the first two; decode steps of
    4 rows (first pass), 10 rows (third) and 6 rows (fourth): 4 of 20 gaps
    followed a call. The reader of ``kimivl_prefill_ms_per_s`` prints it."""
    run_ = _facts(CFG, FAM, PEAKS)
    assert FAM.gaps_behind_a_call(run_) is None  # no ``schedule`` span kept
    rows = [("schedule", t, t + 1, 1, {}) for t in (0, 35_000_000, 65_000_000, 80_000_000)]
    rows += [("prefill", t, t + 500_000, 1, {"chunked": True, "feed": 8192, "start": 0})
             for t in (1_000_000, 40_000_000)]
    rows += [("decode_step", t, t + 500_000, 1, {"rows": n})
             for t, n in ((30_000_000, 4), (70_000_000, 10), (82_000_000, 6))]
    rows.append(("decode_step", 90_000_000, 90_400_000, 1, {}))  # a landing: no rows
    run_["spans"] = types.SimpleNamespace(named=lambda name, *a: [r for r in rows if r[0] == name])
    assert FAM.gaps_behind_a_call(run_) == (pytest.approx(20.0), 20)
    M.reader("kimivl_prefill_ms_per_s")(run_)
    assert "20.0% of the window's 20 gaps follow a prefill call" in capsys.readouterr().out
    assert FAM.gaps_behind_a_call(dict(run_, span_window_ns=None)) is None


@pytest.mark.parametrize("name", METRICS)
def test_a_reader_finds_nothing_on_the_parent_or_another_family(M, CFG, FAM, PEAKS, name):
    read = M.reader(name)
    # the parent's spans carry neither ``feed`` nor ``context_tokens``
    old = _facts(CFG, FAM, PEAKS)
    old["spans"] = types.SimpleNamespace(named=lambda n, *a: [r for r in (
        ("prefill", 1_000_000, 1_500_000, 1, {"bucket_t": 8192, "rows": 1}),
        ("decode_step", 70_000_000, 70_500_000, 1, {"rows": 10})) if r[0] == n])
    assert read(old) is None
    # no device trace, no spans (an untraced run)
    assert read(dict(_facts(CFG, FAM, PEAKS), trace=None, spans=None)) is None
    # another family's cell
    assert read(dict(_facts(CFG, FAM, PEAKS), family=M.family("xing4"))) is None


def test_the_logits_are_never_whole(CFG, FAM, monkeypatch):
    """What the harness does after ``forward_logits`` returns, at rehearsal
    sizes: the slice ``[0, a:b]`` is the whole reference's rows, and the head
    is only ever applied to the rows asked for."""
    from benchmark import weights as W
    from benchmark.reference import xing4

    cfg = {**CFG, **FAM.REHEARSE}
    w = W.make_weights(cfg, 11, FAM.leaf_specs(cfg))
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (1, 48))
    R = FAM.reference
    whole = np.asarray(R.forward_logits(cfg, w, ids, "f32"))
    assert whole.shape == (1, 48, cfg["vocab_size"])
    seen = []
    real = xing4.head_logits
    monkeypatch.setattr(xing4, "head_logits",
                        lambda h, *a: seen.append(h.shape[0]) or real(h, *a))
    lazy = FAM.forward_logits(cfg, w, ids, "fp8")            # the control: every position
    assert isinstance(lazy, R.Logits) and lazy.shape == whole.shape and not seen
    part = np.asarray(R.forward(cfg, w, ids, "f32")[0][0, 30:41])
    assert seen == [11] and np.array_equal(part, whole[0, 30:41])
    with pytest.raises(IndexError, match="one row of the batch"):
        lazy[:, 3]
    # the harness's own slice and reductions go through
    from benchmark import serve_job

    gaps = serve_job.served_gap(FAM, cfg, w, ids[0, :40], ids[0, 40:], pad_to=16)
    assert gaps.shape == (8,) and (gaps >= 0).all()
    assert max(seen) <= 11


def test_reference_precisions_differ_and_the_verdict_follows_the_margin(CFG, FAM, capsys):
    from benchmark import weights as W

    cfg = {**CFG, **FAM.REHEARSE}
    w = W.make_weights(cfg, 11, FAM.leaf_specs(cfg))
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], (1, 48))
    R = FAM.reference
    f32 = np.asarray(R.forward_logits(cfg, w, ids, "f32"))
    assert np.array_equal(f32, np.asarray(R.forward_logits(cfg, w, ids, "f32")))
    assert np.abs(np.asarray(R.forward_logits(cfg, w, ids, "fp8")) - f32).max() > 1e-2
    with pytest.raises(ValueError, match="unknown precision"):
        FAM.forward_logits(cfg, w, ids, "bf16")[0, :2]
    # the 3rd against the 4th of score + e_bias at rehearsal sizes, the
    # narrowest over the two expert layers; above the median: half withheld
    logits, margin = R.forward(cfg, w, ids, "f32")
    margin = np.asarray(margin)
    assert margin.shape == (1, 48) and (margin > 0).all() and np.isfinite(margin).all()
    cut = float(np.median(margin))
    held = np.asarray(R.forward(cfg, w, ids, "f32", min_margin=cut)[0][0])
    decided = margin[0] >= cut
    assert 0 < decided.sum() < 48
    assert np.array_equal(held[decided], f32[0, decided]) and not held[~decided].any()
    judged = np.asarray(FAM.forward_logits(cfg, w, ids, "f32")[0])
    n = int((margin >= FAM.ROUTER_MARGIN).sum())
    assert f"reference: a verdict at {n} of 48 positions" in capsys.readouterr().out
    assert (judged.any(axis=-1) == (margin[0] >= FAM.ROUTER_MARGIN)).all()


def test_the_reference_blocks_as_the_long_contexts_need(CFG, FAM, monkeypatch):
    """The departures about size, switched on at a small size: query rows in
    blocks of 8, feed-forward rows in blocks of 16, a context over 32
    positions padded to ONE length of 64: the same logits."""
    from benchmark import weights as W

    cfg = {**CFG, **FAM.REHEARSE}
    w = W.make_weights(cfg, 5, FAM.leaf_specs(cfg))
    ids = np.random.default_rng(3).integers(0, cfg["vocab_size"], (1, 40))
    R = FAM.reference
    whole = np.asarray(R.forward_logits(cfg, w, ids, "f32"))
    for name, value in (("QUERY_ROWS", 8), ("FFN_ROWS", 16), ("SHORT", 32), ("LONG", 64)):
        monkeypatch.setattr(R, name, value)
    R.attention_sublayer.clear_cache()
    R.ffn_sublayer.clear_cache()
    blocked = np.asarray(R.forward_logits(cfg, w, ids, "f32"))
    R.attention_sublayer.clear_cache()
    R.ffn_sublayer.clear_cache()
    assert np.abs(blocked - whole).max() <= 2e-5 * np.abs(whole).max() + 2e-6


def test_the_cell_rehearses(M, capsys):
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "1.5",
                   "--trace", "1", "--rehearse"])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert "check: served_logit_gap = 0 " in out     # float32 on both sides
    for name in ("kimivl_context_tokens_per_step", "expert_load_max_over_mean",
                 "decode_rows_mean", "decode_step_ms", "prefill_step_ms"):
        assert f"reader: {name} read something" in out, name
    for name in ("latent_prefill_attention_roofline", "kimivl_decode_hbm_mfu_pct",
                 "kimivl_prefill_call_ms", "kimivl_prefill_ms_per_s",
                 "expert_ffn_roofline", "latent_attention_roofline"):
        assert f"reader: {name} found nothing to read" in out, name
    assert "reader: moe_decode_hbm_roofline" not in out   # the other latent cell's alone
    assert "reference: a verdict at " in out
