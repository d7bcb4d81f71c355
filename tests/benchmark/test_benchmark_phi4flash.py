"""The ``phi4flash`` family's files: the counts its readers divide by against
values worked by hand at the published sizes of Phi-4-mini-flash-reasoning (d
2560, 32 layers, 40 query and 20 key/value heads of 64, FFN 10240, vocabulary
200,064 tied, window 512, Mamba d_i 5120 x N 16, K 4, R 160), the six readers
on hand-made facts, the two precisions of its reference, and the cell's
chip-free rehearsal. ``BENCHMARK.json`` lists the configuration, the cell and
the six metrics since PR 35, appended, and no file of the harness was edited
for them."""
import json
import types

import numpy as np
import pytest

from benchmark import run
from benchmark.manifest import Manifest

CELL = "serve-phi4flash-reasoning"
CONFIG = "phi4-mini-flash-3p8b"
METRICS = ["hybrid_decode_hbm_mfu_pct", "state_update_roofline",
           "selective_scan_roofline", "diff_attention_roofline",
           "shared_kv_tokens_per_step", "window_tokens_per_step"]


@pytest.fixture(scope="module")
def M():
    return Manifest()


@pytest.fixture(scope="module")
def CFG(M):
    return M.config(CONFIG)


@pytest.fixture(scope="module")
def FAM(M):
    return M.family("phi4flash")


@pytest.fixture(scope="module")
def PEAKS(M):
    return M.peaks("TPU v5 lite")


def test_manifest_is_sound_and_nothing_is_cut(M, CFG, FAM):
    assert M.validate() == []
    entry = next(c for c in M.data["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == CFG["reduced"] == []
    assert entry["source"] == CFG["source"] and len(entry["source"]) < 200
    # every key of the published config.json at its published value
    published = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
                 "intermediate_size": 10240, "layer_norm_eps": 1e-05,
                 "max_position_embeddings": 262144, "mb_per_layer": 2,
                 "model_type": "phi4flash", "num_attention_heads": 40,
                 "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
                 "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
                 "lm_head_bias": False, "vocab_size": 200064}
    assert {k: CFG[k] for k in published} == published
    assert CFG["dtype"] == "bfloat16" and "deployment" in CFG
    assert {"no_network", "layer_kinds", "mamba_sizes", "projection_order",
            "head_pairing", "differential_form", "window_edge", "positions", "norms",
            "state", "initial_values"} <= set(CFG["assumed"])
    cell = M.workload(CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, "reasoning-pinned")
    assert [m["name"] for m in M.metrics_of(CELL, "end_to_end")] == \
        ["token_gap_p50_ms", "setup_s"]
    mine = {m["name"] for m in M.metrics_of(CELL, "per_layer")}
    assert set(METRICS) <= mine
    # the older serving metrics are the cell's too; the dense and the routed
    # model's whole-step shares list their own cells
    assert {"decode_step_ms", "decode_rows_mean", "device_idle_pct.serve",
            "decode_host_ms.build", "decode_host_ms.dispatch",
            "decode_host_ms.readback", "decode_host_ms.land"} <= mine
    assert not {"decode_hbm_roofline", "moe_decode_hbm_roofline"} & mine
    assert not hasattr(FAM, "weight_bytes")
    # appended behind what the manifest held at PR 35 (a later PR appends behind
    # these in turn, so nothing here says "last")
    after = lambda names, mine, before: names.index(mine) == names.index(before) + 1
    assert after([c["name"] for c in M.data["configs"]], CONFIG, "xing4-29b-a4b-8l")
    assert after([w["name"] for w in M.data["workloads"]], CELL,
                 "serve-xing4-longanswer-pinned")
    metrics = [m["name"] for m in M.data["per_layer"]]
    at = metrics.index("latent_attention_roofline") + 1
    assert metrics[at:at + 6] == METRICS
    for m in M.data["per_layer"][at:at + 6]:
        assert m["workloads"] == [CELL] and m["moves"] == "token_gap_p50_ms"
    gap = next(m for m in M.data["end_to_end"] if m["name"] == "token_gap_p50_ms")
    assert after(gap["workloads"], CELL, "serve-xing4-longanswer-pinned")


def test_the_traffic_is_the_issue_s(M):
    traffic = M.traffic("reasoning-pinned")
    assert traffic["kind"] == "open_loop" and traffic["arrivals"] == "exponential"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 160, "sigma": 0.7,
                                     "lo": 32, "hi": 640}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 704, "sigma": 0.45,
                                     "lo": 192, "hi": 1152}
    assert (traffic["order_seed"], traffic["round"], traffic["temperature"]) == (35, 16, 0.0)
    assert (traffic["ramp_s"], traffic["drain_s"]) == (12.0, 150.0)
    # four fifths of the knee the file states as a number, the ramp at 1.5 x
    assert traffic["rate_per_s"] == round(0.8 * traffic["knee_per_s"], 1)
    assert traffic["ramp_rate_per_s"] == pytest.approx(1.5 * traffic["rate_per_s"])
    # the longest context and what the warm-up adds to it fit the engine's 2,048
    assert 640 + 1152 + 2 * 64 + 8 <= 2048
    cell = json.loads((M.root / "cells" / f"{CELL}.json").read_text())
    assert (cell["check_requests"], cell["trace_s"]) == (8, 4.0)
    assert set(cell["limits"]) == {"served_logit_gap"}


def test_counts_at_the_published_sizes(CFG, FAM):
    kinds = FAM.layer_kinds(CFG)
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu", "cross")] == \
        [9, 8, 1, 7, 7]
    assert FAM.d_inner(CFG) == 5120 and FAM.dt_rank(CFG) == 160
    # a layer's mixer, by kind
    mamba = (2560 * 10240 + 4 * 5120 + 5120 + 5120 * (160 + 32) + 160 * 5120 + 5120
             + 16 * 5120 + 5120 + 5120 * 2560)
    attn = 2560 * (40 + 20 + 20) * 64 + 2560 * 2560 + 4 * 64 + 128
    gmu = 2 * 2560 * 5120
    cross = 2 * 2560 * 2560 + 4 * 64 + 128
    assert (mamba, attn, gmu, cross) == (41_241_600, 19_661_184, 26_214_400, 13_107_584)
    # every layer: a gated FFN and two LayerNorms with gain and bias
    each = 2560 * 20480 + 10240 * 2560 + 2 * 2 * 2560
    assert each == 78_643_200 + 10_240
    total = 9 * mamba + 9 * attn + 7 * gmu + 7 * cross + 32 * each \
        + 200064 * 2560 + 2 * 2560
    assert FAM.param_count(CFG) == total == 3_852_457_984
    assert FAM.weight_bytes_per_step(CFG) == 2 * total == 7_704_915_968
    # ONE layer caches a row a token: K and V of 20 heads of 64 in bfloat16
    assert FAM.kv_bytes_per_token(CFG) == 2 * 20 * 64 * 2 == 5120
    assert FAM.cache_bytes_per_context_token(CFG) == 5120
    assert FAM.paged_readers(CFG) == 8
    assert FAM.window_bytes_per_row(CFG) == 8 * 512 * 5120 == 20_971_520
    assert FAM.window_bytes_per_token(CFG) == 8 * 5120
    assert FAM.state_bytes_per_row(CFG) == 9 * 16 * 5120 * 4 == 2_949_120
    assert FAM.conv_tail_bytes_per_row(CFG) == 9 * 3 * 5120 * 2 == 276_480
    # a decode step at 50 rows, 35,000 tokens of context, 25,000 inside the windows
    assert FAM.decode_step_bytes(CFG, 50, 35_000, 25_000) == (
        7_704_915_968 + 8 * 35_000 * 5120 + 25_000 * 8 * 5120
        + 2 * 50 * (2_949_120 + 276_480)) == 10_485_075_968
    # the kernels: a state read and written, Delta, c, y (float32), B and C a row
    assert FAM.state_update_bytes(CFG, 1) == 2 * 16 * 5120 * 4 + 3 * 5120 * 4 + 2 * 16 * 4 == 716_928
    assert FAM.state_update_flops(CFG, 1) == 6 * 16 * 5120 == 491_520
    assert FAM.selective_scan_bytes(CFG, 100, 2) == 100 * (3 * 5120 * 4 + 128) + 2 * 16 * 5120 * 4
    assert FAM.selective_scan_flops(CFG, 100) == 100 * 491_520


def test_the_initial_values_are_laid_over_the_draw(CFG, FAM):
    """``A_log`` and ``dt_proj.b`` carry Mamba's published initialisation under
    the draw, for ``build`` and ``forward_logits`` alike; every other leaf is
    the draw."""
    import jax.numpy as jnp

    from benchmark import weights as W

    cfg = {**CFG, **FAM.REHEARSE}
    w = W.make_weights(cfg, 5, FAM.leaf_specs(cfg))
    laid = FAM.initial_values(cfg, w)
    changed = {k for k in w if not np.array_equal(np.asarray(w[k]), np.asarray(laid[k]))}
    assert changed == {"front.mamba.A_log", "front.mamba.dt_proj.b",
                       "mid.mamba.A_log", "mid.mamba.dt_proj.b"}
    a = np.asarray(laid["mid.mamba.A_log"] - w["mid.mamba.A_log"])
    assert np.allclose(a[:, 0], np.log(np.arange(1, 17)), atol=1e-6)
    dt = np.asarray(jnp.logaddexp(laid["mid.mamba.dt_proj.b"] - w["mid.mamba.dt_proj.b"], 0.0))
    assert dt[0] == pytest.approx(0.001, rel=1e-3) and dt[-1] == pytest.approx(0.1, rel=1e-3)
    _, leaves = FAM.build(cfg, w)
    assert np.allclose(np.asarray(leaves["mid.mamba.A_log"]._data),
                       np.asarray(laid["mid.mamba.A_log"]))


def _facts(CFG, FAM, PEAKS, step_ms=14.0, steps=2, rows=50, ctx=35_000, win=25_000,
           attrs=True):
    """Two decode steps of ``step_ms`` on the device (nine state updates of 60
    us and sixteen attention reads of 250 us each) and one prefill of 300 real
    tokens in 2 rows (nine scans of 2 ms), inside a traced window."""
    ops, mods, t = [], [], 1_000_000
    for s in range(steps):
        mods.append([f"jit_step({s})", t, int(step_ms * 1e6)])
        for k in range(9):
            ops.append([f"%state_update.{k} = (f32[9,65,16,5120]{{3,2,1,0}}, f32[64,1,5120]"
                        "{2,1,0}) custom-call(...)", t + k * 100_000, 60_000])
        for k in range(16):
            ops.append([f"%paged_attention.{k} = bf16[64,48,128]{{2,1,0}} custom-call(...)",
                        t + 1_000_000 + k * 300_000, 250_000])
        t += int(step_ms * 1e6) + 1_000_000
    mods.append(["jit_prefill(9)", t, 40_000_000])
    for k in range(9):
        ops.append([f"%selective_scan.{k} = (f32[2,256,5120]{{2,1,0}}, f32[2,16,5120]{{2,1,0}}) "
                    "custom-call(...)", t + k * 3_000_000, 2_000_000])
    t += 41_000_000
    red = {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}}, "host": [],
           "sync_ns": 0, "t0_ns": 0, "t1_ns": t, "host_window": (10.0, 20.0)}
    step = {"rows": rows, "bucket": 64}
    if attrs:
        step.update(shared_kv_tokens=ctx, window_tokens=win, state_rows=rows)
    fill = {"rows": 2, **({"scan_tokens": 300} if attrs else {})}
    named = {"decode_step": [("decode_step", 1_000_000 + i, 2_000_000 + i, 1, step)
                             for i in range(steps)],
             "prefill": [("prefill", 3_000_000, 4_000_000, 1, fill)]}
    spans = types.SimpleNamespace(named=lambda name, *a: named.get(name, []))
    return {"trace": red, "spans": spans, "config": CFG, "family": FAM, "peaks": PEAKS,
            "span_window_ns": (0, 10**12)}


def test_readers_on_hand_made_facts(M, CFG, FAM, PEAKS):
    run_ = _facts(CFG, FAM, PEAKS)
    f = FAM.trace_facts(run_)
    assert (f["steps"], f["step_ns"], f["rows"]) == (2, 28_000_000, 50)
    assert (f["shared_kv_tokens"], f["window_tokens"]) == (35_000, 25_000)
    assert (f["scan_tokens"], f["scan_rows"]) == (300, 2)
    # the whole step: 10.485 GB twice over 28 ms of the 819 GB/s peak
    assert M.reader("hybrid_decode_hbm_mfu_pct")(run_) == pytest.approx(
        100 * 2 * 10_485_075_968 / 819e9 / 0.028)                     # 91.4%
    # 18 calls of 50 rows x 716,928 B (memory sets the least time) in 60 us each
    assert M.reader("state_update_roofline")(run_) == pytest.approx(
        100 * 50 * 716_928 / 819e9 / 60e-6)                           # 72.9%
    # 32 calls = 2 steps of 16 readers: 8 of the pool's contexts, 8 of the
    # windows' tokens, and each row's padded queries and output, in and out
    io = 50 * 2 * 2 * 2560 * 2
    need = 2 * (8 * 35_000 * 5120 + 25_000 * 8 * 5120 + 16 * io)
    assert M.reader("diff_attention_roofline")(run_) == pytest.approx(
        100 * need / 819e9 / (32 * 250e-6))                           # 75.5%
    # nine scans of 300 real tokens in 2 rows, 2 ms each: far from either roof
    scan = 9 * (300 * (3 * 5120 * 4 + 128) + 2 * 16 * 5120 * 4)
    assert M.reader("selective_scan_roofline")(run_) == pytest.approx(
        100 * scan / 819e9 / (9 * 2e-3))                              # 1.2%
    assert M.reader("shared_kv_tokens_per_step")(run_) == 35_000
    assert M.reader("window_tokens_per_step")(run_) == 25_000
    # a share over 100% is a fault of a count or of the time, and raises
    with pytest.raises(ValueError, match="hybrid_decode_hbm_mfu_pct"):
        M.reader("hybrid_decode_hbm_mfu_pct")(_facts(CFG, FAM, PEAKS, step_ms=10.0))


def test_readers_find_nothing_in_a_program_that_lacks_the_arch(M, CFG, FAM, PEAKS):
    """The parent's spans carry none of the new attributes, and another
    family has none of the functions: every reader returns None, none raises."""
    old = _facts(CFG, FAM, PEAKS, attrs=False)
    for name in METRICS:
        assert M.reader(name)(old) is None, name
    for name in METRICS:
        assert M.reader(name)(dict(_facts(CFG, FAM, PEAKS), family=M.family("gpt"))) is None
    run_ = _facts(CFG, FAM, PEAKS)
    for name in METRICS[:4]:
        assert M.reader(name)(dict(run_, trace=None)) is None
    assert M.reader("shared_kv_tokens_per_step")(dict(run_, spans=None)) is None


def test_the_cell_rehearses(M, capsys):
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds", "1.5",
                   "--trace", "1", "--rehearse"])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert "check: served_logit_gap = 0 " in out     # float32 on both sides
    for name in ("shared_kv_tokens_per_step", "window_tokens_per_step",
                 "decode_rows_mean", "decode_step_ms", "prefill_step_ms",
                 "decode_host_ms.dispatch"):
        assert f"reader: {name} read something" in out, name
    for name in METRICS[:4]:                         # device-trace readers: no chip here
        assert f"reader: {name} found nothing to read" in out, name
    assert "reader: decode_hbm_roofline" not in out   # the dense cell's alone
    assert "reader: moe_decode_hbm_roofline" not in out


def test_reference_precisions_differ(CFG, FAM):
    from benchmark import weights as W

    cfg = {**CFG, **FAM.REHEARSE}
    w = W.make_weights(cfg, 11, FAM.leaf_specs(cfg))
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 24))
    f32 = np.asarray(FAM.forward_logits(cfg, w, ids, "f32"))
    assert f32.shape == (2, 24, cfg["vocab_size"])
    assert np.array_equal(f32, np.asarray(FAM.forward_logits(cfg, w, ids, "f32")))
    fp8 = np.asarray(FAM.forward_logits(cfg, w, ids, "fp8"))
    assert np.abs(fp8 - f32).max() > 1e-2
    with pytest.raises(ValueError, match="unknown precision"):
        FAM.forward_logits(cfg, w, ids, "int4")
    # causal in every layer: what lies behind a position does not reach it
    longer = np.concatenate([ids, ids[:, :5]], axis=1)
    more = np.asarray(FAM.forward_logits(cfg, w, longer, "f32"))
    assert np.abs(more[:, :24] - f32).max() <= 1e-5 * np.abs(f32).max()
