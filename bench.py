"""Benchmark harness — prints ONE JSON line for the driver.

Primary metric (BASELINE.md north star): GPT bf16 fused-train-step
tokens/sec/chip (single-chip proxy of the GPT-3 1.3B hybrid config; ~355M at
seq 1024 fits one v5e chip). vs_baseline compares against this project's own
recorded best (bench_baseline.json — the reference publishes no in-tree
numbers), ratcheting upward on new bests.

The one JSON line also carries `extra_metrics` covering the other BASELINE
configs measurable on one chip: ResNet-50 AOT inference imgs/sec/chip via the
paddle_tpu.inference Predictor (the deployment path), LeNet eager steps/sec
(per-op dispatch overhead), and the GPT step's model-FLOPs utilization.
"""
from __future__ import annotations

import contextlib
import json
import os
import shutil
import signal
import sys
import tempfile
import time

_V5E_PEAK_BF16 = 197e12  # bf16 FLOP/s per v5e chip

# Wall-clock budget: the driver kills the whole process at its own timeout
# (rc=124, no JSON line — round 5 lost its bench this way). Stay under it:
# configs that would start past the budget are skipped, a config that runs
# long is interrupted via SIGALRM, and the JSON line always prints with
# whatever completed.
_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "600"))


class _BenchTimeout(BaseException):
    # BaseException: the alarm usually lands inside library code wrapped in
    # broad `except Exception` fallbacks (e.g. the lazy-flush replay path),
    # which must not swallow the budget interrupt — the one-shot itimer is
    # already consumed and nothing would re-arm it.
    pass


@contextlib.contextmanager
def _alarm(seconds):
    """Interrupt the body after ``seconds`` (best effort — a signal lands
    once control returns to Python bytecode). No-op where SIGALRM is
    unavailable (non-main thread / non-POSIX)."""
    if seconds <= 0:
        raise _BenchTimeout("budget exhausted")
    try:
        prev = signal.signal(signal.SIGALRM, lambda *_: (_ for _ in ()).throw(_BenchTimeout()))
    except (ValueError, AttributeError, OSError):
        yield
        return
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, prev)


def _gap_probe():
    """Dispatch-gap instrumentation (ROADMAP item 2): host idle time between
    device steps, measured as the attributed block time (lazy_block_ns —
    every sanctioned host wait on the device feeds it) per timed step.
    Returns finish(steps) -> ms/step."""
    from paddle_tpu import profiler

    c0 = profiler.counters().get("lazy_block_ns", 0)

    def finish(steps):
        c1 = profiler.counters().get("lazy_block_ns", 0)
        return round((c1 - c0) / max(steps, 1) / 1e6, 3)

    return finish


def bench_gpt(paddle, jax, np, on_tpu):
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    if on_tpu:
        cfg = GPTConfig(
            vocab_size=50304, hidden_size=1024, num_layers=24, num_heads=16,
            max_position_embeddings=1024, hidden_dropout=0.0, attention_dropout=0.0,
            # unfused CE is ~6% faster at b8 (fits comfortably); the fused
            # path exists for memory-bound configs (1.3B below). Round-5
            # block sweep re-confirmed: fused loses at every block_rows
            # (4096: 43.9k, 8192: 43.6k vs 45.1k unfused, same session).
            # Round-4 optimization search (interleaved in-process A/B, hard
            # syncs): flash-vs-exact attention ±0.1%, fused CE −5%, b16/b32
            # batches −5..−50% (exact attn collapses at b16+; flash holds),
            # optimizer+dispatch ≈ 0 ms (full step == fwd+bwd time).
            # Round-5 decomposition of the 185 ms step (raw-jax replica,
            # per-component ablations on-chip): matmul core 91 ms at 82% of
            # peak, attention 68 ms (37% of step for 6.6% of FLOPs), head+CE
            # 28 ms, LN 7 ms, gelu 2 ms. The flash kernel itself accounts
            # for ~48 ms and already beats stock jax pallas flash 3.6x and
            # splash 3.7x at this shape; the round-5 kernel A/B sweep
            # (multi-row programs, chunk-fused loops, native-layout two-pass,
            # streamed grid, merged backward — all committed behind flags in
            # ops/pallas/flash_attention.py) found the per-head D=64 score
            # matmul pinned near 30 TF/s at short T regardless of structure
            # (the same matmul reaches ~95 TF/s in steady state at T>=4096).
            # The remaining "fused transformer layer" levers (projections
            # inside the kernel) would trade 82%-efficient XLA matmuls for
            # that same pinned regime — the committed A/Bs say it loses.
            fused_lm_loss=False,
        )
        # 30 timed steps: at ~190ms/step the ±4% run-to-run variance seen at
        # 10 steps tightens to ~±1.5% against the ratcheted baseline
        batch, seq, steps = 8, 1024, 30
    else:  # smoke fallback (driver runs on real TPU)
        cfg = GPTConfig(
            vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
            max_position_embeddings=256, hidden_dropout=0.0, attention_dropout=0.0,
        )
        batch, seq, steps = 8, 256, 10

    paddle.seed(0)
    model = GPTForPretraining(cfg)
    model.bfloat16()  # MXU-native dtype
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())

    def loss_fn(m, ids, labels):
        return m.loss(ids, labels)

    step = paddle.jit.compile_train_step(model, loss_fn, opt)

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))
    loss = step(ids, labels)  # compile
    loss = step(ids, labels)
    float(loss.item())

    gap = _gap_probe()
    t0 = time.time()
    for _ in range(steps):
        loss = step(ids, labels)
    final = float(loss.item())  # forces sync
    dt = time.time() - t0

    tokens_per_sec = batch * seq * steps / dt
    n_params = sum(p.size for p in model.parameters())
    # train FLOPs/token ≈ 6N (fwd+bwd matmuls) + 6·L·d·T (causal attention)
    flops_per_token = 6.0 * n_params + 6.0 * cfg.num_layers * cfg.hidden_size * seq
    mfu = tokens_per_sec * flops_per_token / _V5E_PEAK_BF16 if on_tpu else None
    return {
        "name": f"GPT-{n_params/1e6:.0f}M bf16 train (b{batch}xs{seq}, fused step)",
        "tokens_per_sec": round(tokens_per_sec, 1),
        "loss": round(final, 4),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "dispatch_gap_ms_per_step": gap(steps),
    }


def _gpt_train_tokens_per_sec(paddle, np, cfg, batch, seq, steps):
    from paddle_tpu.models.gpt import GPTForPretraining

    paddle.seed(0)
    model = GPTForPretraining(cfg)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    step = paddle.jit.compile_train_step(model, lambda m, i, l: m.loss(i, l), opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))
    loss = step(ids, labels)
    loss = step(ids, labels)
    float(loss.item())
    t0 = time.time()
    for _ in range(steps):
        loss = step(ids, labels)
    final = float(loss.item())
    dt = time.time() - t0
    n_params = sum(p.size for p in model.parameters())
    return batch * seq * steps / dt, n_params, final


def bench_gpt_1p3b(paddle, jax, np, on_tpu):
    """North-star config: GPT-3 1.3B training on ONE chip (BASELINE.json
    1.3B-class). b2 WITHOUT remat + Pallas flash attention + fused LM-head
    CE: flash removes the T² score residuals, so the full activation set
    fits HBM next to the f32 AdamW state — no recompute tax. Measured MFU
    0.66 vs 0.54 for the round-3 b4+remat config."""
    from paddle_tpu.models.gpt import gpt3_1p3b

    if not on_tpu:
        return {"name": "GPT-1.3B single-chip", "skipped": "cpu"}
    cfg = gpt3_1p3b(
        hidden_dropout=0.0, attention_dropout=0.0, remat=False,
        attention_impl="flash", use_mp_layers=False,
    )
    batch, seq, steps = 2, 2048, 8
    tps, n_params, final = _gpt_train_tokens_per_sec(paddle, np, cfg, batch, seq, steps)
    flops_per_token = 6.0 * n_params + 6.0 * cfg.num_layers * cfg.hidden_size * seq
    return {
        "name": f"GPT-1.3B bf16 train (b{batch}xs{seq}, flash, no remat, fused-CE, single chip)",
        "tokens_per_sec": round(tps, 1),
        "mfu": round(tps * flops_per_token / _V5E_PEAK_BF16, 4),
        "loss": round(final, 4),
    }


def bench_gpt_8k_flash(paddle, jax, np, on_tpu):
    """Long-sequence point: 8k tokens through the Pallas flash-attention
    kernel (fwd+bwd), where exact attention's T² scores would dominate.
    No remat: flash keeps activations small enough to skip the recompute
    tax even at 8k (measured MFU 0.38 vs 0.30 with remat). Round-5: unfused
    CE +5% (41.1k vs 39.2k tok/s); attention is 66% of the step here and
    the kernel (12.6 ms/layer fwd+bwd) beats stock jax flash 6.5x and
    splash 8.6x at this shape — the PV/dq matmuls' N=64 lane ceiling
    (~50 TF/s) bounds further gains, so ~0.39-0.41 MFU is the honest
    plateau for D=64 heads on v5e."""
    from paddle_tpu.models.gpt import GPTConfig

    if not on_tpu:
        return {"name": "GPT 8k flash", "skipped": "cpu"}
    cfg = GPTConfig(
        vocab_size=50304, hidden_size=1024, num_layers=12, num_heads=16,
        max_position_embeddings=8192, hidden_dropout=0.0,
        attention_dropout=0.0, attention_impl="flash", remat=False,
        use_mp_layers=False,
        # round-5 A/B: at b2s8192 the full activation set fits HBM, and the
        # unfused CE measured 41.1k vs 39.2k tok/s fused (+5%)
        fused_lm_loss=False,
    )
    batch, seq, steps = 2, 8192, 10
    tps, n_params, final = _gpt_train_tokens_per_sec(paddle, np, cfg, batch, seq, steps)
    flops_per_token = 6.0 * n_params + 6.0 * cfg.num_layers * cfg.hidden_size * seq
    return {
        "name": f"GPT-{n_params/1e6:.0f}M bf16 train (b{batch}xs8192, flash attention)",
        "tokens_per_sec": round(tps, 1),
        "mfu": round(tps * flops_per_token / _V5E_PEAK_BF16, 4),
        "loss": round(final, 4),
    }


def _bf16_wrap(paddle, model):
    """Cast f32 inputs to bf16 at the graph edge so the whole inference body
    runs MXU-native bf16 (weights converted via model.bfloat16())."""
    import paddle_tpu.nn as nn

    class BF16Wrap(nn.Layer):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def forward(self, x):
            return paddle.cast(self.inner(paddle.cast(x, "bfloat16")), "float32")

    model.bfloat16()
    w = BF16Wrap(model)
    w.eval()
    return w


def bench_resnet50_aot(paddle, jax, np, on_tpu):
    """ResNet-50 bf16 AOT inference through the deployment path
    (save → Predictor). bf16 data flow measured +15% over f32 on v5e."""
    from paddle_tpu.vision.models import resnet50
    from paddle_tpu.static import InputSpec
    from paddle_tpu.inference import Config, create_predictor

    paddle.seed(0)
    model = _bf16_wrap(paddle, resnet50().eval())
    # b64 measured ~1.3x the b32 imgs/s on v5e (utilization, same latency
    # class); serving batch is a throughput knob, keep both paths at b64
    batch = 64 if on_tpu else 4
    steps = 20 if on_tpu else 3

    d = tempfile.mkdtemp()
    prefix = os.path.join(d, "resnet50")
    paddle.static.save_inference_model(
        prefix, [InputSpec([batch, 3, 224, 224], "float32", name="image")], model
    )
    pred = create_predictor(Config(prefix))
    shutil.rmtree(d, ignore_errors=True)  # artifact is in memory now (~200 MB on disk)
    x = np.random.RandomState(0).randn(batch, 3, 224, 224).astype(np.float32)
    # device-resident input via the zero-copy handle: measures the chip, not
    # the 19 MB/batch host-to-device copy
    h = pred.get_input_handle(pred.get_input_names()[0])
    h.share_external_data(jax.device_put(jax.numpy.asarray(x)))
    out_h = pred.get_output_handle(pred.get_output_names()[0])
    pred.run()
    out_h.copy_to_cpu()  # block until the first run (and its compile) is done
    pred.run()
    out_h.copy_to_cpu()
    dt = None
    for _ in range(2):  # best-of-2: sheds one-off host stalls
        t0 = time.time()
        for _ in range(steps):
            pred.run()
        out_h.copy_to_cpu().sum()
        elapsed = time.time() - t0
        dt = elapsed if dt is None else min(dt, elapsed)
    return {
        "name": f"ResNet-50 bf16 AOT inference (b{batch}, Predictor, device-resident input)",
        "imgs_per_sec": round(batch * steps / dt, 1),
    }


def bench_resnet50_int8(paddle, jax, np, on_tpu):
    """ResNet-50 int8 serving (PTQ → int8 swap → bf16 inter-layer flow →
    Predictor) — the slim→AnalysisPredictor int8 capability.

    PAIRED measurement: int8 and bf16 predictors run in ALTERNATING timed
    segments, so host load variance hits both equally and the
    reported ``int8_speedup`` is load-invariant (round-4's driver run showed
    1.003x while idle runs showed 1.23x — pure per-run dispatch variance).
    Ceiling note (round-5 microbench, committed): XLA int8 convs on v5e run
    1.1-1.3x their bf16 counterparts (e.g. 3x3 512ch: 91.7 TOP/s vs 71.8
    TFLOP/s), NOT the 2x the 394-TOPS peak implies — the serving speedup is
    bounded by that, and b256 int8 conv lowering REGRESSES (0.81x), so b64
    is the serving batch."""
    from paddle_tpu.vision.models import resnet50
    from paddle_tpu.static import InputSpec
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.quantization import PostTrainingQuantization, convert_to_int8_inference

    batch = 64 if on_tpu else 4
    steps = 20 if on_tpu else 3

    class Calib(paddle.io.Dataset):
        def __len__(self):
            return 2

        def __getitem__(self, i):
            return np.random.RandomState(i).randn(3, 224, 224).astype(np.float32)

    def build(int8):
        paddle.seed(0)
        model = resnet50()
        model.eval()
        if int8:
            loader = paddle.io.DataLoader(Calib(), batch_size=2, num_workers=0)
            ptq = PostTrainingQuantization(model, data_loader=loader, batch_nums=1)
            ptq.quantize()
            convert_to_int8_inference(model, ptq)
        model = _bf16_wrap(paddle, model)  # int8 weights untouched (non-float)
        d = tempfile.mkdtemp()
        prefix = os.path.join(d, "resnet50_q" if int8 else "resnet50_f")
        paddle.static.save_inference_model(
            prefix, [InputSpec([batch, 3, 224, 224], "float32", name="image")], model
        )
        pred = create_predictor(Config(prefix))
        shutil.rmtree(d, ignore_errors=True)
        x = np.random.RandomState(0).randn(batch, 3, 224, 224).astype(np.float32)
        h = pred.get_input_handle(pred.get_input_names()[0])
        h.share_external_data(jax.device_put(jax.numpy.asarray(x)))
        out_h = pred.get_output_handle(pred.get_output_names()[0])
        pred.run(); out_h.copy_to_cpu()
        pred.run(); out_h.copy_to_cpu()
        return pred, out_h

    pred_q, out_q = build(True)
    pred_f, out_f = build(False)

    def segment(pred, out_h):
        t0 = time.time()
        for _ in range(steps):
            pred.run()
        out_h.copy_to_cpu().sum()
        return time.time() - t0

    dt_q = dt_f = None
    for _ in range(3):  # alternating best-of-3: load-paired A/B
        e_q = segment(pred_q, out_q)
        e_f = segment(pred_f, out_f)
        dt_q = e_q if dt_q is None else min(dt_q, e_q)
        dt_f = e_f if dt_f is None else min(dt_f, e_f)
    return {
        "name": f"ResNet-50 int8 AOT inference (b{batch}, Predictor, paired A/B)",
        "imgs_per_sec": round(batch * steps / dt_q, 1),
        "bf16_paired_imgs_per_sec": round(batch * steps / dt_f, 1),
        "int8_speedup": round(dt_f / dt_q, 3),
    }


def bench_lenet_eager(paddle, jax, np, on_tpu):
    """LeNet eager train step — per-op dispatch overhead (first E2E slice)."""
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=model.parameters())
    lossf = paddle.nn.CrossEntropyLoss()
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(64, 1, 28, 28).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (64,)))
    steps = 30 if on_tpu else 10

    def one_step():
        loss = lossf(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    one_step()
    one_step()
    gap = _gap_probe()
    t0 = time.time()
    for _ in range(steps):
        loss = one_step()
    float(loss.item())
    dt = time.time() - t0
    return {
        "name": "LeNet eager train (b64, lazy batched dispatch)",
        "steps_per_sec": round(steps / dt, 2),
        "dispatch_gap_ms_per_step": gap(steps),
    }


def bench_vit_l_aot(paddle, jax, np, on_tpu):
    """ViT-L/16 bf16 AOT inference (BASELINE.json config 5 class: large
    vision transformer through the deployment path)."""
    from paddle_tpu.vision.models import vit_l_16
    from paddle_tpu.static import InputSpec
    from paddle_tpu.inference import Config, create_predictor

    if not on_tpu:
        return {"name": "ViT-L AOT", "skipped": "cpu"}
    paddle.seed(0)
    model = _bf16_wrap(paddle, vit_l_16().eval())
    batch, steps = 16, 20
    d = tempfile.mkdtemp()
    prefix = os.path.join(d, "vitl")
    paddle.static.save_inference_model(
        prefix, [InputSpec([batch, 3, 224, 224], "float32", name="image")], model
    )
    pred = create_predictor(Config(prefix))
    shutil.rmtree(d, ignore_errors=True)
    x = np.random.RandomState(0).randn(batch, 3, 224, 224).astype(np.float32)
    h = pred.get_input_handle(pred.get_input_names()[0])
    h.share_external_data(jax.device_put(jax.numpy.asarray(x)))
    out_h = pred.get_output_handle(pred.get_output_names()[0])
    pred.run(); out_h.copy_to_cpu()
    pred.run(); out_h.copy_to_cpu()
    dt = None
    for _ in range(2):  # best-of-2: sheds one-off host stalls
        t0 = time.time()
        for _ in range(steps):
            pred.run()
        out_h.copy_to_cpu().sum()
        elapsed = time.time() - t0
        dt = elapsed if dt is None else min(dt, elapsed)
    return {
        "name": f"ViT-L/16 bf16 AOT inference (b{batch}, Predictor)",
        "imgs_per_sec": round(batch * steps / dt, 1),
    }


def bench_yolov3_aot(paddle, jax, np, on_tpu):
    """YOLOv3-DarkNet53 bf16 AOT detection inference (the PP-YOLOE BASELINE
    row's YOLO-family point): backbone + FPN heads + yolo_box decode +
    matrix NMS, ALL in one static-shape Predictor graph."""
    from paddle_tpu.vision.models import yolov3_darknet53, YOLOv3Postprocess
    from paddle_tpu.static import InputSpec
    from paddle_tpu.inference import Config, create_predictor

    if not on_tpu:
        return {"name": "YOLOv3 AOT", "skipped": "cpu"}
    paddle.seed(0)
    model = yolov3_darknet53(num_classes=80)
    model.eval()
    post = YOLOv3Postprocess(model, img_hw=(416, 416))
    post = _bf16_wrap(paddle, post)
    batch, steps = 8, 20
    d = tempfile.mkdtemp()
    prefix = os.path.join(d, "yolov3")
    paddle.static.save_inference_model(
        prefix, [InputSpec([batch, 3, 416, 416], "float32", name="image")], post
    )
    pred = create_predictor(Config(prefix))
    shutil.rmtree(d, ignore_errors=True)
    x = np.random.RandomState(0).randn(batch, 3, 416, 416).astype(np.float32)
    h = pred.get_input_handle(pred.get_input_names()[0])
    h.share_external_data(jax.device_put(jax.numpy.asarray(x)))
    out_h = pred.get_output_handle(pred.get_output_names()[0])
    pred.run(); out_h.copy_to_cpu()
    pred.run(); out_h.copy_to_cpu()
    dt = None
    for _ in range(2):
        t0 = time.time()
        for _ in range(steps):
            pred.run()
        out_h.copy_to_cpu().sum()
        elapsed = time.time() - t0
        dt = elapsed if dt is None else min(dt, elapsed)
    return {
        "name": f"YOLOv3-DarkNet53 bf16 AOT detection (b{batch}x416, Predictor+matrixNMS)",
        "imgs_per_sec": round(batch * steps / dt, 1),
    }


def bench_llama_1b(paddle, jax, np, on_tpu):
    """Llama ~1B train step, single-chip proxy of the TP config (BASELINE
    config 4 class: the model's mp_layers carry the Megatron pspecs the
    dryrun executes at mp=8; here the same program runs at world 1)."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if not on_tpu:
        return {"name": "Llama-1B train", "skipped": "cpu"}
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, num_layers=16, num_heads=16,
        max_position_embeddings=2048,
    )
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    step = paddle.jit.compile_train_step(model, lambda m, i, l: m.loss(i, l), opt)
    rng = np.random.RandomState(0)
    batch, seq, steps = 2, 2048, 8
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))
    loss = step(ids, labels)
    loss = step(ids, labels)
    float(loss.item())
    t0 = time.time()
    for _ in range(steps):
        loss = step(ids, labels)
    final = float(loss.item())
    dt = time.time() - t0
    n_params = sum(p.size for p in model.parameters())
    tps = batch * seq * steps / dt
    flops_per_token = 6.0 * n_params + 6.0 * cfg.num_layers * cfg.hidden_size * seq
    return {
        "name": f"Llama-{n_params/1e9:.1f}B bf16 train (b{batch}xs{seq}, TP-layered, single chip)",
        "tokens_per_sec": round(tps, 1),
        "mfu": round(tps * flops_per_token / _V5E_PEAK_BF16, 4),
        "loss": round(final, 4),
    }


def bench_dp8_gpt(paddle, jax, np, on_tpu):
    """DP=8 GPT fused train step with the communication-optimized sync
    (ZeRO-1 sharded weight update + bucketed gradient reduce-scatter,
    FLAGS_shard_weight_update). Runs only when the process sees >= 8
    devices (a real multichip slice, or the dryrun harness's virtual CPU
    mesh); the single-chip driver reports it skipped."""
    devs = jax.devices()
    if len(devs) < 8:
        return {"name": "GPT DP=8 sharded-weight-update train",
                "skipped": f"needs 8 devices, have {len(devs)}"}
    from jax.sharding import Mesh
    from paddle_tpu import profiler
    from paddle_tpu.distributed.engine import HybridParallelEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    if on_tpu:
        cfg = GPTConfig(
            vocab_size=50304, hidden_size=1024, num_layers=24, num_heads=16,
            max_position_embeddings=1024, hidden_dropout=0.0,
            attention_dropout=0.0, fused_lm_loss=False,
        )
        batch, seq, steps = 64, 1024, 10
    else:
        cfg = GPTConfig(
            vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
            max_position_embeddings=64, hidden_dropout=0.0, attention_dropout=0.0,
        )
        batch, seq, steps = 16, 64, 5
    paddle.set_flags({"FLAGS_shard_weight_update": True})
    paddle.seed(0)
    model = GPTForPretraining(cfg)
    if on_tpu:
        model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    mesh = Mesh(np.asarray(devs[:8]), ("dp",))
    eng = HybridParallelEngine(model, opt, lambda m, i, l: m.loss(i, l), mesh=mesh)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))
    eng.train_step(ids, labels)
    float(eng.train_step(ids, labels).item())
    c0 = profiler.counters()
    t0 = time.time()
    for _ in range(steps):
        loss = eng.train_step(ids, labels)
    final = float(loss.item())
    dt = time.time() - t0
    c1 = profiler.counters()
    return {
        "name": f"GPT DP=8 sharded-weight-update train (b{batch}xs{seq})",
        "tokens_per_sec": round(batch * seq * steps / dt, 1),
        "loss": round(final, 4),
        "wus_enabled": int(eng._wus is not None),
        "dp_sync_bytes_per_step": (c1.get("dp_sync_bytes", 0) - c0.get("dp_sync_bytes", 0)) // steps,
    }


def bench_profiler_overhead(paddle, jax, np, on_tpu):
    """Telemetry tax on the hot path (ISSUE-5 acceptance: <2%): a hot
    record+flush loop (one lazy_flush span + flight-ring append per
    iteration) timed with NO profiler vs a constructed-but-CLOSED one.
    Interleaved min-of-N segments, so host load variance hits both arms."""
    from paddle_tpu import profiler

    iters = 150 if on_tpu else 100

    def loop(n):
        t = paddle.to_tensor(np.ones(256, np.float32))
        for _ in range(n):
            t = t + 1.0
            t.numpy()  # materialization point: flush + span every iteration

    loop(30)  # warm the flush executable cache

    def segment():
        t0 = time.time()
        loop(iters)
        return time.time() - t0

    p = profiler.Profiler(timer_only=True)
    p.start()
    p.stop()  # CLOSED; flight recorder still on — the disabled path
    absent, closed = [], []
    # paired segments with ALTERNATING order: CPU-frequency drift and the
    # first-in-pair warmup tax otherwise read as fake overhead (an A/A run
    # of this loop shows ~4% between identical arms when the order is fixed)
    for i in range(8):
        a, b = (absent, closed) if i % 2 == 0 else (closed, absent)
        a.append(segment())
        b.append(segment())
    overhead = min(closed) / min(absent) - 1.0
    return {
        "name": f"profiler disabled-path overhead (lazy dispatch loop x{iters})",
        "overhead_pct": round(overhead * 100.0, 2),
        "absent_us_per_iter": round(min(absent) / iters * 1e6, 2),
        "closed_us_per_iter": round(min(closed) / iters * 1e6, 2),
    }


def bench_watchdog_overhead(paddle, jax, np, on_tpu):
    """Watchdog off-path tax on the LeNet eager step (ISSUE-8 acceptance:
    <=1% with FLAGS_collective_timeout_s=0): the live code path — a
    publish() attr probe per step plus a guard flag compare per host sync —
    against the same loop with both patched to no-ops. Interleaved
    alternating-order min-of-N segments, same discipline as
    bench_profiler_overhead (fixed-order A/B reads CPU drift as fake
    overhead)."""
    import contextlib

    from paddle_tpu.distributed import watchdog
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=model.parameters())
    lossf = paddle.nn.CrossEntropyLoss()
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(64, 1, 28, 28).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (64,)))
    pairs = 40 if on_tpu else 24

    def one_step():
        loss = lossf(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        watchdog.publish(step=0, phase="bench")
        return loss

    one_step(); one_step()  # warm the flush executable cache

    def timed_step():
        t0 = time.perf_counter()
        float(one_step().item())  # item() syncs: the step's guard fires
        return time.perf_counter() - t0

    @contextlib.contextmanager
    def _stubbed():
        orig_guard, orig_publish = watchdog.guard, watchdog.publish
        watchdog.guard = lambda what: contextlib.nullcontext()
        watchdog.publish = lambda *a, **k: None
        try:
            yield
        finally:
            watchdog.guard, watchdog.publish = orig_guard, orig_publish

    # the watchdog tax (~5us/step: one publish + a guard flag probe per
    # host sync) is far below the wall-clock drift of multi-second
    # segments, so the arms alternate at STEP granularity in alternating
    # order — adjacent ~100ms steps see the same CPU budget — and the
    # verdict is the median of per-pair ratios (robust to the occasional
    # descheduled step)
    ratios = []
    for i in range(pairs):
        if i % 2 == 0:
            t_live = timed_step()
            with _stubbed():
                t_stub = timed_step()
        else:
            with _stubbed():
                t_stub = timed_step()
            t_live = timed_step()
        ratios.append(t_live / t_stub)
    ratios.sort()
    overhead = ratios[len(ratios) // 2] - 1.0
    return {
        "name": f"watchdog disabled-path overhead (LeNet eager, {pairs} interleaved step pairs)",
        "overhead_pct": round(overhead * 100.0, 2),
    }


def bench_verify_overhead(paddle, jax, np, on_tpu):
    """Lazy-graph verifier tax on the LeNet train loop (ISSUE-9 acceptance:
    <2% with FLAGS_lazy_verify=1; ~0 when off). Two measurements, one
    verdict: (a) an interleaved per-step-pair A/B (median of ratios, the
    bench_watchdog_overhead discipline) — honest but carries this shared
    box's +-8% scheduler noise; (b) a same-run DIRECT attribution: the
    verifier entry point is wrapped with a timer while the flag-on loop
    runs, so verify time / step time is immune to drift between arms. The
    pinned number is (b); (a) corroborates on quiet boxes (TPU hosts)."""
    from paddle_tpu.framework import flags
    from paddle_tpu.analysis import verify_graph as _vg
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=model.parameters())
    lossf = paddle.nn.CrossEntropyLoss()
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(64, 1, 28, 28).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (64,)))
    pairs = 40 if on_tpu else 24

    def one_step():
        loss = lossf(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    prev = bool(flags.flag("FLAGS_lazy_verify", False))

    def timed_step(verify):
        flags.set_flags({"FLAGS_lazy_verify": verify})
        t0 = time.perf_counter()
        float(one_step().item())
        return time.perf_counter() - t0

    orig_verify = _vg.verify_before_dispatch
    acc = [0.0, 0]  # verify seconds, calls

    def timed_verify(*a, **k):
        t0 = time.perf_counter()
        try:
            return orig_verify(*a, **k)
        finally:
            acc[0] += time.perf_counter() - t0
            acc[1] += 1

    try:
        # warm the flush executable cache under BOTH flag values (inside the
        # try: a timeout/compile failure here must not leak the verifier flag
        # into every later benchmark); the verifier changes no signatures
        # (pinned by test_graph_verify parity), so both arms replay the same
        # executables
        flags.set_flags({"FLAGS_lazy_verify": False})
        one_step(); one_step()
        flags.set_flags({"FLAGS_lazy_verify": True})
        one_step(); one_step()

        # (a) interleaved per-step-pair A/B
        ratios = []
        for i in range(pairs):
            if i % 2 == 0:
                t_on = timed_step(True)
                t_off = timed_step(False)
            else:
                t_off = timed_step(False)
                t_on = timed_step(True)
            ratios.append(t_on / t_off)
        ratios.sort()
        ab_overhead = ratios[len(ratios) // 2] - 1.0

        # (b) direct attribution: verify time as a share of flag-on step time
        _vg.verify_before_dispatch = timed_verify
        flags.set_flags({"FLAGS_lazy_verify": True})
        t0 = time.perf_counter()
        n_steps = 16
        for _ in range(n_steps):
            float(one_step().item())
        total = time.perf_counter() - t0
    finally:
        _vg.verify_before_dispatch = orig_verify
        flags.set_flags({"FLAGS_lazy_verify": prev})
    direct = acc[0] / max(total - acc[0], 1e-9)
    return {
        "name": f"lazy-graph verifier overhead (LeNet eager, {pairs} step pairs + direct attribution)",
        "overhead_pct": round(direct * 100.0, 2),
        "ab_overhead_pct": round(ab_overhead * 100.0, 2),
        "verify_us_per_flush": round(acc[0] / max(acc[1], 1) * 1e6, 1),
        "verified_flushes": acc[1],
        "budget_pct": 2.0,
    }


def bench_stability_overhead(paddle, jax, np, on_tpu):
    """Stability-sentinel tax on the LeNet train loop (ISSUE-13 acceptance:
    enabled-path budget <2%, like bench_verify_overhead; the DISABLED path
    is one attribute probe per flush and one flag probe per fit, pinned ~0
    by the tier-1 inert tripwire). Enabled arm: a sentinel observes every
    step's fused signal pack (loss + grad norm + non-finite rate + update
    ratio, one 4-float readback per step riding the deferred drain) with
    thresholds set so nothing trips. Two measurements, one verdict — the
    bench_verify_overhead discipline: (a) interleaved per-step-pair A/B
    (median of ratios; honest but carries this shared box's scheduler
    noise), and (b) same-run DIRECT attribution — observe() wall time as a
    share of enabled-loop step time, immune to drift between arms. The
    pinned number is (b). Also populates the grad_global_norm / loss_ema
    fields of the main BENCH line."""
    from paddle_tpu.fault.sentinel import StabilitySentinel
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=model.parameters())
    lossf = paddle.nn.CrossEntropyLoss()
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(64, 1, 28, 28).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (64,)))
    params = [p for p in model.parameters() if not p.stop_gradient]
    pairs = 40 if on_tpu else 24
    sent = StabilitySentinel(
        window=256, warmup=10_000, zmax=1e9, max_skips=0, max_rollbacks=0
    )
    step_no = [0]
    acc = [0.0, 0]  # observe seconds, calls

    def one_step(observe):
        loss = lossf(model(x), y)
        loss.backward()
        if observe:
            step_no[0] += 1
            t0 = time.perf_counter()
            sent.observe(
                step_no[0], loss=loss,
                grads=[p.grad for p in params if p.grad is not None],
                params=params, lr=opt.get_lr(),
            )
            acc[0] += time.perf_counter() - t0
            acc[1] += 1
        opt.step()
        opt.clear_grad()
        return loss

    def timed_step(observe):
        t0 = time.perf_counter()
        float(one_step(observe).item())
        return time.perf_counter() - t0

    try:
        # warm both arms' flush executables (the signal pack is an extra
        # fused node, so the enabled arm has its own cache signature)
        one_step(False); one_step(False)
        one_step(True); one_step(True)

        # (a) interleaved per-step-pair A/B
        ratios = []
        for i in range(pairs):
            if i % 2 == 0:
                t_on = timed_step(True)
                t_off = timed_step(False)
            else:
                t_off = timed_step(False)
                t_on = timed_step(True)
            ratios.append(t_on / t_off)
        ratios.sort()
        ab_overhead = ratios[len(ratios) // 2] - 1.0

        # (b) direct attribution: observe() time / enabled-loop step time
        acc[0] = 0.0
        acc[1] = 0
        n_steps = 16
        t0 = time.perf_counter()
        for _ in range(n_steps):
            float(one_step(True).item())
        total = time.perf_counter() - t0
        sent.poll()
    finally:
        sent.close()
    direct = acc[0] / max(total - acc[0], 1e-9)
    return {
        "name": (
            f"stability-sentinel overhead (LeNet eager, {pairs} step pairs "
            "+ direct attribution)"
        ),
        "overhead_pct": round(direct * 100.0, 2),
        "ab_overhead_pct": round(ab_overhead * 100.0, 2),
        "observe_us_per_step": round(acc[0] / max(acc[1], 1) * 1e6, 1),
        "budget_pct": 2.0,
    }


def bench_observe_overhead(paddle, jax, np, on_tpu):
    """Serving-observability tax (ISSUE-20 acceptance: <2% per step): the
    same prompt wave through two warm engines — request tracing + SLO
    histograms armed vs flag-off — as interleaved alternating-order wave
    pairs, median of per-pair ratios (the bench_watchdog_overhead
    discipline; fixed-order A/B reads CPU drift as fake overhead). Ends
    with the structural-zero tripwire: every ``serving.observe`` hook is
    monkeypatched to raise and a flag-off engine must still serve a wave —
    the inert path is one ``is not None`` probe per hook site, never a
    call."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import Engine, observe

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                    num_heads=2, max_position_embeddings=256,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = GPTForPretraining(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size,
                           (int(rng.randint(4, 24)),)).tolist()
               for _ in range(16)]
    max_new = 8
    ekw = dict(block_size=16, num_blocks=256, max_batch=16, max_seq_len=128)
    observe.reset()

    def wave(eng, n=None):
        t0 = time.monotonic()
        hs = [eng.submit(p, max_new_tokens=max_new)
              for p in prompts[:n or len(prompts)]]
        [h.result(timeout=600) for h in hs]
        return time.monotonic() - t0

    pairs = 10 if on_tpu else 6
    with Engine(model, trace=False, metrics_port=0, **ekw) as off, \
            Engine(model, trace=True, metrics_port=0, **ekw) as on:
        wave(off)
        wave(on)  # warm both arms' bucket executables
        ratios = []
        for i in range(pairs):
            if i % 2 == 0:
                t_on, t_off = wave(on), wave(off)
            else:
                t_off, t_on = wave(off), wave(on)
            ratios.append(t_on / t_off)
    ratios.sort()
    overhead = ratios[len(ratios) // 2] - 1.0

    # structural-zero tripwire: a flag-off engine with every hook exploded
    # must still serve (a hook call would fail the wave, not just slow it)
    hooks = [n for n in dir(observe) if n.startswith("on_")]
    saved = {n: getattr(observe, n) for n in hooks}

    def _explode(*a, **k):
        raise AssertionError("observe hook reached from a flag-off engine")

    try:
        for n in hooks:
            setattr(observe, n, _explode)
        with Engine(model, trace=False, metrics_port=0, **ekw) as eng:
            wave(eng, n=4)
        inert_ok = True
    finally:
        for n, f in saved.items():
            setattr(observe, n, f)
    observe.reset()
    return {
        "name": (
            f"serving observability overhead ({len(prompts)} streams x "
            f"{pairs} interleaved wave pairs)"
        ),
        "overhead_pct": round(overhead * 100.0, 2),
        "inert_flag_off": inert_ok,
        "budget_pct": 2.0,
    }


def bench_memory_pressure(paddle, jax, np, on_tpu):
    """HBM-admission enforce-path tax on the LeNet eager loop (ISSUE-14
    acceptance: <2% enabled; the DISABLED path is one flag probe per flush,
    pinned by the tier-1 inert tripwire) plus a pressure drive that reports
    recovery-ladder engagements. Overhead protocol = bench_stability_overhead:
    (a) interleaved per-step-pair A/B (median of ratios), (b) same-run DIRECT
    attribution — preflight() wall time as a share of enabled-loop step time;
    (b) is the pinned number. The enabled arm runs FLAGS_hbm_admission=
    enforce against an effectively-unlimited budget, so every flush pays the
    real admission cost (census walk + compare) and nothing rejects."""
    from paddle_tpu.fault import inject, memory
    from paddle_tpu.framework import flags
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=model.parameters())
    lossf = paddle.nn.CrossEntropyLoss()
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(64, 1, 28, 28).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (64,)))
    pairs = 40 if on_tpu else 24

    def one_step():
        loss = lossf(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    prev = flags.get_flags(["FLAGS_hbm_admission", "FLAGS_hbm_budget_bytes"])

    def timed_step(enforce):
        flags.set_flags({"FLAGS_hbm_admission": "enforce" if enforce else "off"})
        t0 = time.perf_counter()
        float(one_step().item())
        return time.perf_counter() - t0

    orig_preflight = memory.preflight
    acc = [0.0, 0]  # preflight seconds, calls

    def timed_preflight(*a, **k):
        t0 = time.perf_counter()
        try:
            return orig_preflight(*a, **k)
        finally:
            acc[0] += time.perf_counter() - t0
            acc[1] += 1

    try:
        flags.set_flags({"FLAGS_hbm_budget_bytes": 1 << 60})
        # warm both arms (the enforce arm AOT-upgrades the cached entries)
        flags.set_flags({"FLAGS_hbm_admission": "off"})
        one_step(); one_step()
        flags.set_flags({"FLAGS_hbm_admission": "enforce"})
        one_step(); one_step()

        # (a) interleaved per-step-pair A/B
        ratios = []
        for i in range(pairs):
            if i % 2 == 0:
                t_on = timed_step(True)
                t_off = timed_step(False)
            else:
                t_off = timed_step(False)
                t_on = timed_step(True)
            ratios.append(t_on / t_off)
        ratios.sort()
        ab_overhead = ratios[len(ratios) // 2] - 1.0

        # (b) direct attribution: preflight time / enforce-loop step time
        memory.preflight = timed_preflight
        flags.set_flags({"FLAGS_hbm_admission": "enforce"})
        n_steps = 16
        t0 = time.perf_counter()
        for _ in range(n_steps):
            float(one_step().item())
        total = time.perf_counter() - t0

        # pressure drive: a transient injected RESOURCE_EXHAUSTED at the
        # flush dispatch engages the ladder (free pressure → retry)
        from paddle_tpu import profiler as _prof

        flags.set_flags({"FLAGS_hbm_admission": "off"})
        c0 = _prof.counters()
        rec0 = (c0.get("hbm_oom_trips", 0), c0.get("hbm_oom_recoveries", 0))
        inject.arm("hbm.oom:op=lazy_flush,at=2,times=1")
        w = paddle.to_tensor(np.ones((4, 4), np.float32))
        w.stop_gradient = False
        for i in range(3):
            drive_x = paddle.to_tensor(
                np.random.RandomState(i).randn(8, 4).astype(np.float32))
            dl = (paddle.matmul(drive_x, w) ** 2).mean()
            dl.backward()
            w._set_data((w - 0.1 * w.grad)._data)
            w.clear_grad()
            float(dl.item())
        inject.disarm()
        c = _prof.counters()
        trips = c.get("hbm_oom_trips", 0) - rec0[0]
        recov = c.get("hbm_oom_recoveries", 0) - rec0[1]
    finally:
        memory.preflight = orig_preflight
        inject.disarm()
        flags.set_flags(prev)
    direct = acc[0] / max(total - acc[0], 1e-9)
    pred = memory.last_prediction()
    return {
        "name": (
            f"hbm admission enforce overhead (LeNet eager, {pairs} step "
            "pairs + direct attribution) + pressure drive"
        ),
        "overhead_pct": round(direct * 100.0, 2),
        "ab_overhead_pct": round(ab_overhead * 100.0, 2),
        "preflight_us_per_flush": round(acc[0] / max(acc[1], 1) * 1e6, 1),
        "budget_pct": 2.0,
        "ladder_trips": trips,
        "ladder_recoveries": recov,
        "hbm_predicted_peak_bytes": pred.get("hbm_predicted_peak_bytes"),
    }


HOSTEMB_WORKER = """
import os, json, time
os.environ["JAX_PLATFORMS"] = os.environ.get("HE_PLATFORM", "cpu")
import numpy as np
from paddle_tpu.framework import flags
from paddle_tpu.incubate.host_embedding import sharded_host_embedding, ShardedHostEmbeddingTable

rank = int(os.environ["PADDLE_TRAINER_ID"])
V, D = int(os.environ["HE_V"]), int(os.environ["HE_D"])
per, steps = int(os.environ["HE_PER"]), int(os.environ["HE_STEPS"])
emb = sharded_host_embedding(V, D, seed=1)
table = emb.table
assert isinstance(table, ShardedHostEmbeddingTable)
rng = np.random.RandomState(7)  # same stream on every rank (sync PS)
batches = [np.unique((rng.zipf(1.2, per) % V).astype(np.int64)) for _ in range(steps + 1)]
# warmup exchange (row init + store/socket setup)
rows = table.gather(batches[-1])
table.apply_update(batches[-1], np.full((batches[-1].size, D), 0.01, np.float32), 0.1)
t0 = time.perf_counter()
n = 0
for ids in batches[:steps]:
    rows = table.gather(ids)
    table.apply_update(ids, rows * np.float32(0.001), lr=0.1)
    n += ids.size * 2  # one pull + one push per id
dt = time.perf_counter() - t0
from paddle_tpu import profiler
print(json.dumps({"rank": rank, "lookups_per_sec": n / dt,
                  "push_bytes": profiler.counters().get("host_emb_push_bytes", 0)}),
      flush=True)
"""


def _hostemb_sharded_lps(np, world, V, D, per, steps):
    """Spawn a world of sharded-table workers doing table-level pull/push
    rounds; returns rank-0's steady-state lookups/sec (None on any
    failure — the sharded bench is best-effort on CPU CI boxes)."""
    import socket
    import subprocess
    import sys

    try:
        from paddle_tpu.core.native import lib

        if lib() is None:
            return None
    except Exception:
        return None
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    repo = os.path.dirname(os.path.abspath(__file__))
    procs = []
    for rank in range(world):
        env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS",)}
        env.update({
            "PYTHONPATH": repo, "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_EMB_STORE_PORT": str(port),
            "HE_V": str(V), "HE_D": str(D), "HE_PER": str(per),
            "HE_STEPS": str(steps),
        })
        procs.append(subprocess.Popen([sys.executable, "-c", HOSTEMB_WORKER],
                                      env=env, stdout=subprocess.PIPE,
                                      stderr=subprocess.PIPE))
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=180)
            if p.returncode != 0:
                # kill the rest: surviving ranks are blocked forever in the
                # store collective and would outlive the bench
                for q in procs:
                    q.kill()
                return None
            outs.append(json.loads(out.decode().strip().splitlines()[-1]))
    except Exception:
        for p in procs:
            p.kill()
        return None
    r0 = next(o for o in outs if o["rank"] == 0)
    return {"lookups_per_sec": round(r0["lookups_per_sec"], 1),
            "push_bytes": r0["push_bytes"]}


def bench_host_embedding(paddle, jax, np, on_tpu):
    """Host-embedding PS hot path (ROADMAP item 4): interleaved A/B of the
    pre-PR path (pure-numpy fallback, synchronous per-microbatch pull +
    inline push — the kill-switched code IS the old code) against the
    rebuilt path (native gather/scatter, HBM hot-row cache, prefetched pull
    + async push). Metric: embedding lookups/sec through the PS hot path —
    lookups divided by the HOST-BLOCKING time the training loop pays for
    the embedding layer (`host_emb_block_ns`), which is what the LazyTensor
    overlap discipline (arXiv:2102.13267) says should approach zero: host
    table work belongs behind device execution. Wall-clock per step is
    reported alongside so the overlap claim is checkable (a path that
    merely shifted work off the counter would inflate wall time). Both
    sides run identical id streams on identically-seeded tables and must
    land BIT-IDENTICAL tables — the A/B is also a parity pin. Ends with
    2- and 4-process sharded pull/push rounds over the coalesced
    chunk-parallel store transport, and prints ONE `HOSTEMB_PERF` JSON
    line."""
    from paddle_tpu import profiler as _prof
    from paddle_tpu.framework import flags as _fl
    from paddle_tpu.incubate.host_embedding import HostEmbedding
    import paddle_tpu.nn as nn

    if on_tpu:
        rows, dim, mbs, per = 80_000_000, 64, 8, 8192
        rounds, steps = 3, 3
    else:
        rows, dim, mbs, per = 500_000, 32, 8, 8192
        rounds, steps = 3, 3
    lookups_per_step = mbs * per
    rng = np.random.RandomState(0)
    stream = [[(rng.zipf(1.2, per) % rows).astype(np.int64).reshape(64, -1)
               for _ in range(mbs)]
              for _ in range(rounds * (steps + 1) + 4)]

    OLD = {"FLAGS_host_emb_native": False, "FLAGS_host_emb_async_push": False}
    NEW = {"FLAGS_host_emb_native": True, "FLAGS_host_emb_async_push": True}
    prev = _fl.get_flags(list(OLD) + ["FLAGS_host_emb_cache_rows",
                                      "FLAGS_host_emb_cache_min_count"])
    d = tempfile.mkdtemp()
    sides = {}
    try:
        _fl.set_flags({"FLAGS_host_emb_cache_min_count": 2})
        for side in ("old", "new"):
            emb = HostEmbedding(
                rows, dim, path=os.path.join(d, f"{side}.npy"), seed=1,
                cache_rows=(4096 if side == "new" else 0))
            paddle.seed(0)
            head = nn.Linear(dim, 64)
            head2 = nn.Linear(64, 1)
            opt = paddle.optimizer.SGD(
                learning_rate=0.1,
                parameters=head.parameters() + head2.parameters())
            sides[side] = {"emb": emb, "head": head, "head2": head2,
                           "opt": opt, "block_ns": 0, "wall_ns": 0,
                           "flags": OLD if side == "old" else NEW}

        def one_step(side, step_idx):
            st = sides[side]
            emb, head, head2, opt = st["emb"], st["head"], st["head2"], st["opt"]
            new = side == "new"
            loss = None
            for m, ids in enumerate(stream[step_idx]):
                if new and m == 0:
                    # pipelined pull: the whole NEXT step's microbatches are
                    # known now — one union prefetch job staged in advance
                    emb.prefetch(stream[step_idx + 1])
                out = emb(paddle.to_tensor(ids))
                pooled = paddle.mean(out, axis=1)
                loss = paddle.mean(head2(paddle.tanh(head(pooled))) ** 2)
                loss.backward()
            # device work resolved BEFORE the push on BOTH sides, so the PS
            # accounting holds pure host table time, never device waits:
            # old applies inline after, new enqueues pure-host work that
            # overlaps the next step's tracing + device execution
            opt.step()
            opt.clear_grad()
            float(loss.item())
            emb.apply_gradients(lr=0.05)

        # warmup: compile the dense step, touch first rows, warm the cache
        for side in ("old", "new"):
            _fl.set_flags(sides[side]["flags"])
            one_step(side, 0)
            one_step(side, 1)
            sides[side]["emb"].sync()
        # parity probe: after the SAME two steps, both sides' tables must
        # match (native + pipeline are bit-exact vs pure numpy; the
        # dense-leaf hot cache adds summation-order rounding only — over
        # many steps a trained head amplifies those ulps chaotically, so
        # the pin is taken here, not at the end of the timed rounds)
        probe = np.unique(stream[0][0].ravel())[:2048]
        t_old = sides["old"]["emb"].table.gather(probe)
        t_new = sides["new"]["emb"].table.gather(probe)
        rel = float((np.abs(t_new - t_old) /
                     np.maximum(np.abs(t_old), 1e-6)).max())
        parity = rel < 1e-4
        step_idx = 2
        for _ in range(rounds):
            for side in ("old", "new"):
                st = sides[side]
                _fl.set_flags(st["flags"])
                # one untimed re-warm step after the side switch: the other
                # side's round trashed CPU caches (old recompiles every
                # step), which would otherwise bill its first timed step
                one_step(side, step_idx)
                b0 = _prof.counters().get("host_emb_block_ns", 0)
                t0 = time.perf_counter_ns()
                for s in range(1, steps + 1):
                    one_step(side, step_idx + s)
                st["emb"].sync()  # drain: trailing async work charged here
                st["wall_ns"] += time.perf_counter_ns() - t0
                st["block_ns"] += _prof.counters().get("host_emb_block_ns", 0) - b0
            step_idx += steps + 1
        cache_stats = sides["new"]["emb"].cache.stats()
    finally:
        _fl.set_flags(prev)
        shutil.rmtree(d, ignore_errors=True)

    # ---- r04-faithful A/B: the PRE-PR bench shape (ONE b256x64 uniform
    # batch per step over a memmap table). The old path pays its true
    # production pathologies here: the unique-count varies every step, so
    # the traced step graph RECOMPILES per step (the dominant term in the
    # recorded 1.9k lookups/sec), and the whole pull/push is synchronous
    # host work. The new path's HWM-padded shapes compile once and the
    # pull/push pipelines away.
    r04 = {}
    try:
        d2 = tempfile.mkdtemp()
        v2, dim2, b2, ids2 = ((80_000_000, 64, 256, 64) if on_tpu
                              else (8_000_000, 64, 256, 64))
        r04_steps, r04_warm = 4, 2
        rng2 = np.random.RandomState(1)
        batches2 = [rng2.randint(0, v2, (b2, ids2)).astype(np.int64)
                    for _ in range(r04_steps + r04_warm)]
        _fl.set_flags({"FLAGS_host_emb_cache_min_count": 2})
        for side in ("old", "new"):
            _fl.set_flags(OLD if side == "old" else NEW)
            emb = HostEmbedding(v2, dim2, path=os.path.join(d2, f"{side}.npy"),
                                seed=1, cache_rows=(4096 if side == "new" else 0))
            paddle.seed(0)
            head = nn.Linear(dim2, 256)
            head2 = nn.Linear(256, 1)
            new = side == "new"
            def step2(i):
                if new and i + 1 < len(batches2):
                    emb.prefetch(batches2[i + 1])
                out = emb(paddle.to_tensor(batches2[i]))
                loss = paddle.mean(
                    head2(paddle.tanh(head(paddle.mean(out, axis=1)))) ** 2)
                loss.backward()
                float(loss.item())
                emb.apply_gradients(lr=0.05)
            for i in range(r04_warm):
                step2(i)
            emb.sync()
            t0 = time.perf_counter_ns()
            for i in range(r04_warm, r04_warm + r04_steps):
                step2(i)
            emb.sync()
            dt = (time.perf_counter_ns() - t0) / 1e9
            r04[side] = b2 * ids2 * r04_steps / dt
            del emb
    except Exception as e:
        r04 = {"error": str(e)[:200]}
    finally:
        shutil.rmtree(d2, ignore_errors=True)
        _fl.set_flags(prev)

    total_steps = rounds * steps
    total_lookups = total_steps * lookups_per_step

    def lps(ns):
        return total_lookups / (ns / 1e9) if ns > 0 else None

    old_lps, new_lps = lps(sides["old"]["block_ns"]), lps(sides["new"]["block_ns"])
    from paddle_tpu.core import native as _native

    line = {
        "name": (f"Host-embedding PS hot path ({rows/1e6:.1f}M x {dim} table, "
                 f"{mbs}x{per} lookups/step, zipf ids)"),
        "lookups_per_sec": round(new_lps, 1) if new_lps else None,
        "lookups_per_sec_old": round(old_lps, 1) if old_lps else None,
        "ps_speedup_x": (round(new_lps / old_lps, 1)
                         if old_lps and new_lps else None),
        "ps_block_ms_per_step_old": round(
            sides["old"]["block_ns"] / total_steps / 1e6, 3),
        "ps_block_ms_per_step_new": round(
            sides["new"]["block_ns"] / total_steps / 1e6, 3),
        "wall_ms_per_step_old": round(
            sides["old"]["wall_ns"] / total_steps / 1e6, 1),
        "wall_ms_per_step_new": round(
            sides["new"]["wall_ns"] / total_steps / 1e6, 1),
        "wall_speedup_x": round(
            sides["old"]["wall_ns"] / max(sides["new"]["wall_ns"], 1), 2),
        "ab_parity_ok": parity,
        "ab_parity_max_rel_err": rel,
        # r04-faithful shape: lookups/sec through the FULL step, old vs new
        "r04_lookups_per_sec": (round(r04["new"], 1)
                                if "new" in r04 else None),
        "r04_lookups_per_sec_old": (round(r04["old"], 1)
                                    if "old" in r04 else None),
        "r04_speedup_x": (round(r04["new"] / r04["old"], 1)
                          if "new" in r04 and "old" in r04 else None),
        "hot_hit_rate": round(cache_stats["hit_rate"], 4),
        "native": bool(_native.lib() is not None and _native.HAS_EMBED),
        "push_bytes": _prof.counters().get("host_emb_push_bytes", 0),
        "procs": {},
    }
    # sharded pull/push rounds (table-level, coalesced chunk-parallel
    # transport) at 2 and 4 processes
    for world in (2, 4):
        r = _hostemb_sharded_lps(np, world, V=200_000, D=32, per=4096, steps=3)
        if r is not None:
            line["procs"][str(world)] = r
    print("HOSTEMB_PERF " + json.dumps(line))
    return line


def bench_serving(paddle, jax, np, on_tpu):
    """Serving-engine load generator (ROADMAP item 1): >= 64 concurrent
    autoregressive streams through the continuous-batching + paged-KV engine
    on a tiny GPT, submitted from client threads, then a SECOND timed window
    at 4x the measured sustainable load with deadlines + fast-fail shedding
    armed (round 12 resilience layer) — the engine must shed instead of
    stalling, keeping admitted-request p99 bounded. Ends with the
    high-prefix-overlap A/B (`_bench_serving_prefix_spec`) and the
    crash-recovery A/B (`_bench_serving_recovery`: re-prefill vs snapshot
    re-attach MTTR). Prints ONE `SERVE_PERF` JSON line (p50/p99 request
    latency, generated tokens/sec, mean decode batch occupancy, compile
    count, the overload window's shed-rate / deadline-miss-rate /
    p99-under-overload, the prefix/speculative hit- and acceptance-rates
    with speedup-vs-baseline, the recovery round's per-arm MTTR +
    re-prefilled-tokens vs re-attached-blocks, and the observability
    round's TTFT p50/p99, inter-token p99 and cost-model drift gauges)
    and returns the same dict for extra_metrics."""
    import threading

    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import Engine

    paddle.seed(0)
    if on_tpu:
        cfg = GPTConfig(vocab_size=8192, hidden_size=256, num_layers=4,
                        num_heads=4, max_position_embeddings=2048,
                        hidden_dropout=0.0, attention_dropout=0.0)
        streams, max_new, lo, hi = 256, 64, 16, 256
        ekw = dict(block_size=16, num_blocks=8192, max_batch=128,
                   max_seq_len=1024)
    else:
        cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                        num_heads=2, max_position_embeddings=256,
                        hidden_dropout=0.0, attention_dropout=0.0)
        streams, max_new, lo, hi = 64, 8, 4, 32
        ekw = dict(block_size=16, num_blocks=512, max_batch=64,
                   max_seq_len=128)
    model = GPTForPretraining(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (int(rng.randint(lo, hi)),)).tolist()
               for _ in range(streams)]

    with Engine(model, **ekw) as eng:
        # warm EVERY bucket executable the timed wave will touch (all prefill
        # length buckets + every decode width the drain passes through) with
        # an untimed wave of the same prompts, so the timed window measures
        # serving, not compilation — the "warm cache" the compile-count
        # promise is about
        warm = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        [h.result(timeout=600) for h in warm]
        handles = [None] * streams
        clients = 8
        client_errs = []

        def client(cid):
            try:
                for i in range(cid, streams, clients):
                    handles[i] = eng.submit(prompts[i], max_new_tokens=max_new)
            except Exception as e:  # surface the REAL failure, not a None handle
                client_errs.append(e)

        from paddle_tpu import profiler as _prof

        c0 = _prof.counters()
        t0 = time.monotonic()
        threads = [threading.Thread(target=client, args=(c,)) for c in range(clients)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        if client_errs:
            raise client_errs[0]
        outs = [h.result(timeout=600) for h in handles]
        wall = time.monotonic() - t0
        c1 = _prof.counters()
        lat = sorted(h.latency_s for h in handles)
        st = eng.stats()

    gen_tokens = sum(len(o) - len(p) for o, p in zip(outs, prompts))
    assert all(len(o) == len(p) + max_new for o, p in zip(outs, prompts))
    # occupancy over the TIMED window only (counter deltas) — the engine's
    # lifetime mean would dilute it with the warm wave's ramp/drain
    d_live = c1.get("serve_occupancy_live", 0) - c0.get("serve_occupancy_live", 0)
    d_slots = c1.get("serve_occupancy_slots", 0) - c0.get("serve_occupancy_slots", 0)
    p99_unloaded = lat[min(len(lat) - 1, int(len(lat) * 0.99))]
    line = {
        "name": f"serving load-gen (GPT h{cfg.hidden_size}xL{cfg.num_layers}, "
                f"{streams} streams, max_new {max_new})",
        "streams": streams,
        "tokens_per_sec": round(gen_tokens / wall, 1),
        "p50_latency_s": round(lat[len(lat) // 2], 3),
        "p99_latency_s": round(p99_unloaded, 3),
        "batch_occupancy_mean": round(d_live / max(d_slots, 1), 4),
        "compiles": st["compiles"],
        "wall_s": round(wall, 2),
    }
    line["overload"] = _bench_serving_overload(
        np, model, ekw, prompts, max_new, streams / wall, p99_unloaded)
    line["prefix_spec"] = _bench_serving_prefix_spec(
        np, model, cfg.vocab_size, ekw, on_tpu)
    line["recovery"] = _bench_serving_recovery(np, model, ekw, prompts,
                                               max_new)
    line["mesh"] = _bench_serving_mesh(
        np, model, ekw, prompts, max_new, on_tpu)
    line["chunked_prefill"] = _bench_serving_chunked_prefill(
        np, model, cfg.vocab_size, ekw, max_new, on_tpu)
    line["observe"] = _bench_serving_observe(
        np, paddle, model, ekw, prompts, max_new)
    print("SERVE_PERF " + json.dumps(line))
    return line


def _bench_serving_observe(np, paddle, model, ekw, prompts, max_new):
    """SLO observability round (ISSUE-20): re-drive a slice of the stream
    set on a TRACED engine and fold the token-latency SLO quantiles (TTFT
    p50/p99, inter-token p99 — the TPOT line) plus the three cost-model
    drift gauges into SERVE_PERF. ``step_eta`` (shed-ETA decode EMA +
    collective floor vs measured step time) accrues on the traced engine
    itself; ``hbm_admission`` needs the preflight armed while the engine
    steps, so one admission-checked lazy dispatch seeds the predictor
    first; ``kernel_estimate`` (cost-model candidate ordering vs measured
    timings) comes from a small measured search over a stubbed fused_ce
    runner with known per-config timings."""
    from paddle_tpu.framework import flags
    from paddle_tpu.ops.kernels import autotune, registry
    from paddle_tpu.serving import Engine, observe

    observe.reset()
    sub = prompts[: min(32, len(prompts))]
    old_adm = flags._FLAGS.get("FLAGS_hbm_admission")
    flags._FLAGS["FLAGS_hbm_admission"] = "warn"
    try:
        # seed the admission predictor — drift (b) compares it against the
        # post-step census inside the traced engine's scheduler loop
        t = paddle.to_tensor(np.ones((64, 64), np.float32))
        (t @ t).numpy()
        with Engine(model, trace=True, metrics_port=0, **ekw) as eng:
            hs = [eng.submit(p, max_new_tokens=max_new) for p in sub]
            [h.result(timeout=600) for h in hs]
    finally:
        if old_adm is None:
            flags._FLAGS.pop("FLAGS_hbm_admission", None)
        else:
            flags._FLAGS["FLAGS_hbm_admission"] = old_adm

    # drift (c): measured search on a stub runner registered under a name
    # the cost model knows (fused_ce), so candidate estimates differ and
    # the discordant-pair fraction is defined
    saved = registry._REGISTRY.get("fused_ce")
    old_samples = flags._FLAGS.get("FLAGS_kernel_tune_samples")
    flags._FLAGS["FLAGS_kernel_tune_samples"] = 1
    try:
        sleeps = {32: 0.004, 64: 0.0, 128: 0.008}

        def runner(key):
            def make(cfg):
                br = int(cfg["block_rows"])

                def step():
                    time.sleep(sleeps[br])
                    return np.zeros(4, np.float32)

                return step

            return make

        spec = registry.register_kernel(
            "fused_ce", defaults={"block_rows": 32},
            space={"block_rows": (32, 64, 128)}, runner=runner)
        autotune.search(spec, (256, 64, 512, "float32"))
    finally:
        if old_samples is None:
            flags._FLAGS.pop("FLAGS_kernel_tune_samples", None)
        else:
            flags._FLAGS["FLAGS_kernel_tune_samples"] = old_samples
        if saved is not None:
            registry._REGISTRY["fused_ce"] = saved
        else:
            registry._REGISTRY.pop("fused_ce", None)

    book = observe.trace_book()
    out = {
        "streams": len(sub),
        "ttft_p50_s": round(observe.percentile("serve_ttft_seconds", 0.5), 4),
        "ttft_p99_s": round(observe.percentile("serve_ttft_seconds", 0.99), 4),
        "inter_token_p99_s": round(
            observe.percentile("serve_inter_token_seconds", 0.99), 5),
        "timelines": len(book.completed()),
        "drift": {k: round(float(v.get("rel_err", 0.0)), 4)
                  for k, v in observe.drift_gauges().items()},
    }
    observe.reset()
    return out


def _bench_serving_mesh(np, model, ekw, prompts, max_new, on_tpu):
    """Tensor-parallel serving round (ISSUE-19): the same stream set at
    tp=1 vs tp=2 (and tp=4 when the box has the devices and the model the
    heads), reporting per-arm generated tokens/sec, the per-decode-step
    tensor-parallel collective bytes at fp32 vs blockwise-int8
    (EQuARX-style wire shrink), and whether the sharded arms stayed
    bit-identical (the concat-partitioned contract). On a real multi-chip
    backend the tp arms must hold >= 0.8x linear scaling; CPU "devices"
    are virtual slices of one socket, so there the scaling ratio is
    reported but not asserted."""
    import jax

    import paddle_tpu.models.generation as G
    from paddle_tpu.serving import Engine

    ndev = jax.device_count()
    if ndev < 2:
        return {"skipped": f"{ndev} visible device(s), tp needs >= 2"}
    arch_key, _, params, _ = G.gpt_decode_state(model)
    heads = arch_key[1]
    tps = [1, 2] + [4] * (ndev >= 4 and heads % 4 == 0)
    sub = prompts[: min(16, len(prompts))]
    arms, outs = {}, {}
    for tp in tps:
        kw = dict(ekw, tp=tp) if tp > 1 else dict(ekw)
        with Engine(model, **kw) as eng:
            warm = [eng.submit(p, max_new_tokens=max_new) for p in sub]
            [h.result(timeout=600) for h in warm]
            t0 = time.monotonic()
            hs = [eng.submit(p, max_new_tokens=max_new) for p in sub]
            res = [h.result(timeout=600) for h in hs]
            wall = time.monotonic() - t0
        gen = sum(len(o) - len(p) for o, p in zip(res, sub))
        arms[tp] = round(gen / max(wall, 1e-9), 1)
        outs[tp] = res
    fp32_b, int8_b = G.tp_collective_bytes(arch_key, params, ekw["max_batch"], 2)
    scaling = {str(tp): round(arms[tp] / max(tp * arms[1], 1e-9), 3)
               for tp in tps if tp > 1}
    if on_tpu:
        for tp, ratio in scaling.items():
            assert ratio >= 0.8, \
                f"tp={tp} scaling {ratio} below the 0.8x-linear floor"
    return {
        "devices": ndev,
        "tokens_per_sec": {str(tp): arms[tp] for tp in tps},
        "linear_scaling": scaling,
        "scaling_asserted": bool(on_tpu),
        "identical_tokens": all(outs[tp] == outs[1] for tp in tps[1:]),
        "collective_bytes_per_step_fp32": fp32_b,
        "collective_bytes_per_step_int8": int8_b,
        "int8_wire_shrink": round(fp32_b / max(int8_b, 1), 3),
    }


def _bench_serving_chunked_prefill(np, model, vocab, ekw, max_new, on_tpu):
    """Chunked-prefill A/B (ISSUE-19): short streams decode while long
    prompts are admitted mid-flight; the victims' decode-stall p99 (the
    worst inter-token gap — a monolithic prefill freezes every live stream
    for the whole pass) must come down when the same admits run one
    FLAGS_serve_prefill_chunk-sized chunk per scheduler step."""
    import threading

    from paddle_tpu.serving import Engine

    rng = np.random.RandomState(5)
    n_vic, long_len = (8, 768) if on_tpu else (4, 96)
    chunk = ekw["block_size"] * 2
    victims = [rng.randint(0, vocab, (6,)).tolist() for _ in range(n_vic)]
    longs = [rng.randint(0, vocab, (long_len,)).tolist() for _ in range(2)]

    # victims need enough decode steps to still be live while the longs
    # prefill (the whole point of the stall probe) even when the outer
    # bench runs a tiny max_new on the CPU tier
    vic_new = max(max_new, 12)

    def arm(chunked):
        kw = dict(ekw, prefill_chunk=chunk) if chunked else dict(ekw)
        gaps = []
        with Engine(model, **kw) as eng:
            warm = [eng.submit(p, max_new_tokens=max_new)
                    for p in victims + longs]
            [h.result(timeout=600) for h in warm]
            hs = [eng.submit(v, max_new_tokens=vic_new, temperature=0.0,
                             stream=True)
                  for v in victims]
            rows = [[] for _ in hs]

            def consume(h, out):
                last = time.monotonic()
                for _tok in h:
                    now = time.monotonic()
                    out.append(now - last)
                    last = now

            threads = [threading.Thread(target=consume, args=(h, rows[i]))
                       for i, h in enumerate(hs)]
            [t.start() for t in threads]
            deadline = time.monotonic() + 60
            while eng.stats()["decode_steps"] < 1 \
                    and time.monotonic() < deadline:
                time.sleep(0.002)
            lh = [eng.submit(p, max_new_tokens=2) for p in longs]
            [t.join() for t in threads]
            [h.result(timeout=600) for h in lh]
        # drop each victim's first gap (TTFT, includes its own prefill) —
        # the stall metric is the DECODE inter-token gap
        for r in rows:
            gaps.extend(r[1:])
        gaps.sort()
        return {
            "decode_stall_p99_s": round(
                gaps[min(len(gaps) - 1, int(len(gaps) * 0.99))], 4),
            "decode_stall_max_s": round(gaps[-1], 4),
        }

    mono = arm(False)
    chunked = arm(True)
    return {
        "victims": n_vic,
        "long_prompt_tokens": long_len,
        "chunk_tokens": chunk,
        "monolithic": mono,
        "chunked": chunked,
        "stall_p99_reduced": chunked["decode_stall_p99_s"]
        < mono["decode_stall_p99_s"],
    }


def bench_kernel_autotune(paddle, jax, np, on_tpu):
    """Kernel-registry autotune A/B (ISSUE-18): a real measured-timing
    search over the flash-attention config space against a throwaway tuning
    DB, then steady-state timing of the tuned config vs the pinned default,
    a gather-vs-kernel paged-decode step A/B, and the DB hit/miss/search
    accounting. Prints ONE `KERNEL_PERF` JSON line and returns the same
    dict for extra_metrics. The run's tune dir is a temp dir — the
    benchmark never pollutes (or benefits from) the user's cache."""
    import shutil
    import tempfile

    import jax.numpy as jnp

    import paddle_tpu.models.generation as G
    from paddle_tpu import profiler as _prof
    from paddle_tpu.framework import flags
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.ops import kernels as K
    from paddle_tpu.ops.kernels import autotune as _autotune
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_array

    if on_tpu:
        b, h, t, d, dtype = 1, 8, 8192, 128, jnp.bfloat16
        samples, budget_s = 5, 120.0
    else:
        # interpret-mode Pallas is slow: small shape, few samples
        b, h, t, d, dtype = 1, 2, 256, 32, jnp.float32
        samples, budget_s = 2, 10.0

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, t, d), dtype)
    kk = jnp.asarray(rng.randn(b, h, t, d), dtype)
    v = jnp.asarray(rng.randn(b, h, t, d), dtype)

    def time_fn(fn, *args):
        jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(samples):
            t0 = time.monotonic()
            jax.block_until_ready(fn(*args))
            ts.append(time.monotonic() - t0)
        ts.sort()
        return ts[len(ts) // 2] * 1e3

    def flash_with(cfg):
        return jax.jit(lambda a, b_, c: flash_attention_array(
            a, b_, c, causal=True, block_q=int(cfg["block_q"]),
            block_k=int(cfg["block_k"])))

    key = K.flash_attention_key(b, h, t, t, d, q.dtype, True)
    default_cfg = dict(K.get_kernel("flash_attention").defaults)

    tune_td = tempfile.mkdtemp(prefix="bench_tune_")
    knobs = {"FLAGS_kernel_autotune": "search",
             "FLAGS_kernel_tune_dir": tune_td,
             "FLAGS_kernel_tune_samples": samples,
             "FLAGS_kernel_tune_budget_s": budget_s}
    old = {k_: flags._FLAGS.get(k_) for k_ in knobs}
    try:
        flags._FLAGS.update(knobs)
        _autotune.clear_cache()
        c0 = _prof.counters()
        t0 = time.monotonic()
        tuned_cfg = K.resolve_config("flash_attention", key)
        search_s = time.monotonic() - t0
        # rerun with a cold memo: must be a pure disk hit, zero re-search
        _autotune.clear_cache()
        K.resolve_config("flash_attention", key)
        c1 = _prof.counters()
    finally:
        for k_, v_ in old.items():
            if v_ is None:
                flags._FLAGS.pop(k_, None)
            else:
                flags._FLAGS[k_] = v_
        shutil.rmtree(tune_td, ignore_errors=True)
        _autotune.clear_cache()

    default_ms = time_fn(flash_with(default_cfg), q, kk, v)
    tuned_ms = time_fn(flash_with(tuned_cfg), q, kk, v)

    # paged decode: gather builder vs Pallas-kernel builder, one step
    paddle.seed(0)
    if on_tpu:
        # head width 128: Mosaic takes the paged kernel at multiples of it
        gcfg = GPTConfig(vocab_size=8192, hidden_size=1024, num_layers=4,
                         num_heads=8, max_position_embeddings=2048,
                         hidden_dropout=0.0, attention_dropout=0.0)
        B, BS, MB, NB = 64, 16, 16, 2048
    else:
        gcfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                         num_heads=2, max_position_embeddings=256,
                         hidden_dropout=0.0, attention_dropout=0.0)
        B, BS, MB, NB = 8, 8, 4, 64
    model = GPTForPretraining(gcfg)
    model.eval()
    _, arch, params, _ = G.gpt_decode_state(model)
    L, KV, D = len(params["layers"]), arch["kv_heads"], arch["head_dim"]
    kpool = jnp.zeros((L, NB, BS, KV, D), jnp.float32)
    vpool = jnp.zeros((L, NB, BS, KV, D), jnp.float32)
    perm = rng.permutation(np.arange(1, NB))[: B * MB]
    tables = jnp.asarray(perm.reshape(B, MB).astype(np.int32))
    pos = jnp.asarray(rng.randint(0, BS * MB, (B,)).astype(np.int32))
    toks = jnp.asarray(rng.randint(0, gcfg.vocab_size, (B,)).astype(np.int32))
    temps = jnp.zeros((B,), jnp.float32)
    pkey = jax.random.PRNGKey(0)
    gather_fn = jax.jit(G.build_paged_decode(arch, B, BS, MB))
    kernel_fn = jax.jit(G.build_paged_decode_kernel(arch, B, BS, MB))
    args = (params, kpool, vpool, tables, pos, toks, temps, pkey)
    gather_ms = time_fn(gather_fn, *args)
    kernel_ms = time_fn(kernel_fn, *args)

    def delta(name):
        return c1.get(name, 0) - c0.get(name, 0)

    line = {
        "name": "kernel autotune A/B",
        "flash": {
            "shape": f"b{b} h{h} t{t} d{d} {np.dtype(dtype).name} causal",
            "default_config": default_cfg, "tuned_config": tuned_cfg,
            "default_ms": round(default_ms, 3),
            "tuned_ms": round(tuned_ms, 3),
            "speedup": round(default_ms / max(tuned_ms, 1e-9), 3),
        },
        "paged_decode": {
            "shape": f"B{B} L{L} h{gcfg.hidden_size} blocks{MB}x{BS}",
            "gather_ms": round(gather_ms, 3),
            "kernel_ms": round(kernel_ms, 3),
            "speedup": round(gather_ms / max(kernel_ms, 1e-9), 3),
        },
        "db": {"search_s": round(search_s, 2),
               "searches": delta("kernel_tune_searches"),
               "candidates": delta("kernel_tune_candidates"),
               "hits": delta("kernel_tune_hits"),
               "misses": delta("kernel_tune_misses"),
               "rejects": delta("kernel_tune_db_rejects"),
               "budget_stops": delta("kernel_tune_budget_stops")},
    }
    print("KERNEL_PERF " + json.dumps(line))
    return line


def bench_paged_kernel(paddle, jax, np, on_tpu):
    """The paged decode-attention kernel ALONE, at the shape of the
    benchmark's serving cell on the chip (GPT-3 XL: 24 layers chained as the
    decode step chains them, 64 rows, a table 128 wide, 16 x 16 x 128 bf16
    blocks in a 3,679-block pool): milliseconds for the 24 reads and the
    share of the HBM peak that the LIVE K/V bytes over that time make, for
    three fillings (live block counts spread 1-96 a row as the chat mix
    spreads them; every row at 56 blocks; 16 live rows beside 48 dead ones)
    and every ``blocks_per_chunk`` of the registry's space; plus the largest
    difference from the gather read of the same bf16 inputs. ``python
    bench.py paged_kernel`` runs it alone and prints its line. On the CPU a
    tiny float32 shape under the interpreter, and no times."""
    import jax.numpy as jnp

    import paddle_tpu.models.generation as G
    from paddle_tpu.cost_model import device_peaks
    from paddle_tpu.ops import kernels as K

    if on_tpu:
        L, NB, BS, KV, D, H, B, MB = 24, 3679, 16, 16, 128, 16, 64, 128
        dtype, longest, samples = jnp.bfloat16, 96, 5
    else:
        L, NB, BS, KV, D, H, B, MB = 2, 64, 8, 2, 16, 2, 4, 8
        dtype, longest, samples = jnp.float32, 6, 1
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    kpool = jax.random.normal(ks[0], (L, NB, BS, KV, D), dtype)
    vpool = jax.random.normal(ks[1], (L, NB, BS, KV, D), dtype)
    q = jax.random.normal(ks[2], (B, H, D), dtype)
    rng = np.random.RandomState(3)
    ragged = np.exp(rng.normal(np.log(longest / 5), 0.8, B))
    ragged = (ragged * (0.4 * (NB - 1)) / ragged.sum()).astype(int).clip(
        1, longest)
    fillings = {
        "ragged": ragged,
        "every_row_full": np.full(B, longest * 7 // 12),
        "quarter_live": np.r_[ragged[:B // 4], np.zeros(B - B // 4, int)],
    }

    def tables_for(lens):
        free = rng.permutation(np.arange(1, NB))
        tables = np.zeros((B, MB), np.int32)  # dead columns: the trash block
        pos = np.zeros((B,), np.int32)
        at = 0
        for b, n in enumerate(lens):
            if n:
                tables[b, :n] = free[at:at + n]
                at += n
                pos[b] = n * BS - 1 - rng.randint(0, BS)
        return jnp.asarray(tables), jnp.asarray(pos)

    def layers(config):
        def run(q, kpool, vpool, tables, pos):
            x, acc = q, jnp.zeros((B, H * D), jnp.float32)
            for li in range(L):
                o = K.paged_attention_rows(x, kpool, vpool, li, tables, pos,
                                           config=config)
                acc = acc + o.astype(jnp.float32)
                x = (q + o.reshape(B, H, D) * 0.001).astype(dtype)
            return acc

        return jax.jit(run)

    def gather_read(q, kpool, vpool, tables, pos):
        T = MB * BS
        kc = kpool[L - 1, tables].reshape(B, T, KV, D)
        vc = vpool[L - 1, tables].reshape(B, T, KV, D)
        live = jnp.arange(T)[None, :] <= pos[:, None]
        return G._grouped_attention(
            q[:, None], kc, vc, live[:, None, None, None, :],
            H // KV).reshape(B, H * D)

    def best_ms(fn, *args):
        jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(samples):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        return min(ts) * 1e3

    tables, pos = tables_for(fillings["ragged"])
    ref = jax.jit(gather_read)(q, kpool, vpool, tables, pos)
    got = jax.jit(lambda q, k, v, t, p: K.paged_attention_rows(
        q, k, v, L - 1, t, p))(q, kpool, vpool, tables, pos)
    ref, got = (np.asarray(a, np.float32) for a in (ref, got))
    peak = float(device_peaks()["hbm_bw"])
    block_bytes = 2 * BS * KV * D * jnp.dtype(dtype).itemsize  # K and V
    chunks = K.get_kernel("paged_attention").space["blocks_per_chunk"]
    line = {
        "name": "paged_attention kernel alone",
        "shape": f"L{L} B{B} MB{MB} block {BS}x{KV}x{D} "
                 f"{jnp.dtype(dtype).name} pool {NB}",
        "max_abs_diff_vs_gather": float(np.abs(ref - got).max()),
        "max_abs_gather": float(np.abs(ref).max()),
        "fillings": {},
    }
    filled = {name: (tables_for(lens), int(np.maximum(lens, 1).sum()))
              for name, lens in fillings.items()}  # a dead row reads a block
    for name, (_, blocks) in filled.items():
        line["fillings"][name] = {"live_blocks": blocks,
                                  "blocks_per_chunk": {}}
    for c in chunks:
        read = layers({"blocks_per_chunk": c})  # compiled once a chunk size
        for name, ((tables, pos), blocks) in filled.items():
            ms = best_ms(read, q, kpool, vpool, tables, pos)
            line["fillings"][name]["blocks_per_chunk"][str(c)] = {
                "ms": round(ms, 3),
                "hbm_peak_pct": round(
                    100 * blocks * block_bytes * L / (ms * 1e-3) / peak, 1),
            } if on_tpu else "not measured"
    return line


def _bench_serving_recovery(np, model, ekw, prompts, max_new):
    """Crash-recovery A/B (ISSUE-17): the same injected mid-decode crash
    recovered two ways — the PR 12 re-prefill/requeue path vs snapshot
    re-attach (``snapshot=True``). Reports, per arm, the supervisor's
    detect→recover MTTR, the crash→fully-drained wall (the serving-level
    MTTR: when the service has actually caught up), and how many tokens
    were re-prefilled vs how many KV blocks re-attached. The acceptance
    bar: re-attach re-prefills ZERO tokens and drains faster than
    re-prefill (``mttr_speedup_x`` > 1)."""
    from paddle_tpu import profiler as _prof
    from paddle_tpu.fault import inject
    from paddle_tpu.serving import ServingSupervisor

    n = min(16, len(prompts))
    ps = prompts[:n]
    out = {"streams": n, "max_new": max_new}
    try:
        for name, snap in (("reprefill", False), ("reattach", True)):
            c0 = _prof.counters()
            inject.arm("serve.crash:at=6")
            with ServingSupervisor(model, watchdog_s=5.0, snapshot=snap,
                                   **ekw) as sup:
                hs = [sup.submit(p, max_new_tokens=max_new) for p in ps]
                deadline = time.monotonic() + 120
                while not inject.fired_counts().get("serve.crash") \
                        and time.monotonic() < deadline:
                    time.sleep(0.002)
                t0 = time.monotonic()
                [h.result(timeout=600) for h in hs]
                drain = time.monotonic() - t0
                assert sup.restarts == 1
                mode = sup.health()["last_recovery"]["mode"]
            inject.disarm()
            c1 = _prof.counters()

            def d(k):
                return c1.get(k, 0) - c0.get(k, 0)

            out[name] = {
                "mode": mode,
                "supervisor_mttr_ms": d("serve_restart_mttr_ms"),
                "crash_to_drained_s": round(drain, 3),
                "reprefill_tokens": d("serve_reprefill_tokens"),
                "reattached_blocks": d("serve_reattached_blocks"),
                "reprefill_tokens_saved": d("serve_reprefill_tokens_saved"),
            }
    finally:
        inject.disarm()
    out["mttr_speedup_x"] = round(
        out["reprefill"]["crash_to_drained_s"]
        / max(out["reattach"]["crash_to_drained_s"], 1e-9), 3)
    return out


def _bench_serving_prefix_spec(np, model, vocab, ekw, on_tpu):
    """High-prefix-overlap workload mode (ROADMAP item 2): every stream
    shares one long system prompt and differs only in a short user tail —
    the agent/chat serving shape. Three arms over identical prompt sets on
    warm executables: OFF (the PR 11 path), prefix cache ON (tail-only
    prefill against shared KV blocks), and prefix+speculative ON. Reports
    `prefix_hit_rate`, `draft_acceptance_rate`, and `speedup_vs_baseline`
    (cache-on tokens/sec over cache-off) — the ISSUE-16 acceptance bar is
    >= 2x on this workload."""
    from paddle_tpu import profiler as _prof
    from paddle_tpu.serving import Engine

    if on_tpu:
        streams, shared_len, tail_lo, tail_hi, max_new = 128, 768, 8, 48, 32
        spec_k = 4
    else:
        # the shared prefix is most of max_seq_len (the agent-loop shape:
        # a big system prompt + a short user turn). Concurrency and pool
        # are kept SMALL: CPU XLA pays one whole-pool copy-on-write per
        # paged-decode/tail-prefill call (the gather forces the scatter
        # chain off the in-place path — a harness artifact, not a TPU
        # cost), so the pool is sized to just hold max_batch full prompts
        # plus the cache, keeping that artifact out of the A/B's signal
        streams, shared_len, tail_lo, tail_hi, max_new = 64, 224, 4, 12, 2
        spec_k = 2
        ekw = dict(ekw, max_seq_len=256, num_blocks=160, max_batch=8)
    rng = np.random.RandomState(1)
    shared = rng.randint(0, vocab, (shared_len,)).tolist()

    def wave():
        return [shared + rng.randint(0, vocab,
                                     (int(rng.randint(tail_lo, tail_hi)),)).tolist()
                for _ in range(streams)]

    warm_prompts, warm2_prompts, timed_prompts = wave(), wave(), wave()
    arms = {
        "off": {},
        "cache": {"prefix_cache": True},
        "cache+spec": {"prefix_cache": True, "spec_k": spec_k},
    }
    out = {"streams": streams, "shared_prefix_len": shared_len,
           "max_new": max_new, "spec_k": spec_k}
    tps = {}
    for name, extra in arms.items():
        with Engine(model, **dict(ekw, **extra)) as eng:
            # two untimed warm waves: the first compiles the full-length
            # buckets and (when armed) populates the prefix index with the
            # shared system prompt — its streams all MISS an empty cache —
            # and the second exercises the hit path so every tail-prefill
            # bucket the timed wave will touch is already compiled
            for wp in (warm_prompts, warm2_prompts):
                [h.result(timeout=600) for h in
                 [eng.submit(p, max_new_tokens=max_new) for p in wp]]
            c0 = _prof.counters()
            t0 = time.monotonic()
            hs = [eng.submit(p, max_new_tokens=max_new) for p in timed_prompts]
            outs = [h.result(timeout=600) for h in hs]
            wall = time.monotonic() - t0
            c1 = _prof.counters()
            eng._pool.check()
        assert all(len(o) == len(p) + max_new
                   for o, p in zip(outs, timed_prompts))
        gen = sum(max_new for _ in outs)
        tps[name] = gen / wall
        d = {k: c1.get(k, 0) - c0.get(k, 0) for k in (
            "serve_prefix_hits", "serve_prefix_misses",
            "serve_draft_proposed", "serve_draft_accepted")}
        if name == "cache":
            hits, misses = d["serve_prefix_hits"], d["serve_prefix_misses"]
            out["prefix_hit_rate"] = round(hits / max(hits + misses, 1), 4)
        if name == "cache+spec":
            out["draft_acceptance_rate"] = round(
                d["serve_draft_accepted"] / max(d["serve_draft_proposed"], 1), 4)
    # acceptance probe: the timed wave's short generations barely decode, so
    # the steady-state acceptance rate comes from a longer greedy pass (the
    # n-gram drafter feeds on the stream's own repetition, which needs tokens)
    with Engine(model, **dict(ekw, prefix_cache=True, spec_k=spec_k)) as eng:
        c0 = _prof.counters()
        [h.result(timeout=600) for h in
         [eng.submit(p, max_new_tokens=8 * max_new)
          for p in timed_prompts[:streams // 4]]]
        c1 = _prof.counters()
    prop = c1.get("serve_draft_proposed", 0) - c0.get("serve_draft_proposed", 0)
    acc = c1.get("serve_draft_accepted", 0) - c0.get("serve_draft_accepted", 0)
    out["draft_acceptance_rate_long"] = round(acc / max(prop, 1), 4)
    out["tokens_per_sec_off"] = round(tps["off"], 1)
    out["tokens_per_sec_cached"] = round(tps["cache"], 1)
    out["tokens_per_sec_cached_spec"] = round(tps["cache+spec"], 1)
    out["speedup_vs_baseline"] = round(tps["cache"] / tps["off"], 3)
    out["speedup_spec_vs_baseline"] = round(tps["cache+spec"] / tps["off"], 3)
    return out


def _bench_serving_overload(np, model, ekw, prompts, max_new,
                            sustainable_rps, p99_unloaded):
    """Overload window: offer requests open-loop at 4x the closed-loop
    sustainable rate into an engine with load shedding + per-request
    deadlines armed. The acceptance bar: the engine sheds (`Overloaded` at
    submit) and early-fails doomed work (`DeadlineExceeded`) instead of
    letting queue latency grow without bound — p99 of ADMITTED requests
    stays within ~2x the unloaded p99, and the page pool conserves."""
    from paddle_tpu.serving import DeadlineExceeded, Engine, Overloaded

    offered_rps = 4.0 * sustainable_rps
    deadline_s = max(0.25, 2.0 * p99_unloaded)
    window_s = 8.0
    ekw = dict(ekw, shed=True, max_queue=max(8, ekw["max_batch"] // 2))
    shed = missed = failed = 0
    lats = []
    with Engine(model, **ekw) as eng:
        # warm every bucket untimed so the window measures scheduling; the
        # warm wave honors the engine's own shed policy by backing off on
        # the retry_after_s hint (the polite-client contract)
        warm = []
        for p in prompts[:ekw["max_batch"]]:
            while True:
                try:
                    warm.append(eng.submit(p, max_new_tokens=max_new))
                    break
                except Overloaded as e:
                    time.sleep(max(e.retry_after_s, 0.01))
        [h.result(timeout=600) for h in warm]
        handles = []
        t0 = time.monotonic()
        i = 0
        while True:
            due = t0 + i / offered_rps
            now = time.monotonic()
            if due > t0 + window_s:
                break
            if due > now:
                time.sleep(due - now)
            try:
                handles.append(eng.submit(prompts[i % len(prompts)],
                                          max_new_tokens=max_new,
                                          deadline_s=deadline_s))
            except Overloaded:
                shed += 1
            i += 1
        for h in handles:
            try:
                h.result(timeout=600)
                lats.append(h.latency_s)
            except DeadlineExceeded:
                missed += 1
            except Exception:
                failed += 1
        eng._pool.check()  # conservation held through the whole storm
    offered = i
    lats.sort()
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))] if lats else None
    return {
        "offered_rps": round(offered_rps, 2),
        "offered": offered,
        "window_s": window_s,
        "deadline_s": round(deadline_s, 3),
        "shed_rate": round(shed / max(offered, 1), 4),
        "deadline_miss_rate": round(missed / max(offered - shed, 1), 4),
        "failed": failed,
        "completed": len(lats),
        "p99_latency_s": None if p99 is None else round(p99, 3),
        "p99_vs_unloaded": None if p99 is None
        else round(p99 / max(p99_unloaded, 1e-9), 3),
    }


def main():
    t_start = time.time()
    import numpy as np
    import jax

    import paddle_tpu as paddle

    on_tpu = any(d.platform != "cpu" for d in jax.devices())

    def remaining():
        return _BUDGET_S - (time.time() - t_start)

    try:
        # the primary metric gets the lion's share, but must leave enough
        # slack for the JSON line to print before the driver's hard kill —
        # and never arm past the remaining budget even with slow startup
        with _alarm(min(remaining(), max(30.0, remaining() - 30.0))):
            gpt = bench_gpt(paddle, jax, np, on_tpu)
    except (_BenchTimeout, Exception) as e:
        gpt = {
            "name": "GPT bf16 train", "tokens_per_sec": None,
            "loss": None, "mfu": None, "error": str(e)[:200] or type(e).__name__,
        }
    extras = []
    for fn in (bench_resnet50_aot, bench_resnet50_int8, bench_lenet_eager,
               bench_profiler_overhead, bench_watchdog_overhead,
               bench_verify_overhead, bench_stability_overhead,
               bench_observe_overhead, bench_memory_pressure,
               bench_gpt_1p3b, bench_gpt_8k_flash,
               bench_vit_l_aot, bench_yolov3_aot, bench_llama_1b,
               bench_dp8_gpt, bench_serving, bench_host_embedding,
               bench_kernel_autotune, bench_paged_kernel):
        if remaining() < 30.0:
            extras.append({"name": fn.__name__, "skipped": "budget"})
            continue
        try:
            with _alarm(remaining() - 15.0):
                extras.append(fn(paddle, jax, np, on_tpu))
        except (_BenchTimeout, Exception) as e:  # a broken extra must not kill the primary line
            extras.append({"name": fn.__name__, "error": str(e)[:200] or type(e).__name__})

    tokens_per_sec = gpt["tokens_per_sec"]
    baseline_path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench_baseline.json")
    # None (not 1.0) when the primary metric died: a driver gating on
    # vs_baseline must not read a dead run as at-parity with the best
    vs_baseline = 1.0 if tokens_per_sec is not None else None
    try:
        platform = jax.devices()[0].platform
        best = None
        if os.path.exists(baseline_path):
            base = json.load(open(baseline_path))
            if base.get("value") and base.get("platform") == platform:
                best = float(base["value"])
                if tokens_per_sec is not None:
                    vs_baseline = tokens_per_sec / best
        if on_tpu and tokens_per_sec is not None and (best is None or tokens_per_sec > best):
            # ratchet: the recorded best only ever goes up, so a future
            # regression is always visible as vs_baseline < 1.0
            json.dump(
                {"value": tokens_per_sec, "unit": "tokens/sec/chip", "platform": platform},
                open(baseline_path, "w"),
            )
    except Exception:
        pass

    # telemetry snapshot: the run's engine counters + a fresh live-buffer
    # census, so every BENCH_*.json is self-describing about cache hits,
    # donation, sync bytes and memory high-water mark
    from paddle_tpu import profiler

    try:
        profiler.memory_census()
        counters = profiler.counters()
        memory = profiler.memory_stats()
    except Exception:
        counters, memory = {}, {}

    # dispatch-gap (ROADMAP item 2): host idle per device step — the primary
    # fused-step loop's measured block time, falling back to the lazy
    # (LeNet) loop's when the primary died
    gap = gpt.get("dispatch_gap_ms_per_step")
    if gap is None:
        gap = next(
            (e.get("dispatch_gap_ms_per_step") for e in extras
             if e.get("dispatch_gap_ms_per_step") is not None),
            None,
        )

    # training-stability telemetry (ISSUE-13): the last judged sentinel
    # signals (populated by bench_stability_overhead's observed loop; None
    # when no sentinel ran) plus the skip/rollback counters — every BENCH
    # line reports whether the run quarantined or rolled back anything
    try:
        from paddle_tpu.fault import sentinel as _sentinel

        _stab = _sentinel.last_signals()
    except Exception:
        _stab = {}

    # HBM resilience telemetry (ISSUE-14): the most recent preflight
    # prediction (populated by bench_memory_pressure's enforce loop; None
    # when admission never ran) plus the ladder/admission counters — every
    # BENCH line reports whether the run predicted, rejected, or recovered
    try:
        from paddle_tpu.fault import memory as _hbm_mem

        _hbm = _hbm_mem.last_prediction()
    except Exception:
        _hbm = {}

    print(
        json.dumps(
            {
                "metric": gpt["name"] + " throughput",
                "value": tokens_per_sec,
                "unit": "tokens/sec/chip",
                "vs_baseline": round(vs_baseline, 3) if vs_baseline is not None else None,
                "loss": gpt["loss"],
                "mfu": gpt["mfu"],
                "dispatch_gap_ms_per_step": gap,
                "grad_global_norm": _stab.get("grad_norm"),
                "loss_ema": _stab.get("loss_ema"),
                "stability_skips": counters.get("stability_skips", 0),
                "stability_rollbacks": counters.get("stability_rollbacks", 0),
                "hbm_predicted_peak_bytes": _hbm.get("hbm_predicted_peak_bytes"),
                "hbm_oom_recoveries": counters.get("hbm_oom_recoveries", 0),
                "hbm_admission_rejects": counters.get("hbm_admission_rejects", 0),
                # host-embedding PS telemetry (ISSUE-15): hot-cache hit rate
                # + cross-rank push bytes from the run's counters
                "host_emb_hot_hit_rate": round(
                    counters.get("host_emb_hot_hits", 0)
                    / max(counters.get("host_emb_hot_hits", 0)
                          + counters.get("host_emb_hot_misses", 0), 1), 4),
                "host_emb_push_bytes": counters.get("host_emb_push_bytes", 0),
                # kernel-autotune telemetry (ISSUE-18): DB hit/miss counts
                # for the run — nonzero only when FLAGS_kernel_autotune ran
                "kernel_tune_hits": counters.get("kernel_tune_hits", 0),
                "kernel_tune_misses": counters.get("kernel_tune_misses", 0),
                "platform": jax.devices()[0].platform,
                "wall_s": round(time.time() - t_start, 1),
                **({"error": gpt["error"]} if gpt.get("error") else {}),
                "counters": counters,
                "memory": memory,
                "extra_metrics": extras,
            }
        )
    )


def only(names):
    """``python bench.py <name> ...``: the named ``bench_<name>`` functions
    alone, each printing its own line."""
    import numpy as np
    import jax

    import paddle_tpu as paddle

    on_tpu = any(d.platform != "cpu" for d in jax.devices())
    for name in names:
        print(json.dumps(globals()[f"bench_{name}"](paddle, jax, np, on_tpu)),
              flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 1:
        only(sys.argv[1:])
    else:
        main()
