"""chip_smoke.py — does the system still start on the chip?

One process drives the two hot loops once, through the entry points a user
calls, at the full width AND depth of GPT-3 1.3B (``models.gpt.gpt3_1p3b``)
with seeded random weights:

* device line — refuses to run without a TPU; prints what it runs on;
* train phase — ``paddle.jit.compile_train_step`` at b2 x s2048, AdamW, bf16:
  loss finite and falling, the Pallas flash kernel really in the step;
* serve phase — the same weights through ``paddle_tpu.serving.Engine`` at
  default flags with the KV pool filling what the weights leave: two client
  threads, mixed prompt lengths, one stream; checked against
  ``model.generate``; the decode program it compiled must hold the paged
  attention kernel, one call a layer (no hidden fallback to the gather);
  then a model of GPT-2's head width (64, which Mosaic does not take for that
  kernel) must be given the gather step by the engine, and serve;
* four-chip phase (when the host has four chips) — the same model under
  ``fleet`` dp2 x mp2 through ``HybridParallelEngine`` and under
  ``Engine(tp=4)``.

No phase is wrapped in try/except: any failure is a traceback and a non-zero
exit status, and the result line is not printed. Times printed here are
observations of one run, for orientation; they are not benchmark metrics.

    python chip_smoke.py          # on a machine with a TPU; last stdout line:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
import concurrent.futures
import gc
import importlib.metadata
import json
import sys
import time

import numpy as np

SEED = 20260926
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 2048, 4  # the compile call + 3 steps
N_REQUESTS, N_CLIENTS, NEW_TOKENS = 8, 2, 32
PROMPT_LEN = (64, 512)
# forward, dq and dk/dv kernels per attention layer; every layer must have
# them or some layer ran XLA's exact attention unseen
FLASH_CALLS_PER_LAYER = 3
# HBM left free beside weights and KV pool for the programs' own temporaries.
# The decode step reads K/V through the block-table kernel (tens of MB of
# temporaries, printed by the serve phase); the widest prefill program, the
# reference generate() and allocator fragmentation take the rest. The
# benchmark's serving cell sizes its pool with the same headroom.
SERVE_HEADROOM_BYTES = 5 * 2**29  # 2.5 GiB
# Greedy decoding through two correct bf16 programs may part ways at a
# near-tie: logits are bf16 (8 significant bits) and each path rounds the
# residual stream at every layer in a different order (dense cache vs paged
# gather, bucket-padded vs exact-length matmuls, one chip vs four). Where two
# token streams first differ, both candidates must lie within this many bf16
# units-in-the-last-place of the best logit at that position; a wrong block
# table, position or weight shard moves logits by O(1), hundreds of ulps.
NEAR_TIE_ULPS = 8
BF16_EPS = 2.0 ** -8
# first-step loss, four chips vs one, same weights and batch: the row-parallel
# matmuls reduce bf16 partial sums across 'mp' in another order than one chip
# does, a relative error of a few bf16 eps on a loss of ~11
HYBRID_LOSS_RTOL = 1e-2


def log(msg):
    print(msg, flush=True)


# -- device line --------------------------------------------------------------
def device_line():
    import jax
    import jaxlib

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
                 f"({dev.device_kind}); refusing to run")
    import paddle_tpu  # noqa: F401  (sets the compile-cache directory)
    from paddle_tpu.core import native

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {json.dumps(device)}")
    log(f"versions: jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {importlib.metadata.version('libtpu')} "
        f"({' '.join(dev.client.platform_version.split())})")
    log(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    lib = native.lib()
    log(f"native runtime: loaded={lib is not None} "
        f"HAS_SPANS={native.HAS_SPANS} HAS_EMBED={native.HAS_EMBED}")
    # every consumer of the native runtime falls back to pure Python without
    # a word when the library is missing; here that is a failure
    assert lib is not None and native.HAS_SPANS and native.HAS_EMBED, \
        "runtime_cpp/libpaddle_tpu_runtime.so did not build or is stale"
    return device


def _hbm(what):
    """Log every device's memory; return the first device's stats."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    for d, s in zip(jax.devices(), stats):
        log(f"  hbm after {what}, {d}: in_use "
            f"{s['bytes_in_use'] / 2**30:.2f} GiB, peak "
            f"{s['peak_bytes_in_use'] / 2**30:.2f} GiB, limit "
            f"{s['bytes_limit'] / 2**30:.2f} GiB")
    return stats[0]


def _backend_compiles():
    """Count XLA backend compilations (jit-cache misses that reached the
    compiler, persistent-cache hits included) through jax's own monitoring
    event. Returns the live counter dict."""
    import jax

    seen = {"n": 0, "secs": 0.0}

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["n"] += 1
            seen["secs"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return seen


def kernel_calls(compiled):
    """Mosaic kernel calls in the optimized HLO of a compiled program.
    (Counted after compilation: the StableHLO shares one function between
    identical layers.)"""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def count_flash_calls(lowered):
    """Kernel calls of a lowered step. With the compile cache on, this
    second compile is a cache hit."""
    return kernel_calls(lowered.compile())


def decode_program_report(eng, n_layers):
    """The widest decode program the engine holds, compiled again from its
    shapes (a cache hit where the compile cache is on). Where the engine
    chose the block-table kernel it must read K/V through it, one call a
    layer, never through a fallback to the gather; where it chose the gather
    (a head width Mosaic does not take) it holds no kernel call. Logs the
    program's temporaries."""
    import jax

    key = max(k for k in eng._fns if k[0] == "decode")
    _, rows, width = key

    def shape_of(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)

    # the step's packed operand, the previous step's tokens and the base
    # key go in uncommitted, as the engine passes them
    from paddle_tpu.models.generation import STEP_COLS

    small = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in (
        ((rows, width + STEP_COLS), np.int32),
        (eng._no_prev.shape, eng._no_prev.dtype),
        (eng._key.shape, eng._key.dtype))]
    compiled = eng._fns[key].lower(
        jax.tree_util.tree_map(shape_of, eng._compute_params),
        *map(shape_of, eng._cache), *small).compile()
    calls = kernel_calls(compiled)
    temps = compiled.memory_analysis().temp_size_in_bytes
    log(f"  decode program {key}: {calls} kernel call(s) for {n_layers} "
        f"layers, temporaries {temps / 2**20:.1f} MiB beside a pool of "
        f"{eng._cache[0].shape[1]} blocks")
    if eng._paged_kernel:
        assert width == eng._max_blocks, "the decode table is not full width"
        assert calls == n_layers, \
            "the decode program does not read K/V through the paged kernel"
    else:
        assert calls == 0, "a kernel call in the gather step"


# -- train phase --------------------------------------------------------------
def make_model(cfg):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForPretraining

    paddle.seed(SEED)
    model = GPTForPretraining(cfg)
    model.bfloat16()
    return model


def make_batch(cfg, batch, seq):
    """One seeded batch of next-token pairs, repeated every step."""
    ids = np.random.default_rng(SEED).integers(0, cfg.vocab_size, (batch, seq + 1))
    return ids[:, :-1], ids[:, 1:]


def train_phase(cfg, compiles):
    import paddle_tpu as paddle

    log(f"train: GPT {cfg.num_layers}L x {cfg.hidden_size}, "
        f"b{TRAIN_BATCH} x s{TRAIN_SEQ}, AdamW, bf16, compile_train_step")
    t0 = time.monotonic()
    model = make_model(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = paddle.jit.compile_train_step(
        model, lambda m, ids, labels: m.loss(ids, labels), opt)
    x, y = (paddle.to_tensor(a) for a in make_batch(cfg, TRAIN_BATCH, TRAIN_SEQ))
    log(f"  model built in {time.monotonic() - t0:.1f} s (observation)")

    c0, t0 = dict(compiles), time.monotonic()
    losses = [float(step(x, y).item())]
    first_s = time.monotonic() - t0
    t0 = time.monotonic()
    for _ in range(TRAIN_STEPS - 1):
        losses.append(float(step(x, y).item()))
    step_ms = (time.monotonic() - t0) / (TRAIN_STEPS - 1) * 1e3
    log(f"  losses: {[round(v, 4) for v in losses]}")
    log(f"  first call (compile + step) {first_s:.1f} s, of which backend "
        f"compile {compiles['secs'] - c0['secs']:.1f} s in "
        f"{compiles['n'] - c0['n']} program(s); then {step_ms:.0f} ms/step "
        "with a host read of the loss each step (observations)")
    assert all(np.isfinite(losses)), "non-finite training loss"
    assert losses[-1] < losses[0], "loss did not fall on a repeated batch"

    n_flash = count_flash_calls(step.lower(x, y))
    log(f"  flash kernels in the compiled step: {n_flash} "
        f"(expected {FLASH_CALLS_PER_LAYER} x {cfg.num_layers} layers)")
    assert n_flash == FLASH_CALLS_PER_LAYER * cfg.num_layers, \
        "the compiled step does not hold the flash kernel in every layer"
    _hbm("train")
    return model, losses


# -- serve phase --------------------------------------------------------------
def make_prompts(cfg):
    rng = np.random.default_rng(SEED + 1)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, N_REQUESTS)
    lens[:2] = PROMPT_LEN  # both ends of the range are always present
    return [rng.integers(0, cfg.vocab_size, int(n)).tolist() for n in lens]


def pool_blocks_for(model, hbm_stats):
    """KV blocks that fill what the weights leave, less the headroom."""
    from paddle_tpu.framework import flags

    cfg = model.config
    block_size = flags.flag("FLAGS_serve_block_size")  # the engine's default
    block_bytes = 2 * cfg.num_layers * block_size * cfg.hidden_size * 2  # K+V, bf16
    free = hbm_stats["bytes_limit"] - hbm_stats["bytes_in_use"]
    return int((free - SERVE_HEADROOM_BYTES) // block_bytes)


def run_wave(eng, prompts):
    """All prompts from N_CLIENTS threads at once; request 0 is streamed.
    Returns the token lists in prompt order."""

    def client(mine):
        handles = [(i, eng.submit(prompts[i], max_new_tokens=NEW_TOKENS,
                                  temperature=0.0, stream=(i == 0)))
                   for i in mine]
        got = {}
        for i, h in handles:
            streamed = list(h) if i == 0 else None
            got[i] = h.result(timeout=900)
            if streamed is not None:
                assert streamed == got[i][len(prompts[i]):], \
                    "streamed tokens differ from the final result"
        return got

    with concurrent.futures.ThreadPoolExecutor(N_CLIENTS) as pool:
        futures = [pool.submit(client, range(c, len(prompts), N_CLIENTS))
                   for c in range(N_CLIENTS)]
        got = {}
        for f in futures:
            got.update(f.result(timeout=1000))
    outs = [got[i] for i in range(len(prompts))]
    for p, o in zip(prompts, outs):
        assert o[:len(p)] == p and len(o) == len(p) + NEW_TOKENS, \
            "result is not prompt + exactly the requested new tokens"
    return outs


def assert_same_or_near_tie(arbiter, a, b, what):
    """Token lists ``a`` and ``b`` continue the same prompt greedily through
    two programs. Equal is a pass. Otherwise, at the first position where
    they differ, both candidates must be within NEAR_TIE_ULPS bf16 ulps of
    the best logit the (idle) ``arbiter`` engine's prefill program computes
    for their common prefix."""
    if a == b:
        log(f"  {what}: identical tokens")
        return
    i = next(k for k, (u, v) in enumerate(zip(a, b)) if u != v)
    logits = arbiter._debug_prefill_logits(a[:i]).astype(np.float32)
    top = float(logits.max())
    tol = NEAR_TIE_ULPS * BF16_EPS * max(1.0, abs(top))
    gaps = (top - float(logits[a[i]]), top - float(logits[b[i]]))
    log(f"  {what}: tokens part at position {i} ({a[i]} vs {b[i]}); best "
        f"logit {top:.4f}, candidates trail it by {gaps[0]:.4f} and "
        f"{gaps[1]:.4f}, tolerance {tol:.4f} ({NEAR_TIE_ULPS} bf16 ulps)")
    assert max(gaps) <= tol, f"{what}: outputs differ beyond a bf16 near-tie"


def serve_phase(model, prompts, num_blocks, compiles):
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.serving import Engine

    model.eval()
    log(f"serve: serving.Engine, default flags, num_blocks={num_blocks}; "
        f"{len(prompts)} requests from {N_CLIENTS} threads, prompt lengths "
        f"{[len(p) for p in prompts]}, {NEW_TOKENS} new tokens, greedy")
    with Engine(model, num_blocks=num_blocks) as eng:
        assert eng._paged_kernel, \
            "head width 128 on a TPU: the engine must choose the paged kernel"
        t0 = time.monotonic()
        outs = run_wave(eng, prompts)
        wave1_s = time.monotonic() - t0
        stats = eng.stats()
        touched = {eng._bucket_for(len(p)) for p in prompts}
        widest = next(b for b in eng.config.decode_buckets
                      if b >= len(prompts))
        decode_buckets = [b for b in eng.config.decode_buckets if b <= widest]
        log(f"  wave 1: {wave1_s:.1f} s with compiles (observation); "
            f"{stats['compiles']} programs for prefill buckets "
            f"{sorted(touched)} and decode buckets within {decode_buckets}; "
            f"{stats['decode_steps']} decode steps, mean occupancy "
            f"{stats['batch_occupancy_mean']}")
        assert stats["compiles"] <= len(touched) + len(decode_buckets), \
            "more compiled programs than buckets touched"

        # a second identical wave: whatever it compiles must be a bucket the
        # first never reached (client threads race the scheduler, so which
        # decode widths a wave touches can differ) — never a shape it has
        c0 = dict(compiles)
        built0 = profiler.counters().get("serve_compiles", 0)
        t0 = time.monotonic()
        outs2 = run_wave(eng, prompts)
        wave2_s = time.monotonic() - t0
        new_buckets = profiler.counters().get("serve_compiles", 0) - built0
        recompiles = compiles["n"] - c0["n"]
        log(f"  wave 2: {wave2_s:.1f} s warm (observation); {recompiles} "
            f"backend compile(s), {new_buckets} new bucket(s)")
        assert recompiles <= new_buckets, \
            "a warm wave recompiled a program it already had"
        for i, (a, b) in enumerate(zip(outs2, outs)):
            # batch composition differs between waves, so the same request
            # may run in another decode bucket
            assert_same_or_near_tie(eng, a, b, f"wave 2 vs wave 1, request {i}")

        stats = eng.stats()
        assert stats["pages_used"] == 0 and stats["running"] == 0, stats
        eng._pool.check()
        decode_program_report(eng, model.config.num_layers)

        for i in (0, 1):  # the shortest and the longest prompt
            ref = model.generate(paddle.to_tensor(np.asarray([prompts[i]])),
                                 max_new_tokens=NEW_TOKENS, do_sample=False)
            ref = [int(t) for t in np.asarray(ref.numpy())[0]]
            assert_same_or_near_tie(
                eng, outs[i], ref,
                f"engine vs generate(), prompt of {len(prompts[i])}")
        _hbm("serve")
    return outs


def narrow_serve_phase():
    """GPT-2's widths (768 / 12 heads: head width 64) at 2 layers. Mosaic
    takes the paged kernel at multiples of 128 only, so the engine must hand
    this model the gather step, and serve it."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving import Engine

    cfg = GPTConfig(num_layers=2, hidden_dropout=0.0, attention_dropout=0.0)
    model = make_model(cfg)
    model.eval()
    rng = np.random.default_rng(SEED + 2)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (24, 200)]
    head = cfg.hidden_size // cfg.num_heads
    log(f"serve, head width {head}: serving.Engine, default flags")
    with Engine(model, num_blocks=256) as eng:
        assert not eng._paged_kernel, \
            f"the engine chose the paged kernel at head width {head}"
        handles = [eng.submit(p, max_new_tokens=NEW_TOKENS, temperature=0.0)
                   for p in prompts]
        outs = [h.result(timeout=900) for h in handles]
        decode_program_report(eng, cfg.num_layers)
        for p, o in zip(prompts, outs):
            ref = model.generate(paddle.to_tensor(np.asarray([p])),
                                 max_new_tokens=NEW_TOKENS, do_sample=False)
            assert_same_or_near_tie(
                eng, o, [int(t) for t in np.asarray(ref.numpy())[0]],
                f"engine vs generate(), prompt of {len(p)}")


# -- four-chip phase ----------------------------------------------------------
def tp_serve_phase(model, prompts, num_blocks, one_chip_outs):
    from paddle_tpu.serving import Engine

    log(f"four chips: serving.Engine(tp=4), num_blocks={num_blocks}")
    with Engine(model, tp=4, num_blocks=num_blocks) as eng:
        assert all(len(pool.sharding.device_set) == 4
                   for pool in eng._cache), eng._cache[0].sharding
        outs = run_wave(eng, prompts)
        stats = eng.stats()
        assert stats["pages_used"] == 0, stats
        eng._pool.check()
        log(f"  KV pool sharded {eng._cache[0].sharding.spec} over "
            f"{len(eng._cache[0].sharding.device_set)} devices; "
            f"{stats['compiles']} programs")
        decode_program_report(eng, model.config.num_layers)
        for i, (a, b) in enumerate(zip(outs, one_chip_outs)):
            assert_same_or_near_tie(
                eng, a, b, f"tp=4 vs one chip, request {i}")
        _hbm("tp=4 serve")


def hybrid_train_phase(cfg, one_chip_losses):
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.engine import HybridParallelEngine

    batch = 2 * TRAIN_BATCH
    log(f"four chips: fleet dp2 x mp2, HybridParallelEngine, "
        f"b{batch} x s{TRAIN_SEQ}")
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1,
                               "sharding_degree": 1, "sp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = fleet.get_hybrid_communicate_group().mesh
    assert mesh is not None and mesh.size == 4, mesh

    model = make_model(cfg)  # the one-chip run's seed: the same first weights
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    engine = HybridParallelEngine(
        model, opt, lambda m, ids, labels: m.loss(ids, labels), mesh=mesh)
    # each dp replica gets exactly the one-chip batch, so the mean loss and
    # the gradient are the one-chip ones
    x, y = (paddle.to_tensor(np.concatenate([a, a]))
            for a in make_batch(cfg, TRAIN_BATCH, TRAIN_SEQ))
    t0 = time.monotonic()
    losses = [float(engine.train_step(x, y).item()) for _ in range(3)]
    log(f"  losses: {[round(v, 4) for v in losses]} in "
        f"{time.monotonic() - t0:.1f} s with compile (observation); one chip "
        f"had {[round(v, 4) for v in one_chip_losses[:3]]}")
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    rel = abs(losses[0] - one_chip_losses[0]) / abs(one_chip_losses[0])
    log(f"  first-step loss differs from one chip by {rel:.2e} relative "
        f"(tolerance {HYBRID_LOSS_RTOL:.0e})")
    assert rel <= HYBRID_LOSS_RTOL, "hybrid loss is not the one-chip loss"

    spans = [len(p._data.sharding.device_set) for p in model.parameters()]
    assert set(spans) == {4}, f"parameters not on four devices: {set(spans)}"
    qkv = model.gpt.layers[0].attn.qkv.weight._data.sharding
    log(f"  {len(spans)} parameters span 4 devices; qkv weight {qkv.spec}")
    n_flash = count_flash_calls(engine.lower(x, y))
    log(f"  flash kernels in the compiled hybrid step: {n_flash}")
    assert n_flash == FLASH_CALLS_PER_LAYER * cfg.num_layers, n_flash
    _hbm("hybrid train")


# -- main ----------------------------------------------------------------------
def main():
    device = device_line()
    from paddle_tpu import profiler
    from paddle_tpu.models.gpt import gpt3_1p3b

    compiles = _backend_compiles()
    cfg = gpt3_1p3b(hidden_dropout=0.0, attention_dropout=0.0)
    model, losses = train_phase(cfg, compiles)
    gc.collect()  # the train phase's optimizer state and step: unreachable now
    prompts = make_prompts(cfg)
    num_blocks = pool_blocks_for(model, _hbm("releasing the optimizer"))
    outs = serve_phase(model, prompts, num_blocks, compiles)
    gc.collect()  # ... and the engine with its pool
    _hbm("closing the engine")
    narrow_serve_phase()
    gc.collect()

    if device["count"] >= 4:
        tp_serve_phase(model, prompts, num_blocks, outs)
        del model
        gc.collect()
        hybrid_train_phase(cfg, losses)
    else:
        log(f"four chips: skipped: {device['count']} device(s)")

    counters = profiler.counters()
    hidden = {k: counters.get(k, 0) for k in (
        "lazy_donation_fallbacks", "lazy_bg_aot_fallbacks",
        "lazy_eager_replay_fallbacks")}
    log(f"fallback counters: {hidden}")
    assert not any(hidden.values()), "a flush fell back to a slower path"
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
