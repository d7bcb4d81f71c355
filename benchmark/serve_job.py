"""A serving cell: ``serving.Engine`` behind an open-loop generator, at
default flags but for what the traffic file's ``engine`` entry states of the
deployment (context, rows, prefill batch). Set-up checks that the mix fits
the engine's context, builds the model from the seed, sizes the cache pool to
what the weights leave, warms exactly the prefill and decode shapes the
traffic file reaches, and ramps; the window then counts tokens and requests at the
client's side of the stream; afterwards arrivals stop, in-flight requests
drain, the engine is freed and the plain reference is run over a seeded
sample of what was served."""
from __future__ import annotations

import gc
import inspect
import time

import numpy as np

from . import generator as G, weights as W


def pool_blocks(family, cfg, block_size, headroom_bytes):
    """Cache blocks that fill what the weights leave, less the headroom for
    the programs' own temporaries (the sizing of
    ``chip_smoke.pool_blocks_for``); what a cached token costs is the
    family's count."""
    import jax

    stats = jax.devices()[0].memory_stats()
    block_bytes = family.cache_bytes_per_context_token(cfg) * block_size
    free = stats["bytes_limit"] - stats["bytes_in_use"]
    return int((free - headroom_bytes) // block_bytes)


def prefill_buckets(lengths, block_size):
    """The engine's prefill buckets (block_size x powers of two) that these
    prompt lengths fall into."""
    out = set()
    for n in set(int(x) for x in lengths):
        b = block_size
        while b < n:
            b *= 2
        out.add(b)
    return sorted(out)


def engine_config(traffic, cfg):
    """The ``serving.EngineConfig`` this cell's engine resolves to, but for
    the pool: the flags' defaults under the traffic file's ``engine`` entry,
    whose keys are keyword arguments of that class (the harness keeps no list
    of its own), the context held to the configuration's positions. Raises
    what the entry may not hold, by key."""
    from paddle_tpu.serving import EngineConfig

    entry = traffic.get("engine", {})
    takes = set(inspect.signature(EngineConfig.__init__).parameters) - {"self"}
    for key in entry:
        if key in ("num_blocks", "block_size"):
            raise ValueError(
                f"engine: {key!r} is the harness's own: it sizes the pool to what the "
                "weights leave less headroom_bytes, and the prefill buckets by the block")
        if key not in takes:
            raise ValueError(f"engine: {key!r} is no keyword of serving.EngineConfig "
                             f"(it takes {sorted(takes)})")
    return EngineConfig(**entry).resolve(cfg.get("max_position_embeddings", 2 ** 62))


def warm_rows(traffic, resolved):
    """Rows the warm-up's staircase walks down from: the engine's."""
    return int(traffic.get("warm_rows", resolved.max_batch))  # the rehearsal walks fewer


def warm_tail(longest_prompt, longest_ctx, rows):
    """Tokens the warm-up's long request asks for: it holds the mix's longest
    context while ``rows`` short ones retire under it."""
    return max(longest_ctx - longest_prompt, 1) + 2 * rows + 8


def longest_of(schedule):
    """(longest prompt, longest context) of a schedule, as
    ``generator.longest`` reads them from the file."""
    return (int(schedule.prompt_len.max()),
            int((schedule.prompt_len + schedule.out_len).max()))


def fit(traffic, cfg, longest_prompt, longest_ctx):
    """The resolved engine sizes of ``engine_config``, once the mix is known
    to fit them: before anything is built. The warm-up's long request, not the
    mix's longest context alone, is what the engine's context has to hold."""
    resolved = engine_config(traffic, cfg)
    need = longest_prompt + warm_tail(longest_prompt, longest_ctx,
                                      warm_rows(traffic, resolved))
    if need > resolved.max_seq_len:
        raise ValueError(
            f"the mix does not fit the engine's context: its longest context of "
            f"{longest_ctx} tokens and the warm-up's tail make a request of {need}, "
            f"over max_seq_len {resolved.max_seq_len} (the least of the traffic file's "
            "engine.max_seq_len, else FLAGS_serve_max_seq_len, and the "
            "configuration's max_position_embeddings)")
    return resolved


def warm(eng, schedule, traffic, block_size, vocab, log):
    """Reach every shape the traffic reaches, through ``submit`` alone.

    Prefill: one request per bucket the mix's prompt lengths fall into.
    Decode: the engine keeps ONE program per batch-width bucket, gathered
    over the widest context that bucket has ever held (it only grows). A
    server that has run for a while holds the widest in every bucket, so the
    warm-up puts it there: one request as long as the mix's longest context
    stays live while a staircase of short ones retires one by one, which
    walks the live-row count from the engine's ``max_batch`` down through
    every bucket."""
    rng = np.random.default_rng(0xC0FFEE)
    mk = lambda n: rng.integers(0, vocab, int(n), dtype=np.int32)
    buckets = prefill_buckets(schedule.prompt_len, block_size)
    for b in buckets:
        n = max(int(x) for x in schedule.prompt_len if x <= b)
        eng.submit(mk(n), max_new_tokens=1).result(timeout=1200)
    log(f"warm: prefill buckets {buckets}")
    rows = warm_rows(traffic, eng.config)
    longest_prompt, longest_ctx = longest_of(schedule)
    tail = warm_tail(longest_prompt, longest_ctx, rows)
    long_h = eng.submit(mk(longest_prompt), max_new_tokens=tail, stream=True)
    it = iter(long_h)
    next(it)
    short = [eng.submit(mk(block_size), max_new_tokens=2 + 2 * i)
             for i in range(1, rows)]
    for h in short:
        h.result(timeout=1200)
    list(it)
    log(f"warm: decode rows 1..{rows} with a context of {longest_ctx} live; "
        f"{eng.stats()['compiles']} programs")


def padded_len(n, pad_to=256):
    """The length the reference is run at for ``n`` positions: a multiple of
    ``pad_to`` up to 2,048 and of 1,024 beyond, so that a check of contexts up
    to 8,192 compiles six float32 programs past 2,048 and not twenty-four."""
    step = pad_to if n <= 2048 else max(pad_to, 1024)
    return -(-n // step) * step


def served_gap(family, cfg, weights, prompt, tokens, mode="f32", pad_to=256):
    """Run the family's reference once over prompt + served tokens. Returns, for each
    served token, how far its reference logit lies below the reference's
    best at that position; with ``mode`` set to the control's precision the
    token judged at each position is the one the control puts first."""
    import jax.numpy as jnp

    ids = np.concatenate([np.asarray(prompt, np.int64), np.asarray(tokens, np.int64)])
    n = len(ids) - 1  # the last served token is never fed back
    padded = padded_len(n, pad_to)
    x = np.zeros((1, padded), np.int64)
    x[0, :n] = ids[:n]
    ref = family.forward_logits(cfg, weights, x, "f32")[0, len(prompt) - 1:n]
    best = jnp.max(ref, axis=-1)
    if mode == "f32":
        judged = jnp.asarray(ids[len(prompt):])
    else:
        ctl = family.forward_logits(cfg, weights, x, mode)[0, len(prompt) - 1:n]
        judged = jnp.argmax(ctl, axis=-1)
    picked = jnp.take_along_axis(ref, judged[:, None], -1)[:, 0]
    return np.asarray(best - picked)


def sample_finished(served, schedule, seed, k):
    """A seeded sample of the window's finished requests, the one with the
    most served tokens always in it."""
    done = [r for r in served if schedule.in_window[r.index] and r.done
            and not r.error and len(r.tokens) == schedule.out_len[r.index]]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0x5A11])
    pick = rng.permutation(len(rest))[:max(k - 1, 0)]
    return [longest] + [rest[i] for i in pick]


def settling(gaps_ms, gap_at, seconds):
    """The median gap over the window's first quarter, half, three quarters
    and whole: how it settles as the window grows (and the queue with it).
    None where no gap has fallen yet."""
    so_far = [gaps_ms[gap_at <= f * seconds] for f in (0.25, 0.5, 0.75, 1.0)]
    return [float(np.percentile(g, 50)) if len(g) else None for g in so_far]


def setup(ctx, schedule):
    """Model from the seed, the engine with its pool, every shape warm."""
    from paddle_tpu.framework import flags
    from paddle_tpu.serving import Engine

    cfg, traffic, family = ctx.config, ctx.traffic, ctx.family
    fit(traffic, cfg, *longest_of(schedule))
    t = time.monotonic()
    weights = W.make_weights(cfg, ctx.seed, family.leaf_specs(cfg))
    ctx.note("setup_weights_s", time.monotonic() - t)
    t = time.monotonic()
    model, _ = family.build(cfg, weights)
    del weights
    model.eval()
    gc.collect()
    ctx.note("setup_model_s", time.monotonic() - t)
    block_size = int(flags.flag("FLAGS_serve_block_size"))
    blocks = traffic.get("pool_blocks") or pool_blocks(
        family, cfg, block_size, int(traffic["headroom_bytes"]))
    ctx.note("pool_blocks", blocks)
    # default flags under the traffic file's entry; the pool's size is ours
    eng = Engine(model, num_blocks=blocks, **traffic.get("engine", {}))
    for key in ("max_seq_len", "max_batch", "prefill_batch"):
        ctx.note(f"engine_{key}", getattr(eng.config, key))
    t = time.monotonic()
    warm(eng, schedule, traffic, block_size, cfg["vocab_size"], print)
    ctx.note("setup_warm_s", time.monotonic() - t)
    return model, eng


def drive(ctx, eng, schedule, window=False, sample_every=None):
    """Ramp and window. Returns (loop, t_open, t_close, rows at open, the
    engine's stats at close, samples of (t, running, queue_depth)). With
    ``window`` it is a run's measured window (``ctx.open_window``)."""
    temp = float(ctx.traffic["temperature"])
    submit = lambda prompt, n: eng.submit(
        prompt, max_new_tokens=n, temperature=temp, stream=True)
    loop = G.OpenLoop(schedule, submit)
    t_open = time.monotonic() + schedule.ramp_s + 0.05
    t_close = t_open + schedule.seconds
    loop.start(t_open)
    time.sleep(max(0.0, t_open - time.monotonic() - 0.5))
    if window:
        ctx.open_window(t_open)
    time.sleep(max(0.0, t_open - time.monotonic()))
    rows_open, samples = eng.stats()["running"], []
    while sample_every and time.monotonic() < t_close - sample_every:
        time.sleep(sample_every)
        st = eng.stats()
        samples.append((time.monotonic() - t_open, st["running"], st["queue_depth"]))
    ctx.sleep_until(t_close)
    ctx.end_work()
    st = eng.stats()
    return loop, t_open, t_close, rows_open, st, samples


def run(ctx) -> dict:
    cfg, traffic, seed, family = ctx.config, ctx.traffic, ctx.seed, ctx.family
    log = lambda m: print(m, flush=True)
    schedule = G.build_schedule(traffic, ctx.seconds, seed, cfg["vocab_size"])
    model, eng = setup(ctx, schedule)
    try:
        loop, t_open, t_close, rows_at_open, at_close, _ = drive(
            ctx, eng, schedule, window=True)
        rows_at_close, queue_at_close = at_close["running"], at_close["queue_depth"]
        spans = ctx.spans
        ctx.note("setup_ramp_s", schedule.ramp_s)
        ctx.close_window(t_open, t_close)
        peak = ctx.memory_peak()
        served = loop.drain(float(traffic["drain_s"]))
        ctx.note("drain_s", time.monotonic() - t_close)
        stats = eng.stats()
    finally:
        eng.close()
    st = G.window_stats(served, schedule, t_open)
    log(f"window: {st['attempted']} requests due, {st['failed']} failed, "
        f"{st['tokens_in_window']} tokens, {len(st['gaps'])} gaps, rows "
        f"{rows_at_open} at open and {rows_at_close} at close, queue "
        f"{queue_at_close} at close and {stats['queue_depth']} after the drain; "
        f"{at_close['pages_used']} of {at_close['pages_total']} KV blocks held at close")

    # free the program, then the reference over a sample of what it served
    picked = sample_finished(served, schedule, seed, int(ctx.cell["check_requests"]))
    del eng, model, loop
    gc.collect()
    t = time.monotonic()
    weights = W.make_weights(cfg, seed, family.leaf_specs(cfg))
    worst, where, n_tok = 0.0, "no finished request", 0
    for r in picked:
        gaps = served_gap(family, cfg, weights, schedule.prompts[r.index], r.tokens)
        n_tok += len(gaps)
        if gaps.max() >= worst:
            worst, where = float(gaps.max()), f"request {r.index} token {int(gaps.argmax())}"
    if not picked:
        worst = float("inf")
    ctx.note("reference_s", time.monotonic() - t)
    ctx.note("reference_tokens", n_tok)

    gaps_ms, ttft_ms = st["gaps"] * 1e3, st["ttft"] * 1e3
    e2e = {}
    if len(gaps_ms):
        log("gaps ms p5/25/50/75/95/99: " + " ".join(
            f"{np.percentile(gaps_ms, q):.2f}" for q in (5, 25, 50, 75, 95, 99)))
        log("gap p50 ms over the window's first quarter/half/three quarters/whole: "
            + " ".join("none" if x is None else f"{x:.3f}"
                       for x in settling(gaps_ms, st["gap_at"], schedule.seconds)))
        log("ttft ms p10/50/90: " + " ".join(
            f"{np.percentile(ttft_ms, q):.1f}" for q in (10, 50, 90))
            + f"; generator late p99 {np.percentile(st['late'], 99) * 1e3:.2f} ms")
        e2e["token_gap_p50_ms"] = float(np.percentile(gaps_ms, 50))
        # ISSUE 23's other end-to-end metrics, for the cells that can bound
        # them (PERF.md section 7); a cell reports what BENCHMARK.json lists
        e2e["token_gap_p95_ms"] = float(np.percentile(gaps_ms, 95))
        e2e["served_tokens_per_s"] = st["tokens_in_window"] / schedule.seconds
    if len(ttft_ms):
        e2e["ttft_p50_ms"] = float(np.percentile(ttft_ms, 50))
    return {
        "numbers": {"served_logit_gap": (worst, f"{where}, {n_tok} tokens of "
                                         f"{len(picked)} requests")},
        "attempted": st["attempted"], "failed": st["failed"],
        "t_open": t_open, "t_close": t_close, "memory_peak_bytes": peak,
        "counts": {"requests": st["attempted"], "tokens": st["tokens_in_window"],
                   "prompt_tokens": int(schedule.prompt_len[schedule.in_window].sum()),
                   "output_tokens": int(schedule.out_len[schedule.in_window].sum())},
        "end_to_end": e2e,
        "facts": {"served": served, "schedule": schedule, "stats": st,
                  "gaps_ms": gaps_ms, "ttft_ms": ttft_ms, "spans": spans,
                  "rows_at_open": rows_at_open, "rows_at_close": rows_at_close,
                  "tokens_per_s": st["tokens_in_window"] / schedule.seconds},
    }
