"""Global flag registry.

Reference: gflags exported via ``paddle/fluid/platform/flags.cc`` (53 flags) +
``pybind/global_value_getter_setter.cc`` → ``paddle.set_flags/get_flags`` and
``FLAGS_*`` env pickup. Here flags mostly steer debug behavior (nan/inf
checking, deterministic ops) and XLA options.
"""
from __future__ import annotations

import os
from typing import Dict

_FLAGS: Dict[str, object] = {
    "FLAGS_check_nan_inf": False,          # reference operator.cc:1171 nan/inf scan
    # Lazy-mode per-op nan/inf attribution (checkify-style): every flush is
    # re-run unfused with every node output checked, so NaNs in fused-away
    # dead intermediates are caught too and the first non-finite value is
    # attributed to the op that produced it. ~2x compute — the reference's
    # documented debug-mode cost. Only consulted when FLAGS_check_nan_inf
    # is set.
    "FLAGS_check_nan_inf_per_op": False,
    # Verify checkpoint shard checksums against the manifest on load (skipped
    # automatically for legacy checkpoints without a manifest).
    "FLAGS_ckpt_verify_on_load": True,
    "FLAGS_cudnn_deterministic": False,
    "FLAGS_eager_delete_tensor_gb": 0.0,
    "FLAGS_allocator_strategy": "auto_growth",
    "FLAGS_fraction_of_gpu_memory_to_use": 0.92,
    "FLAGS_use_bf16_matmul": True,         # TPU-native: allow bf16 matmul precision
    "FLAGS_jit_cache_size": 4096,
    "FLAGS_log_level": 0,
    # Lazy-graph IR verifier (analysis/verify_graph.py): re-derive and
    # cross-check the pending graph's wiring, leaf table, donation mask and
    # cache signature immediately before every dispatch, raising a
    # structured GraphInvariantError naming the offending node. Default on
    # in the test suite (conftest); off in production, where the disabled
    # path costs one flag probe per flush (a tier-1 tripwire pins it).
    "FLAGS_lazy_verify": False,
    # Runtime ownership assertions (analysis/thread_checks.py): wrap
    # `# guarded_by:`-annotated shared structures in proxies that make an
    # unguarded/foreign-thread mutation raise at the mutation site, so races
    # fail deterministically in the chaos/async suites instead of corrupting
    # a table. Opt-in; consulted at structure WRAP time, not per mutation.
    "FLAGS_thread_checks": False,
    # Lazy-flush buffer donation: dead-after-flush inputs (rebound params,
    # optimizer moments, accumulated grads) are passed as donate_argnums so
    # XLA updates weights in place instead of copying ~3x model size per
    # step. FLAGS_lazy_donate=0 is the kill-switch.
    "FLAGS_lazy_donate": True,
    # Async lazy runtime (arXiv:2102.13267 overlap): the flush returns at
    # executable DISPATCH (results are unblocked jax.Array futures), the
    # NaN/Inf guard scan and the telemetry memory census run off the critical
    # path (deferred to the next flush/materialization/lazy.sync(), trip
    # surfaces ≤1 step late), and host readback waits are attributed via
    # `block` spans + lazy_block_ns. FLAGS_lazy_async=0 is the kill-switch
    # restoring the fully synchronous behavior.
    "FLAGS_lazy_async": True,
    # Background compilation of flush-cache misses: the miss step (and any
    # same-signature step until the compile lands) executes via the un-jitted
    # replay while a worker thread compiles the fused executable. OPT-IN:
    # the unfused replay can differ from the fused executable by ~1 ulp and
    # the pickup step depends on compile latency, so loops that pin bitwise
    # reproducibility across runs must leave it off. Needs FLAGS_lazy_async.
    "FLAGS_lazy_bg_compile": False,
    # ZeRO-1 sharded weight update for pure-DP meshes (arXiv:2004.13336):
    # reduce_scatter(grads) -> each replica updates its 1/dp shard of params
    # + optimizer moments -> all_gather(params), with grads coalesced into
    # reverse-backward-order buckets (fleet/grad_buckets.py). Beside 'mp'
    # the same step exchanges each leaf by ppermute and updates it whole
    # (engine._build_dp_step). On by default; the engine falls back to the
    # replicated GSPMD update for other axes ('sp', 'pp', 'sharding'),
    # non-elementwise rules (LAMB/LARS) and grad accumulation.
    "FLAGS_shard_weight_update": True,
    # EQuARX-style blockwise int8 compression of the DP gradient collectives
    # (collective.py quantized_* prims). Off by default — lossy; enable with
    # FLAGS_quantized_allreduce_error_feedback to carry the compression
    # residual into the next step.
    "FLAGS_quantized_allreduce": False,
    "FLAGS_quantized_allreduce_block": 128,
    "FLAGS_quantized_allreduce_error_feedback": False,
    # Gradient-bucket byte cap (reference DataParallel comm_buffer_size=25MB).
    "FLAGS_dp_bucket_bytes": 25 * 1024 * 1024,
    # Per-flush live-buffer memory census (jax.live_arrays() walk feeding the
    # profiler's live_bytes/peak gauges and lazy_flush span attrs) without a
    # running Profiler; Profiler(profile_memory=True) turns it on per session.
    "FLAGS_profile_memory": False,
    # Serving engine defaults (paddle_tpu/serving/ — continuous batching +
    # paged KV cache): KV block size in tokens, total preallocated blocks in
    # the pool (block 0 is the reserved trash block), the decode batch-width
    # ceiling (bucketed in powers of two up to this), the fixed prefill
    # batch width, the per-sequence length cap (clamped to the model's
    # max_position_embeddings), and the weight-only int8 serving path.
    # EngineConfig fields override per engine.
    "FLAGS_serve_block_size": 16,
    "FLAGS_serve_num_blocks": 512,
    "FLAGS_serve_max_batch": 64,
    "FLAGS_serve_prefill_batch": 4,
    "FLAGS_serve_max_seq_len": 2048,
    "FLAGS_serve_int8": False,
    # Serving throughput multipliers (PR 16). FLAGS_serve_prefix_cache keeps
    # retired prompts' KV blocks in a refcounted prefix index so admission
    # can match the longest cached prefix (chained block-granularity hashes
    # over prompt token chunks) and prefill only the tail.
    # FLAGS_serve_spec_k > 0 arms speculative decoding: a drafter proposes k
    # tokens per step and the target model verifies all k in ONE batched
    # paged-decode step, accepting the longest agreeing prefix (greedy
    # output stays bit-identical to non-speculative decode).
    # FLAGS_serve_drafter picks the proposer: "ngram" (host-side prompt
    # lookup, no extra model) — a small same-family model can be passed to
    # Engine(drafter=...) directly. FLAGS_serve_draft_window bounds the
    # model drafter's dense attention window in tokens. Both features
    # default OFF and their code paths are never reached unconfigured
    # (pinned by the inert tripwire in tests/test_serving_prefix.py).
    "FLAGS_serve_prefix_cache": False,
    "FLAGS_serve_spec_k": 0,
    "FLAGS_serve_drafter": "ngram",
    "FLAGS_serve_draft_window": 64,
    # Serving resilience (serving/engine.py + serving/supervisor.py).
    # FLAGS_serve_max_queue sets the queue depth at which the shed policy
    # engages (0 = never); it is only enforced when FLAGS_serve_shed is ALSO
    # set, in which case submit() past the cap fast-fails with a structured
    # Overloaded (Retry-After-style retry_after_s hint) instead of letting
    # queue latency grow without bound — with shed off, the queue stays
    # unbounded (PR 11 semantics). FLAGS_serve_watchdog_s is the
    # ServingSupervisor's liveness
    # deadline: a crashed or wedged engine scheduler thread is detected
    # within this many seconds (heartbeat staleness), in-flight work is
    # failed or requeued, and the engine restarts over the same model/pool
    # config. All three are EngineConfig/supervisor overridable per engine;
    # none adds threads or host syncs when left at the defaults.
    "FLAGS_serve_max_queue": 0,
    "FLAGS_serve_shed": False,
    "FLAGS_serve_watchdog_s": 10.0,
    # Serving state durability (PR 17). With FLAGS_serve_snapshot on, the
    # ServingSupervisor's crash recovery captures the dead engine's frozen
    # serving state (PagePool bookkeeping + KV pool arrays + block tables +
    # prefix-cache chain, validated end-to-end) and the replacement engine
    # RE-ATTACHES the surviving blocks — streams resume mid-decode with
    # zero re-prefilled tokens, bit-identical to an uninterrupted run. A
    # capture that fails validation falls back to the PR 12 re-prefill
    # path, so recovery is never worse than before. Off (default): the
    # snapshot/adopt code paths are never reached (inert tripwire in
    # tests/test_serving_snapshot.py); Engine.handoff() is an explicit API
    # and needs no flag. Supervisor snapshot= overrides per instance.
    "FLAGS_serve_snapshot": False,
    # Multi-chip serving (PR 19). FLAGS_serve_tp shards attention heads,
    # FFN columns, the LM head, and the KV PagePool over a tp-sized mesh
    # axis (0/1 = single-chip, the exact prior code path). Every tensor-
    # parallel boundary is a concat-style all_gather of column-partitioned
    # outputs (never a psum of partials), so greedy decode stays
    # bit-identical to the single-chip engine. FLAGS_serve_prefill_chunk
    # splits prompt prefill into chunks of that many tokens (must be a
    # multiple of the KV block size; 0 = monolithic prefill) interleaved
    # one chunk per scheduler step with the live decode batch, so a long
    # prompt no longer stalls every in-flight stream for a full prefill.
    # FLAGS_serve_tp_int8 quantizes the per-step tensor-parallel
    # all_gather payloads to blockwise int8 (EQuARX-style, lossy — greedy
    # tokens may differ; off by default). All three default OFF and their
    # code paths are never reached unconfigured (inert tripwire in
    # tests/test_serving_tp.py).
    "FLAGS_serve_tp": 0,
    "FLAGS_serve_prefill_chunk": 0,
    "FLAGS_serve_tp_int8": False,
    # Serving SLO observability (PR 20, serving/observe.py).
    # FLAGS_serve_trace arms request-scoped tracing + the SLO metric layer:
    # every submitted request carries a trace id attached to each span it
    # touches (queue wait, shed, prefix match, prefill chunks, decode steps,
    # CoW, eviction, relay), completed per-request timelines land in a
    # bounded ring (FLAGS_serve_trace_ring capacity, chrome-trace/JSONL
    # exportable), and TTFT / inter-token gap / end-to-end / queue-wait
    # histograms per priority class flow into export_metrics(). Off
    # (default): the observe module is never touched — one attribute probe
    # per step, engine behavior byte-identical (inert tripwire in
    # tests/test_serving_observe.py). FLAGS_serve_metrics_port > 0 starts
    # the opt-in stdlib http.server telemetry thread (/metrics, /healthz,
    # /readyz, /debug/requests); 0 (default) = zero threads.
    "FLAGS_serve_trace": False,
    "FLAGS_serve_trace_ring": 256,
    "FLAGS_serve_metrics_port": 0,
    # Training stability sentinel (fault/sentinel.py): statistical anomaly
    # detection over per-step signals (loss, global grad norm, update/param
    # ratio, non-finite rate) with a skip -> rollback -> halt policy ladder,
    # batch quarantine and sample-exact auto-rollback. FLAGS_stability_enable
    # turns the hapi.Model.fit wiring on (one flag probe per fit call when
    # off); loops can also pass a configured StabilitySentinel explicitly.
    # window/warmup/zmax parameterize the robust (median/MAD) statistics;
    # max_skips/max_rollbacks/cooldown shape the escalation ladder;
    # anchor_interval + ckpt_dir configure the rollback anchor checkpoint;
    # quarantine_dir (when set) persists the quarantine log as JSONL.
    "FLAGS_stability_enable": False,
    "FLAGS_stability_window": 64,
    "FLAGS_stability_warmup": 8,
    "FLAGS_stability_zmax": 8.0,
    "FLAGS_stability_max_skips": 2,
    "FLAGS_stability_max_rollbacks": 2,
    "FLAGS_stability_cooldown": 16,
    "FLAGS_stability_anchor_interval": 25,
    "FLAGS_stability_ckpt_dir": "",
    "FLAGS_stability_quarantine_dir": "",
    # HBM exhaustion resilience (fault/memory.py). FLAGS_hbm_admission gates
    # the preflight memory-admission check on the lazy flush: "off" (default;
    # the whole disabled path is one flag probe per flush), "warn" (predict
    # and attach the estimate to the compile/flush spans, warn once per
    # executable when over budget, dispatch anyway), "enforce" (raise a
    # structured HbmBudgetExceeded BEFORE the dispatch touches the device).
    # FLAGS_hbm_budget_bytes overrides the device budget (0 = resolve from
    # the backend's reported capacity minus FLAGS_hbm_reserve_bytes; on
    # backends that report no capacity — CPU — 0 means no budget, so
    # admission only predicts/attributes and never rejects).
    "FLAGS_hbm_admission": "off",
    "FLAGS_hbm_budget_bytes": 0,
    "FLAGS_hbm_reserve_bytes": 256 * 1024 * 1024,
    # Host-embedding parameter server (incubate/host_embedding.py).
    # FLAGS_host_emb_native routes the table's batched unique/gather and the
    # SelectedRows-style sparse update through runtime_cpp/embed.cc
    # (multi-threaded, bit-exact with the numpy fallback); it silently falls
    # back when the .so is unbuilt/stale or the table dtype isn't float32.
    # FLAGS_host_emb_threads caps the kernel thread count (0 = hardware).
    # FLAGS_host_emb_cache_rows sizes the HBM hot-row cache (rows; 0 = off);
    # admission needs FLAGS_host_emb_cache_min_count sightings, and when the
    # PR 14 HBM budget is resolvable the cache is clamped to
    # FLAGS_host_emb_cache_frac of it (and registers a free_pressure handler
    # that halves it under memory pressure). FLAGS_host_emb_async_push makes
    # apply_gradients enqueue the sparse update to the PS worker thread
    # (host table work hides behind device execution; ordering vs later
    # gathers/prefetches is preserved by the worker's FIFO). Sharded-table
    # transport: FLAGS_host_emb_chunk_bytes per store message (the pre-PR
    # path used 512 KiB), FLAGS_host_emb_transport_threads parallel store
    # clients per peer exchange (0 = serial pre-PR behavior), and
    # FLAGS_host_emb_push_fp16 opts into float16 cross-rank grad payloads
    # (EQuARX-style byte shrink; lossy, off by default).
    "FLAGS_host_emb_native": True,
    "FLAGS_host_emb_threads": 16,
    "FLAGS_host_emb_cache_rows": 0,
    "FLAGS_host_emb_cache_min_count": 3,
    "FLAGS_host_emb_cache_frac": 0.25,
    "FLAGS_host_emb_async_push": False,
    "FLAGS_host_emb_chunk_bytes": 4 * 1024 * 1024,
    "FLAGS_host_emb_transport_threads": 4,
    "FLAGS_host_emb_push_fp16": False,
    # JAX persistent compilation cache (warm executable starts across
    # processes). Dir defaults to ~/.cache/paddle_tpu/xla when unset.
    "FLAGS_xla_persistent_cache": True,
    "FLAGS_xla_persistent_cache_dir": "",
    "FLAGS_xla_persistent_cache_min_compile_secs": 0.5,
    # Kernel autotuning (ops/kernels/). FLAGS_kernel_autotune: "off" makes
    # resolve_config a pure dict probe returning each kernel's pinned
    # defaults (byte-identical traces to the pre-registry call sites);
    # "ondemand" reads persisted winners from the tuning DB but never
    # searches; "search" runs a measured-timing search on a DB miss and
    # persists the verified winner. FLAGS_kernel_tune_dir overrides the DB
    # location (default ~/.cache/paddle_tpu/tune). Per-kernel search budget
    # and timing samples: FLAGS_kernel_tune_budget_s (monotonic deadline),
    # FLAGS_kernel_tune_samples (median-of-k, compile excluded).
    "FLAGS_kernel_autotune": "off",
    "FLAGS_kernel_tune_dir": "",
    "FLAGS_kernel_tune_budget_s": 20.0,
    "FLAGS_kernel_tune_samples": 5,
    # Serving kernel kill-switch. FLAGS_serve_int8_kernel keeps the int8
    # LM-head weight quantized end-to-end via the fused int8 matmul kernel
    # instead of dequantizing it densely each step. (The paged-attention
    # decode kernel has no flag: the engine builds it wherever Mosaic
    # compiles it, by backend and head width: models/generation.py
    # paged_kernel_default.)
    "FLAGS_serve_int8_kernel": False,
}

# Env pickup at import (reference: gflags env integration)
for _k in list(_FLAGS):
    if _k in os.environ:
        v = os.environ[_k]
        cur = _FLAGS[_k]
        if isinstance(cur, bool):
            _FLAGS[_k] = v.lower() in ("1", "true", "yes")
        elif isinstance(cur, float):
            _FLAGS[_k] = float(v)
        elif isinstance(cur, int):
            _FLAGS[_k] = int(v)
        else:
            _FLAGS[_k] = v


def register_flag(name: str, default):
    """Register a new flag (plugins/tests). Registration is explicit so that
    ``set_flags`` can reject typos instead of creating dead flags."""
    _FLAGS.setdefault(name, default)


def set_flags(flags: dict):
    for k, v in flags.items():
        if k not in _FLAGS:
            # A typo like FLAGS_chek_nan_inf would otherwise create a dead
            # flag and silently disable the debug mode the user asked for.
            import difflib

            hint = difflib.get_close_matches(k, _FLAGS, n=1)
            raise KeyError(
                f"unknown flag {k!r}"
                + (f"; did you mean {hint[0]!r}?" if hint else "")
                + " (use framework.flags.register_flag to add new flags)"
            )
        _FLAGS[k] = v


def get_flags(flags):
    if isinstance(flags, str):
        flags = [flags]
    return {k: _FLAGS.get(k) for k in flags}


def flag(name, default=None):
    return _FLAGS.get(name, default)
