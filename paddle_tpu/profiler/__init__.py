"""Profiler.

Parity: reference new profiler (``paddle/fluid/platform/profiler/`` —
Profiler composes HostTracer + CudaTracer(CUPTI), chrome-trace export, stat
aggregation) and python API (``python/paddle/profiler/``). TPU-native: host
events + structured spans recorded in a Python/C++ ring buffer; device
timeline delegated to jax.profiler (XProf / tensorboard trace), the TPU
equivalent of CUPTI.

Layers (each usable alone):

* **engine counters** — always-on integer bumps at flush/step granularity
  (:func:`counters`), exported as JSON or Prometheus text
  (:mod:`.export`); the benchmark's per-layer readers take their deltas
  over the timed window (``benchmark/``);
* **span tracer** (:mod:`.spans`) — nested, attributed spans
  (``train_step`` → ``lazy_flush`` → ``trace``/``donate``/``compile``/
  ``execute``; ``dp_sync`` → per-bucket; ``ckpt_save`` →
  ``serialize``/``commit``) recorded while a :class:`Profiler` runs;
* **set-up account** (:func:`setup_account`) — always on: the spans that
  compiled, with their stages, and the few that build a process
  (``engine_init``, ``program_build``, ``step_text``), kept in one bounded
  table and made into rows when read: what a cold start cost, by program;
* **flight recorder** (:mod:`.flight`) — always-on bounded ring of the last
  N spans + a JSON post-mortem dump on NaN trips, preemption drains,
  checkpoint-save failure, or an uncaught training-loop exception;
* **memory accounting** — per-flush live-buffer census over
  ``jax.live_arrays()`` with a high-water-mark gauge (:func:`memory_census`),
  on under ``Profiler(profile_memory=True)`` or ``FLAGS_profile_memory``.
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Dict, List, Optional

import jax


class ProfilerTarget:
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class _Event:
    __slots__ = ("name", "start", "end", "tid")

    def __init__(self, name, start, end, tid=0):
        self.name = name
        self.start = start
        self.end = end
        self.tid = tid


_events: List[_Event] = []
_enabled = False
_memory_on = False  # set while a Profiler(profile_memory=True) session runs

# Engine counters (always on — integer bumps at flush/step granularity, not
# per-op): lazy-flush executable cache behavior and buffer donation. The
# donation counter counts argument positions PASSED as donate_argnums; on
# backends that ignore the aliasing hint the count still reflects what the
# liveness pass proved dead.
_counters: Dict[str, int] = {}


def counter_inc(name: str, n: int = 1):
    _counters[name] = _counters.get(name, 0) + n


def counters() -> Dict[str, int]:
    """Snapshot of engine counters.

    Lazy engine (always on): ``lazy_flushes``, ``lazy_cache_hits``,
    ``lazy_donated_buffers``, ``lazy_donation_fallbacks`` (a flush re-built
    without donation after XLA refused it) and
    ``lazy_eager_replay_fallbacks`` (a flush whose executable failed and was
    replayed op by op, un-jitted — correct but unfused; zero on a healthy
    run).

    Async runtime (FLAGS_lazy_async): ``lazy_blocks`` / ``lazy_block_ns``
    (attributed host waits on the device: the dispatch gap a step),
    ``lazy_deferred_checks`` (NaN/Inf scans moved off the
    critical path), ``lazy_bg_compiles`` / ``lazy_bg_replays`` /
    ``lazy_bg_pickups`` / ``lazy_bg_compile_failures`` /
    ``lazy_bg_aot_fallbacks`` (FLAGS_lazy_bg_compile background compilation:
    misses compiling off-thread, steps served by the un-jitted replay
    meanwhile, compiled executables picked up, and fallbacks), and
    ``io_device_prefetched`` (batches staged on device by the
    DevicePrefetcher input stage).

    Fault tolerance: ``ckpt_saves`` / ``ckpt_save_failures`` /
    ``ckpt_resume_fallbacks`` (crash-safe checkpointing),
    ``preemption_drains`` (PreemptionGuard SIGTERM drains),
    ``retry_attempts`` (fault/retry.py backoff retries), ``naninf_trips``
    (FLAGS_check_nan_inf trips, eager and lazy), and
    ``naninf_donation_suppressed`` (flushes that skipped buffer donation to
    keep pre-step state inspectable under the nan guard).

    DP gradient-sync set (per train step, analytic wire accounting from the
    bucket plan): ``dp_sync_bytes`` (per-replica payload bytes entering the
    DP GRADIENT collectives — reduce-scatter for the ZeRO-1 path, both ring
    phases for bucketed all-reduce; int8+scale bytes when
    FLAGS_quantized_allreduce is on), ``dp_gather_bytes`` (ZeRO-1
    updated-param all-gather, full precision), ``dp_buckets`` /
    ``dp_reduce_scatters`` / ``dp_all_reduces`` (collective launches), and
    ``wus_enabled`` (1 when the engine runs the sharded weight update),
    ``dp_reduce_leaves`` / ``dp_reduce_async`` (states, not sums: the
    gradient arrays the engine's compiled dp step reduces over 'dp', and
    those whose reduce is a start/done pair with compute scheduled between,
    read once from the executable's scheduled text), and beside 'mp'
    ``mp_weight_exchanges`` / ``mp_activation_gathers`` from the same text
    (the fused QKV weight's transfers over 'mp' that run beside compute, and
    the all-gathers of token-shaped data over 'mp' they are there to remove)
    and ``mp_reduce_exchanges`` / ``mp_reduce_async`` /
    ``mp_activation_reduces`` (the blocks of partial products exchanged over
    'mp' by ``ppermute``, those of them that run beside compute, and the
    token-shaped all-reduces over 'mp' they are there to remove).

    Serving engine (paddle_tpu/serving/): ``serve_requests`` /
    ``serve_admitted`` / ``serve_retired`` / ``serve_cancelled`` /
    ``serve_failed`` (request lifecycle), ``serve_prefills`` /
    ``serve_decode_steps`` / ``serve_tokens`` (work done),
    ``serve_compiles`` (bucket programs built — bounded by the bucket
    count), ``serve_pages_allocated`` / ``serve_pages_freed`` (KV block
    pool churn), ``serve_backpressure`` (admissions stalled on pool
    exhaustion), ``serve_preempted`` (sequences evicted for re-prefill),
    ``serve_occupancy_live`` / ``serve_occupancy_slots`` (live rows vs
    padded batch slots per decode step — their ratio is mean batch
    occupancy), ``serve_expert_assignments`` (a routed-expert arch: the
    (token, choice) pairs the programs routed, padding not counted; the
    distinct experts a step hit are its span's ``experts_touched``, the
    table by layer and expert is ``Engine.stats()["expert_tokens"]``),
    ``serve_state_rebuilds`` / ``serve_state_rows`` (an arch whose layers
    keep a window, a recurrent state or a convolution's last inputs a row:
    evictions that cost a re-prefill of that state, and the live rows whose
    state the decode steps updated; the slots held are
    ``Engine.stats()["state_slots_used"]``),
    ``serve_decode_ahead`` (decode steps enqueued while the step before was
    still unread on the device: over ``serve_decode_steps``, how often the
    loop ran one step ahead of the host), ``serve_decode_drains`` (landings
    forced with nothing enqueued behind: eviction, the OOM back-off, handoff,
    shutdown, containment), ``serve_decode_wasted_rows`` (row-steps computed
    for a row that had ended on an EOS value, a cancel or a deadline),
    ``serve_decode_blocks_read`` (KV blocks the block-table
    kernel's decode steps read: ``decode_build``'s ``blocks_live``, what the
    rows hold, summed over those steps; over slots x the engine's table
    width it is the share of a padded read that was needed. Steps that
    gather, the speculative verify and decode where the engine keeps the
    gather builder, read bucket x gather width whatever is live and do not
    move it), and ``serve_engine_errors``. Live gauges (queue depth,
    page-pool utilization, in-flight request table) come from
    ``Engine.stats()`` and ride every flight-recorder dump via the
    engine's context provider.

    Serving resilience (round 12): ``serve_shed`` (submissions fast-failed
    ``Overloaded`` at the queue cap), ``serve_deadline_shed`` (queued
    requests shed expired/doomed at admission) and
    ``serve_deadline_expired`` (running/preempted requests expired at a
    step boundary), ``serve_wedged_close`` (close() joins that timed out on
    a wedged scheduler thread), ``serve_crash_detected`` /
    ``serve_wedge_detected`` / ``serve_restarts`` / ``serve_requeued`` /
    ``serve_relayed`` (ServingSupervisor recovery: failures detected,
    engines restarted, requests resubmitted onto the fresh engine, and
    originals completed through the recovery relay — a requeued request's
    CONTINUATION counts once in serve_requests/serve_retired on the new
    engine, while the original's relay completion counts only in
    serve_relayed, so lifecycle counters stay per-logical-outcome), and
    ``serve_pool_damaged`` (serve.pool_corrupt chaos firings).

    HBM exhaustion resilience (fault/memory.py): ``hbm_admission_checks`` /
    ``hbm_admission_rejects`` (preflight admission decisions under
    ``FLAGS_hbm_admission``), ``hbm_oom_trips`` (classified
    RESOURCE_EXHAUSTED events, wherever they fired), ``hbm_oom_recoveries``
    (ladder rungs that brought the step/stream back — flush retry, engine
    microbatch degrade), ``hbm_degraded_steps`` (engine steps re-run
    through the grad-accumulate scan path), ``hbm_cache_evicted`` (cold
    lazy executables dropped by free_pressure), ``serve_pool_shrunk`` /
    ``serve_pages_parked`` / ``serve_pages_unparked`` (serving KV-block
    admission-headroom shrink under pressure), and
    ``stability_coordinated_trips`` / ``stability_barrier_timeouts`` (the
    sentinel's cross-rank VerdictBarrier adoptions and degraded rounds).

    Kernel autotuning (ops/kernels/, FLAGS_kernel_autotune):
    ``kernel_tune_hits`` / ``kernel_tune_misses`` (registry config
    resolutions served by the tuning DB vs falling back / searching),
    ``kernel_tune_searches`` (measured-timing searches run),
    ``kernel_tune_candidates`` (candidate configs timed),
    ``kernel_tune_verify_fails`` (candidates rejected by the
    against-default output check), ``kernel_tune_candidate_errors``
    (candidates that failed to compile/run), ``kernel_tune_budget_stops``
    (searches cut short by FLAGS_kernel_tune_budget_s), and
    ``kernel_tune_db_rejects`` (torn/corrupt DB entries rejected and
    deleted). All zero while autotuning is off — resolution is then a
    dict probe that touches none of this machinery.

    Prefix cache + CoW KV sharing (serving/prefix.py): ``serve_prefix_hits``
    / ``serve_prefix_misses`` (admissions that found / missed a cached
    prompt prefix), ``serve_prefix_blocks_shared`` (KV blocks adopted from
    the cache instead of re-prefilled), ``serve_prefix_evicted`` (cached
    prefixes dropped by the LRU bound), and ``serve_cow_copies``
    (copy-on-write block duplications when a shared block is written).

    Chunked prefill (FLAGS_serve_prefill_chunk): ``serve_prefill_chunks``
    (prompt chunks executed through the chunk bucket),
    ``serve_prefill_context_tokens`` (the positions those calls attended
    over: what was cached of their rows plus what they fed).

    Speculative decoding (FLAGS_serve_spec_k): ``serve_draft_proposed``
    / ``serve_draft_accepted`` (draft tokens proposed vs accepted by the
    target-model verify — their ratio is the acceptance rate).

    Serving state durability (rounds 17-18): ``serve_snapshots`` /
    ``serve_snapshot_failed`` / ``serve_snapshot_rejected`` (KV-pool
    snapshot writes, failures, and stale/corrupt restores rejected),
    ``serve_pool_restores`` (pools rebuilt from a snapshot),
    ``serve_adoptions`` (engines adopting a restored pool),
    ``serve_reattached`` / ``serve_reattached_blocks`` (crash re-attach:
    requests resumed onto snapshot KV state and the blocks they kept),
    ``serve_reprefill_tokens`` / ``serve_reprefill_tokens_saved`` (tokens
    re-prefilled after recovery vs spared by re-attach),
    ``serve_handoffs`` (zero-downtime engine→engine handoffs), and
    ``serve_restart_mttr_ms`` (cumulative supervisor detect→ready repair
    time).

    Serving observability (this round): ``serve_trace_evicted`` (completed
    request timelines dropped from the bounded trace ring) and
    ``serve_http_bind_failed`` (endpoint start-ups that lost the port —
    telemetry never takes serving down).

    Host embedding offload (incubate/host_embedding.py): ``host_emb_lookups`` /
    ``host_emb_block_ns`` (gather round-trips and attributed host-wait
    time), ``host_emb_hot_hits`` / ``host_emb_hot_misses`` (device-resident
    hot-shard membership), ``host_emb_cache_admitted`` /
    ``host_emb_cache_evicted`` / ``host_emb_cache_shrinks`` (hot-cache
    churn), ``host_emb_prefetch_hits`` / ``host_emb_prefetch_drops`` /
    ``host_emb_prefetch_patched`` (lookahead pipeline), and
    ``host_emb_push_bytes`` (host-side gradient write-back volume).

    Numeric stability sentinel (stability/): ``stability_observed`` /
    ``stability_trips`` / ``stability_skips`` / ``stability_halts`` /
    ``stability_rollbacks`` / ``stability_readbacks`` (steps watched,
    verdicts tripped, and the skip/halt/rollback reactions plus device
    readbacks the policy paid for).

    Cluster plumbing: ``ckpt_coordinated_commits`` (multi-host checkpoint
    barrier commits), ``heartbeat_failures`` (elastic heartbeat misses),
    ``watchdog_trips`` (collective-watchdog stall detections),
    ``io_quarantine_skips`` (poisoned input batches skipped), and
    ``lazy_verify_passes`` (FLAGS_lazy_verify replay cross-checks).

    Compilation, charged to the program span it fired under
    (profiler/spans.py; an event outside any span is not counted):
    ``compile_trace_ns`` / ``compile_lower_ns`` / ``compile_backend_ns``
    (nanoseconds of ``jax.monitoring``'s jaxpr-trace, jaxpr-to-MLIR and
    backend-compile stages; nested traces counted once; a load from the
    persistent cache is a backend stage) and ``compile_cache_hits``
    (programs the persistent compilation cache served). The innermost open
    span carries the same as ``compile_trace_s`` / ``compile_lower_s`` /
    ``compile_backend_s`` / ``compile_cache_hits`` attributes, and
    ``compile_cache_misses`` beside them (programs compiled and WRITTEN to
    the persistent cache: 0 in a warm process; a checkout at a new path, a
    new jax or a new shape reads otherwise).

    Set-up (:func:`setup_account` keeps the spans themselves):
    ``setup_import_ns`` (the first to the last line of
    ``paddle_tpu/__init__.py``, ``import jax`` inside it when the package is
    the first to import it), ``param_init_ns`` / ``param_init_bytes`` /
    ``param_init_leaves`` (the initializer calls of
    ``Layer.create_parameter``: host time, bytes and arrays drawn; a model
    that is then given its weights drew them to be thrown away), and
    ``setup_account_dropped`` (spans the bounded account did not keep).

    Telemetry: ``flight_dumps`` (flight-recorder post-mortems written by
    this process).

    Export: :func:`export_metrics` (JSON or Prometheus text) embeds this
    snapshot plus the memory gauges; ``Profiler.export`` embeds it as
    chrome-trace metadata.
    """
    return dict(_counters)


# The counter registry: every counter the package bumps, by name. The
# ``counter-registry`` lint rule (analysis/lint.py) enforces the three-way
# contract — every ``counter_inc`` literal in the package appears here,
# every name here is bumped somewhere, and every name here is documented
# (double-backticked) in the :func:`counters` docstring above. Adding a
# counter means adding it in all three places; the lint failure names the
# one you forgot.
KNOWN_COUNTERS = frozenset({
    "ckpt_coordinated_commits", "ckpt_resume_fallbacks",
    "ckpt_save_failures", "ckpt_saves",
    "compile_backend_ns", "compile_cache_hits", "compile_cache_misses",
    "compile_lower_ns", "compile_trace_ns",
    "dp_all_reduces", "dp_buckets", "dp_gather_bytes",
    "dp_reduce_async", "dp_reduce_leaves",
    "dp_reduce_scatters", "dp_sync_bytes",
    "flight_dumps",
    "hbm_admission_checks", "hbm_admission_rejects", "hbm_cache_evicted",
    "hbm_degraded_steps", "hbm_oom_recoveries", "hbm_oom_trips",
    "heartbeat_failures",
    "host_emb_block_ns", "host_emb_cache_admitted",
    "host_emb_cache_evicted", "host_emb_cache_shrinks",
    "host_emb_hot_hits", "host_emb_hot_misses", "host_emb_lookups",
    "host_emb_prefetch_drops", "host_emb_prefetch_hits",
    "host_emb_prefetch_patched", "host_emb_push_bytes",
    "io_device_prefetched", "io_quarantine_skips",
    "kernel_tune_budget_stops", "kernel_tune_candidate_errors",
    "kernel_tune_candidates", "kernel_tune_db_rejects",
    "kernel_tune_hits", "kernel_tune_misses", "kernel_tune_searches",
    "kernel_tune_verify_fails",
    "lazy_bg_aot_fallbacks", "lazy_bg_compile_failures",
    "lazy_bg_compiles", "lazy_bg_pickups", "lazy_bg_replays",
    "lazy_block_ns", "lazy_blocks", "lazy_cache_hits",
    "lazy_deferred_checks", "lazy_donated_buffers",
    "lazy_donation_fallbacks", "lazy_eager_replay_fallbacks",
    "lazy_flushes", "lazy_verify_passes",
    "mp_activation_gathers", "mp_activation_reduces", "mp_reduce_async",
    "mp_reduce_exchanges", "mp_weight_exchanges",
    "naninf_donation_suppressed", "naninf_trips",
    "param_init_bytes", "param_init_leaves", "param_init_ns",
    "preemption_drains", "retry_attempts",
    "serve_admitted", "serve_adoptions", "serve_backpressure",
    "serve_cancelled", "serve_compiles", "serve_cow_copies",
    "serve_crash_detected", "serve_deadline_expired",
    "serve_deadline_shed", "serve_decode_ahead", "serve_decode_blocks_read",
    "serve_decode_drains", "serve_decode_steps", "serve_decode_wasted_rows",
    "serve_draft_accepted", "serve_draft_proposed",
    "serve_engine_errors", "serve_expert_assignments",
    "serve_failed", "serve_handoffs", "serve_http_bind_failed",
    "serve_occupancy_live", "serve_occupancy_slots",
    "serve_pages_allocated", "serve_pages_freed", "serve_pages_parked",
    "serve_pages_unparked", "serve_pool_damaged",
    "serve_pool_restores", "serve_pool_shrunk", "serve_preempted",
    "serve_prefill_chunks", "serve_prefill_context_tokens", "serve_prefills",
    "serve_prefix_blocks_shared", "serve_prefix_evicted",
    "serve_prefix_hits", "serve_prefix_misses",
    "serve_reattached", "serve_reattached_blocks", "serve_relayed",
    "serve_reprefill_tokens", "serve_reprefill_tokens_saved",
    "serve_requests", "serve_requeued", "serve_restart_mttr_ms",
    "serve_restarts", "serve_retired", "serve_shed",
    "serve_snapshot_failed", "serve_snapshot_rejected",
    "serve_snapshots", "serve_state_rebuilds", "serve_state_rows",
    "serve_tokens",
    "serve_trace_evicted", "serve_wedge_detected", "serve_wedged_close",
    "setup_account_dropped", "setup_import_ns",
    "stability_barrier_timeouts", "stability_coordinated_trips",
    "stability_halts", "stability_observed", "stability_readbacks",
    "stability_rollbacks", "stability_skips", "stability_trips",
    "watchdog_trips", "wus_enabled",
})


def reset_counters():
    """Clear the counters. ``setup_import_ns`` stays: it is stamped once, as
    the package is imported, and nothing can count it again."""
    stamp = _counters.get("setup_import_ns")
    _counters.clear()
    if stamp is not None:
        _counters["setup_import_ns"] = stamp


# -- memory accounting --------------------------------------------------------
_mem: Dict[str, int] = {
    "live_bytes": 0, "live_arrays": 0, "peak_live_bytes": 0,
    "last_delta_bytes": 0, "censuses": 0,
}


def memory_census() -> Dict[str, int]:
    """Walk ``jax.live_arrays()`` and refresh the gauges: current live
    device-buffer bytes/count, the delta since the previous census, and the
    process high-water mark. Called per lazy flush while memory profiling is
    active; cheap enough to call directly at snapshot points (bench)."""
    total = 0
    count = 0
    try:
        for a in jax.live_arrays():
            try:
                total += int(a.nbytes)
                count += 1
            except Exception:
                pass
    except Exception:
        return dict(_mem)
    _mem["last_delta_bytes"] = total - _mem["live_bytes"]
    _mem["live_bytes"] = total
    _mem["live_arrays"] = count
    _mem["censuses"] += 1
    if total > _mem["peak_live_bytes"]:
        _mem["peak_live_bytes"] = total
    return dict(_mem)


def memory_stats() -> Dict[str, int]:
    """Last-census gauges WITHOUT a fresh walk (safe mid-crash)."""
    return dict(_mem)


def _memory_active() -> bool:
    if _enabled and _memory_on:
        return True
    try:
        from ..framework import flags as _flags

        return bool(_flags.flag("FLAGS_profile_memory", False))
    except Exception:
        return False


# Native host recorder (runtime_cpp/trace.cc) when built — GIL-cheap record.
_native = None
_native_rec = None
_native_spans = False
_native_tried = False


def _native_recorder():
    global _native, _native_rec, _native_spans, _native_tried
    if _native_rec is not None or _native_tried:
        return _native_rec
    _native_tried = True
    try:
        from ..core import native as _native_mod

        _native = _native_mod.lib()
        if _native is not None:
            _native_rec = _native.ptt_create(1 << 16)
            _native_spans = bool(getattr(_native_mod, "HAS_SPANS", False))
    except Exception:
        _native = None
    return _native_rec


def _record(name: str, t0: int, tid: int = 0):
    """Hot-path event sink: dispatch/lazy/jit call this with a start stamp
    taken only when ``_enabled`` was already true (reference records every
    traced op the same way, imperative/tracer.cc:177). Events land in
    exactly ONE sink — the C++ ring when built, else the Python list —
    and ``export()``/``summary()`` merge the sinks."""
    t1 = time.perf_counter_ns()
    if not _enabled:
        return
    rec = _native_recorder()
    if rec is not None:
        nid = _native.ptt_intern(rec, name.encode())
        _native.ptt_record(rec, nid, tid, t0, t1)
    else:
        _events.append(_Event(name, t0, t1, tid))


class RecordEvent:
    """Reference: platform/profiler.h RecordEvent push/pop. Events land in
    the C++ ring buffer when the native runtime is built (Python list
    otherwise — one sink, merged at export)."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._t0 = None

    def begin(self):
        self._t0 = time.perf_counter_ns()

    def end(self):
        if _enabled and self._t0 is not None:
            _record(self.name, self._t0)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def _reset_session():
    """Clear every session sink (python events, span list + attr table,
    native rings) so a new recording starts from an empty timeline."""
    _events.clear()
    spans._reset_session()
    rec = _native_recorder()
    if rec is not None:
        _native.ptt_reset(rec)


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Step-state schedule for ``Profiler.step()`` (reference
    ``profiler.make_scheduler``): after ``skip_first`` warmup steps, cycle
    through ``closed`` CLOSED steps, ``ready`` READY steps and ``record``
    recording steps (the last of which is RECORD_AND_RETURN — the trace is
    handed to ``on_trace_ready`` at the next ``step()``). ``repeat`` bounds
    the number of cycles (0 = unlimited)."""
    closed, ready, record = int(closed), int(ready), int(record)
    repeat, skip_first = int(repeat), int(skip_first)
    if record < 1:
        raise ValueError("make_scheduler: record must be >= 1")
    if min(closed, ready, repeat, skip_first) < 0:
        raise ValueError("make_scheduler: negative phase length")
    cycle = closed + ready + record

    def schedule(step: int) -> int:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


class Profiler:
    """Host-span profiler with an optional step scheduler.

    Without a scheduler, ``start()`` records until ``stop()`` (legacy
    behavior). With ``scheduler=make_scheduler(...)``, call ``step()`` once
    per train step: recording turns on only for the scheduled windows, and
    ``on_trace_ready(prof)`` fires at the end of each RECORD_AND_RETURN
    window (and at ``stop()`` if a window is still open)."""

    def __init__(
        self,
        targets=None,
        scheduler=None,
        on_trace_ready=None,
        timer_only=False,
        record_shapes=False,
        profile_memory=False,
        with_flops=False,
    ):
        self.timer_only = timer_only
        self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.profile_memory = profile_memory
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._jax_tracing = False
        self._trace_dir = None

    # -- state machine -----------------------------------------------------
    def _recording(self) -> bool:
        return self.current_state in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN,
        )

    def _apply(self, new_state: int):
        global _enabled
        was = self._recording()
        self.current_state = new_state
        now = self._recording()
        if now and not was:
            _enabled = True
            if not self.timer_only and not self._jax_tracing:
                self._trace_dir = os.environ.get(
                    "PADDLE_TPU_TRACE_DIR", "/tmp/paddle_tpu_trace"
                )
                try:
                    jax.profiler.start_trace(self._trace_dir)
                    self._jax_tracing = True
                except Exception:
                    self._jax_tracing = False
        elif was and not now:
            _enabled = False
            if self._jax_tracing:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                self._jax_tracing = False

    def start(self):
        global _memory_on
        self.step_num = 0
        _reset_session()
        if self.profile_memory:
            _memory_on = True
        first = (
            self.scheduler(0) if self.scheduler is not None else ProfilerState.RECORD
        )
        self._apply(first)

    def stop(self):
        global _memory_on
        was = self._recording()
        self._apply(ProfilerState.CLOSED)
        if self.profile_memory:
            _memory_on = False
        if was and self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def step(self):
        """Advance the scheduler one train step. Drives the CLOSED → READY →
        RECORD → RECORD_AND_RETURN transitions; when the step that just
        finished was RECORD_AND_RETURN, the collected trace is handed to
        ``on_trace_ready`` and the session buffers reset for the next
        cycle."""
        finished_window = self.current_state == ProfilerState.RECORD_AND_RETURN
        self.step_num += 1
        new = (
            self.scheduler(self.step_num)
            if self.scheduler is not None
            else ProfilerState.RECORD
        )
        if finished_window:
            self._apply(ProfilerState.CLOSED)
            if self.on_trace_ready is not None:
                self.on_trace_ready(self)
            _reset_session()
        self._apply(new)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- output ------------------------------------------------------------
    def export(self, path, format="json"):
        """Chrome-trace export (reference chrometracing_logger.cc) with the
        engine-counter snapshot, memory gauges and flags embedded as trace
        ``metadata`` (self-describing traces); ``format="jsonl"`` writes the
        greppable one-object-per-line stream instead."""
        from . import export as _export

        if format in ("json", "chrome"):
            _export.chrome_trace(path)
        elif format in ("jsonl", "ndjson"):
            _export.jsonl(path)
        else:
            raise ValueError(f"unknown export format {format!r}")

    def summary(self, sorted_by="total", op_detail=True, thread_sep=False, time_unit="ms"):
        """Aggregate table over events + spans: calls, total, avg, min, max
        per name (reference profiler.summary shape). ``sorted_by`` one of
        ``total``/``calls``/``avg``/``min``/``max``/``name`` (None =
        total)."""
        from . import export as _export

        div = {"s": 1e9, "ms": 1e6, "us": 1e3, "ns": 1.0}.get(time_unit, 1e6)
        agg: Dict[str, list] = {}
        rows = [
            (e.name, e.end - e.start) for e in _export.merged_events()
        ] + [
            (s["name"], s["t1"] - s["t0"]) for s in _export.merged_spans()
        ]
        for name, dur in rows:
            r = agg.get(name)
            if r is None:
                agg[name] = [1, dur, dur, dur]
            else:
                r[0] += 1
                r[1] += dur
                r[2] = min(r[2], dur)
                r[3] = max(r[3], dur)

        sorted_by = sorted_by or "total"
        keys = {
            "total": lambda kv: -kv[1][1],
            "calls": lambda kv: -kv[1][0],
            "avg": lambda kv: -(kv[1][1] / kv[1][0]),
            "min": lambda kv: -kv[1][2],
            "max": lambda kv: -kv[1][3],
            "name": lambda kv: kv[0],
        }
        if sorted_by not in keys:
            raise ValueError(
                f"summary: unknown sorted_by {sorted_by!r}; expected one of "
                f"{sorted(keys)}"
            )
        u = time_unit if time_unit in ("s", "ms", "us", "ns") else "ms"
        lines = [
            f"{'name':40s} {'calls':>8s} {'total_' + u:>12s} "
            f"{'avg_' + u:>10s} {'min_' + u:>10s} {'max_' + u:>10s}"
        ]
        for name, (calls, total, mn, mx) in sorted(agg.items(), key=keys[sorted_by]):
            lines.append(
                f"{name:40s} {calls:8d} {total / div:12.3f} "
                f"{total / calls / div:10.3f} {mn / div:10.3f} {mx / div:10.3f}"
            )
        return "\n".join(lines)


@contextlib.contextmanager
def profiler_guard(**kwargs):
    p = Profiler(**kwargs)
    p.start()
    try:
        yield p
    finally:
        p.stop()


# Submodules import the package (counters/memory/_enabled), so they load
# AFTER those definitions.
from . import flight  # noqa: E402,F401
from . import spans  # noqa: E402,F401
from .spans import kept_span, setup_account, span  # noqa: E402,F401

# Compilation is charged to the program span it fired under (spans.py). The
# listeners do work only when jax reports a compile stage.
jax.monitoring.register_event_duration_secs_listener(spans._on_compile_duration)
jax.monitoring.register_event_listener(spans._on_compile_event)


def events() -> List[_Event]:
    """Merged flat-event view across sinks (Python list + native ring)."""
    from . import export as _export

    return _export.merged_events()


def span_events() -> List[dict]:
    """Merged finished-span view (dicts with ids, tid, times, attrs)."""
    from . import export as _export

    return _export.merged_spans()


def export_metrics(path: Optional[str] = None, format: str = "json"):
    """Counter + memory snapshot as JSON (default) or Prometheus text
    exposition format; returns the serialized string (and writes it to
    ``path`` when given)."""
    from . import export as _export

    return _export.export_metrics(path, format=format)
