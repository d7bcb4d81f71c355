"""Serving engine — continuous batching + paged KV cache over compiled decode.

The inference stack's Predictor serves one fixed-shape request at a time;
real traffic is many concurrent autoregressive streams of ragged lengths.
This engine is the production front door over the scheduler-drivable decode
programs in ``models/generation.py``:

* **async request queue + continuous batching** — ``submit()`` enqueues from
  any thread; a dedicated engine thread admits and retires sequences EVERY
  decode step (a finished stream's slot is refilled next step, not at the
  end of a static batch), so batch occupancy tracks offered load;
* **bucketed batch shapes** — prompts prefill in length buckets (powers of
  two in block units) at a fixed prefill batch width, decode runs at the
  smallest power-of-two batch width covering the live set; each bucket jits
  ONCE per engine (``serve_compiles``) and warm executables reuse the
  persistent compilation cache across processes (PR 1);
* **paged KV cache** — fixed-size KV blocks in a preallocated pool, a
  per-sequence block table read by the block-table attention kernel
  (``build_paged_decode_kernel``: only the blocks a row has live; the
  gather step ``build_paged_decode`` is its plain reference and the CPU
  tier's decode: the arch's one ``qkv`` / ``finish`` around another read of
  the context), so HBM holds ``Σ ceil(len/block)`` blocks
  instead of ``B × T_max`` dense caches. Pool exhaustion is backpressure:
  admission stalls the queue, and a running sequence that can't grow evicts
  the youngest peer (freed blocks, state requeued for re-prefill from its
  accumulated tokens) rather than failing anything;
* **prefill/decode phase separation** — prompt prefill is a dense causal
  pass batched by length bucket; decode is one packed batch with per-row
  positions and live masks;
* **the decode loop runs one step ahead of the host** — in one scheduler
  iteration ``_decode`` builds and enqueues step k+1, THEN reads back and
  lands step k; the tokens step k+1 feeds stay on the device
  (``generation.feed_tokens_back``: ``where(src >= 0, prev[src], host)``),
  so host and device no longer take turns. The price is a ONE-STEP
  RETIREMENT LAG: a row whose budget ends with the token in flight is left
  out of step k+1 (the host can count), but a row that ends on an EOS value,
  a cancel or a deadline is found one step late; the row-step computed for
  it is thrown away (``serve_decode_wasted_rows``), its token reaches neither
  the result nor the stream, and its blocks are freed when it retires (the
  device runs programs in order, so whoever inherits a block writes it after
  the dead row's last write). THE DRAIN RULE: anything that takes a sequence
  out of the running set other than a landing first lands the step in
  flight (``_drain``, ``serve_decode_drains``): eviction from block growth,
  the OOM back-off, snapshot / handoff, shutdown and crash containment. The
  speculative step stays synchronous (its accept length is a host
  comparison). Nothing chooses between the two but what the engine can
  observe: ``spec_k``, a row's budget, whether a request is done;
* **int8 serving** (``int8=True``) — weight-only int8 via the PTQ rounding
  (serving/int8.py), dequantized inside the compiled programs;
* **deadlines, priorities, load shedding** (resilience layer) —
  ``submit(deadline_s=, priority=)`` attaches a completion deadline and an
  admission/eviction priority to a request. The scheduler sheds expired and
  doomed requests at admission and at every step boundary (a queued request
  that cannot meet its deadline even if admitted now — prefill + full token
  budget at the measured decode-step EMA — fails early with a structured
  :class:`DeadlineExceeded` instead of occupying the batch), eviction under
  pool pressure is priority-then-youngest, and the overload policy
  (``FLAGS_serve_max_queue`` + ``FLAGS_serve_shed``) turns unbounded queue
  growth into fast-fail :class:`Overloaded` with a Retry-After-style
  ``retry_after_s`` hint. None of it costs anything unconfigured: the sweep
  is gated on a has-deadlines bool, priority selection on a has-priorities
  bool, the shed check is two attribute probes — zero threads, zero host
  syncs (pinned by the inert tripwire in tests/test_serving_resilience.py);
* **liveness + drain** — the scheduler thread heartbeats every loop
  iteration (``health()``/``ready()`` probes read it; a ServingSupervisor
  monitors it), and ``close(drain=True)`` stops admission, completes queued
  and running work, then stops — the graceful-rolling-restart half of the
  supervisor's crash/wedge recovery (serving/supervisor.py).

* **HBM pressure** (fault/memory.py) — a ``RESOURCE_EXHAUSTED`` inside a
  serving step is classified and answered by PARKING free KV blocks
  (``PagePool.park`` — admission headroom shrinks, continuous batching
  backs off to a smaller resident working set) and retrying on the next
  scheduler iteration: the PR 11 invariant "pool exhaustion is never a
  crash" extends to HBM exhaustion — streams complete late under
  backpressure. Training-side pressure reaches live engines through
  ``request_pool_shrink`` (the registered ``free_pressure`` handler), and a
  shrink-proof OOM streak falls through to the crash-containment path so
  clients are never hung.

Every scheduler action is a profiler span (``schedule`` holding ``admit``,
``prefill`` with ``prefill_readback``/``prefill_land``, ``decode_build``,
``decode_step`` with ``decode_readback``/``decode_land``, and ``evict``)
with ``serve_*`` counters: the host phases of a step are named where the
work happens, and lie in a ``jax.profiler`` trace beside the device's
operations (profiler/spans.py). In the plain loop a ``decode_step`` holds the
enqueue of step k+1 (its self time) and the read-back and landing of step k,
whose ``rows`` / ``bucket`` / ``step`` it carries (``ahead`` 1; 0 where
nothing was enqueued behind another); ``serve_decode_ahead`` over
``serve_decode_steps`` is how often the loop ran ahead. The engine registers
a flight-recorder
context provider so crash dumps carry the in-flight request table. Chaos
points ``serve.crash``
/ ``serve.wedge`` / ``serve.slow_step`` / ``serve.pool_corrupt`` /
``hbm.oom`` / ``hbm.pressure`` (fault/inject.py) fire at the scheduler
step boundary when armed; ``serve.snapshot_corrupt`` tears a state capture
inside :meth:`Engine.snapshot` so adoption must fall back.

Serving state durability (snapshot/adopt/handoff): the engine's whole live
state — page pool bookkeeping, KV pool arrays, per-sequence block tables,
and the prefix-cache chain — is capturable at a step boundary
(:meth:`Engine.snapshot`), adoptable by a fresh engine
(:meth:`Engine.adopt`: survivors resume mid-decode with ZERO re-prefilled
tokens; a capture that fails validation falls back whole to re-prefill
through the preemption/resume machinery), and transferable end-to-end by
:meth:`Engine.handoff` (quiesce → export snapshot + queue + in-flight
handles → successor adopts) — the zero-downtime restart/upgrade primitive.
"""
from __future__ import annotations

import collections
import contextlib
import copy
import itertools
import queue as _queue
import threading
import time
import weakref
from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np

from ..fault import inject as _inject
from ..framework import flags
from ..profiler import counter_inc, flight
from ..profiler.spans import account_row, current_span, kept_span, span
from .pool import PagePool, SnapshotError, TRASH_BLOCK

__all__ = [
    "Engine", "EngineConfig", "RequestHandle", "Readiness", "ServeError",
    "RequestCancelled", "DeadlineExceeded", "Overloaded", "SnapshotError",
]

SNAPSHOT_VERSION = 1  # engine-level snapshot format (pool has its own)

_engine_ids = itertools.count(1)


class ServeError(RuntimeError):
    pass


class RequestCancelled(ServeError):
    pass


class DeadlineExceeded(ServeError):
    """The request's ``deadline_s`` passed (or provably cannot be met) before
    completion — shed by the scheduler at admission or a step boundary."""

    def __init__(self, msg: str, request_id: Optional[int] = None):
        super().__init__(msg)
        self.request_id = request_id


class Overloaded(ServeError):
    """Fast-fail load shed: the submission queue hit ``FLAGS_serve_max_queue``
    with ``FLAGS_serve_shed`` armed. ``retry_after_s`` is the Retry-After-style
    backoff hint (estimated time for one queue slot to drain)."""

    def __init__(self, msg: str, retry_after_s: float = 0.0):
        super().__init__(msg)
        self.retry_after_s = float(retry_after_s)


class Readiness(dict):
    """``ready()`` payload: a JSON-able dict (the ``/readyz`` body) whose
    truth value is the ready bit itself, so ``if eng.ready():`` call sites
    keep their boolean semantics."""

    def __bool__(self) -> bool:
        return bool(self.get("ready"))


class EngineConfig:
    """Serving knobs. ``None`` fields resolve from the ``FLAGS_serve_*``
    registry at engine construction, so fleet-wide defaults are one
    ``set_flags`` away while tests override per-engine."""

    def __init__(self, block_size=None, num_blocks=None, max_batch=None,
                 max_seq_len=None, prefill_batch=None, int8=None,
                 decode_buckets=None, seed=0, max_queue=None, shed=None,
                 prefix_cache=None, spec_k=None, drafter=None,
                 draft_window=None, tp=None, prefill_chunk=None,
                 tp_int8=None, trace=None, metrics_port=None):
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len
        self.prefill_batch = prefill_batch
        self.int8 = int8
        self.decode_buckets = decode_buckets
        self.seed = seed
        self.max_queue = max_queue
        self.shed = shed
        # throughput multipliers (PR 16): prefix-cache KV sharing and
        # speculative decoding. ``drafter`` is "ngram" or a small
        # same-family model instance (same tokenizer/vocab as the target).
        self.prefix_cache = prefix_cache
        self.spec_k = spec_k
        self.drafter = drafter
        self.draft_window = draft_window
        # mesh-native serving (PR 19): tensor-parallel degree, chunked
        # prefill grain, and EQuARX-style int8 tp collectives
        self.tp = tp
        self.prefill_chunk = prefill_chunk
        self.tp_int8 = tp_int8
        # SLO observability (PR 20): request tracing + token-latency
        # histograms + cost-drift gauges, and the opt-in telemetry endpoint
        # (0 = no HTTP thread)
        self.trace = trace
        self.metrics_port = metrics_port

    def resolve(self, model_max_positions: int) -> "EngineConfig":
        def pick(v, name):
            # explicit 0 must reach validation, not silently fall back
            return int(v if v is not None else flags.flag(name))

        self.block_size = pick(self.block_size, "FLAGS_serve_block_size")
        self.num_blocks = pick(self.num_blocks, "FLAGS_serve_num_blocks")
        self.max_batch = pick(self.max_batch, "FLAGS_serve_max_batch")
        self.prefill_batch = pick(self.prefill_batch, "FLAGS_serve_prefill_batch")
        max_seq = pick(self.max_seq_len, "FLAGS_serve_max_seq_len")
        self.max_seq_len = min(max_seq, int(model_max_positions))
        if self.int8 is None:
            self.int8 = bool(flags.flag("FLAGS_serve_int8", False))
        # 0 is the meaningful default here (unbounded queue), so only None
        # falls back to the flag
        self.max_queue = int(self.max_queue if self.max_queue is not None
                             else flags.flag("FLAGS_serve_max_queue", 0))
        if self.shed is None:
            self.shed = bool(flags.flag("FLAGS_serve_shed", False))
        if self.prefix_cache is None:
            self.prefix_cache = bool(flags.flag("FLAGS_serve_prefix_cache",
                                                False))
        self.spec_k = int(self.spec_k if self.spec_k is not None
                          else flags.flag("FLAGS_serve_spec_k", 0))
        if self.drafter is None:
            self.drafter = flags.flag("FLAGS_serve_drafter", "ngram")
        self.draft_window = int(self.draft_window
                                if self.draft_window is not None
                                else flags.flag("FLAGS_serve_draft_window", 64))
        self.tp = pick(self.tp, "FLAGS_serve_tp")
        self.prefill_chunk = pick(self.prefill_chunk,
                                  "FLAGS_serve_prefill_chunk")
        if self.tp_int8 is None:
            self.tp_int8 = bool(flags.flag("FLAGS_serve_tp_int8", False))
        if self.trace is None:
            self.trace = bool(flags.flag("FLAGS_serve_trace", False))
        self.metrics_port = int(
            self.metrics_port if self.metrics_port is not None
            else flags.flag("FLAGS_serve_metrics_port", 0))
        if self.metrics_port < 0:
            raise ValueError("serving: metrics_port must be >= 0 (0 = off)")
        if self.tp < 0:
            raise ValueError("serving: tp must be >= 0 (0/1 = single-chip)")
        if self.prefill_chunk < 0:
            raise ValueError("serving: prefill_chunk must be >= 0 "
                             "(0 = monolithic prefill)")
        if self.prefill_chunk and self.block_size \
                and self.prefill_chunk % self.block_size:
            raise ValueError(
                "serving: prefill_chunk must be a multiple of block_size "
                "(chunk boundaries write K/V through the paged scatter)")
        if self.tp >= 2 and (self.spec_k or 0) > 0:
            raise ValueError(
                "serving: speculative decoding is not yet supported under "
                "tensor-parallel serving (set spec_k=0 or tp<=1)")
        if self.spec_k < 0:
            raise ValueError("serving: spec_k must be >= 0")
        if self.spec_k and self.draft_window < 2:
            raise ValueError("serving: draft_window must be >= 2")
        if self.block_size < 1 or self.num_blocks < 2 or self.max_batch < 1 \
                or self.prefill_batch < 1 or self.max_seq_len < 1:
            raise ValueError(
                "serving: block_size/max_batch/prefill_batch/max_seq_len "
                ">= 1 and num_blocks >= 2 required"
            )
        if self.max_queue < 0:
            raise ValueError("serving: max_queue must be >= 0 (0 = unbounded)")
        if self.decode_buckets is None:
            b, buckets = 1, []
            while b < self.max_batch:
                buckets.append(b)
                b *= 2
            self.decode_buckets = tuple(buckets) + (self.max_batch,)
        else:
            # drop widths past the ceiling, keep ascending order, and make
            # sure max_batch itself is present so every live set has a bucket
            kept = sorted({int(b) for b in self.decode_buckets
                           if 0 < int(b) <= self.max_batch})
            if not kept or kept[-1] != self.max_batch:
                kept.append(self.max_batch)
            self.decode_buckets = tuple(kept)
        return self


class _Request:
    __slots__ = (
        "id", "prompt", "max_new_tokens", "eos_token_id", "temperature",
        "tokens", "error", "done", "stream_q", "cancelled",
        "t_submit", "t_done", "priority", "deadline",
        # SLO observability (PR 20): the trace id rides the request object
        # itself, so snapshot/harvest/handoff records (which carry requests
        # whole) preserve it across recovery with no extra plumbing
        "trace", "t_submit_ns", "t_first_tok", "t_last_tok",
    )

    def __init__(self, rid, prompt, max_new_tokens, eos_token_id, temperature,
                 stream, priority=0, deadline=None):
        self.id = rid
        self.prompt = prompt  # list[int]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.temperature = float(temperature)
        self.tokens: Optional[List[int]] = None  # final ids, set at finish
        self.error: Optional[BaseException] = None
        self.done = threading.Event()
        self.stream_q = _queue.Queue() if stream else None
        self.cancelled = False
        self.t_submit = time.monotonic()
        self.t_done: Optional[float] = None
        self.priority = int(priority)           # higher = more important
        self.deadline = deadline                # absolute monotonic, or None
        self.trace: Optional[str] = None        # set by observe.on_submit
        self.t_submit_ns = 0                    # span-clock submit stamp
        self.t_first_tok = 0.0                  # first-token wall time (TTFT)
        self.t_last_tok = 0.0                   # last-token wall time (gaps)


def _finish(req: _Request, tokens=None, error=None, count=True) -> bool:
    """Terminal state for a request: result lands, the stream closes, the
    handle's waiters wake. Returns False when the request was already
    finished — crash sweeps, supervisor relays, and the scheduler may race,
    and first-writer-wins keeps that benign. Shared with the
    ServingSupervisor, which finishes ORPHANED requests (their engine is
    dead) without an Engine instance in hand. ``count=False`` skips the
    lifecycle counters: a relay completing the ORIGINAL of a requeued
    request would otherwise double-count the continuation the new engine
    already counted."""
    if req.done.is_set():
        return False
    req.tokens = list(tokens) if tokens is not None else None
    req.error = error
    req.t_done = time.monotonic()
    if count:
        counter_inc("serve_cancelled" if isinstance(error, RequestCancelled)
                    else "serve_failed" if error is not None
                    else "serve_retired")
    if req.stream_q is not None:
        req.stream_q.put(None)
    req.done.set()
    return True


def _ngram_propose(tokens, k: int, max_n: int = 3) -> List[int]:
    """Prompt-lookup drafting (the zero-model fallback drafter): find the
    most recent EARLIER occurrence of the longest suffix n-gram
    (n = max_n..1) and propose the up-to-k tokens that followed it. Returns
    [] when nothing recurs — the verify step then degenerates to plain
    decode for that row. O(len²) worst case on pathological prompts; real
    traffic hits in the first few candidates."""
    L = len(tokens)
    for n in range(min(max_n, L - 1), 0, -1):
        pat = tokens[L - n:]
        for i in range(L - n - 1, -1, -1):
            if tokens[i:i + n] == pat:
                fol = tokens[i + n:i + n + k]
                if fol:
                    return fol
    return []


class _PrefixCache:
    """Hash-keyed index of shared prompt-prefix KV blocks (engine-thread
    only, like the pool it feeds). Chained block-granularity hashes: block
    j's key is ``(parent block id, tuple of chunk-j tokens)`` — the parent
    link pins the exact content of everything before the chunk, so two
    different prefixes can never alias through a hash collision (dict
    hashing is a fast path, equality is exact). The index holds its OWN
    reference on every cached block (``PagePool.share``), so retirement of
    the inserting sequence leaves the KV resident for future admissions;
    :meth:`evict` drops LRU LEAF entries whose block nobody else maps
    (refcount 1) — pinned shared blocks and chain interiors are never
    evicted from under a reader."""

    __slots__ = ("_pool", "_bs", "_entries", "_by_bid", "_tick")

    def __init__(self, pool: PagePool, block_size: int):
        self._pool = pool
        self._bs = block_size
        # key -> [block id, last-use tick, cached-child count]
        self._entries: Dict[tuple, list] = {}
        self._by_bid: Dict[int, tuple] = {}  # reverse map for chain edits
        self._tick = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def blocks(self) -> int:
        """Pool blocks currently pinned by the index."""
        return len(self._entries)

    def match(self, tokens, limit: int) -> List[int]:
        """Longest cached chain over full block-size chunks of ``tokens``,
        capped at ``limit`` blocks. Returns block ids WITHOUT bumping
        refcounts — the caller shares them once the rest of admission is
        known to succeed."""
        self._tick += 1
        bids: List[int] = []
        parent = -1
        for j in range(limit):
            ent = self._entries.get(
                (parent, tuple(tokens[j * self._bs:(j + 1) * self._bs])))
            if ent is None:
                break
            ent[1] = self._tick
            bids.append(ent[0])
            parent = ent[0]
        return bids

    def insert(self, tokens, blocks, start: int, full: int) -> int:
        """Index ``blocks[start:full]`` of a freshly prefilled sequence
        (chunk j's chain parent is ``blocks[j-1]``, cached and fresh blocks
        alike). Stops at the first already-present key: that content is
        cached under a DIFFERENT block id, and chaining ours beside it
        would orphan the children. Takes one index-owned reference per
        inserted block."""
        inserted = 0
        for j in range(start, full):
            parent = -1 if j == 0 else blocks[j - 1]
            key = (parent, tuple(tokens[j * self._bs:(j + 1) * self._bs]))
            if key in self._entries:
                break
            self._pool.share([blocks[j]])
            self._tick += 1
            self._entries[key] = [blocks[j], self._tick, 0]
            self._by_bid[blocks[j]] = key
            pk = self._by_bid.get(parent)
            if pk is not None:
                self._entries[pk][2] += 1
            inserted += 1
        return inserted

    def evict(self, need: int) -> int:
        """Free up to ``need`` blocks by dropping LRU leaf entries whose
        block only the index maps; dropping a leaf may expose its parent as
        the next candidate. Returns blocks actually returned to the free
        list (0 when everything left is pinned)."""
        freed = 0
        while freed < need:
            leaves = [(ent[1], key) for key, ent in self._entries.items()
                      if ent[2] == 0 and self._pool.refcount(ent[0]) == 1]
            if not leaves:
                break
            freed += self._drop(min(leaves)[1])
        if freed:
            counter_inc("serve_prefix_evicted", freed)
        return freed

    def _drop(self, key) -> int:
        bid = self._entries.pop(key)[0]
        del self._by_bid[bid]
        pk = self._by_bid.get(key[0])
        if pk is not None:
            self._entries[pk][2] -= 1
        self._pool.free([bid])
        return 1

    def release_all(self) -> None:
        """Drop every index-owned reference (engine shutdown)."""
        bids = [ent[0] for ent in self._entries.values()]
        self._entries.clear()
        self._by_bid.clear()
        if bids:
            self._pool.free(bids)


class _Seq:
    """Scheduler-side state of one admitted sequence. ``tokens`` holds
    prompt + generated ids; the newest id's KV is NOT yet in cache — its
    write position is ``pos = len(tokens) - 1``, which is also the next
    decode step's fed token. ``cached_blocks`` counts the leading blocks
    admission matched from the prefix cache (shared, already filled — the
    prefill pass runs only the tail). ``chunk_pos`` is the chunked-prefill
    cursor: prompt tokens below it have K/V in cache (0 outside the chunked
    path, where the whole prompt lands in one prefill pass). ``slot`` is the
    row's index in the decode step in flight (-1 when it has none): that
    step writes ``pos`` and its token is still on the device, so the step
    enqueued behind it feeds ``prev[slot]`` at ``pos + 1``. ``row_slot`` is
    the sequence's slot in the caches that hold a fixed size a row (a window,
    a recurrent state): granted with its blocks, held until it retires or is
    evicted, 0 (the trash slot) for an arch that has none."""

    __slots__ = ("req", "tokens", "blocks", "prompt_len", "cached_blocks",
                 "chunk_pos", "slot", "row_slot")

    def __init__(self, req: _Request, tokens: List[int]):
        self.req = req
        self.tokens = tokens
        self.blocks: List[int] = []
        self.prompt_len = len(req.prompt)
        self.cached_blocks = 0
        self.chunk_pos = 0
        self.slot = -1
        self.row_slot = 0

    @property
    def pos(self) -> int:
        return len(self.tokens) - 1

    @property
    def next_pos(self) -> int:
        """The position the next step to be BUILT writes: one past ``pos``
        while a step is in flight for the row."""
        return len(self.tokens) - (self.slot < 0)

    @property
    def ends_in_flight(self) -> bool:
        """The token in flight is the last of the row's budget: known
        without reading it, so the row is not fed again."""
        return self.slot >= 0 \
            and self.generated + 1 >= self.req.max_new_tokens

    @property
    def generated(self) -> int:
        return len(self.tokens) - self.prompt_len


class _Flight(NamedTuple):
    """The decode step in flight: enqueued, its tokens not yet read."""

    arrays: tuple    # the program's outputs past the pools: ``next_tokens``
    #                  padded to ``max_batch``, then what the arch reports
    rows: list       # the sequences by row at dispatch
    bucket: int
    pos: np.ndarray  # the position each row's step writes
    t0: float        # dispatch time and whether the program was warm: what
    warm: bool       # the step-time EMA needs at landing


class RequestHandle:
    """Client-side handle: blocking ``result()``, streaming iteration, and
    ``cancel()``."""

    def __init__(self, req: _Request, engine: "Engine"):
        self._req = req
        self._engine = engine

    @property
    def request_id(self) -> int:
        return self._req.id

    @property
    def done(self) -> bool:
        return self._req.done.is_set()

    @property
    def latency_s(self) -> Optional[float]:
        t = self._req.t_done
        return None if t is None else t - self._req.t_submit

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Full token ids (prompt + generated), like ``generate()``. Raises
        the request's failure (``RequestCancelled`` after ``cancel()``)."""
        if not self._req.done.wait(timeout):
            raise TimeoutError(f"request {self._req.id} still in flight")
        if self._req.error is not None:
            raise self._req.error
        return list(self._req.tokens)

    def cancel(self) -> None:
        self._engine._cancel(self._req)

    def __iter__(self):
        """Generated token ids as they land (``submit(stream=True)``). Ends
        cleanly on completion OR cancellation; terminal errors re-raise.
        One-shot: tokens are consumed destructively, and iterating a handle
        whose stream was already drained terminates instead of blocking."""
        if self._req.stream_q is None:
            raise ServeError("submit(stream=True) to iterate tokens")

        def finish():
            if self._req.error is not None and not isinstance(
                    self._req.error, RequestCancelled):
                raise self._req.error

        while True:
            try:
                # the timeout only matters on an already-drained stream
                # (sentinel consumed by a prior iteration); live streams
                # return as soon as a token lands
                item = self._req.stream_q.get(timeout=0.1)
            except _queue.Empty:
                if self._req.done.is_set() and self._req.stream_q.empty():
                    finish()
                    return
                continue
            if item is None:
                finish()
                return
            yield item


class Engine:
    """Continuous-batching serving engine over a paged KV cache.

    ``model`` says what serves it: ``model.decode_state()`` returns its arch
    plug and weight tree (``GPTForPretraining``, ``LlamaForCausalLM``,
    ``MLAMoEForCausalLM``, ``PhiFlashForCausalLM``; full logical weights).
    The engine thread owns all scheduler state; only the submission queue and
    stop flag cross threads (guarded below).

    The cache is what the arch declares (``generation.cache_pools``): a
    K pool and a V pool of ``(layers, blocks, block_size, kv_heads,
    head_dim)`` for GPT and Llama, ONE pool of padded latent rows for the MLA
    arch. ``PagePool``, block tables, growth, eviction and copy-on-write
    count blocks and never look inside one, so they serve either. An arch
    whose layers cache different KINDS of thing (``PhiFlashForCausalLM``: one
    layer paged K/V, eight a window a row, nine a recurrent state a row,
    fourteen nothing) gets a pool a kind over the layers of that kind, and
    each request a row slot beside its blocks, held for its life and freed
    with them (retire, evict); a re-prefill rebuilds what the slot held.
    """

    def __init__(self, model, config: Optional[EngineConfig] = None, **overrides):
        # ``engine_init``: what a process pays once an engine, in the set-up
        # account whether or not anything under it compiles
        with kept_span("engine_init") as sp:
            self._init(sp, model, config, overrides)

    def _init(self, sp, model, config, overrides):
        import jax
        import jax.numpy as jnp

        from ..models import generation as G

        self._jax, self._jnp, self._G = jax, jnp, G
        # the model says what serves it: its arch plug and weight tree
        if not callable(getattr(model, "decode_state", None)):
            raise TypeError(
                f"serving.Engine: unsupported model {type(model).__name__} "
                "(expected GPTForPretraining, LlamaForCausalLM, "
                "MLAMoEForCausalLM or PhiFlashForCausalLM: a model with "
                "decode_state(), whose arch says what each layer caches)"
            )
        arch_key, arch, params, max_pos = model.decode_state()
        if config is not None and overrides:
            raise ValueError("pass EngineConfig OR keyword overrides, not both")
        # resolve a COPY: the caller's EngineConfig stays pristine (this
        # engine's model clamps max_seq_len, so a reused config must not
        # carry one model's clamp into the next engine)
        cfg = copy.copy(config or EngineConfig(**overrides)).resolve(max_pos)
        self.config = cfg
        self._arch = arch
        self._arch_key = arch_key
        self._dtype = params["wte"].dtype
        self._compute_params = params
        # tensor-parallel serving (PR 19): tp >= 2 shards heads / FFN
        # columns / the LM head / the KV pool over a "tp" mesh axis. 0/1
        # leaves every code path below byte-for-byte the single-chip one.
        self._tp = int(cfg.tp) if int(cfg.tp) >= 2 else 0
        # an arch with the plain prefill and decode programs and no other
        # yet: every other path is refused by name, here or at its call,
        # never served by GPT's code. One that brings the layer of a tail
        # call (the MLA arch) has the tail program, so chunked prefill is
        # served; what it still lacks of the prefix cache is the index
        tail = "tail_layer" in arch
        for path, on in (("tp", self._tp), ("int8", cfg.int8),
                         ("speculative verify", int(cfg.spec_k)),
                         ("the prefix index" if tail
                          else "prefix cache / tail prefill", cfg.prefix_cache),
                         ("chunked prefill",
                          int(cfg.prefill_chunk) and not tail)):
            if on:
                self._refuse(path)
        self._tp_mesh = None
        self._tp_vocab = None
        if self._tp:
            ndev = len(jax.devices())
            if self._tp > ndev:
                raise ValueError(
                    f"serving: tp={self._tp} exceeds the {ndev} visible "
                    "devices")
            from jax.sharding import Mesh

            self._tp_mesh = Mesh(
                np.array(jax.devices()[:self._tp]), ("tp",))
            G.tp_validate(arch_key, params, self._tp)
        if cfg.int8:
            from .int8 import attach_int8_head, dequantize_tree, \
                quantize_params

            with kept_span("quantize_params"):
                self._compute_params = quantize_params(params)
            if flags.flag("FLAGS_serve_int8_kernel", False):
                # keep the head's int8 bytes visible to the compiled step so
                # the decode head runs the weight-only int8_matmul kernel
                self._dequant = lambda p, _d=self._dtype: attach_int8_head(
                    dequantize_tree(p, _d), p)
            else:
                self._dequant = lambda p, _d=self._dtype: dequantize_tree(
                    p, _d)
        else:
            self._dequant = None
        if self._tp:
            # pack the (possibly int8-tagged) tree into per-device column
            # slices stacked on a leading tp axis; dequantization moves
            # INSIDE the shard_map body (per-tensor scales make
            # slice-then-dequantize bitwise dequantize-then-slice), so the
            # engine-side wrapper is retired. FLAGS_serve_int8_kernel is a
            # single-chip head fusion and is ignored under tp.
            with kept_span("pack_params", tp=self._tp):
                packed, self._tp_vocab = G.tp_pack_params(
                    arch_key, self._compute_params, self._tp)
                rep_s, shard_s = G.tp_param_shardings(self._tp_mesh)
                self._compute_params = {
                    "rep": jax.device_put(packed["rep"], rep_s),
                    "shard": jax.device_put(packed["shard"], shard_s),
                }
            self._dequant = None
        self._n_layers = len(params.get("layers", ()))
        self._spec_k = int(cfg.spec_k)
        # speculative verify writes reach pos + spec_k: widen the block
        # tables so a real write can never clamp into the trash block
        self._max_blocks = -(-(cfg.max_seq_len + self._spec_k)
                             // cfg.block_size)
        # under tp the KV pool is sharded on the kv-heads axis: every device
        # owns heads/tp of EVERY block, so the replicated host-side block
        # tables / PagePool bookkeeping index all shards identically. The
        # zeros are CREATED sharded: built on one device and then spread, a
        # pool sized for the mesh does not fit the chip it starts on.
        pool_s = G.tp_pool_sharding(self._tp_mesh) if self._tp else None
        # a pool a kind of cache, over the layers of that kind: one paged
        # kind over every layer unless the arch says what each layer caches
        pools = G.cache_pools(arch, self._n_layers, cfg.num_blocks,
                              cfg.block_size, cfg.max_batch)
        with kept_span("pool_alloc", pools=len(pools)):
            self._cache = tuple(
                jnp.zeros(shape, dtype or self._dtype, device=pool_s)
                for _, shape, dtype in pools)
        self._cache_kinds = tuple(kind for kind, _, _ in pools)
        sp.set(pool_bytes=sum(int(c.nbytes) for c in self._cache),
               pool_blocks=int(cfg.num_blocks),
               params_bytes=sum(int(getattr(a, "nbytes", 0)) for a in
                                jax.tree_util.tree_leaves(self._compute_params)))
        self._pool = PagePool(cfg.num_blocks)
        # row slots of the caches that hold a fixed size a row (None: the
        # arch has none); slot 0 is the trash slot of a bucket's padding
        self._row_slots = (list(range(cfg.max_batch, 0, -1))
                           if G.cache_slots(arch) else None)
        self._window = (arch["cache"].get("window_tokens", 0)
                        if self._row_slots is not None else 0)
        sp.set(row_slots=len(self._row_slots or ()))
        self._state_rebuilds = self._state_rows = 0
        # routed experts: live tokens each expert took, by expert layer, as
        # the programs report them beside their tokens
        self._expert_tokens = (
            np.zeros((arch["expert_layers"], arch["experts"]), np.int64)
            if arch.get("experts") else None)
        self._prefill_buckets = self._make_prefill_buckets()
        self._prefix = (_PrefixCache(self._pool, cfg.block_size)
                        if cfg.prefix_cache else None)
        # drafter: None when spec is off, True for the host-side n-gram
        # proposer, or (arch, params, window) for a small model drafter
        self._drafter = None
        if self._spec_k:
            d = cfg.drafter
            if d is None or d == "ngram":
                self._drafter = True
            elif isinstance(d, str):
                raise ValueError(f"serving: unknown drafter {d!r}")
            elif callable(getattr(d, "decode_state", None)):
                _, darch, dparams, dmax = d.decode_state()
                if darch.get("plain_paths_only"):
                    raise TypeError(
                        f"serving: unsupported drafter {type(d).__name__}")
                self._drafter = (darch, dparams,
                                 max(2, min(cfg.draft_window,
                                            int(dmax) - self._spec_k)))
            else:
                raise TypeError(
                    f"serving: unsupported drafter {type(d).__name__}"
                )

        # engine-thread-only scheduler state
        self._fns: Dict[tuple, object] = {}
        # (kind, *bucket) -> (its ``program_build`` span, the span it was
        # built under, whose first call compiles it): ``stats()["programs"]``
        self._programs: Dict[tuple, tuple] = {}
        # per-decode-bucket gather width (blocks), high-water, pow2-rounded
        self._decode_mb: Dict[int, int] = {}
        # decode reads K/V through the block-table kernel wherever Mosaic
        # compiles it for this arch (backend and head width); the
        # speculative and tail-prefill programs gather
        self._paged_kernel = bool(self._G.paged_kernel_default(arch))
        # what the kernel's copy schedule does with a step's positions, for
        # the ``decode_step`` span: the arch's own account, or a call a layer
        # of an arch that caches K and V per head
        self._step_attrs = arch.get("step_attrs")
        if self._paged_kernel and "cache" not in arch:
            count, reads = self._G.paged_step_attrs, ((
                self._n_layers, arch["kv_heads"] // (self._tp or 1),
                arch["rep"], arch["head_dim"], 0),)
            self._step_attrs = lambda *step: count(reads, *step)
        self._running: List[_Seq] = []
        # the decode loop runs one step ahead of the host: the step whose
        # tokens are still on the device (None between bursts and wherever
        # the true state was needed: _drain), what a step is fed for
        # ``prev`` when nothing is, and how often the mechanism engaged
        self._flight: Optional[_Flight] = None
        self._no_prev = jnp.zeros((cfg.max_batch,), jnp.int32)
        self._ahead = self._drains = self._wasted_rows = 0
        self._resume: List[_Seq] = []  # preempted, awaiting re-prefill
        self._admitting: List[_Seq] = []  # popped off the queue, mid-prefill
        # chunked prefill (PR 19): seqs whose prompt is being prefilled one
        # FLAGS_serve_prefill_chunk-token chunk per scheduler step, so a
        # long admit no longer stalls the live decode batch for a whole
        # prefill. 0 = monolithic prefill, the exact prior path.
        self._chunk = int(cfg.prefill_chunk) if int(cfg.prefill_chunk) > 0 \
            else 0
        # the longest un-cached prompt tail the whole-prompt program takes: a
        # chunk, or what the arch says that program holds where that is less
        # (``prompt_max``); a longer one is fed in calls, or refused at submit
        # where the engine has no ``prefill_chunk``
        self._prompt_max = arch.get("prompt_max")
        self._whole_max = min(self._chunk, self._prompt_max or self._chunk)
        self._prefilling: List[_Seq] = []
        self._chunk_counts: list = []  # expert counts of calls not read back yet
        self._chunk_tokens = 0  # the tokens those calls fed
        self._chunk_flight = None  # logits of a call that was not read back
        # analytic floor for the shed ETA while the decode EMA is cold: the
        # cost model's estimate of the per-step tp collective term (0.0 on
        # a single chip; a device the model holds no peaks for is an error)
        self._step_floor_s = 0.0
        if self._tp:
            from ..cost_model import CostModel

            fp32_b, int8_b = G.tp_collective_bytes(
                arch_key, params, cfg.max_batch, self._tp)
            wire = int8_b if cfg.tp_int8 else fp32_b
            self._step_floor_s = CostModel().kernel_estimate(
                "tp_collective", (int(wire), int(self._tp)), {}) / 1e3
        self._key = jax.random.PRNGKey(cfg.seed)
        self._rng = np.random.default_rng(cfg.seed)
        self._step_i = 0
        self._occ_live = 0
        self._occ_slots = 0
        # resilience gauges (engine-thread writes, racy cross-thread reads by
        # design): decode service-time EMA (compile steps excluded — it feeds
        # deadline feasibility), completed-request latency EMA (Retry-After
        # hints), and the scheduler-thread heartbeat that health()/the
        # supervisor read
        self._ema_step_s = 0.0
        self._ema_req_s = 0.0
        self._beat = time.monotonic()
        # True while a FIRST-CALL compiled program is building (jit compile
        # can dwarf a step): the supervisor widens its staleness limit 10x
        # so a cold start is not misread as a wedge — a thread genuinely
        # wedged inside a compile is still caught, just later
        self._compiling = False
        # HBM pressure (fault/memory.py): cross-thread shrink request the
        # scheduler applies at its next step boundary (engine-thread-only
        # pool ownership holds; -1 = default fraction; guarded by _cv), the
        # consecutive OOM-step streak that bounds in-place recovery before
        # the crash containment path takes over, and the clean-step
        # countdown that gradually returns parked blocks once pressure
        # clears (a transient OOM must not ratchet capacity down forever)
        self._shrink_req = 0  # guarded_by: _cv
        self._oom_streak = 0
        self._unpark_countdown = 0
        # serving SLO observability (PR 20): when armed, `_obs` is the
        # serving.observe module and the scheduler tags spans / feeds the
        # token-latency histograms / records cost drift. Unconfigured, the
        # module is never even imported and every hook site is one
        # attribute-is-None probe (inert tripwire).
        self._t_start = time.monotonic()
        self._obs = None
        self._endpoint = None
        if cfg.trace:
            from . import observe as _observe

            _observe.trace_book()  # create + register the span observer
            self._obs = _observe

        # cross-thread state
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._waiting: "collections.deque[_Request]" = collections.deque()  # guarded_by: _cv
        self._stop = False  # guarded_by: _cv
        self._draining = False  # guarded_by: _cv
        # serving state durability: handoff() sets the request word and the
        # scheduler consumes it at its next step boundary (quiesce, then the
        # thread exits WITHOUT failing handles — the exported snapshot owns
        # them). The unconfigured path costs one bool probe per iteration
        # inside an already-held _cv block (inert tripwire). _last_recovery
        # is the most recent adopt() outcome for health() probes (written
        # once per adopt on the adopting thread, racy reads by design).
        self._handoff_req = False  # guarded_by: _cv
        self._quiesced = threading.Event()
        self._last_recovery: Optional[dict] = None
        self._broken: Optional[BaseException] = None
        self._ids = itertools.count(1)
        # once-true latches (set under _cv, read lock-free by the scheduler):
        # the deadline sweep and the priority admission scan run ONLY after a
        # deadline'd / prioritized request has ever been submitted — the
        # unconfigured path stays a flag probe (inert tripwire)
        self._deadline_seen = False
        self._has_prio = False
        # supervision hooks (set by ServingSupervisor; None/False = PR 11
        # behavior exactly): a supervised crash leaves scheduler state for
        # the supervisor to harvest instead of failing every handle, and the
        # loop publishes serve.step phase records into the PR 8 watchdog
        # progress table
        self._supervised = False
        self._watchdog = None
        self._failed = threading.Event()

        # Both the flight registry and the scheduler thread hold only a
        # weakref: an abandoned (never-closed) engine stays collectable —
        # __del__ then runs close(), the thread exits at its next deref,
        # and the provider reports itself gone (the DevicePrefetcher
        # teardown discipline from PR 6).
        self._provider = f"serving_{next(_engine_ids)}"
        wr = weakref.ref(self)
        flight.add_context_provider(
            self._provider,
            lambda _wr=wr: (
                e._flight_context() if (e := _wr()) is not None
                else {"closed": True}
            ),
        )
        # the serving rung of fault/memory.free_pressure: a training-side
        # OOM can ask every live engine to give HBM back (pool headroom
        # shrink → admission backpressure). Weakly bound — a collected
        # engine drops out of the registry by itself.
        from ..fault import memory as _fmem

        _fmem.register_pressure_handler(
            self._provider, lambda eng: eng.request_pool_shrink(), owner=self)
        self._thread = threading.Thread(
            target=_engine_loop, args=(wr,), daemon=True, name=self._provider)
        self._thread.start()
        # telemetry endpoint last: its handlers probe health()/stats() on a
        # fully-constructed engine. Holds only a weakref to the engine; a
        # failed bind is a counter, never a serving failure.
        if cfg.metrics_port:
            from . import observe as _observe

            self._endpoint = _observe.start_endpoint(self, cfg.metrics_port)

    # ------------------------------------------------------------------ API
    def submit(self, prompt_ids, max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None, temperature: float = 0.0,
               stream: bool = False, deadline_s: Optional[float] = None,
               priority: int = 0, _shed_exempt: bool = False,
               _trace: Optional[str] = None) -> RequestHandle:
        """Enqueue one request (any thread). ``temperature == 0`` is greedy.
        ``stream=True`` additionally feeds the handle's iterator per token.
        ``deadline_s`` (seconds from now) attaches a completion deadline: the
        scheduler sheds the request with :class:`DeadlineExceeded` — raised
        from ``result()`` — once it expires or provably cannot finish in
        time. ``priority`` (higher = more important, default 0) orders
        admission and inverts eviction (priority-then-youngest). Under the
        shed policy (``max_queue`` + ``shed``) a full queue fast-fails this
        call with :class:`Overloaded` instead of queuing without bound —
        except for ``_shed_exempt`` submissions (supervisor-internal:
        requeued work the engine already accepted once must not be shed by
        its own recovery)."""
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if not prompt:
            raise ValueError("serving: empty prompt")
        if int(max_new_tokens) < 1:
            # prefill always yields the first generated token, so a 0-token
            # budget cannot honor the prompt+max_new output contract
            raise ValueError("serving: max_new_tokens must be >= 1")
        if deadline_s is not None and float(deadline_s) <= 0.0:
            raise ValueError("serving: deadline_s must be positive")
        total = len(prompt) + int(max_new_tokens)
        if total > self.config.max_seq_len:
            raise ValueError(
                f"serving: prompt + max_new_tokens = {total} exceeds "
                f"max_seq_len {self.config.max_seq_len}"
            )
        if self._prompt_max and not self._chunk \
                and len(prompt) > self._prompt_max:
            raise ValueError(
                f"serving: the {self._arch['name']} arch prefills a whole "
                f"prompt of at most {self._prompt_max} tokens, and this one "
                f"has {len(prompt)}; set prefill_chunk to serve it in calls")
        # spec verify maps up to spec_k write slots past the last token
        if -(-(total + self._spec_k) // self.config.block_size) \
                > self._pool.num_blocks - 1:
            raise ValueError(
                "serving: request needs more KV blocks than the whole pool; "
                "raise FLAGS_serve_num_blocks"
            )
        cfg = self.config
        with self._cv:
            if self._stop or self._broken is not None:
                raise ServeError("serving engine is closed") from self._broken
            if self._draining:
                raise ServeError(
                    "serving engine is draining (close(drain=True)); "
                    "submit to its replacement"
                )
            if cfg.shed and not _shed_exempt and cfg.max_queue > 0 \
                    and len(self._waiting) >= cfg.max_queue:
                counter_inc("serve_shed")
                hint = round(max(0.05, len(self._waiting)
                                 * (self._ema_req_s or 0.1) / cfg.max_batch), 3)
                raise Overloaded(
                    f"serving queue full ({len(self._waiting)} >= "
                    f"max_queue={cfg.max_queue}); retry after ~{hint}s",
                    retry_after_s=hint,
                )
            req = _Request(next(self._ids), prompt, max_new_tokens,
                           eos_token_id, temperature, stream,
                           priority=priority,
                           deadline=(time.monotonic() + float(deadline_s))
                           if deadline_s is not None else None)
            if req.deadline is not None:
                self._deadline_seen = True
            if req.priority != 0:
                self._has_prio = True
            if self._obs is not None:
                # assign (or, for a supervisor requeue carrying ``_trace``,
                # re-attach) the trace id before the scheduler can see the
                # request — the timeline must exist before its first event
                self._obs.on_submit(req, trace=_trace)
            self._waiting.append(req)
            counter_inc("serve_requests")
            self._cv.notify()
        return RequestHandle(req, self)

    def generate(self, prompt_ids, **kw) -> List[int]:
        """Synchronous convenience: submit + wait."""
        return self.submit(prompt_ids, **kw).result()

    def stats(self) -> dict:
        """Scheduler gauges (safe from any thread; running-set reads are
        racy snapshots by design)."""
        with self._lock:
            depth = len(self._waiting)
        occ = self._occ_live / self._occ_slots if self._occ_slots else 0.0
        return {
            "queue_depth": depth,
            "running": len(self._running),
            "preempted_waiting": len(self._resume),
            "batch_occupancy_mean": round(occ, 4),
            "pages_total": self._pool.num_blocks - 1,
            "pages_used": self._pool.used_blocks,
            "pages_free": self._pool.free_blocks,
            "pages_parked": self._pool.parked_blocks,
            "pages_cached": (self._prefix.blocks
                            if self._prefix is not None else 0),
            "compiles": len(self._fns),
            "programs": self._program_rows(),
            "decode_steps": self._step_i,
            "decode_ahead": self._ahead,
            "decode_drains": self._drains,
            "decode_wasted_rows": self._wasted_rows,
            **({"expert_tokens": self._expert_tokens.tolist()}
               if self._expert_tokens is not None else {}),
            **(self._slot_stats() if self._row_slots is not None else {}),
        }

    def _program_rows(self) -> List[dict]:
        """The cold-start report: one row a program this engine built, in
        the order they were built: ``kind`` and ``bucket``, ``build_s`` (the
        builder's Python and ``jax.jit``), then what its first call cost
        under the span that made it (``span``: a ``prefill``, a
        ``decode_step``): ``trace_s`` / ``lower_s`` / ``backend_s``, whether
        the persistent cache served it (``cache_hits`` / ``cache_misses``)
        and ``first_run_s``, that span's time less the stages and the build.
        The stages read 0 until that span has ended."""
        rows = []
        for key, (build, call) in list(self._programs.items()):
            first = account_row(call) if call is not None and call.t1 else {}
            build_s = build.dur_ns / 1e9
            rows.append({
                "kind": key[0], "bucket": list(key[1:]), "build_s": build_s,
                "span": call.name if call is not None else None,
                **{k: first.get(k, 0) for k in
                   ("trace_s", "lower_s", "backend_s", "cache_hits",
                    "cache_misses")},
                "first_run_s": max(first.get("first_run_s", 0.0) - build_s, 0.0),
            })
        return rows

    def _slot_stats(self) -> dict:
        """Of an arch whose caches are of several kinds: the row slots, the
        evictions that cost a re-prefill of state, the rows whose state the
        decode steps updated, the bytes held by kind."""
        held: Dict[str, int] = {}
        for kind, pool in zip(self._cache_kinds, self._cache):
            held[kind] = held.get(kind, 0) + int(pool.nbytes)
        total = self.config.max_batch
        return {"state_slots_total": total,
                "state_slots_used": total - len(self._row_slots),
                "state_rebuilds": self._state_rebuilds,
                "state_rows": self._state_rows,
                "cache_bytes": held}

    def _take_slot(self, seq: "_Seq"):
        """A row slot with the blocks admission just granted: there is one
        for every row the batch has room for."""
        if self._row_slots is not None:
            seq.row_slot = self._row_slots.pop()

    def _release(self, seq: "_Seq"):
        """Give back what a sequence holds of the caches: its blocks and its
        row slot. What the slot held is dead from here: whoever takes it next
        is prefilled into it, which overwrites every layer's window and
        state."""
        if seq.blocks:
            self._pool.free(seq.blocks)
            seq.blocks = []
        if seq.row_slot:
            self._row_slots.append(seq.row_slot)
            seq.row_slot = 0

    def _refuse(self, path: str):
        """Raise for a path the arch has no program for (an arch that says
        ``plain_paths_only``: the plain prefill and decode programs, and the
        tail program where it brings ``tail_layer``; GPT and Llama have every
        path)."""
        if self._arch.get("plain_paths_only"):
            raise NotImplementedError(
                f"serving: the {self._arch['name']} arch does not support "
                f"{path} yet")

    def _note_experts(self, sp, counts: np.ndarray, tokens: int):
        """A program's ``(expert layers, experts)`` count of the live tokens
        each expert took, onto its span, the counter and ``stats()``; of the
        ``tokens`` live tokens' (token, choice) pairs (``expert_pairs``), how
        many an expert this chip holds took (``expert_pairs_held``: the rows
        the expert product's combine moves). An arch whose counts are of the
        experts held alone says how many a token chooses
        (``experts_per_token``); one that counts every expert and holds some
        says which columns (``experts_held``)."""
        self._expert_tokens += counts
        touched = int(np.count_nonzero(counts))
        assigned = int(counts.sum())
        per_token = self._arch.get("experts_per_token")
        held = self._arch.get("experts_held")
        sp.set(experts_touched=touched, expert_tokens_max=int(counts.max()),
               expert_assignments=assigned,
               expert_pairs=(assigned if per_token is None
                             else int(tokens) * per_token * counts.shape[0]),
               expert_pairs_held=(assigned if held is None
                                  else int(counts[:, list(held)].sum())))
        counter_inc("serve_expert_assignments", assigned)

    def debug_requests(self) -> List[dict]:
        """Live in-flight request table (``/debug/requests``): phase, age,
        blocks held, trace id — the flight-provider data on demand instead
        of only post-mortem. Any thread; racy snapshot by design, same
        contract as :meth:`stats`."""
        now = time.monotonic()
        with self._lock:
            waiting = list(self._waiting)
        rows = [{
            "id": req.id, "phase": "queued",
            "age_s": round(now - req.t_submit, 3),
            "priority": req.priority, "prompt_len": len(req.prompt),
            "generated": 0, "blocks": 0, "trace": req.trace,
        } for req in waiting]
        for phase, seqs in (("prefilling", self._admitting),
                            ("chunk_prefill", self._prefilling),
                            ("running", self._running),
                            ("preempted", self._resume)):
            for s in list(seqs):
                rows.append({
                    "id": s.req.id, "phase": phase,
                    "age_s": round(now - s.req.t_submit, 3),
                    "priority": s.req.priority, "prompt_len": s.prompt_len,
                    "generated": s.generated, "blocks": len(s.blocks),
                    "trace": s.req.trace,
                })
        return rows

    def health(self) -> dict:
        """Liveness probe (any thread): scheduler-thread aliveness, heartbeat
        age, and failure state. ``ok`` is the single bit an external monitor
        should alarm on; the rest is diagnosis."""
        alive = self._thread.is_alive()
        with self._lock:
            depth = len(self._waiting)
            draining = self._draining
            stopped = self._stop
        beat_age = time.monotonic() - self._beat
        # heartbeat staleness folds into ok: an alive-but-wedged scheduler
        # must flip the probe even without a supervisor. Same staleness
        # contract as the supervisor: watchdog_s, 10x while a first-call
        # compile runs
        thr = max(1.0, float(flags.flag("FLAGS_serve_watchdog_s", 10.0) or 10.0))
        stale = beat_age > thr * (10.0 if self._compiling else 1.0)
        # last adopt() outcome (reattach|reprefill), or mode "none": probes
        # distinguish a degraded (re-prefill) recovery from clean. The
        # internal monotonic stamp becomes an AGE — a probe scraping two
        # replicas must not compare raw monotonic clocks across processes.
        lr = (dict(self._last_recovery) if self._last_recovery
              else {"mode": "none"})
        t_rec = lr.pop("t", None)
        if t_rec is not None:
            lr["age_s"] = round(time.monotonic() - t_rec, 3)
        return {
            "ok": alive and self._broken is None and not stopped and not stale,
            "thread_alive": alive,
            "beat_age_s": round(beat_age, 3),
            "stale": stale,
            "broken": repr(self._broken) if self._broken is not None else None,
            "draining": draining,
            "queue_depth": depth,
            "running": len(self._running),
            "pages_free": self._pool.free_blocks,
            "uptime_s": round(time.monotonic() - self._t_start, 3),
            "last_recovery": lr,
        }

    def ready(self) -> "Readiness":
        """Readiness probe: accepting new submissions right now — healthy,
        not draining, and (under the shed policy) queue below the cap. The
        rolling-restart contract: flip a replica's traffic away when this
        goes False, then ``close(drain=True)`` it. Returns a
        :class:`Readiness` dict (the ``/readyz`` body) that is truthy
        exactly when ready."""
        h = self.health()
        ready, reason = True, None
        if not h["ok"]:
            ready, reason = False, "unhealthy"
        elif h["draining"]:
            ready, reason = False, "draining"
        else:
            cfg = self.config
            if cfg.shed and cfg.max_queue > 0 \
                    and h["queue_depth"] >= cfg.max_queue:
                ready, reason = False, "queue_full"
        return Readiness(ready=ready, reason=reason,
                         queue_depth=h["queue_depth"],
                         uptime_s=h["uptime_s"],
                         last_recovery=h["last_recovery"])

    def close(self, timeout: float = 30.0, drain: bool = False) -> None:
        """Stop the engine thread. Plain ``close()`` fails outstanding
        requests with ``ServeError``; ``close(drain=True)`` first stops
        admission (``submit`` raises, ``ready()`` goes False) and lets
        queued + running work complete within ``timeout`` — the graceful
        half of a rolling restart. A ``join`` that times out (wedged
        scheduler thread) marks the engine broken and fails every
        outstanding handle instead of returning with clients blocked
        forever in ``result()``. Idempotent."""
        deadline = time.monotonic() + max(0.0, float(timeout))
        on_sched_thread = threading.current_thread() is self._thread
        if drain:
            with self._cv:
                self._draining = True
                self._cv.notify()
            if not on_sched_thread:
                self._thread.join(max(0.0, deadline - time.monotonic()))
        with self._cv:
            self._stop = True
            self._cv.notify()
        # provider first: it must go even when the join below is skipped
        # (close() can run ON the scheduler thread — __del__ fires there
        # when the loop's deref holds the last reference); same for this
        # engine's watchdog unit record — stale units must not outlive it
        flight.remove_context_provider(self._provider)
        if self._endpoint is not None:
            self._endpoint.close()
            self._endpoint = None
        from ..fault import memory as _fmem

        _fmem.unregister_pressure_handler(self._provider)
        if self._watchdog is not None:
            try:
                self._watchdog.remove_unit(self._provider)
            except Exception:  # lint: ok(oom-handler) — store bookkeeping, nothing dispatches in this try
                pass
        if not on_sched_thread:
            # drain path: the drain join above may have consumed the whole
            # budget on legitimate work — give the post-stop join a real
            # floor (a healthy thread exits within ~one step of _stop), so
            # a merely-slow drain is not misdiagnosed as a wedged scheduler
            self._thread.join(max(2.0 if drain else 0.1,
                                  deadline - time.monotonic()))
            if self._thread.is_alive():
                counter_inc("serve_wedged_close")
                self._broken = self._broken or ServeError(
                    f"serving engine scheduler thread wedged: close() join "
                    f"timed out after {timeout}s"
                )
        # Wedged join, a supervised crash whose supervisor never harvested,
        # or __del__ firing on the scheduler thread all leave handles
        # pending — fail them (handle state only, no pool mutation: a
        # wedged thread may still own the pool). No-op on a clean shutdown.
        self._fail_outstanding(self._broken or ServeError("serving engine closed"))

    def _fail_outstanding(self, err: BaseException) -> None:
        """Fail every pending handle without touching the page pool — safe
        to run from any thread, idempotent per request via the done-guard
        in ``_finish``."""
        with self._cv:
            waiting = list(self._waiting)
            self._waiting.clear()
        seqs = list(self._running) + list(self._resume) \
            + list(self._admitting) + list(self._prefilling)
        for req in waiting + [s.req for s in seqs]:
            try:
                self._finish_request(req, error=ServeError(str(err)))
            except Exception:  # lint: ok(oom-handler) — handle-state sweep, nothing dispatches in this try
                pass

    # -- serving state durability: snapshot / adopt / handoff -----------------
    def _compat_key(self) -> tuple:
        """Shape/dtype fingerprint an adopted snapshot must match exactly —
        the KV pool arrays and block tables are only meaningful against the
        same paged-cache geometry."""
        cfg = self.config
        # tp degree + KV shard layout close a silent-corruption hole: a
        # tp=2 pool array is numerically identical gathered, but adopting
        # it onto a different mesh shape would re-shard live KV under the
        # replicated block tables — refuse instead (structured error,
        # re-prefill fallback)
        return (self._n_layers, int(cfg.num_blocks), int(cfg.block_size),
                *self._G.cache_row_shapes(self._arch)[0],
                str(self._dtype), int(self._tp),
                "kv-shard/tp" if self._tp else "replicated")

    def snapshot(self) -> dict:
        """O(blocks) consistent capture of the live serving state: pool
        bookkeeping (with CRC), the KV pool arrays, every in-flight
        sequence's tokens + block table, the prefix-cache chain, and
        per-block KV content fingerprints.

        Caller contract: the scheduler must be quiesced (``handoff``) or
        dead (supervised crash — the loop's state is frozen) — a LIVE
        scheduler would tear the capture, so this refuses one. The capture
        shares the engine's immutable jnp arrays (cheap); on donating
        backends discard it after ``adopt`` — the successor's first step
        consumes the buffers."""
        self._refuse("snapshots")
        if self._thread.is_alive() and not self._quiesced.is_set() \
                and not self._failed.is_set():
            raise ServeError(
                "snapshot requires a quiesced or dead scheduler thread "
                "(use handoff(), or capture after a supervised crash)")
        self._settle()  # a capture holds landed tokens only
        with span("serve_snapshot", step=self._step_i,
                  running=len(self._running)) as sp:
            pool_snap = self._pool.snapshot()
            seqs, seen = [], set()
            for phase, group in (("running", self._running),
                                 ("resume", self._resume),
                                 ("admitting", self._admitting),
                                 ("prefilling", self._prefilling)):
                for s in group:
                    if s.req.id in seen:
                        continue  # landed mid-prefill: the _running view wins
                    seen.add(s.req.id)
                    seqs.append({"phase": phase, "req": s.req,
                                 "tokens": list(s.tokens),
                                 "blocks": list(s.blocks),
                                 "prompt_len": s.prompt_len,
                                 "cached_blocks": s.cached_blocks})
            prefix = None
            if self._prefix is not None:
                prefix = {"entries": {k: list(v) for k, v
                                      in self._prefix._entries.items()},
                          "tick": self._prefix._tick}
            owned = sorted(self._pool._owned)
            kpool, vpool = self._cache
            sums = self._G.kv_block_checksums(kpool, vpool, owned)
            snap = {"version": SNAPSHOT_VERSION, "compat": self._compat_key(),
                    "pool": pool_snap, "kpool": kpool,
                    "vpool": vpool, "seqs": seqs, "prefix": prefix,
                    "step_i": self._step_i,
                    "fingerprint": {"bids": owned, "sums": sums}}
            if _inject.should_fire("serve.snapshot_corrupt"):
                # chaos: tear the pool capture mid-write — the CRC no longer
                # matches, and adopt()'s validation MUST reject it whole
                if pool_snap["free"]:
                    pool_snap["free"].pop()
                else:
                    pool_snap["ref"] = dict(pool_snap["ref"],
                                            **{TRASH_BLOCK: 1})
            sp.set(seqs=len(seqs), owned_blocks=len(owned))
            counter_inc("serve_snapshots")
            return snap

    def adopt(self, snap: dict, only=None, fallback: str = "reprefill"):
        """Adopt a :meth:`snapshot` into THIS (fresh, traffic-free) engine.

        Validation first, mutation after: compat key, pool restore
        (conservation + CRC), per-sequence block-table coverage, prefix
        chain bijection/acyclicity, exact refcount↔mapping agreement, and
        KV content fingerprints all must hold before any state is
        installed. On success the survivors' ORIGINAL request objects go
        straight into the running set — they resume mid-decode with zero
        re-prefilled tokens and their existing handles/streams keep
        working. A capture that fails validation raises
        :class:`SnapshotError` when ``fallback="raise"``; with the default
        ``fallback="reprefill"`` every in-flight record is re-admitted
        whole through the preemption/resume machinery instead (re-prefill
        from accumulated tokens — never worse than the PR 12 path).

        ``only`` (set of request ids, or None for all) filters which
        records are adopted; the rest have their block references released.
        Returns an info dict: ``mode`` (reattach|reprefill), ``installed``
        (request ids now owned by this engine), block/token counts, and
        ``duration_s``."""
        t0 = time.monotonic()
        self._refuse("snapshots (adopt)")
        with span("serve_adopt", seqs=len(snap.get("seqs", ()))) as sp:
            try:
                pool = self._validate_snapshot(snap)
                info = self._attach(snap, pool, only)
            except SnapshotError as e:
                counter_inc("serve_snapshot_rejected")
                if fallback != "reprefill":
                    raise
                info = self._adopt_reprefill(snap, only)
                info["reject_reason"] = str(e)
            info["duration_s"] = round(time.monotonic() - t0, 6)
            sp.set(mode=info["mode"])
        # stamped copy: health() turns "t" into an age; the caller's info
        # dict stays exactly the documented shape
        self._last_recovery = dict(info, t=time.monotonic())
        counter_inc("serve_adoptions")
        return info

    def _validate_snapshot(self, snap: dict) -> PagePool:
        """The extended check(): everything that must hold before adoption.
        Raises SnapshotError; never mutates engine state."""
        try:
            version = snap.get("version")
            compat = tuple(snap.get("compat", ()))
            seqs = snap["seqs"]
            prefix = snap.get("prefix")
            kpool, vpool = snap["kpool"], snap["vpool"]
            fp = snap["fingerprint"]
        except Exception as e:  # lint: ok(oom-handler) — dict probing, nothing dispatches in this try
            raise SnapshotError(f"malformed engine snapshot: {e!r}") from e
        if version != SNAPSHOT_VERSION:
            raise SnapshotError(
                f"engine snapshot version {version!r} != {SNAPSHOT_VERSION}")
        if compat != self._compat_key():
            raise SnapshotError(
                f"snapshot geometry {compat} does not match this engine's "
                f"{self._compat_key()} — cross-config adoption refused")
        if kpool.shape != self._cache[0].shape or kpool.dtype != self._dtype \
                or vpool.shape != self._cache[1].shape:
            raise SnapshotError("KV pool array shape/dtype mismatch")
        pool = PagePool.restore(snap["pool"])
        bs = self.config.block_size
        refs: Dict[int, int] = {}
        for rec in seqs:
            blocks, tokens = rec["blocks"], rec["tokens"]
            rid = rec["req"].id
            if not tokens or len(tokens) < rec["prompt_len"]:
                raise SnapshotError(f"seq {rid}: empty/short token record")
            for b in blocks:
                if b == TRASH_BLOCK or pool.refcount(b) < 1:
                    raise SnapshotError(
                        f"seq {rid} maps unowned block {b}")
                refs[b] = refs.get(b, 0) + 1
            if rec["phase"] == "running":
                # written KV covers positions [0, pos): the table must too
                if len(blocks) * bs < len(tokens) - 1:
                    raise SnapshotError(
                        f"seq {rid}: block table covers {len(blocks) * bs} "
                        f"positions < written {len(tokens) - 1}")
                if len(blocks) > self._max_blocks:
                    raise SnapshotError(f"seq {rid}: table too wide")
        if prefix is not None:
            by_bid: Dict[int, tuple] = {}
            kids: Dict[int, int] = {}
            for key, ent in prefix["entries"].items():
                bid = ent[0]
                if bid in by_bid:
                    raise SnapshotError(
                        f"prefix index maps block {bid} twice")
                if pool.refcount(bid) < 1:
                    raise SnapshotError(
                        f"prefix index holds unowned block {bid}")
                by_bid[bid] = key
                refs[bid] = refs.get(bid, 0) + 1
            for key, ent in prefix["entries"].items():
                parent = key[0]
                if parent != -1:
                    if parent not in by_bid:
                        raise SnapshotError(
                            f"prefix chain parent {parent} not in index")
                    kids[parent] = kids.get(parent, 0) + 1
                hops = 0
                while parent != -1:
                    parent = by_bid[parent][0]
                    hops += 1
                    if hops > len(by_bid):
                        raise SnapshotError("prefix chain cycle")
            for key, ent in prefix["entries"].items():
                if ent[2] != kids.get(ent[0], 0):
                    raise SnapshotError(
                        f"prefix child-count diverged on block {ent[0]}")
        # refcount ↔ mapping agreement must be EXACT: every owned block is
        # referenced precisely refcount times by sequences + the index —
        # any torn mid-mutation state (leaked alloc, half-finished retire,
        # stale table) lands here and falls back instead of serving
        for b in sorted(pool._owned):
            if pool.refcount(b) != refs.get(b, 0):
                raise SnapshotError(
                    f"block {b}: pool refcount {pool.refcount(b)} != "
                    f"{refs.get(b, 0)} mapped references")
        # KV content fingerprints: the bytes the survivors will read must be
        # the bytes the dead engine wrote — never a wrong-KV serve
        if list(fp["bids"]) != sorted(pool._owned):
            raise SnapshotError("fingerprint block set diverged from pool")
        sums = self._G.kv_block_checksums(kpool, vpool, fp["bids"])
        if not np.array_equal(sums, fp["sums"]):
            raise SnapshotError("KV content fingerprint mismatch")
        return pool

    def _attach(self, snap: dict, pool: PagePool, only) -> dict:
        """Install a validated snapshot (re-attach). Builds everything
        off-lock against the restored local pool, then installs under _cv in
        one notify — the idle scheduler thread picks the survivors up at its
        next iteration."""
        running, resume, installed = [], [], []
        blocks_attached = tokens_saved = 0
        max_id = 0
        any_deadline = any_prio = False
        for rec in snap["seqs"]:
            req = rec["req"]
            max_id = max(max_id, req.id)
            if (only is not None and req.id not in only) \
                    or req.done.is_set():
                if rec["blocks"]:
                    pool.free(rec["blocks"])
                continue
            s = _Seq(req, list(rec["tokens"]))
            s.prompt_len = rec["prompt_len"]
            if rec["phase"] == "running":
                s.blocks = list(rec["blocks"])
                s.cached_blocks = rec["cached_blocks"]
                running.append(s)
                blocks_attached += len(s.blocks)
                tokens_saved += len(s.tokens)
            else:
                # resume/admitting rows re-prefill from accumulated tokens
                # through the engine's own preemption machinery — exactly
                # what an uninterrupted engine would have done with them
                if rec["blocks"]:
                    pool.free(rec["blocks"])
                resume.append(s)
            installed.append(req.id)
            any_deadline |= req.deadline is not None
            any_prio |= req.priority != 0
        queue = []
        for req in snap.get("queue", ()):
            max_id = max(max_id, req.id)
            if (only is not None and req.id not in only) \
                    or req.done.is_set():
                continue
            queue.append(req)
            installed.append(req.id)
            any_deadline |= req.deadline is not None
            any_prio |= req.priority != 0
        # prefix index: rebind the chain to the restored pool when armed on
        # both sides; otherwise release the index-held references so
        # conservation holds without it
        new_prefix = (None if self._prefix is None
                      else _PrefixCache(pool, self.config.block_size))
        if snap.get("prefix") is not None:
            ps = snap["prefix"]
            if new_prefix is not None:
                new_prefix._entries = {k: list(v)
                                       for k, v in ps["entries"].items()}
                new_prefix._by_bid = {ent[0]: k for k, ent
                                      in new_prefix._entries.items()}
                new_prefix._tick = int(ps["tick"])
            else:
                bids = [ent[0] for ent in ps["entries"].values()]
                if bids:
                    pool.free(bids)
        with self._cv:
            if self._stop or self._draining or self._broken is not None:
                raise ServeError("adopt: engine is stopped/draining/broken")
            if self._step_i or self._running or self._resume \
                    or self._admitting or self._waiting:
                raise ServeError("adopt requires a fresh engine (no traffic)")
            self._pool = pool
            self._cache = (snap["kpool"], snap["vpool"])
            self._prefix = new_prefix
            self._running.extend(running)
            self._resume.extend(resume)
            self._waiting.extend(queue)
            if any_deadline:
                self._deadline_seen = True
            if any_prio:
                self._has_prio = True
            if max_id:
                # adopted ids stay unique against future submissions (the
                # supervisor's harvest dedup and spans key on req.id)
                self._ids = itertools.count(max_id + 1)
            self._cv.notify()
        counter_inc("serve_reattached", len(running))
        counter_inc("serve_reattached_blocks", blocks_attached)
        counter_inc("serve_reprefill_tokens_saved", tokens_saved)
        return {"mode": "reattach", "installed": sorted(installed),
                "reattached": len(running), "resumed": len(resume),
                "queued": len(queue), "blocks_reattached": blocks_attached,
                "reprefill_tokens_saved": tokens_saved,
                "reprefill_tokens": 0}

    def _adopt_reprefill(self, snap: dict, only) -> dict:
        """Whole-state fallback for a rejected snapshot: every in-flight
        record becomes a resume entry (re-prefill from its accumulated
        tokens into the ORIGINAL request/handle), queued requests re-queue.
        No pool/KV state from the snapshot is trusted or touched."""
        resume, queue, installed = [], [], []
        tokens_reprefilled = 0
        max_id = 0
        any_deadline = any_prio = False
        for rec in snap.get("seqs", ()):
            req = rec["req"]
            max_id = max(max_id, req.id)
            if (only is not None and req.id not in only) \
                    or req.done.is_set():
                continue
            s = _Seq(req, list(rec["tokens"]))
            s.prompt_len = rec["prompt_len"]
            resume.append(s)
            installed.append(req.id)
            tokens_reprefilled += len(s.tokens)
            any_deadline |= req.deadline is not None
            any_prio |= req.priority != 0
        for req in snap.get("queue", ()):
            max_id = max(max_id, req.id)
            if (only is not None and req.id not in only) \
                    or req.done.is_set():
                continue
            queue.append(req)
            installed.append(req.id)
            any_deadline |= req.deadline is not None
            any_prio |= req.priority != 0
        with self._cv:
            if self._stop or self._draining or self._broken is not None:
                raise ServeError("adopt: engine is stopped/draining/broken")
            self._resume.extend(resume)
            self._waiting.extend(queue)
            if any_deadline:
                self._deadline_seen = True
            if any_prio:
                self._has_prio = True
            if max_id:
                self._ids = itertools.count(max_id + 1)
            self._cv.notify()
        counter_inc("serve_reprefill_tokens", tokens_reprefilled)
        return {"mode": "reprefill", "installed": sorted(installed),
                "reattached": 0, "resumed": len(resume),
                "queued": len(queue), "blocks_reattached": 0,
                "reprefill_tokens_saved": 0,
                "reprefill_tokens": tokens_reprefilled}

    def handoff(self, timeout: float = 30.0) -> dict:
        """Planned zero-downtime handoff: quiesce the scheduler at its next
        step boundary, then export snapshot + queue + in-flight handles.

        After this returns, THIS engine is terminally stopped (``submit``
        raises, ``close()`` releases only plumbing — the handles live
        inside the returned snapshot) and a successor adopts the snapshot:
        ``new.adopt(old.handoff())``. Survivors resume mid-decode without
        re-prefill; a validation failure falls back whole to re-prefill
        inside ``adopt``. If the engine crashes before the quiesce lands,
        this raises ``ServeError`` and the normal crash path owns the
        handles (failed, or supervisor-recovered) — every interleaving
        either completes the handoff or falls back whole."""
        self._refuse("snapshots (handoff)")
        if threading.current_thread() is self._thread:
            raise ServeError("handoff() cannot run on the scheduler thread")
        with self._cv:
            if self._stop or self._draining or self._broken is not None:
                raise ServeError("handoff: engine is stopped/draining/broken")
            if self._handoff_req:
                raise ServeError("handoff already in progress")
            self._handoff_req = True
            self._cv.notify()
        deadline = time.monotonic() + max(0.0, float(timeout))
        while not self._quiesced.wait(timeout=0.05):
            if self._broken is not None or self._failed.is_set() \
                    or not self._thread.is_alive():
                raise ServeError(
                    "engine failed before handoff quiesce"
                ) from self._broken
            if time.monotonic() > deadline:
                raise ServeError(
                    f"handoff quiesce timed out after {timeout}s")
        # the loop exits right after signalling; join so the state is frozen
        self._thread.join(max(1.0, deadline - time.monotonic()))
        with span("serve_handoff", step=self._step_i):
            snap = self.snapshot()
            with self._cv:
                snap["queue"] = list(self._waiting)
                self._waiting.clear()
            # the snapshot is the single owner of every handle now: clear
            # the scheduler lists so close() cannot fail adopted streams
            self._running, self._resume, self._admitting = [], [], []
        counter_inc("serve_handoffs")
        return snap

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close(timeout=2.0)
        except Exception:  # lint: ok(oom-handler) — teardown guard, nothing dispatches in this try
            pass

    # ------------------------------------------------------- engine thread
    def _run_once(self):
        """One scheduler iteration (bounded idle wait). Truthy = stopped;
        the ``"handoff"`` sentinel additionally tells the loop to exit
        WITHOUT ``_shutdown`` — the handoff snapshot owns the handles."""
        self._beat = time.monotonic()  # heartbeat: health() / supervisor
        with self._cv:
            land_first = self._handoff_req and self._flight is not None
            if self._handoff_req and not self._stop and not land_first:
                # handoff quiesce: this is a step boundary (no _step in
                # flight, the decode step that ran ahead landed), so the
                # capture is consistent by construction.
                # _stop flips under the same lock, so submit() raises and a
                # supervisor monitor sees a closed engine, never a crash.
                self._stop = True
                self._quiesced.set()
                return "handoff"
            idle = not (self._waiting or self._running or self._resume
                        or self._prefilling or self._flight is not None)
            if self._draining and idle:
                self._stop = True  # drain complete: fall through to stop
            if not self._stop and idle:
                self._cv.wait(timeout=0.5)
            if self._stop:
                return True
            has_work = bool(self._waiting or self._running or self._resume
                            or self._prefilling or self._flight is not None)
        if land_first:
            # outside the lock (a device read); the next iteration quiesces
            self._drain()
        elif has_work:
            self._step()
        if self._watchdog is not None:
            # supervised engines ride the PR 8 progress table: the scheduler
            # thread's serving step/phase lands in every crash dump's
            # cross-rank view (rate-limited inside publish)
            self._watchdog.publish(step=self._step_i, phase="serve.step",
                                   unit=self._provider)
        return False

    def _step(self):
        self._apply_pool_shrink()
        try:
            if _inject._armed:
                self._chaos_step()
            self._step_impl()
        except Exception as e:
            from ..fault import memory as _mem

            if not _mem.is_oom(e):
                raise
            # RESOURCE_EXHAUSTED inside a serving step: give HBM back (pool
            # headroom shrink → admission backpressure) and let the next
            # scheduler iteration retry — streams complete late, never crash.
            # A streak that shrinking cannot break falls through to the
            # crash-containment path (handles failed / supervisor restart),
            # so sustained exhaustion can never hang clients either.
            self._on_oom(e)

    def _step_impl(self):
        with span("schedule", step=self._step_i,
                  running=len(self._running)) as sp:
            self._drain_cancels()
            if self._deadline_seen:
                self._shed_sweep()
            # track mid-prefill sequences so a loop crash fails their
            # handles instead of orphaning them (they are in neither
            # _waiting nor _running until prefill lands); cleared only on
            # success — _shutdown sweeps it after a crash
            self._admitting = self._admit()
            if self._admitting and self._chunk:
                self._admitting = self._chunk_divert(self._admitting)
            if self._admitting:
                self._prefill(self._admitting)
            self._admitting = []
            if self._prefilling:
                self._chunk_step()
            if self._spec_k:
                if self._running:
                    self._decode_spec()
            elif self._running or self._flight is not None:
                self._decode()
            sp.set(running_after=len(self._running))
            if self._obs is not None and flags.flag(
                    "FLAGS_hbm_admission", "off") != "off":
                # drift predictor (b): the admission preflight's predicted
                # peak vs the realized post-step live census. Only priced
                # when admission is armed — preflight already pays a census
                # per dispatch, so this adds one more per scheduler step.
                self._hbm_drift()
            self._oom_streak = 0
            self._maybe_unpark()

    def _hbm_drift(self):
        from .. import profiler as _prof
        from ..fault import memory as _fmem

        pred = _fmem.last_prediction().get("hbm_predicted_peak_bytes")
        if pred:
            live = int(_prof.memory_census().get("live_bytes", 0))
            if live:
                self._obs.drift("hbm_admission", pred, live)

    # clean scheduler steps (work done, no OOM) before parked blocks start
    # returning to circulation; halved-back gradually so a recurrence
    # re-parks quickly (class attr so tests can compress the window)
    _UNPARK_AFTER = 64

    def _maybe_unpark(self):
        """Pressure decay: after a clean-step window, return parked blocks
        to the free list half at a time — a transient OOM must not leave the
        pool permanently shrunk."""
        if not self._pool.parked_blocks:
            return
        if self._unpark_countdown > 0:
            self._unpark_countdown -= 1
            return
        # (PagePool.unpark counts serve_pages_unparked — the one decay
        # counter; no engine-level duplicate)
        self._pool.unpark(max(self._pool.parked_blocks // 2, 1))
        self._unpark_countdown = self._UNPARK_AFTER

    def _apply_pool_shrink(self):
        """Apply a cross-thread shrink request (engine thread only — the
        scheduler is the pool's single owner; the request word is read and
        cleared under _cv so a writer landing mid-apply is never lost)."""
        with self._cv:
            req = self._shrink_req
            self._shrink_req = 0
        if not req:
            return
        n = req if req > 0 else max(self._pool.free_blocks // 4, 1)
        parked = self._pool.park(n)
        if parked:
            counter_inc("serve_pool_shrunk", parked)
            self._unpark_countdown = self._UNPARK_AFTER

    def request_pool_shrink(self, blocks: Optional[int] = None) -> dict:
        """(any thread) Ask the scheduler to park KV blocks at its next step
        boundary — admission headroom shrinks, continuous batching backs
        off, nothing crashes. ``blocks=None`` parks a quarter of the free
        list. The serving callback fault/memory.free_pressure runs."""
        with self._cv:
            self._shrink_req = int(blocks) if blocks else -1
            self._cv.notify()
        return {"requested_blocks": blocks or "free/4",
                "pages_free": self._pool.free_blocks,
                "pages_parked": self._pool.parked_blocks}

    def _on_oom(self, exc: BaseException) -> None:
        from ..fault import memory as _mem

        self._oom_streak += 1
        if self._oom_streak > 8:
            # shrinking is not helping — contain, don't loop (the engine
            # loop's containment handler notes THIS exhaustion, so it is
            # not recorded twice)
            raise exc
        _mem.note_oom("serve.step", exc)
        # the decode step in flight lands, or is dropped where the error was
        # its own: what follows works on landed positions
        self._settle()
        # a mid-prefill OOM strands sequences in _admitting (blocks granted,
        # KV never written): free the grant and route them through the
        # preemption/resume path — they re-prefill from their accumulated
        # tokens once headroom allows, exactly like an evicted peer
        for seq in self._admitting:
            try:
                self._release(seq)
            except Exception:  # lint: ok(oom-handler) — pool itself may be what broke; the sweep must reach every seq
                pass
            seq.blocks = []
            if not seq.req.done.is_set():
                self._resume.append(seq)
        self._admitting = []
        # ditto a mid-chunked-prefill OOM: partial chunk K/V is abandoned
        # with the blocks — resume re-prefills the whole prompt
        for seq in self._prefilling:
            try:
                if seq.blocks:
                    self._pool.free(seq.blocks)
            except Exception:  # lint: ok(oom-handler) — pool itself may be what broke; the sweep must reach every seq
                pass
            seq.blocks = []
            seq.chunk_pos = 0
            if not seq.req.done.is_set():
                self._resume.append(seq)
        self._prefilling = []
        self._chunk_flight, self._chunk_counts = None, []
        self._chunk_tokens = 0
        if self._prefix is not None and len(self._prefix):
            # cached-prefix KV is the most expendable resident state under
            # exhaustion — drop half before parking shrinks live headroom
            self._prefix.evict(max(len(self._prefix) // 2, 1))
        parked = self._pool.park(max(self._pool.free_blocks // 4, 1))
        if parked:
            counter_inc("serve_pool_shrunk", parked)
        self._unpark_countdown = self._UNPARK_AFTER

    def _chaos_step(self):
        """``serve.*`` chaos points, consulted once per scheduler step while
        injection is armed (the unarmed path is one module-attribute probe in
        ``_step``). ``serve.crash`` raises out of the loop, ``serve.wedge``
        hangs the scheduler thread (forever unless ``ms=`` bounds it),
        ``serve.slow_step`` is a straggler delay, ``serve.pool_corrupt``
        breaks pool conservation so a later free raises."""
        step = self._step_i
        if _inject.should_fire("serve.slow_step", step=step):
            time.sleep(_inject.point_cfg("serve.slow_step").get("ms", 100) / 1000.0)
        if _inject.should_fire("serve.pool_corrupt", step=step):
            self._pool.damage()
        if _inject.should_fire("serve.wedge", step=step):
            ms = _inject.point_cfg("serve.wedge").get("ms")
            if ms:
                time.sleep(ms / 1000.0)
            else:
                _inject._hang("serve.wedge")
        if _inject.should_fire("serve.crash", step=step):
            raise ServeError(f"injected serve.crash at engine step {step}")
        if _inject.should_fire("hbm.pressure", step=step):
            blocks = _inject.point_cfg("hbm.pressure").get("blocks")
            if blocks:
                self.request_pool_shrink(blocks)
        # synthesized RESOURCE_EXHAUSTED at the serving dispatch site —
        # raises into _step's OOM handler (shrink + backpressure, no crash)
        _inject.maybe_hbm_oom("serve.step", step=step)

    def _shed_sweep(self):
        """Step-boundary deadline enforcement. Runs only once a deadline'd
        request has ever been submitted (``_deadline_seen``) — the
        unconfigured path never reaches here. Expired requests fail wherever
        they sit; a queued request that cannot meet its deadline even if
        admitted NOW (prefill + full token budget at the measured decode-step
        EMA) is shed at admission — rejecting early is cheaper than paying a
        prefill it will abandon."""
        now = time.monotonic()
        # while the measured decode EMA is cold, the cost model's analytic
        # per-step tp-collective term is the feasibility floor — a sharded
        # engine's first deadline'd admits would otherwise assume 0-cost
        # steps and accept doomed work
        ema = max(self._ema_step_s, self._step_floor_s)
        shed = []
        with self._cv:
            for req in [r for r in self._waiting if r.deadline is not None]:
                eta = (1 + req.max_new_tokens) * ema
                if now >= req.deadline:
                    self._waiting.remove(req)
                    shed.append((req, f"expired in queue "
                                 f"({now - req.deadline:.3f}s late)"))
                elif now + eta > req.deadline:
                    self._waiting.remove(req)
                    shed.append((req, f"doomed at admission: needs "
                                 f"~{eta:.3f}s but the deadline is in "
                                 f"{req.deadline - now:.3f}s"))
        for req, why in shed:
            counter_inc("serve_deadline_shed")
            if self._obs is not None:
                self._obs.on_shed(
                    req, "expired" if now >= (req.deadline or now) else "doomed")
            self._finish_request(req, error=DeadlineExceeded(
                f"request {req.id} {why}", request_id=req.id))
        for seq in [s for s in self._running
                    if s.req.deadline is not None
                    and now >= s.req.deadline]:
            counter_inc("serve_deadline_expired")
            self._retire(seq, error=DeadlineExceeded(
                f"request {seq.req.id} deadline expired mid-decode "
                f"({seq.generated}/{seq.req.max_new_tokens} generated)",
                request_id=seq.req.id))
        for seq in [s for s in self._resume
                    if s.req.deadline is not None
                    and now >= s.req.deadline]:
            self._resume.remove(seq)
            counter_inc("serve_deadline_expired")
            self._finish_request(seq.req, error=DeadlineExceeded(
                f"request {seq.req.id} deadline expired while preempted "
                f"({seq.generated}/{seq.req.max_new_tokens} generated)",
                request_id=seq.req.id))
        for seq in [s for s in self._prefilling
                    if s.req.deadline is not None
                    and now >= s.req.deadline]:
            self._prefilling.remove(seq)
            if seq.blocks:
                self._pool.free(seq.blocks)
                seq.blocks = []
            counter_inc("serve_deadline_expired")
            self._finish_request(seq.req, error=DeadlineExceeded(
                f"request {seq.req.id} deadline expired mid-chunked-prefill "
                f"({seq.chunk_pos}/{len(seq.tokens)} tokens cached)",
                request_id=seq.req.id))

    # -- admission ----------------------------------------------------------
    def _make_prefill_buckets(self) -> Sequence[int]:
        bs, t_pad = self.config.block_size, self._max_blocks * self.config.block_size
        buckets, b = [], bs
        while b < t_pad:
            buckets.append(b)
            b *= 2
        buckets.append(t_pad)
        return tuple(buckets)

    def _bucket_for(self, n: int) -> int:
        for b in self._prefill_buckets:
            if b >= n:
                return b
        raise ValueError(f"no prefill bucket covers length {n}")

    def _headroom_ok(self, need: int, extra_running: int) -> bool:
        # AFTER granting `need`, keep one spare block per running sequence so
        # the next decode steps don't immediately preempt what admission
        # just packed in (a prefill paid, then discarded, is pure waste)
        return self._pool.free_blocks - need >= len(self._running) + extra_running

    def _alloc_with_reclaim(self, need: int, extra_running: int):
        """Block grant with prefix-cache reclaim: unpinned cached blocks are
        free headroom in disguise, so LRU cache entries are evicted before
        admission declares backpressure or a grower preempts a peer."""
        if self._headroom_ok(need, extra_running):
            got = self._pool.alloc(need)
            if got is not None:
                return got
        if self._prefix is not None and len(self._prefix):
            want = (need + len(self._running) + extra_running
                    - self._pool.free_blocks)
            if self._prefix.evict(max(want, 1)) \
                    and self._headroom_ok(need, extra_running):
                return self._pool.alloc(need)
        return None

    def _match_prefix(self, tokens) -> List[int]:
        """Longest-prefix cache lookup for an admission candidate; matched
        blocks are shared (refcount-bumped) here — callers must ``free``
        them on any later admission failure. Capped one token short of the
        whole sequence: prefill must always produce first-token logits."""
        limit = (len(tokens) - 1) // self.config.block_size
        bids = self._prefix.match(tokens, limit)
        if bids:
            self._pool.share(bids)
            counter_inc("serve_prefix_hits")
            counter_inc("serve_prefix_blocks_shared", len(bids))
        else:
            counter_inc("serve_prefix_misses")
        return bids

    def _admit(self) -> List[_Seq]:
        admitted: List[_Seq] = []
        # a sequence in mid-prefill (chunked) holds its batch slot from its
        # admission: it lands among the running rows without asking again
        taken = lambda: (len(self._running) + len(self._prefilling)
                         + len(admitted))
        with span("admit") as sp:
            # preempted sequences first: they already hold tokens and their
            # latency clock is running
            still_resume = []
            for seq in self._resume:
                if taken() >= self.config.max_batch:
                    still_resume.append(seq)
                    continue
                matched = (self._match_prefix(seq.tokens)
                           if self._prefix is not None else [])
                need = (-(-len(seq.tokens) // self.config.block_size)
                        - len(matched))
                blocks = self._alloc_with_reclaim(need, len(admitted) + 1)
                if blocks is None:
                    if matched:
                        self._pool.free(matched)
                    still_resume.append(seq)
                    continue
                seq.blocks = matched + blocks
                seq.cached_blocks = len(matched)
                self._take_slot(seq)
                admitted.append(seq)
                if self._obs is not None and matched:
                    self._obs.on_prefix_match(
                        seq.req, len(matched) * self.config.block_size,
                        len(matched))
            self._resume = still_resume
            # ONE ordered snapshot per admission pass, not an O(queue) scan
            # per batch slot: strict priority order, FIFO within a class,
            # and only the best remaining candidate is considered at each
            # slot — if it doesn't fit, nothing behind it bypasses it (no
            # starvation of large high-priority requests). Submits landing
            # mid-pass wait for the next step (ms away). Concurrent removal
            # (close/harvest while the engine is dying) is handled by the
            # remove() ValueError guards below.
            with self._cv:
                if self._has_prio and len(self._waiting) > 1:
                    cand = sorted(self._waiting,
                                  key=lambda r: (-r.priority, r.id))
                else:
                    cand = list(self._waiting)
            for req in cand:
                if taken() >= self.config.max_batch:
                    break
                with self._cv:
                    if req.cancelled:
                        try:
                            self._waiting.remove(req)
                        except ValueError:
                            continue  # already drained elsewhere
                        self._finish_request(req, error=RequestCancelled(
                            f"request {req.id} cancelled"))
                        continue
                    matched = (self._match_prefix(req.prompt)
                               if self._prefix is not None else [])
                    need = (-(-len(req.prompt) // self.config.block_size)
                            - len(matched))
                    blocks = self._alloc_with_reclaim(need, len(admitted) + 1)
                    if blocks is None:
                        if matched:
                            self._pool.free(matched)
                        counter_inc("serve_backpressure")
                        break
                    try:
                        self._waiting.remove(req)
                    except ValueError:  # raced away mid-pass — undo the grant
                        self._pool.free(matched + blocks)
                        continue
                seq = _Seq(req, list(req.prompt))
                seq.blocks = matched + blocks
                seq.cached_blocks = len(matched)
                self._take_slot(seq)
                admitted.append(seq)
                if self._obs is not None:
                    self._obs.on_admit(req)
                    if matched:
                        self._obs.on_prefix_match(
                            req, len(matched) * self.config.block_size,
                            len(matched))
            if admitted:
                counter_inc("serve_admitted", len(admitted))
            sp.set(admitted=len(admitted), resume_waiting=len(self._resume))
        return admitted

    # -- prefill -------------------------------------------------------------
    def _prefill(self, seqs: List[_Seq]):
        jnp = self._jnp
        bw = self.config.prefill_batch
        bs = self.config.block_size
        # rows that matched the prefix cache run the TAIL program (bucketed
        # by tail length, reading the shared prefix from the pool); misses
        # run the PR 11 full-prompt program unchanged
        groups: Dict[int, List[_Seq]] = {}
        tail_groups: Dict[int, List[_Seq]] = {}
        for s in seqs:
            if s.cached_blocks:
                tail = len(s.tokens) - s.cached_blocks * bs
                tail_groups.setdefault(self._bucket_for(tail), []).append(s)
            else:
                groups.setdefault(self._bucket_for(len(s.tokens)), []).append(s)
        for t_bucket in sorted(groups):
            group = groups[t_bucket]
            for i in range(0, len(group), bw):
                chunk = group[i:i + bw]
                with span("prefill", bucket_t=t_bucket, bucket_b=bw,
                          rows=len(chunk)) as sp:
                    if self._obs is not None:
                        sp.set(traces=tuple(s.req.trace for s in chunk))
                    # heartbeat before a potentially-long op (first-call jit
                    # compile): the supervisor's staleness clock starts HERE,
                    # so only a genuinely wedged op trips it
                    self._beat = time.monotonic()
                    n_fns = len(self._fns)
                    fn = self._get_fn("prefill", bw, t_bucket)
                    self._compiling = len(self._fns) != n_fns
                    ids = np.zeros((bw, t_bucket), np.int32)
                    lens = np.ones((bw,), np.int32)
                    tables = np.full((bw, self._max_blocks), TRASH_BLOCK,
                                     np.int32)
                    for r, s in enumerate(chunk):
                        ids[r, :len(s.tokens)] = s.tokens
                        lens[r] = len(s.tokens)
                        tables[r, :len(s.blocks)] = s.blocks
                    # real tokens, the bucket's padding left out (an arch
                    # with row slots runs them through its scans)
                    real = sum(len(s.tokens) for s in chunk)
                    sp.set(prompt_tokens=real)
                    if self._row_slots is not None:
                        sp.set(scan_tokens=real)
                    if "prefill_attrs" in self._arch:
                        # what the arch says of these lengths (the keys its
                        # prompt attention must score, by kind of layer)
                        sp.set(**self._arch["prefill_attrs"](
                            [len(s.tokens) for s in chunk]))
                    logits, *extras = self._run(
                        fn, self._compute_params, jnp.asarray(ids),
                        jnp.asarray(lens), jnp.asarray(tables),
                        *self._slot_operand(chunk, bw))
                    counter_inc("serve_prefills")
                    rows, *extras = self._prefill_readback(logits, *extras)
                    if extras:
                        self._note_experts(sp, extras[0], real)
                    self._land_prefill(chunk, rows)
        for t_bucket in sorted(tail_groups):
            group = tail_groups[t_bucket]
            for i in range(0, len(group), bw):
                chunk = group[i:i + bw]
                with span("prefill", bucket_t=t_bucket, bucket_b=bw,
                          rows=len(chunk), shared=True) as sp:
                    if self._obs is not None:
                        sp.set(traces=tuple(s.req.trace for s in chunk))
                    self._beat = time.monotonic()
                    n_fns = len(self._fns)
                    fn = self._get_fn("prefill_tail", bw, t_bucket)
                    self._compiling = len(self._fns) != n_fns
                    ids = np.zeros((bw, t_bucket), np.int32)
                    starts = np.zeros((bw,), np.int32)
                    lens = np.ones((bw,), np.int32)
                    tables = np.full((bw, self._max_blocks), TRASH_BLOCK,
                                     np.int32)
                    for r, s in enumerate(chunk):
                        start = s.cached_blocks * bs
                        ids[r, :len(s.tokens) - start] = s.tokens[start:]
                        starts[r] = start
                        lens[r] = len(s.tokens) - start
                        tables[r, :len(s.blocks)] = s.blocks
                    logits, = self._run(
                        fn, self._compute_params, jnp.asarray(ids),
                        jnp.asarray(starts), jnp.asarray(lens),
                        jnp.asarray(tables))
                    counter_inc("serve_prefills")
                    rows, = self._prefill_readback(logits)
                    self._land_prefill(chunk, rows)

    def _slot_operand(self, rows: List[_Seq], width: int) -> tuple:
        """What a prefill program of an arch with row slots takes after the
        tables: each row's slot, the trash slot for the bucket's padding."""
        if self._row_slots is None:
            return ()
        slots = np.zeros((width,), np.int32)
        slots[:len(rows)] = [s.row_slot for s in rows]
        return (self._jnp.asarray(slots),)

    def _run(self, fn, params, *args, pools_first=False):
        """Call a compiled program with the cache pools in their slot (last,
        or right after the parameters) and keep the pools it returns; what
        else it returned comes back."""
        args = (*self._cache, *args) if pools_first else (*args, *self._cache)
        out = fn(params, *args)
        n = len(self._cache)
        self._cache = tuple(out[:n])
        return out[n:]

    def _prefill_readback(self, *arrays) -> List[np.ndarray]:
        """``prefill_readback``: the blocking copy of a prefill program's
        logits (and what else it reports) to the host (the wait for the
        program is in it)."""
        with span("prefill_readback"):
            rows = [np.asarray(a) for a in arrays]
        # beat BEFORE dropping the compile grace: a monitor poll between the
        # two would see a stale beat at the 1x limit and declare a spurious
        # wedge after a long compile
        self._beat = time.monotonic()
        self._compiling = False
        return rows

    def _land_prefill(self, chunk: List[_Seq], rows: np.ndarray):
        """Post-prefill landing (``prefill_land``): index cacheable prompt
        blocks (while the sequence still owns them — the index takes its own
        reference, so a first-token retirement keeps the KV resident), then
        sample the first generated token from its full-vocabulary row and
        move the sequence into the running set."""
        with span("prefill_land", rows=len(chunk)):
            for r, s in enumerate(chunk):
                if self._prefix is not None:
                    full = s.prompt_len // self.config.block_size
                    if full > s.cached_blocks:
                        self._prefix.insert(s.tokens, s.blocks,
                                            s.cached_blocks, full)
                self._append_token(s, self._sample_host(rows[r], s.req))
                if not s.req.done.is_set():
                    self._running.append(s)
            if self._obs is not None:
                # ONE host clock read covers the whole landed group: prefill
                # always emits each row's first token (TTFT)
                self._obs.on_tokens([s.req for s in chunk], time.monotonic())

    # -- chunked prefill (PR 19) ---------------------------------------------
    def _chunk_divert(self, seqs: List[_Seq]) -> List[_Seq]:
        """Route admitted sequences whose un-cached prompt tail exceeds one
        chunk (or what the arch's whole-prompt program holds, if that is
        less) into the incremental queue; the rest (short prompts gain
        nothing from chunking) keep the monolithic path. The diverted
        sequence already owns ALL its prompt blocks — only the K/V writes
        are spread over steps."""
        keep: List[_Seq] = []
        bs = self.config.block_size
        for s in seqs:
            if len(s.tokens) - s.cached_blocks * bs > self._whole_max:
                s.chunk_pos = s.cached_blocks * bs
                self._prefilling.append(s)
            else:
                keep.append(s)
        return keep

    def _chunk_step(self):
        """Advance chunked prefill by AT MOST one program call (<=
        prefill_batch rows x one chunk of tokens each), then fall through
        to the live decode batch — the scheduler-step interleave that keeps
        a 4k-token admit from freezing every in-flight stream. Each chunk
        is a tail feed at absolute positions: chunk boundaries are
        block-aligned (prefill_chunk % block_size == 0, cached prefixes are
        whole blocks), earlier chunks' K/V is read back through the block
        table, and the write goes through the existing paged scatter — so
        prefix-cached tails compose and the result is bit-identical to
        monolithic prefill. Intermediate chunk logits are discarded; the
        final chunk lands the sequence exactly like a monolithic pass.

        Every call is of ONE shape, the chunk's bucket, a prompt's last call
        padded to it (``lens`` says what is real): an engine has one tail
        program a ``prefill_chunk``, compiled by the first long prompt it is
        shown, and no remainder reaches a bucket that nothing warmed."""
        jnp = self._jnp
        bw = self.config.prefill_batch
        batch = self._prefilling[:bw]
        feeds = [min(self._chunk, len(s.tokens) - s.chunk_pos)
                 for s in batch]
        t_bucket = self._bucket_for(self._chunk)
        cached = [s.chunk_pos for s in batch]
        with span("prefill", bucket_t=t_bucket, bucket_b=bw,
                  rows=len(batch), chunked=True, start=sum(cached),
                  feed=sum(feeds), context_tokens=sum(cached) + sum(feeds),
                  # live (query, key) pairs a layer and a head: a fed
                  # position sees what is cached and the fed up to itself
                  attended_pairs=sum(f * c + f * (f + 1) // 2
                                     for c, f in zip(cached, feeds)),
                  calls_left=sum(
                      -(-(len(s.tokens) - s.chunk_pos - f) // self._chunk)
                      for s, f in zip(batch, feeds))) as sp:
            if self._obs is not None:
                sp.set(traces=tuple(s.req.trace for s in batch))
            self._beat = time.monotonic()
            n_fns = len(self._fns)
            fn = self._get_fn("prefill_tail", bw, t_bucket)
            self._compiling = len(self._fns) != n_fns
            ids = np.zeros((bw, t_bucket), np.int32)
            starts = np.zeros((bw,), np.int32)
            lens = np.ones((bw,), np.int32)
            tables = np.full((bw, self._max_blocks), TRASH_BLOCK, np.int32)
            for r, s in enumerate(batch):
                ids[r, :feeds[r]] = s.tokens[s.chunk_pos:s.chunk_pos
                                             + feeds[r]]
                starts[r] = s.chunk_pos
                lens[r] = feeds[r]
                tables[r, :len(s.blocks)] = s.blocks
            if "call_attrs" in self._arch:
                # what the arch says its call reads of the pool
                sp.set(**self._arch["call_attrs"](
                    cached, feeds, self.config.block_size, self._max_blocks))
            if self._chunk_flight is not None:
                # ONE call in flight for an arch whose tail program holds a
                # scratch of its whole context (``tail_scratch``): the call
                # before this one was not read back, and a second program's
                # temporaries beside the first's need not fit beside a pool
                # that fills the chip. The decode step enqueued between them
                # keeps the device busy meanwhile. Other archs' calls overlap
                with span("prefill_wait"):
                    self._jax.block_until_ready(self._chunk_flight)
                self._chunk_flight = None
                self._beat = time.monotonic()
            logits, *extras = self._run(
                fn, self._compute_params, jnp.asarray(ids),
                jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(tables))
            counter_inc("serve_prefill_chunks")
            counter_inc("serve_prefill_context_tokens",
                        sum(cached) + sum(feeds))
            done = [r for r, s in enumerate(batch)
                    if s.chunk_pos + feeds[r] >= len(s.tokens)]
            # an arch that routes experts reports every call's counts; they
            # stay on the device until a final chunk is read back anyway
            self._chunk_counts += extras
            self._chunk_tokens += sum(feeds)
            if done:  # only final chunks need the logits host-side
                rows, *counts = self._prefill_readback(
                    logits, *self._chunk_counts)
                if counts:
                    self._note_experts(sp, np.sum(counts, axis=0),
                                       self._chunk_tokens)
                self._chunk_counts, self._chunk_tokens = [], 0
            else:
                if "tail_scratch" in self._arch:
                    self._chunk_flight = logits
                self._beat = time.monotonic()
                self._compiling = False
            for r, s in enumerate(batch):
                s.chunk_pos += feeds[r]
            if done:
                finished = [batch[r] for r in done]
                self._prefilling = [s for s in self._prefilling
                                    if s not in finished]
                counter_inc("serve_prefills", len(finished))
                self._land_prefill(finished, rows[done])

    def _sample_host(self, logits_row: np.ndarray, req: _Request) -> int:
        """First generated token (prefill output) is sampled host-side; the
        greedy argmax matches the in-graph decode argmax bit-for-bit."""
        if req.temperature <= 0.0:
            return int(np.argmax(logits_row))
        z = logits_row.astype(np.float64) / max(req.temperature, 1e-6)
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    # -- decode --------------------------------------------------------------
    def _grow_blocks(self):
        """Every live sequence needs block ``pos // block_size`` mapped
        before the step; pool exhaustion preempts a peer (evict → requeue
        for re-prefill) — backpressure, never failure. Victim selection is
        priority-then-youngest: the lowest-priority peer goes first, ties
        broken by the youngest request; a grower never evicts a
        higher-priority peer — it preempts ITSELF instead. The block is the
        one the step to be built writes (``next_pos``: one past the position
        of a step in flight), and a row whose budget ends in flight needs
        none. Eviction takes a sequence out of the running set, so the step
        in flight lands first (``_drain``), which may itself free what was
        missing. Returns the number of blocks it mapped."""
        grown = 0
        for seq in list(self._running):
            while seq in self._running and not seq.ends_in_flight:
                # spec verify writes k slots past pos — map those blocks too
                need = ((seq.next_pos + self._spec_k)
                        // self.config.block_size + 1 - len(seq.blocks))
                if need <= 0:
                    break
                got = self._pool.alloc(need)
                if got is None and self._prefix is not None \
                        and len(self._prefix):
                    # reclaim unpinned cache before preempting a peer
                    self._prefix.evict(need - self._pool.free_blocks)
                    got = self._pool.alloc(need)
                if got is not None:
                    seq.blocks.extend(got)
                    grown += need
                    break
                if self._flight is not None:
                    self._drain()
                    continue
                victims = [s for s in self._running if s is not seq]
                if not victims:
                    # a lone sequence always fits (submit() bounds it), so
                    # this is unreachable unless accounting broke
                    raise ServeError(
                        f"page pool exhausted by a single sequence "
                        f"(request {seq.req.id})"
                    )
                victim = min(victims,
                             key=lambda s: (s.req.priority, -s.req.id))
                if victim.req.priority > seq.req.priority:
                    self._evict(seq)
                    break
                self._evict(victim)
        return grown

    def _evict(self, seq: _Seq):
        with span("evict", request=seq.req.id, generated=seq.generated) as sp:
            if self._obs is not None:
                sp.set(traces=(seq.req.trace,))
            if seq.row_slot:
                # the window and the state go with the slot: the re-prefill
                # pays for them again, not for the paged rows alone
                self._state_rebuilds += 1
                counter_inc("serve_state_rebuilds")
            self._release(seq)
            self._running.remove(seq)
            self._resume.append(seq)
            counter_inc("serve_preempted")

    def _gather_width(self, bb: int) -> int:
        """Per-decode-bucket gather width, for the steps that GATHER their
        context (the speculative verify, and decode where the engine keeps
        the gather builder; the kernel decode step takes the table whole and
        never comes here). The compiled step gathers this many blocks per
        row instead of the engine-wide ``_max_blocks`` — sized to the
        bucket's HIGH-WATER live
        block count, rounded up to a power of two (recompiles bounded at
        log2 per bucket), never shrinking. A width upgrade REPLACES the
        bucket's compiled entry, so ``stats()['compiles']`` stays bounded
        by the bucket count. Bit-identity is free: the dropped columns were
        all trash-block padding behind every row's live mask."""
        hw = max(len(s.blocks) for s in self._running)
        mb = self._decode_mb.get(bb, 0)
        if hw > mb:
            mb = 1
            while mb < hw:
                mb *= 2
            mb = min(mb, self._max_blocks)
            old = self._decode_mb.get(bb)
            if old is not None:
                self._fns.pop(("decode", bb, old), None)
                self._fns.pop(("spec", bb, old), None)
            self._decode_mb[bb] = mb
        return mb

    def _cow_guard(self, seq: _Seq):
        """Copy-on-write: a write-range block still shared with the prefix
        index or a peer is copied into a private block before the step
        writes it. The admission policy keeps shared prefix blocks strictly
        BELOW every write column (matching is capped at full prompt blocks,
        writes start at ``prompt_len``), so this is defense in depth — it
        keeps peers bit-intact even if a future scheduler maps shared
        blocks more aggressively."""
        bs = self.config.block_size
        lo, hi = seq.next_pos // bs, (seq.next_pos + self._spec_k) // bs
        for col in range(lo, min(hi + 1, len(seq.blocks))):
            bid = seq.blocks[col]
            if self._pool.refcount(bid) <= 1:
                continue
            repl = self._alloc_with_reclaim(1, 0)
            if repl is None:
                raise ServeError(
                    f"page pool exhausted during copy-on-write "
                    f"(request {seq.req.id})"
                )
            new = repl[0]
            self._cache = tuple(p.at[:, new].set(p[:, bid])
                                for p in self._cache)
            seq.blocks[col] = new
            self._pool.free([bid])
            counter_inc("serve_cow_copies")
            if self._obs is not None:
                self._obs.on_cow(seq.req.trace, 1)

    # The host phases of a decode step, plain and speculative alike, each a
    # span where the work happens: ``decode_build`` (under ``schedule``), then
    # ``decode_step`` from the dispatch to the end of the landing, holding
    # ``decode_readback`` and ``decode_land``; what is left of ``decode_step``
    # is the program lookup, the transfer of the step's operand and the
    # enqueue (the speculative step still splits its key and makes four
    # transfers). In the plain loop the step BUILT and enqueued is one ahead
    # of the step read and landed (``_decode``).
    def _decode_build(self, k: int = 0):
        """``decode_build``: map the blocks the step will write, guard shared
        ones, choose the bucket and fill the step's host arrays (``k`` draft
        columns beside each row's pending token). The rows are the running
        set less those whose budget ends with the token in flight; a row in
        flight is fed from the device (``src``: its row there) at one past
        its landed position, the others their last token from the host
        (``src`` -1). Returns None when no row is left to step, else (rows,
        bucket, table width, drafts, tables, positions, tokens,
        temperatures, packed): for the plain step (``k`` 0) the arrays are
        views of ``packed``, the one operand that crosses to the device."""
        with span("decode_build") as sp:
            grown = self._grow_blocks()
            rows = [s for s in self._running if not s.ends_in_flight]
            n = len(rows)
            sp.set(rows=n, blocks_grown=grown)
            if not n:
                return None
            if self._prefix is not None:
                for s in rows:
                    self._cow_guard(s)
            bb = next(b for b in self.config.decode_buckets if b >= n)
            # the kernel step's work follows each row's live blocks, so it
            # takes the table whole: one program a bucket, no regrowth
            kernel_step = self._paged_kernel and not k
            mb = self._max_blocks if kernel_step else self._gather_width(bb)
            blocks_live = sum(len(s.blocks) for s in rows)
            sp.set(bucket=bb, blocks_live=blocks_live)
            if kernel_step:
                # a gathering step reads bucket x width blocks, live or not
                counter_inc("serve_decode_blocks_read", blocks_live)
            drafts = self._propose(bb) if k else None
            if k:
                tables = np.empty((bb, mb), np.int32)
                pos = np.zeros((bb,), np.int32)
                toks = np.zeros((bb, k + 1), np.int32)
                temps = np.zeros((bb,), np.float32)
                ints = src = None
            else:
                # the plain step takes ONE operand from the host, its
                # arrays side by side (generation.feed_tokens_back)
                # (an arch with row slots: one column more, the row's slot)
                ints = np.zeros((bb, mb + self._G.STEP_COLS
                                 + (self._row_slots is not None)), np.int32)
                tables, (pos, src, toks, temps) = ints[:, :mb], (
                    ints[:, mb + c] for c in range(self._G.STEP_COLS))
                temps = temps.view(np.float32)
                src[:] = -1
            tables[:] = TRASH_BLOCK
            for r, s in enumerate(rows):
                tables[r, :len(s.blocks)] = s.blocks
                pos[r] = s.next_pos
                temps[r] = s.req.temperature
                if k:
                    toks[r, 0] = s.tokens[-1]
                else:
                    toks[r] = s.tokens[-1]
                    src[r] = s.slot
                    if s.row_slot:
                        ints[r, -1] = s.row_slot
            if k:
                toks[:n, 1:] = drafts[:n]
        return rows, bb, mb, drafts, tables, pos, toks, temps, ints

    def _decode_readback(self, *arrays):
        """``decode_readback``: the blocking copy of the step's tokens to the
        host (the wait for the step's program is in it)."""
        with span("decode_readback"):
            out = [np.asarray(a) for a in arrays]
        self._beat = time.monotonic()  # beat before dropping compile grace
        self._compiling = False
        return out

    @contextlib.contextmanager
    def _decode_land(self, rows_live: List[_Seq]):
        """``decode_land``: the step's tokens into their streams, with the
        retirements and page frees that follow; the caller appends inside
        and sets ``tokens``. ``rows_live``: the step's rows that are still
        running when it lands."""
        with span("decode_land") as sp:
            yield sp
            if self._obs is not None:
                # one host clock read at step retire, attributed to every
                # row that emitted a token this step (TTFT / inter-token
                # gap; a multi-accept speculative step IS one interval at
                # step granularity)
                self._obs.on_tokens([s.req for s in rows_live],
                                    time.monotonic())
            sp.set(retired=sum(s.req.done.is_set() for s in rows_live))

    def _step_done(self, sp, warm: bool, t0: float, n: int, bb: int):
        """Book-keeping of a decode step whose tokens have reached the host,
        before they land (a client that holds its result sees the counters
        of the step that produced it)."""
        # decode service-time EMA feeds deadline feasibility + Retry-After
        # hints; compile steps are excluded — they would make every early
        # deadline look doomed
        if warm:
            dt = time.monotonic() - t0
            if self._obs is not None and self._ema_step_s:
                # drift predictor (a): the shed-ETA per-step estimate the
                # sweep would have used for THIS step vs its measured time
                rel = self._obs.drift(
                    "step_eta", max(self._ema_step_s, self._step_floor_s), dt)
                sp.set(cost_drift=round(rel, 6))
            self._ema_step_s = (dt if not self._ema_step_s
                                else 0.8 * self._ema_step_s + 0.2 * dt)
        self._step_i += 1
        self._occ_live += n
        self._occ_slots += bb
        counter_inc("serve_decode_steps")
        counter_inc("serve_occupancy_live", n)
        counter_inc("serve_occupancy_slots", bb)
        if self._row_slots is not None:
            self._state_rows += n
            counter_inc("serve_state_rows", n)

    def _decode(self):
        """One plain decode iteration, one step AHEAD of the host: build and
        enqueue step k+1 for the rows that will still be live, THEN read back
        and land step k, which the device finished, or is finishing, while
        the host built. The fed tokens of the rows in flight never visit the
        host (``generation.feed_tokens_back``).

        What the host knows without the token it uses: a row whose budget
        ends with the token in flight is left out of step k+1. A row that
        ends on an EOS value, is cancelled or misses its deadline is found
        one step late: the row-step computed for it is thrown away
        (``serve_decode_wasted_rows``) and its token reaches neither the
        result nor the stream. Its blocks are freed when it retires: the
        device runs programs in the order they were enqueued, so whoever
        inherits a block writes it after the dead row's last write.

        The ``decode_step`` span holds the enqueue of step k+1 (its self
        time), then ``decode_readback`` and ``decode_land`` of step k;
        ``rows`` / ``bucket`` / ``step`` and the expert counts describe the
        step that LANDS in it (``ahead`` 1); where the enqueue built a new
        program the span also carries its compile stages, with
        ``compiled_bucket``. The iteration that finds nothing in flight only
        enqueues (``ahead`` 0, no landing; its attributes are the enqueued
        step's), and one whose rows all end with the step in flight only
        lands."""
        jnp = self._jnp
        built = self._decode_build()
        prev = self._flight  # read AFTER the build, which may have drained
        if built is None:
            if prev is not None:
                with self._landing_span(prev) as sp:
                    self._land(prev, sp)
            return
        rows, bb, mb, _, _, pos, _, temps, ints = built
        # a width upgrade pops the old entry, so compare by key presence,
        # not _fns length
        warm = ("decode", bb, mb) in self._fns
        with (self._landing_span(prev, ahead=1) if prev is not None else
              span("decode_step", bucket=bb, rows=len(rows),
                   step=self._step_i, ahead=0,
                   **self._context_attrs(pos, len(rows), bb))) as sp:
            self._beat = time.monotonic()  # staleness clock covers this op
            if not warm:
                # the compile stages this span will carry are of the program
                # it ENQUEUES, which may not be the landing step's bucket
                sp.set(compiled_bucket=bb)
            fn = self._get_fn("decode", bb, mb)
            self._compiling = not warm
            # only a sampling row reads the key: a step that has one gets a
            # key of its own, every other the base key as it lies on the
            # device (sampled streams are not replay-stable: one key a step,
            # shared by the rows of whatever batch the step holds)
            key = self._key
            if temps.any():
                key = self._jax.random.fold_in(
                    key, self._step_i + (prev is not None))
            t0 = time.monotonic()
            # an arch with routed experts reports the rows each expert took
            # beside the tokens: ONE blocking read for both, when it lands
            arrays = self._run(
                fn, self._compute_params, jnp.asarray(ints),
                self._no_prev if prev is None else prev.arrays[0], key,
                pools_first=True)
            for s in prev.rows if prev is not None else ():
                s.slot = -1
            for r, s in enumerate(rows):
                s.slot = r
            self._flight = _Flight(arrays, rows, bb, pos, t0, warm)
            # the call compiled if it had to: its grace ends here, and the
            # read that follows (of a step enqueued earlier) has its own beat
            self._beat = time.monotonic()
            self._compiling = False
            if prev is None:
                if self._obs is not None:
                    sp.set(traces=tuple(s.req.trace for s in rows))
                return
            self._ahead += 1
            counter_inc("serve_decode_ahead")
            self._land(prev, sp)

    def _context_attrs(self, pos: np.ndarray, n: int, bucket: int) -> dict:
        """What a step of ``n`` live rows writing ``pos`` reads of the
        caches, as the arch says it. An arch whose block-table read has a
        copy schedule says what the schedule does with these positions in
        the ``bucket``'s program (``step_attrs``; an arch that caches K and V
        per head reads them through the kernel where the engine built the
        kernel step, a call a layer). Of an arch whose caches are of several
        kinds, beside that, what the step reads of each, under the names the
        arch declares (``cache["span_attrs"]``, attribute -> kind): the
        context of a paged layer (every reader sees the same tokens), the
        tokens inside the windows, the rows whose state is updated. The rows
        that pad the bucket are not counted."""
        arch, attrs = self._arch, {}
        if self._row_slots is None:
            # one kind of cache: every layer reads the rows' whole contexts
            attrs["context_tokens"] = int(pos[:n].astype(np.int64).sum()) + n
        if self._step_attrs is not None:
            attrs.update(self._step_attrs(
                pos[:n], bucket, self.config.block_size, self._max_blocks,
                self._dtype))
        if self._row_slots is not None:
            ctx = pos[:n].astype(np.int64) + 1
            of_kind = {"paged": int(ctx.sum()),
                       "window": int(np.minimum(ctx, self._window).sum()),
                       "state": n}
            attrs.update((name, of_kind[kind]) for name, kind in
                         arch["cache"].get("span_attrs", {}).items())
        return attrs

    def _landing_span(self, fl: _Flight, ahead: int = 0, **attrs):
        """The ``decode_step`` span a step lands in, with the attributes
        that describe it."""
        sp = span("decode_step", bucket=fl.bucket, rows=len(fl.rows),
                  step=self._step_i, ahead=ahead, **attrs,
                  **self._context_attrs(fl.pos, len(fl.rows), fl.bucket))
        if self._obs is not None:
            sp.set(traces=tuple(s.req.trace for s in fl.rows))
        return sp

    def _land(self, fl: _Flight, sp):
        """Read step ``fl``'s tokens back and land them, through the row list
        of its dispatch: a row whose request is already done is skipped. A
        device error of the step surfaces here, at its read; the record goes
        with whatever was enqueued behind it (which was fed its tokens), so
        the error's handler finds landed positions and an empty pipeline."""
        if self._flight is fl:  # nothing was enqueued behind it
            self._drop_flight()
        try:
            nxt, *extras = self._decode_readback(*fl.arrays)
            if extras:
                self._note_experts(sp, extras[0], len(fl.rows))
            self._step_done(sp, fl.warm, fl.t0, len(fl.rows), fl.bucket)
            live = [(r, s) for r, s in enumerate(fl.rows)
                    if not s.req.done.is_set()]
            with self._decode_land([s for _, s in live]) as land:
                for r, s in live:
                    if s.pos != fl.pos[r]:
                        raise ServeError(
                            f"request {s.req.id}: the step in flight wrote "
                            f"position {int(fl.pos[r])}, the sequence is at "
                            f"{s.pos}")
                    self._append_token(s, int(nxt[r]))
                land.set(tokens=len(live))
        except Exception:
            self._drop_flight()
            raise
        wasted = len(fl.rows) - len(live)
        if wasted:
            self._wasted_rows += wasted
            counter_inc("serve_decode_wasted_rows", wasted)

    def _drop_flight(self):
        fl, self._flight = self._flight, None
        for s in fl.rows if fl is not None else ():
            s.slot = -1

    def _drain(self):
        """Land the step in flight NOW, with nothing enqueued behind it:
        whatever takes a sequence out of the running set other than a
        landing (eviction, the OOM back-off, snapshot and handoff, shutdown,
        crash containment) needs the true positions first. Scheduler thread,
        or a thread that owns a dead or quiesced scheduler's state."""
        fl = self._flight
        if fl is None:
            return
        self._drains += 1
        counter_inc("serve_decode_drains")
        with self._landing_span(fl, drain=1) as sp:
            self._land(fl, sp)

    def _settle(self):
        """``_drain`` for the error paths: a step whose read fails is
        dropped (``_land``), and its rows are stepped again from their
        landed positions or fail with the engine."""
        try:
            self._drain()
        except Exception:  # lint: ok(oom-handler) — the record is dropped; the caller is already handling the failure
            pass

    # -- speculative decode ---------------------------------------------------
    def _propose(self, bb: int) -> np.ndarray:
        """Per-row draft proposals (bb, spec_k) for the greedy rows, -1
        padded (a -1 can never equal a verify argmax, so unproposed slots
        accept nothing and the step degenerates to plain decode)."""
        k = self._spec_k
        drafts = np.full((bb, k), -1, np.int32)
        greedy_rows = [(r, s) for r, s in enumerate(self._running)
                       if s.req.temperature <= 0.0]
        if not greedy_rows:
            return drafts
        if self._drafter is True:  # host-side n-gram prompt lookup
            for r, s in greedy_rows:
                got = _ngram_propose(s.tokens, k)
                drafts[r, :len(got)] = got
            return drafts
        darch, dparams, W = self._drafter
        ids = np.zeros((bb, W), np.int32)
        lens = np.ones((bb,), np.int32)
        for r, s in greedy_rows:
            tl = min(len(s.tokens), W)
            ids[r, :tl] = s.tokens[-tl:]
            lens[r] = tl
        warm = ("draft", bb) in self._fns
        with span("draft", bucket=bb, rows=len(greedy_rows)):
            self._beat = time.monotonic()
            fn = self._get_fn("draft", bb)
            self._compiling = not warm
            out = np.asarray(fn(dparams, self._jnp.asarray(ids),
                                self._jnp.asarray(lens)))
            self._beat = time.monotonic()
            self._compiling = False
        for r, _ in greedy_rows:
            drafts[r] = out[r]
        return drafts

    def _decode_spec(self):
        """One speculative scheduler step: draft k tokens per row, verify
        all of them (plus the pending next-input token) in ONE compiled
        paged step, accept the longest agreeing prefix. Greedy rows emit
        1..k+1 tokens per step bit-identically to plain decode; sampling
        rows take the j=0 sampled token and accept no drafts."""
        jnp, jax = self._jnp, self._jax
        k = self._spec_k
        # synchronous: the accept length is a host comparison, so a step
        # cannot be built before the one before it has landed
        self._drain()
        built = self._decode_build(k)
        if built is None:
            return
        rows_live, bb, mb, drafts, tables, pos, toks, temps, _ = built
        n = len(rows_live)
        warm = ("spec", bb, mb) in self._fns
        with span("decode_step", bucket=bb, rows=n, step=self._step_i,
                  spec_k=k) as sp:
            if self._obs is not None:
                sp.set(traces=tuple(s.req.trace for s in self._running))
            self._beat = time.monotonic()
            fn = self._get_fn("spec", bb, mb)
            self._compiling = not warm
            self._key, sub = jax.random.split(self._key)
            t0 = time.monotonic()
            greedy, sampled = self._decode_readback(*self._run(
                fn, self._compute_params, jnp.asarray(tables),
                jnp.asarray(pos), jnp.asarray(toks), jnp.asarray(temps), sub,
                pools_first=True))
            self._step_done(sp, warm, t0, n, bb)
            proposed = accepted = emitted = 0
            with self._decode_land(rows_live) as land:
                for r, s in enumerate(rows_live):
                    if temps[r] > 0.0:
                        self._append_token(s, int(sampled[r]))
                        emitted += 1
                        continue
                    nprop = int(np.sum(drafts[r] >= 0))
                    m = 0
                    while m < nprop and drafts[r, m] == greedy[r, m]:
                        m += 1
                    proposed += nprop
                    accepted += m
                    # the m accepted drafts re-emerge as the target's own
                    # argmax continuations, plus the bonus token after the
                    # last one
                    for j in range(m + 1):
                        if s.req.done.is_set():
                            break
                        self._append_token(s, int(greedy[r, j]))
                        emitted += 1
                land.set(tokens=emitted)
            sp.set(drafted=proposed, accepted=accepted)
        counter_inc("serve_draft_proposed", proposed)
        counter_inc("serve_draft_accepted", accepted)

    def _append_token(self, seq: _Seq, tok: int):
        """Record one generated token; retire the sequence when it hits eos,
        its budget, or a cancel flag."""
        req = seq.req
        seq.tokens.append(tok)
        counter_inc("serve_tokens")
        if req.stream_q is not None:
            req.stream_q.put(tok)
        if req.cancelled:
            self._retire(seq, error=RequestCancelled(
                f"request {req.id} cancelled"))
        elif (req.eos_token_id is not None and tok == req.eos_token_id) \
                or seq.generated >= req.max_new_tokens:
            self._retire(seq)

    def _retire(self, seq: _Seq, error: Optional[BaseException] = None):
        self._release(seq)
        if seq in self._running:
            self._running.remove(seq)
        self._finish_request(seq.req, tokens=seq.tokens, error=error)

    def _finish_request(self, req: _Request, tokens=None, error=None):
        if _finish(req, tokens=tokens, error=error):
            if self._obs is not None:
                self._obs.on_done(req, error)
            if error is None:
                # completed-request latency EMA drives the Overloaded
                # retry_after_s hint
                lat = req.t_done - req.t_submit
                self._ema_req_s = (lat if not self._ema_req_s
                                   else 0.8 * self._ema_req_s + 0.2 * lat)

    # -- cancellation / teardown ---------------------------------------------
    def _cancel(self, req: _Request):
        with self._cv:
            req.cancelled = True
            self._cv.notify()

    def _drain_cancels(self):
        for seq in [s for s in self._running if s.req.cancelled]:
            self._retire(seq, error=RequestCancelled(
                f"request {seq.req.id} cancelled"))
        for seq in [s for s in self._resume if s.req.cancelled]:
            self._resume.remove(seq)
            self._finish_request(seq.req, error=RequestCancelled(
                f"request {seq.req.id} cancelled"))
        # mid-chunked-prefill cancels free their (fully allocated) prompt
        # blocks immediately — chunks already written are simply abandoned
        for seq in [s for s in self._prefilling if s.req.cancelled]:
            self._prefilling.remove(seq)
            if seq.blocks:
                self._pool.free(seq.blocks)
                seq.blocks = []
            self._finish_request(seq.req, error=RequestCancelled(
                f"request {seq.req.id} cancelled"))
        # queued-but-unadmitted cancels must not wait for a batch slot: a
        # saturated engine would otherwise sit on them for minutes
        with self._cv:
            cancelled = [r for r in self._waiting if r.cancelled]
            for req in cancelled:
                self._waiting.remove(req)
        for req in cancelled:
            self._finish_request(req, error=RequestCancelled(
                f"request {req.id} cancelled"))

    def _shutdown(self):
        err = self._broken or ServeError("serving engine closed")
        self._settle()  # the tokens of the step in flight, before the error
        if self._prefix is not None:
            try:
                self._prefix.release_all()
            except Exception:  # lint: ok(oom-handler) — corrupt-pool containment sweep, crash already classified in _step
                pass
        with self._cv:
            waiting = list(self._waiting)
            self._waiting.clear()
        for req in waiting:
            self._finish_request(req, error=ServeError(str(err)))
        # _admitting covers sequences a crash caught mid-prefill; the
        # done-guard in _finish_request dedupes any that made it to _running.
        # Per-sequence guards: when the crash WAS a pool inconsistency, the
        # same free() would raise again here — one bad sequence must not
        # stop us failing the remaining handles.
        for seq in list(self._running) + list(self._resume) \
                + list(self._admitting) + list(self._prefilling):
            try:
                self._release(seq)
            except Exception:  # lint: ok(oom-handler) — corrupt-pool containment sweep, crash already classified in _step
                pass
            seq.blocks = []
            try:
                self._finish_request(seq.req, error=ServeError(str(err)))
            except Exception:  # lint: ok(oom-handler) — handle-state sweep, nothing dispatches in this try
                pass
        self._running, self._resume, self._admitting = [], [], []
        self._prefilling = []

    # -- compiled-program cache ----------------------------------------------
    def _get_fn(self, kind: str, *bucket):
        """One jitted program per (kind, bucket shape); the count of entries
        IS the compile count the bucket policy promises (<= buckets used)."""
        key = (kind,) + bucket
        fn = self._fns.get(key)
        if fn is None:
            # ``program_build`` holds the builders' Python and ``jax.jit``;
            # the stages of the compilation land on the span this is called
            # under, at the program's first call
            names = (("bucket_b", "bucket_t") if kind.startswith("prefill")
                     else ("bucket", "width"))
            with kept_span("program_build", kind=kind,
                           **dict(zip(names, bucket))) as sp:
                fn = self._fns[key] = self._build_fn(kind, bucket)
            self._programs[key] = (sp, current_span())
            counter_inc("serve_compiles")
        return fn

    def _build_fn(self, kind: str, bucket: tuple):
        jax, G = self._jax, self._G
        if self._tp:
            # tensor-parallel builders: packed param tree, shard_map
            # body, dequantization inside the body — no outer dequant
            # wrapper. Same call signatures, same donation slots.
            tpkw = dict(mesh=self._tp_mesh, vocab=self._tp_vocab,
                        dtype=self._dtype,
                        int8_wire=bool(self.config.tp_int8))
            if kind == "prefill":
                bw, t_bucket = bucket
                raw = G.build_tp_paged_prefill(
                    self._arch_key, bw, t_bucket,
                    self.config.block_size, self._max_blocks, **tpkw)
                donate = (4, 5)
            elif kind == "prefill_tail":
                bw, t_bucket = bucket
                raw = G.build_tp_paged_tail_prefill(
                    self._arch_key, bw, t_bucket,
                    self.config.block_size, self._max_blocks, **tpkw)
                donate = (5, 6)
            elif kind == "decode":
                bb, mb = bucket
                raw = G.build_tp_paged_decode(
                    self._arch_key, bb, self.config.block_size, mb,
                    use_kernel=self._paged_kernel, **tpkw)
                donate = (1, 2)
            else:  # spec/draft excluded by EngineConfig validation
                raise RuntimeError(
                    f"serving: program kind {kind!r} has no "
                    "tensor-parallel build")
            if kind == "decode":
                raw = G.feed_tokens_back(raw, bb, self.config.max_batch,
                                         mb, len(self._cache))
            return jax.jit(raw, donate_argnums=donate)
        if kind == "prefill":
            bw, t_bucket = bucket
            raw = G.build_paged_prefill(
                self._arch, bw, t_bucket, self.config.block_size,
                self._max_blocks)
            first = 4 + (self._row_slots is not None)  # after the slots
            donate = tuple(range(first, first + len(self._cache)))
        elif kind == "prefill_tail":
            bw, t_bucket = bucket
            raw = G.build_paged_tail_prefill(
                self._arch, bw, t_bucket, self.config.block_size,
                self._max_blocks)
            donate = tuple(range(5, 5 + len(self._cache)))
        elif kind == "spec":
            bb, mb = bucket
            raw = G.build_paged_spec_decode(
                self._arch, bb, self._spec_k, self.config.block_size, mb)
            donate = (1, 2)
        elif kind == "draft":
            # drafter weights, not the (possibly int8) target params —
            # no dequant wrapper, nothing donated
            (bb,) = bucket
            darch, _, W = self._drafter
            return jax.jit(G.build_window_draft(darch, bb, W, self._spec_k))
        else:
            bb, mb = bucket
            build = (G.build_paged_decode_kernel if self._paged_kernel
                     else G.build_paged_decode)
            raw = G.feed_tokens_back(
                build(self._arch, bb, self.config.block_size, mb), bb,
                self.config.max_batch, mb, len(self._cache),
                slots=self._row_slots is not None)
            donate = tuple(range(1, 1 + len(self._cache)))
        if self._dequant is not None:
            dq, inner = self._dequant, raw

            def raw(params, *args, _dq=dq, _inner=inner):
                return _inner(_dq(params), *args)

            # the device line tells programs apart by name (``jit_step``
            # / ``jit_prefill``): the int8 wrapper keeps the builder's
            raw.__name__ = raw.__qualname__ = inner.__name__

        # donation lets XLA update the pools in place — on every backend,
        # the CPU tier included, so a host-side reference that outlives a
        # step fails in tier-1 ("Array has been deleted") and not first
        # on the chip
        return jax.jit(raw, donate_argnums=donate)

    # -- flight-recorder context ----------------------------------------------
    def _flight_context(self) -> dict:
        with self._lock:
            depth = len(self._waiting)
        return {
            "queue_depth": depth,
            "step": self._step_i,
            "spec_k": self._spec_k,
            # mesh + chunked-prefill state (PR 19): post-mortems on a
            # sharded engine must name the mesh, and a stall diagnosis
            # needs the chunk backlog at the crash step
            "tp": self._tp,
            "prefill_chunk": self._chunk,
            "chunk_queue_depth": len(self._prefilling),
            "pending_chunks": sum(
                -(-(len(s.tokens) - s.chunk_pos) // max(self._chunk, 1))
                for s in list(self._prefilling)),
            "prefix_cached_blocks": (self._prefix.blocks
                                     if self._prefix is not None else 0),
            "pages": {"used": self._pool.used_blocks,
                      "free": self._pool.free_blocks,
                      "parked": self._pool.parked_blocks},
            "decode_in_flight": self._flight is not None,
            "running": [
                {"id": s.req.id, "prompt_len": s.prompt_len,
                 "generated": s.generated, "pos": s.pos,
                 "blocks": len(s.blocks)}
                for s in list(self._running)
            ],
        }

    # -- test/debug hook -------------------------------------------------------
    def _debug_prefill_logits(self, prompt_ids) -> np.ndarray:
        """Logits at the prompt's last token through the REAL bucketed
        prefill program, with every table entry pointed at the trash block
        (no allocation, pool contents untouched where it matters). Callers
        must hold the engine idle — this runs on the calling thread."""
        jnp = self._jnp
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        t_bucket = self._bucket_for(len(prompt))
        bw = self.config.prefill_batch
        fn = self._get_fn("prefill", bw, t_bucket)
        ids = np.zeros((bw, t_bucket), np.int32)
        ids[0, :len(prompt)] = prompt
        lens = np.ones((bw,), np.int32)
        lens[0] = len(prompt)
        tables = np.full((bw, self._max_blocks), TRASH_BLOCK, np.int32)
        logits, *_ = self._run(
            fn, self._compute_params, jnp.asarray(ids), jnp.asarray(lens),
            jnp.asarray(tables), *self._slot_operand([], bw))
        return np.asarray(logits[0])


def _engine_loop(wr):
    """Scheduler thread body. Holds the engine only through a weakref and
    re-derefs every iteration, so an abandoned engine is GC-collectable
    (its __del__ runs close(); a dead deref also just ends the thread)."""
    while True:
        eng = wr()
        if eng is None:
            return
        try:
            stopped = eng._run_once()
        except Exception as e:
            # fail loudly into every pending handle rather than leave
            # clients blocked on events that will never fire — and nothing
            # (not even a failing post-mortem) may stand between the crash
            # and that sweep. An exhaustion that defeated the in-step shrink
            # ladder lands here too — classified, then contained.
            from ..fault import memory as _mem

            if _mem.is_oom(e):
                _mem.note_oom("serve.loop", e)
            eng._broken = e
            # what is harvested, captured or failed below holds landed
            # tokens only
            eng._settle()
            try:
                counter_inc("serve_engine_errors")
                flight.dump("serving_loop_error", extra={"exception": repr(e)})
            finally:
                if eng._supervised:
                    # leave queued/in-flight scheduler state intact for the
                    # supervisor to harvest (requeue onto the restarted
                    # engine, or fail structurally) — _shutdown here would
                    # fail handles the restart could still save. The kick
                    # wakes the monitor without waiting out its poll.
                    eng._failed.set()
                else:
                    eng._shutdown()
            return
        if stopped:
            # handoff quiesce exits WITHOUT failing handles: the exported
            # snapshot is their owner from here (Engine.handoff docstring)
            if stopped != "handoff":
                eng._shutdown()
            return
        del eng
