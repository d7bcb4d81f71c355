"""Structured span tracer — nested, attributed host spans.

Reference parity: the new profiler composes HostTracer events into ONE
timeline with parent/child structure (``paddle/fluid/platform/profiler/``
HostEventRecorder + chrome-trace nesting). Here spans are the coarse-grained
skeleton of a training step — ``train_step`` → ``lazy_flush`` →
``trace``/``donate``/``compile``/``execute``, ``dp_sync`` → per-bucket
collective, ``ckpt_save`` → ``serialize``/``commit`` — each carrying typed
attributes (graph node count, executable-cache key + hit/miss, donated
bytes, bucket bytes, fallback reason) so the single most important lazy-mode
question — "did this step recompile, replay a cached executable, or stall on
sync?" — is answerable from the trace.

Two sinks, different lifetimes:

* the **flight recorder** (:mod:`.flight`) receives every finished span,
  always — a bounded deque append, so the disabled-path cost is near zero
  (spans exist only at flush/step/save granularity, never per op);
* the **profiler session** receives spans only while a
  :class:`~paddle_tpu.profiler.Profiler` is recording — into the native span
  ring (``runtime_cpp/trace.cc`` ``ptt_span_record``) when built, else a
  Python list; attributes ride in a bounded side table keyed by span id and
  are re-joined at export. Exactly ONE sink holds the timing record, so
  ``export()`` never double-counts.

Beside its host-clock record every span is a ``jax.profiler.TraceAnnotation``
of the same name: while any ``jax.profiler`` trace runs, the spans lie on the
host plane of the xplane file, on the clock of the device operations, with
their scalar attributes as the event's stats. And compilation is charged to
the span it fired under (:func:`_on_compile_duration`): a ``decode_step`` or
``train_step`` that carries ``compile_backend_s`` is a step that compiled.
Those spans, and the few that build a process (:func:`kept_span`), are what
:func:`setup_account` keeps: the cold start, by program.
"""
from __future__ import annotations

import itertools
import sys
import threading
import time
from typing import Dict, List, Optional

__all__ = ["Span", "span", "kept_span", "setup_account", "account_row",
           "current_span", "active_spans", "add_span_observer",
           "remove_span_observer"]

_ids = itertools.count(1)  # GIL-atomic enough; 0 means "no parent"
_tls = threading.local()

# Compact per-thread display ids (chrome traces want small ints, and
# threading.get_ident() values are neither small nor stable across runs).
_tid_map: Dict[int, int] = {}
_tid_lock = threading.Lock()


def _tid() -> int:
    ident = threading.get_ident()
    t = _tid_map.get(ident)
    if t is None:
        with _tid_lock:
            t = _tid_map.setdefault(ident, len(_tid_map))
    return t


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = []
        _tls.stack = s
    return s


class Span:
    """One finished (or in-flight) span. ``attrs`` is a plain dict the owner
    may mutate until ``__exit__`` — e.g. the flush sets ``cache=hit/miss``
    only after the executable-cache probe."""

    __slots__ = ("name", "span_id", "parent_id", "tid", "t0", "t1", "attrs",
                 "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.span_id = next(_ids)
        self.parent_id = 0
        self.tid = 0
        self.t0 = 0
        self.t1 = 0
        self.attrs = attrs
        self._ann = None

    # -- context manager ---------------------------------------------------
    def __enter__(self) -> "Span":
        st = _stack()
        self.parent_id = st[-1].span_id if st else 0
        self.tid = _tid()
        st.append(self)
        self._ann = _annotate(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t1 = time.perf_counter_ns()
        if self._ann is not None:
            _close_annotation(self, exc_type, exc, tb)
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        elif self in st:  # mis-nested exit (generator teardown): repair
            st.remove(self)
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        _emit(self)
        return False

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    @property
    def dur_ns(self) -> int:
        return self.t1 - self.t0

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "tid": self.tid,
            "t0": self.t0,
            "t1": self.t1,
            "dur_us": (self.t1 - self.t0) / 1000.0,
            "attrs": dict(self.attrs),
        }

    def __repr__(self):
        return (
            f"Span({self.name!r}, id={self.span_id}, parent={self.parent_id}, "
            f"dur_us={(self.t1 - self.t0) / 1000.0:.1f}, attrs={self.attrs})"
        )


# ``jax.profiler.TraceAnnotation``, bound at the first span (this module
# imports no jax); False once it failed to bind or to construct.
_annotation = None


def _annotate(sp: Span):
    """The span as an open annotation in the profiler's own trace, or None
    while no ``jax.profiler`` trace is running (one static call). A failure
    turns annotations off for the process and never reaches the span."""
    global _annotation
    ann = _annotation
    if ann is None:
        try:
            import jax

            ann = _annotation = jax.profiler.TraceAnnotation
        except Exception:
            ann = _annotation = False
    if not ann:
        return None
    try:
        if not ann.is_enabled():
            return None
        live = ann(sp.name)
        live.__enter__()
        return live
    except Exception:
        _annotation = False
        return None


def _close_annotation(sp: Span, exc_type, exc, tb) -> None:
    """Close the span's annotation; its scalar attributes, as they stand now
    (``blocks_grown``, ``compile_backend_s``, ... are set while the span is
    open), become the event's stats."""
    global _annotation
    try:
        sp._ann.set_metadata(**{k: v for k, v in sp.attrs.items()
                                if type(v) in (int, float, str, bool)})
        sp._ann.__exit__(exc_type, exc, tb)
    except Exception:
        _annotation = False


def span(name: str, **attrs) -> Span:
    """``with span("lazy_flush", nodes=n) as sp: ... sp.set(cache="hit")``"""
    return Span(name, **attrs)


class _KeptSpan(Span):
    """A span a set-up site asked the account to keep (:func:`kept_span`):
    it is in the account already, so the compile listener, which keeps the
    spans it charges, leaves it alone."""

    __slots__ = ()


def kept_span(name: str, **attrs) -> Span:
    """A :func:`span` that the set-up account keeps whether or not anything
    compiles under it (``engine_init``, ``program_build``, ``step_text``):
    for sites that run once a process or once a program, never a step."""
    sp = _KeptSpan(name, **attrs)
    _keep(sp)
    return sp


def current_span() -> Optional[Span]:
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


def active_spans() -> List[Span]:
    """The current thread's OPEN span stack, outermost first (post-mortem
    dumps serialize this to name the span a failure happened inside)."""
    return list(getattr(_tls, "stack", ()) or ())


# -- compilation, charged to the span it fired under --------------------------
# ``jax.monitoring`` reports each stage of a compilation as it ends, on the
# thread that compiled. The two listeners below are registered once, by the
# package; they run only when such an event fires, never on a warm step. An
# event outside any span of the program is not the program's and is left
# alone.
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
# event -> the span attribute it is charged to; the counters are named one
# by one where they are bumped (``paddle_tpu.analysis`` reads the literals)
_STAGE = {_TRACE: "compile_trace_s", _LOWER: "compile_lower_s",
          _BACKEND: "compile_backend_s", _CACHE_LOAD: "compile_cache_load_s",
          _CACHE_SAVED: "compile_cache_saved_s"}
_COUNT = {_CACHE_HIT: "compile_cache_hits", _CACHE_MISS: "compile_cache_misses"}
_CHARGES = frozenset(_STAGE.values()) | frozenset(_COUNT.values())

# -- the set-up account --------------------------------------------------------
# The Span objects that compiled (noted by the listeners below at their first
# charge) and the few a set-up site asked for by name (``kept_span``), in the
# order they were noted. Rows are computed from them when the account is READ:
# a span that neither compiles nor is such a site never comes near this list,
# so a warm step pays nothing for it. Bounded: a server that compiles a new
# bucket an hour must not grow.
_KEPT_MAX = 256
_kept: List["Span"] = []
_kept_lock = threading.Lock()


def _keep(sp: "Span") -> None:
    with _kept_lock:
        if len(_kept) < _KEPT_MAX:
            _kept.append(sp)
            return
    sys.modules[__package__].counter_inc("setup_account_dropped")


def _charged(sp: "Span") -> None:
    """Called by the listeners BEFORE they charge ``sp``: its first charge
    puts it into the account."""
    if not isinstance(sp, _KeptSpan) and _CHARGES.isdisjoint(sp.attrs):
        _keep(sp)


def account_row(sp: "Span") -> dict:
    """One row of the set-up account: the span's name, ``site`` (True for a
    set-up site kept by name, False for a span kept because it compiled), its
    scalar attributes, its thread and its ends on ``time.perf_counter_ns``,
    the compile stages charged to it in seconds, the persistent cache's hits
    and misses under it with what the loads took and saved, and
    ``first_run_s``, its duration less the three stages (for a span that
    compiled a program: the program's first run and the step's own work)."""
    a = sp.attrs
    stages = [a.get(k, 0.0) for k in
              ("compile_trace_s", "compile_lower_s", "compile_backend_s")]
    row = {"name": sp.name, "site": isinstance(sp, _KeptSpan)}
    for k, v in a.items():
        if k not in _CHARGES and type(v) in (int, float, str, bool):
            row.setdefault(k, v)
    row.update(
        tid=sp.tid, t0_ns=sp.t0, t1_ns=sp.t1,
        trace_s=stages[0], lower_s=stages[1], backend_s=stages[2],
        cache_hits=a.get("compile_cache_hits", 0),
        cache_misses=a.get("compile_cache_misses", 0),
        cache_load_s=a.get("compile_cache_load_s", 0.0),
        cache_saved_s=a.get("compile_cache_saved_s", 0.0),
        first_run_s=max((sp.t1 - sp.t0) / 1e9 - sum(stages), 0.0))
    return row


def setup_account() -> List[dict]:
    """What this process compiled and what built it, as rows in order of
    time (:func:`account_row`): every span a compile stage was charged to,
    once, and the set-up sites kept by name (``engine_init`` with
    ``pool_alloc`` / ``pack_params`` / ``quantize_params``,
    ``program_build``, ``step_text``). A row's ``first_run_s`` is its own:
    what the kept spans inside it took is theirs, so the rows add up. Spans
    still open are left out. At most 256 spans are kept; counter
    ``setup_account_dropped`` counts the rest."""
    with _kept_lock:
        kept = sorted((sp for sp in _kept if sp.t1), key=lambda sp: sp.t0)
    rows = [account_row(sp) for sp in kept]
    for i, (sp, row) in enumerate(zip(kept, rows)):
        inside, end = 0, sp.t0
        for other in kept[i + 1:]:
            if other.t0 >= sp.t1:
                break
            if other.tid == sp.tid and other.t0 >= end and other.t1 <= sp.t1:
                inside, end = inside + other.t1 - other.t0, other.t1
        row["first_run_s"] = max(row["first_run_s"] - inside / 1e9, 0.0)
    return rows


def _reset_account() -> None:
    with _kept_lock:
        _kept.clear()


def _own_trace_ns(dur_ns: int, root_t0: int) -> int:
    """What of a trace event no earlier event has counted. A jit called
    while another is being traced reports its own trace, inner before outer,
    so the durations overlap: keep (arrival, duration) of the events of this
    thread that no later one has swallowed, and take those that arrived
    inside the new event's stretch off it."""
    now = time.perf_counter_ns()
    seen = getattr(_tls, "traces", None)
    if seen is None:
        seen = _tls.traces = []
    elif seen and seen[0][0] < root_t0:  # from before the outermost open span
        seen[:] = [e for e in seen if e[0] >= root_t0]
    inner = 0
    while seen and seen[-1][0] >= now - dur_ns:
        inner += seen.pop()[1]
    seen.append((now, dur_ns))
    return max(dur_ns - inner, 0)


def _on_compile_duration(event: str, duration: float, **_) -> None:
    key = _STAGE.get(event)
    if key is None:
        return
    st = getattr(_tls, "stack", None)
    if not st:
        return
    pkg, ns = sys.modules[__package__], int(duration * 1e9)
    if event == _BACKEND:  # the compiler itself, or the load of a cached program
        pkg.counter_inc("compile_backend_ns", ns)
    elif event == _LOWER:
        pkg.counter_inc("compile_lower_ns", ns)
    elif event == _TRACE:
        ns = _own_trace_ns(ns, st[0].t0)
        pkg.counter_inc("compile_trace_ns", ns)
    # the cache's own two (what the load took, what it saved: either may be
    # negative) have no counter: they lie inside the backend stage
    _charged(st[-1])
    attrs = st[-1].attrs
    attrs[key] = attrs.get(key, 0.0) + ns / 1e9


def _on_compile_event(event: str, **_) -> None:
    key = _COUNT.get(event)
    st = getattr(_tls, "stack", None)
    if key is None or not st:
        return
    _charged(st[-1])
    attrs = st[-1].attrs
    attrs[key] = attrs.get(key, 0) + 1
    if event == _CACHE_HIT:
        sys.modules[__package__].counter_inc("compile_cache_hits")
    else:  # the program was compiled and written to the cache
        sys.modules[__package__].counter_inc("compile_cache_misses")


# -- session sink ------------------------------------------------------------
# Python-side finished spans for the recording session (used when the native
# span ring is unavailable). Attrs always live Python-side: the native ring
# holds only (name_id, tid, t0, t1, span_id, parent_id).
_span_events: List[Span] = []
_span_attrs: Dict[int, dict] = {}  # span_id -> attrs (joined at export)
_SPAN_ATTRS_MAX = 1 << 16  # matches the native ring capacity


_pkg = None  # the parent package module, bound lazily (import-order safe)

# Span observers (serving/observe.py request tracing): called with every
# FINISHED span, synchronously on the emitting thread. The empty-tuple probe
# is the entire disabled-path cost; observers must be cheap and never raise
# (a raising observer is dropped from the fan-out, never from the sinks).
_observers: tuple = ()
_observers_lock = threading.Lock()


def add_span_observer(fn) -> None:
    global _observers
    with _observers_lock:
        if fn not in _observers:
            _observers = _observers + (fn,)


def remove_span_observer(fn) -> None:
    global _observers
    with _observers_lock:
        _observers = tuple(o for o in _observers if o is not fn)


def _emit(sp: Span) -> None:
    global _pkg
    if _pkg is None:
        _pkg = sys.modules[__package__]
    if _observers:
        for fn in _observers:
            try:
                fn(sp)
            except Exception:
                remove_span_observer(fn)
    _pkg.flight.record(sp)
    if not _pkg._enabled:
        return
    rec = _pkg._native_recorder()
    if rec is not None and _pkg._native_spans:
        nid = _pkg._native.ptt_intern(rec, sp.name.encode())
        _pkg._native.ptt_span_record(
            rec, nid, sp.tid, sp.t0, sp.t1, sp.span_id, sp.parent_id
        )
        # the native record is timing-only; attrs ride this side table until
        # export re-joins them by span id. Evict oldest when full: the ring
        # keeps the NEWEST spans, so the table must age out the same way or
        # post-wraparound spans export attr-less while dead spans pin dicts.
        if sp.attrs:
            if len(_span_attrs) >= _SPAN_ATTRS_MAX:
                _span_attrs.pop(next(iter(_span_attrs)))
            _span_attrs[sp.span_id] = dict(sp.attrs)
    else:
        _span_events.append(sp)  # Span carries its own attrs to export


def update_attrs(sp: Span, **attrs) -> None:
    """Attach attributes to an ALREADY-FINISHED span (async runtime: the
    deferred memory census lands on the producing ``lazy_flush`` span after
    it closed). Python sinks (session list, flight ring) hold the Span object
    itself, so mutating it is enough; when the span's timing record went to
    the native ring, the side-table copy is refreshed too."""
    sp.attrs.update(attrs)
    if _pkg is not None and sp.span_id in _span_attrs:
        _span_attrs[sp.span_id] = dict(sp.attrs)
    elif (
        _pkg is not None
        and _pkg._enabled
        and _pkg._native_spans
        and sp.attrs
        and _pkg._native_recorder() is not None
    ):
        if len(_span_attrs) >= _SPAN_ATTRS_MAX:
            _span_attrs.pop(next(iter(_span_attrs)))
        _span_attrs[sp.span_id] = dict(sp.attrs)


def _reset_session() -> None:
    _span_events.clear()
    _span_attrs.clear()
