"""HBM exhaustion resilience — preflight admission + the OOM recovery ladder.

Device memory was the last unmanaged failure class in the robustness stack:
an XLA ``RESOURCE_EXHAUSTED`` was a raw crash wherever it fired — the lazy
flush, the fused engine step, a serving step. Following the LazyTensor
discipline of making runtime state observable and recoverable
(arXiv:2102.13267) and the ZeRO insight that memory pressure should be
traded for recomputation/communication rather than failure
(arXiv:2004.13336), this module makes OOM a *managed* condition:

* **Classifier** (:func:`is_oom` / :func:`classify`) — ONE place that
  decides whether an exception is a device-memory exhaustion (the
  ``XlaRuntimeError`` type or the ``RESOURCE_EXHAUSTED``/out-of-memory
  status text, chained causes included). Every ``except`` that can see an
  OOM in the dispatch layers routes through it (analysis ``oom-handler``
  lint rule).
* **Preflight admission** (:func:`preflight`) — at compile time the lazy
  flush captures each executable's ``memory_analysis()`` (via
  ``cost_model.executable_memory``) keyed like the executable cache; before
  each dispatch the predicted extra footprint (temp + output − donated/alias
  bytes) plus the current live-array census is compared against the device
  budget (``FLAGS_hbm_budget_bytes``, default backend capacity −
  ``FLAGS_hbm_reserve_bytes``). ``FLAGS_hbm_admission`` picks the policy:
  ``off`` (one flag probe per flush — the whole disabled path), ``warn``,
  or ``enforce`` (structured :class:`HbmBudgetExceeded` BEFORE the device
  is touched). Predictions ride the ``compile``/``lazy_flush`` spans.
* **Recovery ladder** when ``RESOURCE_EXHAUSTED`` fires anyway: classify →
  :func:`free_pressure` (evict cold lazy executable-cache entries, refresh
  the live census, shrink serving-pool admission headroom) → retry once →
  (engine training step only) degrade through the existing
  ``grad_accumulate`` scan path at 2×/4× microbatching — bit-identical to a
  run configured with that accumulation from the start → halt with a
  :class:`HbmExhausted` + flight post-mortem carrying the census, the
  per-executable memory attributions and every recovery attempt.

Chaos: ``hbm.oom`` / ``hbm.pressure`` (fault/inject.py) synthesize
``RESOURCE_EXHAUSTED`` at named dispatch sites / sustained pressure;
tests/test_memory_pressure.py is the suite.

Zero-cost disabled path: nothing imports this module until an exception is
being classified or ``FLAGS_hbm_admission`` is flipped on — the tier-1
inert tripwire pins that the classifier and the preflight are never called
by an unconfigured training loop.
"""
from __future__ import annotations

import collections
import threading
import warnings
import weakref
from typing import Callable, Dict, List, Optional

__all__ = [
    "HbmBudgetExceeded", "HbmExhausted", "is_oom", "classify", "note_oom",
    "preflight", "free_pressure", "budget_bytes", "last_prediction",
    "attributions", "note_executable", "post_mortem",
    "register_pressure_handler",
]

# RESOURCE_EXHAUSTED status text markers (jaxlib renders the absl status
# code into the message; PjRt allocators add their own out-of-memory prose).
# The full set is consulted only for the XLA runtime-error types; a PLAIN
# exception must carry one of the unambiguous markers — "Failed to
# allocate" alone appears in plenty of non-device errors (inodes, TLS,
# sockets) and must not conjure a phantom memory incident.
_OOM_MARKERS = (
    "RESOURCE_EXHAUSTED", "Resource exhausted", "Out of memory",
    "out of memory", "OOM when allocating", "Failed to allocate",
)
_OOM_MARKERS_STRONG = (
    "RESOURCE_EXHAUSTED", "Resource exhausted", "Out of memory",
    "out of memory", "OOM when allocating",
)


class HbmBudgetExceeded(RuntimeError):
    """Preflight admission rejected a dispatch: the predicted footprint
    would exceed the device budget. Raised BEFORE the device is touched —
    the executable is compiled and cached, nothing was dispatched. Carries
    the numbers the message names so callers can react programmatically."""

    def __init__(self, where: str, predicted_bytes: int, live_bytes: int,
                 budget_bytes: int, peak_bytes: int = 0):
        super().__init__(
            f"HBM admission rejected dispatch at '{where}': predicted "
            f"{predicted_bytes} bytes (live census {live_bytes} + executable "
            f"peak {peak_bytes}) exceeds budget {budget_bytes} bytes "
            f"(FLAGS_hbm_admission=enforce; raise FLAGS_hbm_budget_bytes, "
            f"free buffers, or shrink the step)"
        )
        self.where = where
        self.predicted_bytes = int(predicted_bytes)
        self.live_bytes = int(live_bytes)
        self.budget_bytes = int(budget_bytes)
        self.peak_bytes = int(peak_bytes)


class HbmExhausted(RuntimeError):
    """The OOM recovery ladder ran out of rungs (or recovery was impossible
    — donated inputs already invalidated). Carries the attempts made and
    the flight post-mortem path; ``__cause__`` is the original
    ``RESOURCE_EXHAUSTED``."""

    def __init__(self, where: str, attempts: List[dict],
                 dump_path: Optional[str] = None):
        names = [a.get("action", "?") for a in attempts]
        super().__init__(
            f"HBM exhausted at '{where}' and the recovery ladder failed "
            f"(attempts: {names or ['none possible']}; post-mortem: "
            f"{dump_path or 'unavailable'})"
        )
        self.where = where
        self.attempts = list(attempts)
        self.dump_path = dump_path


# -- classifier ---------------------------------------------------------------
def classify(exc: BaseException) -> Optional[dict]:
    """The ONE decision point for "is this a device-memory exhaustion".
    Walks the cause/context chain; matches the ``XlaRuntimeError`` binding
    type by name (imports of jaxlib internals stay out of the hot path) AND
    the RESOURCE_EXHAUSTED status markers, so both real PjRt errors and the
    synthesized ``hbm.oom`` chaos payloads classify identically. Returns
    ``{"kind": "hbm_oom", "type": ..., "message": ...}`` or None."""
    seen = set()
    e: Optional[BaseException] = exc
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        msg = str(e)
        typename = type(e).__name__
        if typename in ("XlaRuntimeError", "JaxRuntimeError") or isinstance(
                e, MemoryError):
            if any(m in msg for m in _OOM_MARKERS) or isinstance(e, MemoryError):
                return {"kind": "hbm_oom", "type": typename,
                        "message": msg[:500]}
        elif any(m in msg for m in _OOM_MARKERS_STRONG) and isinstance(e, Exception):
            # some wrappers re-raise the status text under a plain
            # RuntimeError (and the chaos fallback does when the binding is
            # not constructible) — but only the unambiguous markers count
            # for a non-XLA type
            return {"kind": "hbm_oom", "type": typename, "message": msg[:500]}
        e = e.__cause__ or e.__context__
    return None


def is_oom(exc: BaseException) -> bool:
    return classify(exc) is not None


# -- budget -------------------------------------------------------------------
_budget_cache: List[Optional[int]] = [None]  # resolved once per process


def budget_bytes(refresh: bool = False) -> int:
    """The device budget the admission check compares against:
    ``FLAGS_hbm_budget_bytes`` when set, else the backend-reported capacity
    (``device.memory_stats()['bytes_limit']``) minus
    ``FLAGS_hbm_reserve_bytes``. 0 = no budget resolvable (CPU reports no
    capacity): admission still predicts and attributes, never rejects."""
    from ..framework import flags

    explicit = int(flags.flag("FLAGS_hbm_budget_bytes", 0) or 0)
    if explicit:
        return explicit
    if _budget_cache[0] is None or refresh:
        cap = 0
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats()
            cap = int((stats or {}).get("bytes_limit", 0) or 0)
        except Exception:
            cap = 0
        if cap:
            cap = max(cap - int(flags.flag("FLAGS_hbm_reserve_bytes", 0) or 0), 0)
        _budget_cache[0] = cap
    return _budget_cache[0]


# -- per-executable attribution registry -------------------------------------
_lock = threading.Lock()
_ATTR_MAX = 256
_attr: "collections.OrderedDict" = collections.OrderedDict()  # guarded_by: _lock
_events: "collections.deque" = collections.deque(maxlen=32)  # guarded_by: _lock
_last: Dict[str, int] = {}  # most recent preflight numbers (BENCH line)
_warned: set = set()  # guarded_by: _lock
_provider_installed = False


def note_executable(key: str, mem: Optional[dict]) -> None:
    """Record one executable's memory analysis, keyed like the executable
    cache (the flush-signature hash) — the post-mortem's per-executable
    attribution table."""
    if mem is None:
        return
    with _lock:
        _attr[key] = dict(mem)
        _attr.move_to_end(key)
        while len(_attr) > _ATTR_MAX:
            _attr.popitem(last=False)
    _ensure_provider()


def analyze_compiled(compiled, key: Optional[str] = None) -> Optional[dict]:
    """``cost_model.executable_memory`` + registry note in one call (the
    lazy flush's compile-time capture)."""
    from ..cost_model import executable_memory

    mem = executable_memory(compiled)
    if mem is not None and key is not None:
        note_executable(key, mem)
    return mem


def attributions(top: int = 16) -> List[dict]:
    """The per-executable memory table, largest peak first."""
    with _lock:
        rows = [{"key": k, **v} for k, v in _attr.items()]
    rows.sort(key=lambda r: -r.get("peak_bytes", 0))
    return rows[:top]


def last_prediction() -> Dict[str, int]:
    """Most recent preflight numbers (predicted/live/budget bytes): the
    serving engine's cost-drift gauge reads the predicted peak against the
    census after a step (``Engine._hbm_drift``), and a flight-recorder
    dump carries them whole (this module's context provider)."""
    return dict(_last)


# -- preflight admission ------------------------------------------------------
def preflight(mem: Optional[dict], where: str, span=None,
              donated_bytes: int = 0) -> Optional[Dict[str, int]]:
    """Compare the executable's predicted footprint against the device
    budget BEFORE dispatch. ``mem`` is the compile-time
    ``executable_memory`` dict (None — e.g. a background-compile replay
    step — predicts nothing and admits).

    Estimate = current live-array census + temp + output −
    max(alias, donated) bytes: the arguments are already IN the census, and
    outputs aliasing donated inputs must not count twice — backends that
    honor the aliasing hint report it as ``alias_bytes``; backends that
    silently decline (CPU) leave alias at 0, so the donation mask's own
    byte count is the fallback correction (the donated buffers die at
    dispatch either way).

    Policy per ``FLAGS_hbm_admission``: ``warn`` warns once per call site,
    ``enforce`` raises :class:`HbmBudgetExceeded`. Callers gate on the flag
    — this function is never reached when admission is ``off`` (pinned by
    the tier-1 inert tripwire).
    """
    from .. import profiler as _prof
    from ..framework import flags

    _ensure_provider()
    census = _prof.memory_census()
    live = int(census.get("live_bytes", 0))
    if mem is None:
        pred = {"hbm_live_bytes": live}
        if span is not None:
            span.set(**pred)
        return None
    extra = (int(mem.get("temp_bytes", 0)) + int(mem.get("output_bytes", 0))
             - max(int(mem.get("alias_bytes", 0)), int(donated_bytes)))
    extra = max(extra, 0)
    pressure = 0
    from . import inject as _inject

    if _inject._armed:
        pressure = _inject.pressure_bytes()
    predicted = live + extra + pressure
    budget = budget_bytes()
    peak = int(mem.get("peak_bytes", 0))
    _prof.counter_inc("hbm_admission_checks")
    _last.update(
        hbm_predicted_peak_bytes=predicted, hbm_live_bytes=live,
        hbm_extra_bytes=extra, hbm_budget_bytes=budget,
        hbm_exec_peak_bytes=peak,
    )
    if span is not None:
        span.set(
            hbm_predicted_peak_bytes=predicted, hbm_live_bytes=live,
            hbm_extra_bytes=extra, hbm_budget_bytes=budget,
        )
    if budget and predicted > budget:
        _prof.counter_inc("hbm_admission_rejects")
        mode = str(flags.flag("FLAGS_hbm_admission", "off"))
        if mode == "enforce":
            raise HbmBudgetExceeded(where, predicted, live, budget, peak)
        with _lock:
            first = where not in _warned
            _warned.add(where)
        if first:
            warnings.warn(
                f"HBM admission: predicted {predicted} bytes exceeds budget "
                f"{budget} bytes at '{where}' (FLAGS_hbm_admission=warn — "
                f"dispatching anyway)",
                RuntimeWarning,
            )
    return _last.copy()


# -- pressure relief ----------------------------------------------------------
# Subsystems that can give memory back under pressure register a handler
# (weakly bound): the serving engine parks KV blocks (admission headroom
# shrink → backpressure), future residents can drop caches. Handlers run on
# the CALLING thread and must be cheap + thread-safe (the serving handler
# only sets a request flag its scheduler thread applies).
_pressure_handlers: Dict[str, Callable[[], Optional[dict]]] = {}


def register_pressure_handler(name: str, fn, owner=None) -> None:
    """Register a pressure-relief callback. With ``owner`` given, the
    handler is dropped automatically once the owner is collected (serving
    engines come and go; a dead engine must not pin itself here — the
    weakref's finalizer pops the registry entry)."""
    if owner is not None:
        wr = weakref.ref(owner, lambda _r, _n=name: _pressure_handlers.pop(_n, None))
        orig = fn

        def fn(_wr=wr, _orig=orig):  # noqa: F811 — deliberate rebind
            o = _wr()
            return _orig(o) if o is not None else None

    _pressure_handlers[name] = fn


def unregister_pressure_handler(name: str) -> None:
    _pressure_handlers.pop(name, None)


def free_pressure(reason: str = "oom") -> dict:
    """The ladder's give-memory-back rung: evict cold lazy executable-cache
    entries (compiled programs pin temp allocations and constants), run the
    pressure handlers (serving pool shrink), refresh the live census.
    Returns a summary dict that joins the recovery-attempt record."""
    from .. import profiler as _prof
    from ..core import lazy as lazy_mod

    evicted = lazy_mod.evict_cold()
    if evicted:
        _prof.counter_inc("hbm_cache_evicted", evicted)
    handlers = {}
    for name, fn in list(_pressure_handlers.items()):
        try:
            handlers[name] = fn()
        except Exception as e:
            handlers[name] = {"error": repr(e)}
    census = _prof.memory_census()
    return {
        "reason": reason,
        "evicted_executables": evicted,
        "handlers": handlers,
        "live_bytes": census.get("live_bytes", 0),
    }


# -- event log + post-mortem --------------------------------------------------
def note_oom(where: str, exc: BaseException) -> dict:
    """Record one classified OOM (counter + bounded event ring feeding the
    flight context provider). Returns the classification."""
    from .. import profiler as _prof

    info = classify(exc) or {"kind": "hbm_oom", "type": type(exc).__name__,
                             "message": str(exc)[:500]}
    info["where"] = where
    _prof.counter_inc("hbm_oom_trips")
    with _lock:
        _events.append(dict(info))
    _ensure_provider()
    return info


def post_mortem(where: str, attempts: List[dict],
                exc: Optional[BaseException] = None) -> Optional[str]:
    """Flight dump for an unrecovered exhaustion: the live census, the
    per-executable memory attributions, the budget, and every recovery
    attempt the ladder made."""
    from .. import profiler as _prof
    from ..profiler import flight

    try:
        census = _prof.memory_census()
    except Exception:
        census = _prof.memory_stats()
    return flight.dump(
        "hbm_exhausted",
        extra={
            "where": where,
            "census": dict(census),
            "budget_bytes": budget_bytes(),
            "attributions": attributions(),
            "attempts": list(attempts),
            "exception": repr(exc) if exc is not None else None,
        },
    )


def _context() -> dict:
    with _lock:
        events = list(_events)
    return {
        "budget_bytes": budget_bytes(),
        "last_prediction": dict(_last),
        "recent_oom": events[-8:],
        "attributions": attributions(top=8),
    }


def _ensure_provider() -> None:
    """Install the flight context provider on first real use — every crash
    dump from then on carries the budget, the last prediction, and the OOM
    event tail. Never installed by an unconfigured loop (this module is not
    even imported there)."""
    global _provider_installed
    if not _provider_installed:
        from ..profiler import flight

        flight.add_context_provider("hbm", _context)
        _provider_installed = True
