"""Lazy eager-op batching (LazyTensor engine) with an async runtime.

TPU-native answer to the reference's per-op dispatch engineering
(``paddle/fluid/imperative/tracer.cc:170`` hot loop +
``prepared_operator.cc:129`` PreparedOp caching): instead of shaving the cost
of ONE op launch, eager ops are queued into a growing expression graph and
executed as a SINGLE XLA computation at materialization points
(``.numpy()``/``.item()``/print/host control flow). In steady state a train
loop flushes once per iteration — backward(i) + optimizer-update(i) +
forward(i+1) fuse into one cached executable, giving eager code compiled-step
throughput (SURVEY §7 hard part (a): LazyTensor-style lazy batching).

Design:
  * ``LazyArray`` — placeholder carrying only an aval (shape/dtype). Tensors
    hold these in ``_data`` exactly like a ``jax.Array``; any host access
    (``__array__``, unknown attribute) forces a flush.
  * ``record(name, fn, inputs)`` — append one node; output avals come from a
    cached ``jax.eval_shape`` probe, so shape/dtype errors still surface at
    the op call site like eager mode. The wiring descriptors, leaf table and
    signature parts are built HERE, incrementally — the flush no longer walks
    the whole graph again, so per-step host work on cache hits is one
    liveness sweep plus a dict probe.
  * ``flush()`` — replay the pending nodes inside ``jax.jit``. The executable
    cache is keyed on the graph *signature* (per-node fn identity incl.
    closure values, input wiring, leaf avals, liveness mask, donation mask),
    so the second identical iteration reuses the compiled step.
  * autograd defers ``jax.vjp`` into the graph (vjp composes under tracing),
    so backward is recorded, not executed, until the next materialization.

Async runtime (``FLAGS_lazy_async``, default ON — arXiv:2102.13267's point:
overlap host graph construction with device execution):

  * the flush returns as soon as the fused executable is DISPATCHED; results
    land in ``LazyArray._concrete`` as unblocked ``jax.Array`` futures, and
    the host traces step k+1 while the device executes step k. Host waits are
    instrumented: ``timed_block`` (called by ``Tensor.numpy()`` and
    ``LazyArray.__array__``) emits a ``block`` span and feeds the
    ``lazy_block_ns`` counter (the dispatch gap: host waits on the device).
  * the FLAGS_check_nan_inf scan and the telemetry memory census move off the
    critical path: they are enqueued against the dispatched arrays and run at
    the next flush, the next materialization, or :func:`sync` — the trip
    surfaces at most one step late, with the producing ``lazy_flush`` span
    attribution preserved in the flight-recorder dump. Donation stays
    suppressed while the guard is armed (pre-step state survives, PR 2).
  * ``FLAGS_lazy_bg_compile`` (opt-in): an executable-cache miss compiles on
    a background thread while the current step completes via the un-jitted
    replay, so new-shape warmup no longer stalls the loop. Opt-in because the
    unfused replay can differ from the fused executable by ~1 ulp, and WHEN
    the compiled executable is picked up depends on compile latency — loops
    that pin bitwise reproducibility across runs must leave it off.
  * ``FLAGS_lazy_async=0`` restores the fully synchronous PR-2 behavior:
    in-flush NaN scan, in-flush census, no block instrumentation.

Correctness fallback: if jitted replay fails, nodes run eagerly one-by-one.

Known cost trade-off: materializing the loss BEFORE backward() (print/log
every step) splits the iteration into two executables, and the tape backward
re-derives the forward inside its vjp — i.e. forward FLOPs run twice, like
``jax.value_and_grad`` after a separate forward eval. Loops that materialize
after ``opt.step()`` (or only every N steps) pay nothing.
"""
from __future__ import annotations

import collections
import sys
import threading
import time
import warnings
import weakref
from typing import Any, Callable, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

__all__ = [
    "LazyArray", "record", "flush", "sync", "lazy_enabled", "set_lazy_mode",
    "lazy_guard", "is_lazy", "maybe_lazy_binary", "lazy_full",
    "note_rebound", "timed_block", "evict_cold",
]

_state = threading.local()
_DEFAULT_ENABLED = True  # flipped off per-thread via set_lazy_mode(False)

# Stability-sentinel drain tap (fault/sentinel.py): invoked at the same
# boundaries as the deferred NaN/Inf drain so the sentinel's per-step fused
# scalar readback rides the existing deferred-check path instead of adding
# sync points of its own. None while no sentinel is active — the disabled
# path is this one attribute probe per flush (tier-1 inert tripwire).
_stability_tap = None

# Flush when the pending graph reaches this many nodes even without a
# materialization point (a loop that never prints would otherwise grow the
# graph unboundedly). Boundaries then land at consistent offsets across
# identical iterations, so the signature cache still hits.
_MAX_PENDING = 2048


def lazy_enabled() -> bool:
    return getattr(_state, "enabled", _DEFAULT_ENABLED)


def set_lazy_mode(enabled: bool) -> None:
    """Turn lazy eager batching on/off for this thread (flushes first)."""
    flush()
    _state.enabled = bool(enabled)


class lazy_guard:
    """Context manager: ``with lazy_guard(False): ...`` for per-op dispatch."""

    def __init__(self, enabled: bool = True):
        self._want = bool(enabled)

    def __enter__(self):
        self._prev = lazy_enabled()
        set_lazy_mode(self._want)
        return self

    def __exit__(self, *exc):
        set_lazy_mode(self._prev)
        return False


def is_lazy(x) -> bool:
    return isinstance(x, LazyArray)


def concrete(x):
    """Materialize a LazyArray to its jax.Array (identity for anything else).
    External consumers (orbax, dlpack, ctypes buffers) need real buffers."""
    return x._value() if isinstance(x, LazyArray) else x


class _Node:
    __slots__ = ("key", "fn", "inputs", "n_out", "out_refs", "gix", "graph")

    def __init__(self, key, fn, inputs, n_out):
        self.key = key
        self.fn = fn
        self.inputs = inputs  # LazyArray | jax.Array | np scalar
        self.n_out = n_out
        self.out_refs = None  # list of weakrefs to output LazyArrays
        self.gix = 0  # index in its graph's node list (wiring descriptor)
        self.graph = None  # owning _Graph while pending; None once flushed


class LazyArray:
    """Placeholder for a pending node output. Metadata (shape/dtype) is free;
    everything else materializes the whole pending graph."""

    __slots__ = ("_node", "_idx", "aval", "_concrete", "__weakref__")

    def __init__(self, node, idx, aval):
        self._node = node
        self._idx = idx
        self.aval = aval
        self._concrete = None

    # -- free metadata ----------------------------------------------------
    @property
    def shape(self):
        return self.aval.shape

    @property
    def dtype(self):
        return self.aval.dtype

    @property
    def ndim(self):
        return len(self.aval.shape)

    @property
    def size(self):
        return int(np.prod(self.aval.shape)) if self.aval.shape else 1

    def astype(self, dt):
        dt = np.dtype(dt) if not hasattr(dt, "dtype") else dt
        if np.dtype(dt) == np.dtype(self.dtype):
            return self
        (out,), _ = record(
            "astype", lambda x: x.astype(dt), [self], key=("lazy_astype", str(dt))
        )
        return out

    # -- materialization --------------------------------------------------
    def _value(self):
        if self._concrete is None:
            flush()
        if self._concrete is None:  # node died before flush (shouldn't happen)
            raise RuntimeError("LazyArray was never materialized")
        # a deferred NaN/Inf check against THIS flush must surface here, at
        # the materialization point, not one step later
        _drain_deferred()
        return self._concrete

    def __jax_array__(self):
        return self._value()

    def __array__(self, dtype=None):
        a = np.asarray(timed_block(self._value()))
        return a.astype(dtype) if dtype is not None else a

    def __getattr__(self, name):
        # private attrs never delegate (hasattr probes must stay cheap and
        # must not force a flush)
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self._value(), name)

    def __repr__(self):
        st = "pending" if self._concrete is None else "ready"
        return f"LazyArray(shape={tuple(self.shape)}, dtype={self.dtype}, {st})"

    def __len__(self):
        if not self.aval.shape:
            raise TypeError("len() of a 0-d array")
        return self.aval.shape[0]

    def __iter__(self):
        return iter(self._value())

    def __bool__(self):
        return bool(self._value())

    def __float__(self):
        return float(self._value())

    def __int__(self):
        return int(self._value())

    def __format__(self, spec):
        # the wait is attributed like every other readback (block span +
        # lazy_block_ns) — an f-string on a pending loss is a host sync too
        v = timed_block(self._value())
        return format(np.asarray(v) if self.ndim else v.item(), spec)  # lint: ok(host-sync)

    @staticmethod
    def _rev(fn):
        def rev(a, b):
            return fn(b, a)

        rev.__name__ = "r_" + fn.__name__
        return rev

    def __getitem__(self, idx):
        # stay lazy for static indices (ints/slices): a stray `lazy[0]` in a
        # library must not split the fused iteration into two executables
        try:
            hash(idx)
        except TypeError:
            return self._value()[idx]
        (out,), _ = record(
            "lazy_getitem", lambda a: a[idx], [self],
            key=("lazy_getitem", str(idx)),
        )
        return out

    # arithmetic stays LAZY (recorded into the pending graph) — raw operator
    # use on a LazyArray must not force a full flush of the iteration
    def _binop(self, other, op, name):
        if _no_tracer(other):
            return maybe_lazy_binary(op, self, other, name=name)
        return op(self._value(), other)

    def __add__(self, o):
        return self._binop(o, jnp.add, "lazy_add")

    def __radd__(self, o):
        return self._binop(o, self._rev(jnp.add), "lazy_radd")

    def __sub__(self, o):
        return self._binop(o, jnp.subtract, "lazy_sub")

    def __rsub__(self, o):
        return self._binop(o, self._rev(jnp.subtract), "lazy_rsub")

    def __mul__(self, o):
        return self._binop(o, jnp.multiply, "lazy_mul")

    def __rmul__(self, o):
        return self._binop(o, self._rev(jnp.multiply), "lazy_rmul")

    def __truediv__(self, o):
        return self._binop(o, jnp.divide, "lazy_div")

    def __rtruediv__(self, o):
        return self._binop(o, self._rev(jnp.divide), "lazy_rdiv")

    def __neg__(self):
        (out,), _ = record("lazy_neg", jnp.negative, [self], key=("lazy_neg",))
        return out

    def __matmul__(self, o):
        return self._binop(o, jnp.matmul, "lazy_matmul")

    def __pow__(self, o):
        return self._binop(o, jnp.power, "lazy_pow")

    def __lt__(self, o):
        return self._value() < o

    def __le__(self, o):
        return self._value() <= o

    def __gt__(self, o):
        return self._value() > o

    def __ge__(self, o):
        return self._value() >= o


class _Graph:
    """One pending-graph epoch. The trace structures the old flush used to
    rebuild per step — wiring descriptors, the deduped leaf table, donation
    refcount bookkeeping, signature parts — are maintained INCREMENTALLY by
    ``record``, so a cache-hit flush only sweeps output liveness."""

    __slots__ = (
        "nodes", "leaves", "leaf_pos", "leaf_avals", "direct_uses",
        "descs", "keyparts",
    )

    def __init__(self):
        self.nodes: List[_Node] = []
        self.leaves: list = []  # deduped external inputs, in first-use order
        self.leaf_pos: dict = {}  # id(leaf) -> index in `leaves`
        self.leaf_avals: list = []  # per-leaf (shape, dtype, kind) sig parts
        self.direct_uses: dict = {}  # id(leaf) -> occurrences in node inputs
        self.descs: list = []  # per-node wiring descriptor tuples
        self.keyparts: list = []  # per-node (node.key, descs) signature parts


def _graph() -> _Graph:
    g = getattr(_state, "graph", None)
    if g is None:
        g = _Graph()
        _state.graph = g
    return g


# -- donation candidates -----------------------------------------------------
# Buffers whose holder rebound them THROUGH the pending graph (a Tensor's
# _data replaced by a flush output, an optimizer moment replaced by its
# update, a grad buffer replaced by its accumulation). These are the
# dead-after-flush candidates the liveness pass in _flush_impl may pass as
# donate_argnums. Ids only — holding a reference here would defeat the
# refcount deadness test that guards against user-held aliases.
_DONATE_IDS_MAX = 65536


def note_rebound(old):
    """Record that ``old`` (a jax.Array, or a LazyArray wrapping one) was
    replaced by a pending-graph output in whatever slot held it. No-op when
    nothing is queued — candidacy only means anything for buffers feeding the
    pending graph."""
    g = getattr(_state, "graph", None)
    if g is None or not g.nodes:
        return
    if isinstance(old, LazyArray):
        old = old._concrete
    if old is None or not isinstance(old, jax.Array):
        return
    s = getattr(_state, "donate_ids", None)
    if s is None:
        s = set()
        _state.donate_ids = s
    if len(s) < _DONATE_IDS_MAX:
        s.add(id(old))


def _false():
    return False


_donation_warnings_filtered = False


def _ignore_donation_warnings():
    """XLA may decline an aliasing hint (layout/sharding mismatch) and jax
    warns per unusable donation — correct but noisy once per train step.
    Installed ONCE: catch_warnings around every flush would copy/restore the
    process-global filter list on the hot path (and isn't thread-safe).
    Action "once" (not "ignore"): the filter is process-global and jax emits
    the SAME text for a user's own jit(donate_argnums=...) — one surviving
    diagnostic per warn-site keeps their misconfiguration visible while
    killing the per-step repeat."""
    global _donation_warnings_filtered
    if not _donation_warnings_filtered:
        warnings.filterwarnings(
            "once", message=r"Some donated buffers were not usable"
        )
        _donation_warnings_filtered = True


def _donation_mask(leaves, cand, direct_uses):
    """Leaf positions provably dead after this flush: marked as rebound AND
    the only strong references left are the pending graph's own input lists.
    Runs in its own frame so the caller's loop variables can't inflate the
    refcount of the leaf under test. A leaf still reachable through a live
    LazyArray is protected automatically: that LazyArray's ``_concrete``
    reference inflates the refcount past the graph-only budget."""
    out = []
    for j in range(len(leaves)):
        x = leaves[j]
        i = id(x)
        if (
            i not in cand
            or not isinstance(x, jax.Array)
            or isinstance(x, jax.core.Tracer)
        ):
            x = None
            continue
        # Refcount at this point for a dead buffer: one per occurrence in a
        # node's input list, plus the graph `leaves` list, the loop binding
        # `x`, and getrefcount's own argument. Anything above that is a live
        # Tensor / user alias / residual capture — donation would corrupt it.
        if sys.getrefcount(x) == direct_uses.get(i, 0) + 3:
            out.append(j)
        x = None
    return tuple(out)


# -- aval probing (cached) ---------------------------------------------------
_aval_cache: dict = {}
_AVAL_CACHE_MAX = 8192
_sds_cache: dict = {}  # (shape, dtype) -> ShapeDtypeStruct (records are hot)


def _aval_of(x):
    if isinstance(x, LazyArray):
        return x.aval  # already a ShapeDtypeStruct from the probe
    if isinstance(x, jax.Array):
        k = (x.shape, x.dtype)
        s = _sds_cache.get(k)
        if s is None:
            if len(_sds_cache) > _AVAL_CACHE_MAX:
                _sds_cache.clear()
            s = _sds_cache[k] = jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
        return s
    a = np.asarray(x)
    return jax.ShapeDtypeStruct(a.shape, a.dtype)


def _leaf_sig(x):
    """Per-leaf signature component: shape/dtype (+ python-scalar typing —
    a plain float traces weakly typed, an np.float32 doesn't). Folding these
    into the flush signature keeps one cache entry per real trace, which the
    AOT background-compile path requires (a compiled executable, unlike
    jax.jit, cannot silently re-trace on a dtype change)."""
    if isinstance(x, jax.Array):
        return (x.shape, x.dtype)
    if isinstance(x, (bool, int, float, complex)):
        return type(x).__name__
    a = np.asarray(x)
    return (a.shape, a.dtype)


def _probe(key, fn, in_avals):
    ck = (key, tuple((a.shape, a.dtype) for a in in_avals))
    try:
        hash(ck)
    except TypeError:
        ck = None
    if ck is not None:
        hit = _aval_cache.get(ck)
        if hit is not None:
            return hit
    out = jax.eval_shape(fn, *in_avals)
    single = not isinstance(out, (tuple, list))
    avals = (out,) if single else tuple(out)
    res = (avals, single)
    if ck is not None:
        if len(_aval_cache) > _AVAL_CACHE_MAX:
            _aval_cache.clear()
        _aval_cache[ck] = res
    return res


def _typed(v):
    """Tag scalars with their type: 1, 1.0 and True are == and hash-equal in
    Python, but produce different traced programs (int64 vs float64 vs bool
    constants) — an untyped key silently serves the wrong executable."""
    if isinstance(v, (bool, int, float, complex)):
        return (type(v).__name__, v)
    if isinstance(v, tuple):
        return tuple(_typed(x) for x in v)
    return v


def _fn_key(fn):
    """Stable identity for a function: code object + closure/default VALUES.
    Shared by dispatch.py (per-op jit cache) and this module (flush
    signature); keyword-only defaults are part of the key."""
    try:
        cells = tuple(
            _typed(c.cell_contents) for c in (getattr(fn, "__closure__", None) or ())
        )
        defaults = tuple(_typed(v) for v in (getattr(fn, "__defaults__", None) or ()))
        kwdefaults = tuple(
            sorted((k, _typed(v)) for k, v in (getattr(fn, "__kwdefaults__", None) or {}).items())
        )
        code = getattr(fn, "__code__", None)
        key = (code, cells, defaults, kwdefaults) if code is not None else fn
        hash(key)
        return key
    except (TypeError, ValueError, AttributeError):
        return fn


def record(name, fn, inputs, key=None):
    """Append one op to the pending graph.

    ``fn(*arrays)`` must be pure over JAX arrays. Returns
    ``(outputs: list[LazyArray], single: bool)``. ``key`` identifies fn for
    the executable cache; when None it is derived from fn's code + closure
    values (correct as long as the closure holds only hashables).

    The wiring descriptor, leaf-table entries and signature part for the node
    are built here — incremental tracing — so ``flush`` does not re-walk the
    graph (tentpole of the async runtime: host work per cache-hit step is a
    liveness sweep + executable-cache probe + dispatch).
    """
    g = _graph()
    leaf_pos = g.leaf_pos
    leaves = g.leaves
    ins = []
    descs = []
    # Leaf-table/direct_uses mutations are staged and committed only after
    # _probe succeeds: a caught shape/dtype error from eval_shape must leave
    # the pending graph exactly as it was (an orphan leaf would perturb the
    # flush signature and overcount direct_uses, breaking the donation mask).
    new_leaves = []  # (x, leaf_sig) in reservation order
    new_pos = {}
    du_bump = {}
    for x in inputs:
        if isinstance(x, LazyArray):
            if x._concrete is None:
                n = x._node
                if n.graph is not g:
                    raise RuntimeError(
                        "lazy graph invariant violated: input from a "
                        "flushed-but-unmaterialized node"
                    )
                ins.append(x)
                descs.append(("n", n.gix, x._idx))
                continue
            x = x._concrete
        j = leaf_pos.get(id(x))
        if j is None:
            j = new_pos.get(id(x))
            if j is None:
                j = len(leaves) + len(new_leaves)
                new_pos[id(x)] = j
                new_leaves.append((x, _leaf_sig(x)))
        du_bump[id(x)] = du_bump.get(id(x), 0) + 1
        ins.append(x)
        descs.append(("l", j))
    in_avals = [_aval_of(x) for x in ins]
    k = key if key is not None else _fn_key(fn)
    avals, single = _probe((name, k), fn, in_avals)
    for x, sig in new_leaves:
        leaf_pos[id(x)] = len(leaves)
        leaves.append(x)
        g.leaf_avals.append(sig)
    du = g.direct_uses
    for ident, c in du_bump.items():
        du[ident] = du.get(ident, 0) + c
    node = _Node((name, k), fn, ins, len(avals))
    node.gix = len(g.nodes)
    node.graph = g
    outs = [LazyArray(node, i, a) for i, a in enumerate(avals)]
    node.out_refs = [weakref.ref(o) for o in outs]
    g.nodes.append(node)
    descs = tuple(descs)
    g.descs.append(descs)
    g.keyparts.append((node.key, descs))
    if len(g.nodes) >= _MAX_PENDING:
        flush()
    return outs, single


# -- flush -------------------------------------------------------------------
# The executable cache is shared by every thread running lazy mode (graphs
# are thread-local, compiled steps are not) — an OrderedDict's reorder/evict
# is not atomic, so probes and inserts serialize on _cache_lock (one
# uncontended acquire per flush; the lock is NOT held across trace/compile).
_cache_lock = threading.Lock()
_flush_cache: "collections.OrderedDict" = collections.OrderedDict()  # guarded_by: _cache_lock
_FLUSH_CACHE_MAX = 128


def evict_cold(keep: int = 4) -> int:
    """Drop cold executable-cache entries, keeping the ``keep`` most
    recently used — the lazy runtime's pressure-relief rung
    (fault/memory.free_pressure): a compiled program pins its constants and
    workspace, so under RESOURCE_EXHAUSTED the cold tail is the cheapest
    memory to give back (an evicted signature merely recompiles if it ever
    comes back). Returns the number evicted."""
    n = 0
    with _cache_lock:
        while len(_flush_cache) > max(int(keep), 0):
            _flush_cache.popitem(last=False)
            n += 1
    return n


def _interp(fns, wiring, leaf_vals, on_node=None):
    """The one interpreter for the graph wiring descriptors
    (``("l", leaf_ix)`` / ``("n", node_ix, out_ix)``): used traced inside the
    jitted replay AND eagerly by the per-op nan checker — one format, one
    reader. Returns the per-node output env; ``on_node(i, outs)`` observes
    each node as it lands."""
    env: list = [None] * len(fns)
    for i, f in enumerate(fns):
        args = [
            leaf_vals[d[1]] if d[0] == "l" else env[d[1]][d[2]]
            for d in wiring[i]
        ]
        o = f(*args)
        env[i] = tuple(o) if isinstance(o, (tuple, list)) else (o,)
        if on_node is not None:
            on_node(i, env[i])
    return env


# span-tracer module, bound once at first flush (same pattern as
# dispatch._prof — flush runs once per iteration, not per op, so the span is
# cheap; the flight recorder keeps it even with the profiler closed)
_spans_mod = None


def _spans():
    global _spans_mod
    if _spans_mod is None:
        from ..profiler import spans

        _spans_mod = spans
    return _spans_mod


def _flags_mod():
    from ..framework import flags

    return flags


def pending_summary() -> dict:
    """Post-mortem view of this thread's pending graph (flight recorder):
    node count and the tail of op names awaiting execution."""
    g = getattr(_state, "graph", None)
    nodes = g.nodes if g is not None else []
    return {
        "pending_nodes": len(nodes),
        "tail_ops": [n.key[0] for n in nodes[-8:]],
        # census-only entries (payload None) carry no NaN/Inf scan — a dump
        # must not claim a check was pending when only a census was
        "deferred_checks": sum(
            1 for e in (getattr(_state, "deferred", ()) or ()) if e[1] is not None
        ),
    }


# -- async runtime: host-wait instrumentation & deferred post-flush work -----
def _timed_block(x, where: str):
    """Block until ``x`` is ready under a ``block`` span, feeding the
    dispatch-gap counters (``lazy_blocks`` / ``lazy_block_ns``). This is the
    ONLY sanctioned way the runtime waits on the device — the tier-1
    tripwire asserts no ``block`` span ever appears inside ``lazy_flush``."""
    from .dispatch import _prof
    from ..distributed import watchdog as _watchdog

    t0 = time.perf_counter_ns()
    with _spans().span("block", where=where):
        # deadline on the host sync: a peer rank that died mid-step leaves
        # this wait blocked forever in multi-controller runs — the watchdog
        # (FLAGS_collective_timeout_s>0) converts that into an attributed
        # resumable exit. A flag probe when disabled.
        with _watchdog.guard(f"block:{where}"):
            jax.block_until_ready(x)
    p = _prof()
    p.counter_inc("lazy_blocks")
    p.counter_inc("lazy_block_ns", time.perf_counter_ns() - t0)
    return x


def timed_block(x, where: str = "readback"):
    """Public wrapper used at host readback sites (``Tensor.numpy()``,
    ``LazyArray.__array__``, metric updates): waits for an in-flight
    ``jax.Array`` (or a sequence of them) with the wait ATTRIBUTED (block
    span + lazy_block_ns), so host idle time between device steps is
    measurable instead of hiding inside ``np.asarray``. Identity for ready
    arrays, non-arrays, tracers, and when ``FLAGS_lazy_async`` is off (the
    old behavior blocked silently)."""
    if isinstance(x, (list, tuple)):
        arrs = [
            a for a in x
            if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer)
        ]
        if not arrs or not _flags_mod().flag("FLAGS_lazy_async", True):
            return x
        try:
            if all(a.is_ready() for a in arrs):
                return x
        except Exception:  # lint: ok(oom-handler) — readiness probe, nothing dispatches in this try
            pass
        _timed_block(arrs, where)
        return x
    if not isinstance(x, jax.Array) or isinstance(x, jax.core.Tracer):
        return x
    if not _flags_mod().flag("FLAGS_lazy_async", True):
        return x
    try:
        if x.is_ready():  # committed futures skip the span entirely
            return x
    except Exception:  # lint: ok(oom-handler) — readiness probe, nothing dispatches in this try
        pass
    return _timed_block(x, where)


def _enqueue_deferred(sp, check_payload, census, results):
    d = getattr(_state, "deferred", None)
    if d is None:
        d = []
        _state.deferred = d
    d.append((sp, check_payload, census, results))
    # verify at ENQUEUE time: flush() drains this queue before the next
    # _flush_impl runs, so a pre-dispatch check there would only ever see an
    # empty queue — here is the one point a malformed entry can exist
    if _flags_mod().flag("FLAGS_lazy_verify", False):
        from ..analysis.verify_graph import _verify_deferred

        _verify_deferred(d)


def _drain_deferred():
    """Run the post-flush work deferred off the critical path: the memory
    census (attrs attached to the PRODUCING lazy_flush span post-hoc) and
    the NaN/Inf scan — which blocks on the dispatched arrays under a
    ``block`` span and raises with the producing-span attribution intact.
    Called at flush entry, at every materialization point, and by sync()."""
    d = getattr(_state, "deferred", None)
    if not d:
        return
    entries = list(d)
    del d[:]  # reentrancy/raise-safe: one trip drops the batch
    spans_mod = _spans()
    for sp, payload, census, results in entries:
        if census:
            from .dispatch import _prof

            mem = _prof().memory_census()
            attrs = dict(
                live_bytes=mem["live_bytes"],
                live_arrays=mem["live_arrays"],
                peak_live_bytes=mem["peak_live_bytes"],
                delta_bytes=mem["last_delta_bytes"],
            )
            if sp is not None:
                spans_mod.update_attrs(sp, **attrs)
        if payload is not None:
            with spans_mod.span(
                "lazy_deferred_check",
                producing_span=(sp.span_id if sp is not None else 0),
            ):
                _timed_block(results, "deferred_naninf")
                _nan_check(*payload, deferred=True, producing=sp)


def sync():
    """Synchronization barrier for the async runtime: dispatch everything
    pending, surface any deferred NaN/Inf trip, and block (attributed) until
    the device finished the last dispatched step. With ``FLAGS_lazy_async=0``
    every flush already behaves like this."""
    flush()
    _drain_deferred()
    tap = _stability_tap
    if tap is not None:
        tap()
    inflight = getattr(_state, "inflight", None)
    if inflight:
        _state.inflight = None
        _timed_block(inflight, "sync")


# -- background compilation ---------------------------------------------------
class _BgCompile:
    """One background compile of a flush signature: ``jax.jit(replay)
    .lower(*leaves).compile()`` on a daemon worker thread while the training
    loop keeps stepping through the un-jitted replay. Lowering from the live
    leaves (not synthetic avals) captures exact shapes/dtypes/weak-types; the
    thread's reference to them dies with the compile."""

    __slots__ = ("ready", "value", "error", "_thread")

    def __init__(self, replay, donate_ix, leaves):
        self.ready = False
        self.value = None
        self.error = None

        def work(leaves=leaves):
            try:
                jf = (
                    jax.jit(replay, donate_argnums=donate_ix)
                    if donate_ix
                    else jax.jit(replay)
                )
                self.value = jf.lower(*leaves).compile()
            except Exception as e:  # surfaced as a sync-compile fallback
                from ..fault import memory as _mem

                if _mem.is_oom(e):  # compile-time RESOURCE_EXHAUSTED counts
                    _mem.note_oom("lazy_bg_compile", e)
                self.error = e
            finally:
                self.ready = True  # publish AFTER value/error (GIL ordering)

        self._thread = threading.Thread(
            target=work, daemon=True, name="lazy-bg-compile"
        )
        self._thread.start()


def flush():
    """Execute all pending nodes as one jitted XLA computation and write the
    results back into the live LazyArrays. With ``FLAGS_lazy_async`` (default)
    the host returns as soon as the executable is dispatched — the results in
    ``LazyArray._concrete`` are unblocked futures."""
    if getattr(_state, "flushing", False):
        return
    # deferred work from the PREVIOUS flush surfaces before new work is
    # dispatched — a deferred NaN trip is ≤1 step late, never dropped
    _drain_deferred()
    tap = _stability_tap
    if tap is not None:
        tap()  # non-blocking readiness sweep; never raises, never flushes
    g = getattr(_state, "graph", None)
    if g is None or not g.nodes:
        return
    _state.flushing = True
    try:
        _state.graph = None  # fresh epoch for anything recorded during flush
        # the sync() handle on the previous step's results must die BEFORE
        # the donation mask runs — a held results list would inflate the
        # refcount of every rebound buffer and defeat in-place updates
        _state.inflight = None
        with _spans().span("lazy_flush", nodes=len(g.nodes)) as sp:
            _flush_impl(g, sp)
    finally:
        _state.flushing = False


def _flush_impl(g: _Graph, sp=None):
    nodes = g.nodes
    leaves = g.leaves
    descs_all = g.descs

    # The wiring/signature was built incrementally by record(); the only
    # flush-time trace work left is the output-liveness sweep.
    with _spans().span("trace", nodes=len(nodes)) as trace_span:
        alive_parts = tuple(
            tuple(r() is not None for r in n.out_refs) for n in nodes
        )
        trace_span.set(leaves=len(leaves))

    # Liveness pass: donate leaves that were rebound through this graph and
    # that nothing outside the graph still references. The mask is part of
    # the executable signature, so a cache hit always replays with the same
    # donation layout it was compiled with. Donation is SUPPRESSED while
    # FLAGS_check_nan_inf is set: a donated buffer is destroyed by the flush,
    # and on a NaN trip the pre-step state must survive for inspection (and
    # for the per-op unfused replay).
    _flags = _flags_mod()

    check_nan = bool(_flags.flag("FLAGS_check_nan_inf", False))
    async_on = bool(_flags.flag("FLAGS_lazy_async", True))
    # HBM preflight admission (fault/memory.py): "off" (default) costs this
    # one probe — fault.memory is never imported, no census runs, the
    # executable compiles through the plain jax.jit path (inert tripwire)
    admission = _flags.flag("FLAGS_hbm_admission", "off")
    donate_ix: tuple = ()
    cand = getattr(_state, "donate_ids", None)
    if cand and _flags.flag("FLAGS_lazy_donate", True):
        if check_nan:
            from .dispatch import _prof as _prof_fn

            _prof_fn().counter_inc("naninf_donation_suppressed")
            if sp is not None:
                sp.set(donation="suppressed_naninf")
        else:
            with _spans().span("donate", candidates=len(cand)) as dsp:
                donate_ix = _donation_mask(leaves, cand, g.direct_uses)
                dsp.set(donated=len(donate_ix))
    # snapshot for the preflight-rejection path: a rejected dispatch must
    # put the donation intent back, or the retry flush would re-key (and
    # recompile) WITHOUT donation — a bigger footprint exactly when memory
    # is tightest
    cand_snapshot = set(cand) if cand else None
    if cand:
        cand.clear()

    # Graph IR verifier (analysis/verify_graph.py): re-derive the wiring /
    # leaf table / donation mask / signature from ground truth and cross-
    # check the record-time memoization, BEFORE anything is dispatched or
    # cached. Off by default — this probe is the entire disabled-path cost.
    if _flags.flag("FLAGS_lazy_verify", False):
        from ..analysis.verify_graph import verify_before_dispatch

        # deferred entries are verified where they are enqueued (see
        # _enqueue_deferred) — by this point flush() has already drained them
        verify_before_dispatch(g, donate_ix)

    try:
        sig = (tuple(g.keyparts), alive_parts, tuple(g.leaf_avals), donate_ix)
        hash(sig)
    except TypeError:
        sig = None

    from .dispatch import _prof

    prof = _prof()
    prof.counter_inc("lazy_flushes")

    with _cache_lock:
        entry = _flush_cache.get(sig) if sig is not None else None
        if entry is not None:
            _flush_cache.move_to_end(sig)
    cache_hit = entry is not None
    if sp is not None:
        # the executable-cache key: stable within a process (str hashing is
        # seeded per-process), enough to correlate hit/miss spans in a trace
        sp.set(
            cache="hit" if cache_hit else "miss",
            cache_key=(f"{hash(sig) & 0xFFFFFFFFFFFFFFFF:016x}" if sig is not None else None),
        )
    precompiled = False
    if entry is None:
        fns = [n2.fn for n2 in nodes]
        wiring = descs_all
        live = [
            (i, j)
            for i, n2 in enumerate(nodes)
            for j in range(n2.n_out)
            if n2.out_refs[j]() is not None
        ]

        def replay(*leaf_vals):
            env = _interp(fns, wiring, leaf_vals)
            return [env[i][j] for (i, j) in live]

        if (
            async_on
            and sig is not None
            and _flags.flag("FLAGS_lazy_bg_compile", False)
        ):
            # compile off-thread; THIS step (and any same-signature step
            # until the compile lands) completes via the un-jitted replay
            # (no memory prediction until the pickup — admission skips it)
            task = _BgCompile(replay, donate_ix, list(leaves))
            entry = [None, live, replay, donate_ix, task, None]
            prof.counter_inc("lazy_bg_compiles")
        elif admission != "off":
            # admission needs the executable's memory_analysis BEFORE the
            # first dispatch: compile ahead-of-time (the bg-compile pickup
            # shape — entry[0] is an AOT Compiled, the aot fallback rung
            # re-traces on aval drift) and key the prediction like the
            # executable cache
            from ..fault import memory as _hbm

            jf = (
                jax.jit(replay, donate_argnums=donate_ix)
                if donate_ix
                else jax.jit(replay)
            )
            with _spans().span("compile", cache="miss", admission=admission) as csp:
                compiled = jf.lower(*leaves).compile()
                mem = _hbm.analyze_compiled(
                    compiled,
                    key=(f"{hash(sig) & 0xFFFFFFFFFFFFFFFF:016x}"
                         if sig is not None else None),
                )
                if mem is not None:
                    csp.set(
                        hbm_exec_peak_bytes=mem["peak_bytes"],
                        hbm_temp_bytes=mem["temp_bytes"],
                        hbm_output_bytes=mem["output_bytes"],
                        hbm_alias_bytes=mem["alias_bytes"],
                    )
            entry = [compiled, live, replay, donate_ix, None, mem]
            precompiled = True
        else:
            jitted = (
                jax.jit(replay, donate_argnums=donate_ix)
                if donate_ix
                else jax.jit(replay)
            )
            # list, not tuple: the donation-error fallback swaps in a
            # non-donating executable under the same signature
            entry = [jitted, live, replay, donate_ix, None, None]
        if sig is not None:
            with _cache_lock:
                _flush_cache[sig] = entry
                if len(_flush_cache) > _FLUSH_CACHE_MAX:
                    _flush_cache.popitem(last=False)
    else:
        prof.counter_inc("lazy_cache_hits")

    jitted, live, replay, don, task = entry[:5]
    mem_pred = entry[5] if len(entry) > 5 else None
    donated_bytes = (
        sum(int(getattr(leaves[j], "nbytes", 0)) for j in don) if don else 0
    )
    if sp is not None and don:
        sp.set(donated_buffers=len(don), donated_bytes=donated_bytes)
    if jitted is None and task is not None:
        # background compile in flight: pick it up if finished, else keep
        # stepping through the replay fallback
        if task.ready:
            if task.error is None:
                jitted = entry[0] = task.value
                entry[4] = None
                prof.counter_inc("lazy_bg_pickups")
                if sp is not None:
                    sp.set(bg_compile="picked_up")
            else:
                # bg compile failed — compile synchronously under this
                # signature; a persistent error then surfaces on execution
                jitted = entry[0] = (
                    jax.jit(replay, donate_argnums=don) if don else jax.jit(replay)
                )
                entry[4] = None
                prof.counter_inc("lazy_bg_compile_failures")
                if sp is not None:
                    sp.set(bg_compile="failed", bg_error=type(task.error).__name__)
    if (
        admission != "off"
        and mem_pred is None
        and task is None
        and jitted is not None
        and hasattr(jitted, "lower")
    ):
        # cache entry predates the admission flag flip (or was built by the
        # plain path): upgrade it IN PLACE once — lower+compile the same
        # jitted (donation mask already baked in; the persistent compilation
        # cache makes this warm) and capture its memory analysis
        from ..fault import memory as _hbm

        try:
            with _spans().span("compile", cache="upgrade", admission=admission) as csp:
                compiled = jitted.lower(*leaves).compile()
                mem_pred = _hbm.analyze_compiled(
                    compiled,
                    key=(f"{hash(sig) & 0xFFFFFFFFFFFFFFFF:016x}"
                         if sig is not None else None),
                )
                if mem_pred is not None:
                    csp.set(hbm_exec_peak_bytes=mem_pred["peak_bytes"])
            entry[0] = jitted = compiled
            if len(entry) > 5:
                entry[5] = mem_pred
            precompiled = True
        except Exception as e:
            if _hbm.is_oom(e):  # even the upgrade compile can exhaust HBM
                _hbm.note_oom("lazy_flush.compile", e)
                raise
            mem_pred = None  # no prediction; admission admits, dispatch as-is

    # a bg-compile pickup leaves an AOT Compiled in entry[0]; unlike jax.jit
    # it cannot re-trace, so execution failures get an extra fallback rung
    aot = jitted is not None and not hasattr(jitted, "lower")

    if admission != "off" and jitted is not None:
        # predicted peak + live census vs the device budget, BEFORE the
        # device is touched. An enforce rejection reinstates the pending
        # epoch: nothing was dispatched, so the caller can free memory or
        # raise the budget and simply flush again.
        from ..fault import memory as _hbm

        try:
            _hbm.preflight(
                mem_pred, "lazy_flush", span=sp, donated_bytes=donated_bytes
            )
        except Exception:
            cur = getattr(_state, "graph", None)
            if cur is None or not cur.nodes:
                _state.graph = g
            if cand_snapshot:
                # restore the donation intent too: the retry flush then
                # re-derives the SAME donation mask → same signature →
                # cache hit on this already-compiled (donating) executable
                s = getattr(_state, "donate_ids", None)
                if s is None:
                    s = set()
                    _state.donate_ids = s
                s.update(cand_snapshot)
            raise

    results = None
    if jitted is None:
        # replay-while-compiling: one eager pass, correct but unfused
        prof.counter_inc("lazy_bg_replays")
        if sp is not None:
            sp.set(bg_compile="pending")
        with _spans().span("execute", cache="miss", fallback="bg_compiling"):
            results = replay(*leaves)
    else:
        try:
            if don:
                _ignore_donation_warnings()
            from .dispatch import _fault_inject as _finj

            if _finj is not None:
                # hbm.oom chaos: the synthesized RESOURCE_EXHAUSTED raises
                # from inside this try, so the recovery ladder below handles
                # it exactly like a real device OOM
                _finj.maybe_hbm_oom("lazy_flush")
            # a miss pays trace+compile inside this first invocation (unless
            # admission already compiled ahead-of-time); a hit is a pure
            # executable launch — with the async runtime the host RETURNS at
            # dispatch ("dispatch" span), only the sync kill-switch path
            # keeps the old "execute" attribution
            span_name = (
                "compile"
                if not cache_hit and not precompiled
                else ("dispatch" if async_on else "execute")
            )
            with _spans().span(
                span_name, cache="hit" if cache_hit else "miss"
            ):
                results = jitted(*leaves)
            if don:
                prof.counter_inc("lazy_donated_buffers", len(don))
        except Exception as e:
            from ..fault import memory as _hbm

            if _hbm.is_oom(e):
                # RESOURCE_EXHAUSTED: classify → free pressure → retry once
                # → structured halt. NEVER the eager-replay fallback — an
                # unfused replay of an OOM'd graph would OOM harder on a
                # real device (and silently un-fuse on CPU tests).
                results = _oom_recover(e, entry, leaves, sp, prof)
            else:
                donated_dead = any(
                    getattr(l, "is_deleted", _false)()
                    for l in leaves
                    if isinstance(l, jax.Array)
                )
                if aot and not donated_dead:
                    # AOT executables (bg-compile pickups / admission
                    # precompiles) don't re-trace on an input-aval drift the
                    # way jax.jit does — swap in the polymorphic jit under
                    # the same signature and retry
                    prof.counter_inc("lazy_bg_aot_fallbacks")
                    if sp is not None:
                        sp.set(fallback="aot_retrace")
                    jitted = entry[0] = (
                        jax.jit(replay, donate_argnums=don) if don else jax.jit(replay)
                    )
                    try:
                        with _spans().span("compile", cache="miss", fallback="aot_retrace"):
                            results = jitted(*leaves)
                        if don:
                            prof.counter_inc("lazy_donated_buffers", len(don))
                    except Exception as e2:
                        if _hbm.is_oom(e2):
                            results = _oom_recover(e2, entry, leaves, sp, prof)
                        else:
                            results = _fallback_execute(
                                entry, leaves, replay, don, donated_dead, sp, prof
                            )
                else:
                    results = _fallback_execute(
                        entry, leaves, replay, don, donated_dead, sp, prof
                    )

    for (i, j), val in zip(live, results):
        o = nodes[i].out_refs[j]()
        if o is not None:
            o._concrete = val
    _state.inflight = results  # sync() blocks on the last dispatched step

    mem_active = prof._memory_active()
    if async_on and (check_nan or mem_active):
        # post-flush scans move OFF the critical path: enqueued against the
        # dispatched arrays, they run at the next flush / materialization /
        # sync() — the host returns now, overlapping step k+1's trace with
        # step k's device execution
        payload = None
        if check_nan:
            payload = (
                [n2.key[0] for n2 in nodes],
                [n2.fn for n2 in nodes],
                live,
                results,
                leaves,
                descs_all,
            )
            prof.counter_inc("lazy_deferred_checks")
        _enqueue_deferred(sp, payload, mem_active, results)
    else:
        # Memory accounting (profiler profile_memory / FLAGS_profile_memory):
        # live-buffer census at the flush boundary — the point where donated
        # inputs are gone and outputs exist, so the delta IS the step's real
        # memory effect and the peak gauge tracks the high-water mark.
        if mem_active:
            mem = prof.memory_census()
            if sp is not None:
                sp.set(
                    live_bytes=mem["live_bytes"],
                    live_arrays=mem["live_arrays"],
                    peak_live_bytes=mem["peak_live_bytes"],
                    delta_bytes=mem["last_delta_bytes"],
                )
        # FLAGS_check_nan_inf with the async runtime OFF: scan the flush
        # outputs synchronously AFTER the writeback (the materialized state
        # stays inspectable — donation was suppressed above, so pre-step
        # buffers survive too) and raise within the same step.
        if check_nan:
            _nan_check(
                [n2.key[0] for n2 in nodes],
                [n2.fn for n2 in nodes],
                live, results, leaves, descs_all,
            )

    # Release the graph's buffer references: without this, a live LazyArray
    # output (e.g. a held loss) would pin every input buffer of its whole
    # step through node.inputs until the handle died.
    for n2 in nodes:
        n2.inputs = ()
        n2.graph = None


def _fallback_execute(entry, leaves, replay, don, donated_dead, sp, prof):
    """Donation-rejection / eager fallbacks shared by the jit and AOT paths
    (semantics unchanged from the synchronous runtime)."""
    if don and not donated_dead:
        # XLA rejected the donation (or the donating executable failed
        # before invalidating inputs): permanently fall back to a
        # non-donating executable under this signature
        prof.counter_inc("lazy_donation_fallbacks")
        if sp is not None:
            sp.set(fallback="donation_rejected")
        jitted = jax.jit(replay)
        entry[0] = jitted
        entry[3] = ()
        try:
            with _spans().span("compile", cache="miss", fallback="donation_rejected"):
                return jitted(*leaves)
        except Exception as e:
            from ..fault import memory as _mem

            if _mem.is_oom(e):
                # never eat an exhaustion into an unfused replay — it would
                # OOM harder on a real device and silently un-fuse on CPU
                raise
            prof.counter_inc("lazy_eager_replay_fallbacks")
            if sp is not None:
                sp.set(fallback="eager_replay")
            with _spans().span("execute", fallback="eager_replay"):
                return replay(*[jnp.asarray(v) for v in leaves])
    elif donated_dead:
        # inputs were invalidated mid-execution; eager replay impossible
        raise
    else:
        # fallback: run un-jitted (still one pass, concrete ops)
        prof.counter_inc("lazy_eager_replay_fallbacks")
        if sp is not None:
            sp.set(fallback="eager_replay")
        with _spans().span("execute", fallback="eager_replay"):
            return replay(*[jnp.asarray(v) for v in leaves])


def _oom_recover(exc, entry, leaves, sp, prof):
    """Flush-level OOM recovery ladder (fault/memory.py): classify the
    RESOURCE_EXHAUSTED, free pressure (evict cold executables, refresh the
    census, shrink serving pools), retry the SAME executable once, and halt
    with a structured :class:`~paddle_tpu.fault.memory.HbmExhausted` plus a
    flight post-mortem (census + per-executable attributions + attempts)
    when the retry fails too. The microbatch-degrade rung lives one layer
    up, in the engine's train step — the flush has no batch axis to split."""
    from ..fault import memory as _hbm

    attempts = [{"action": "classify", **_hbm.note_oom("lazy_flush", exc)}]
    if sp is not None:
        sp.set(hbm_oom=type(exc).__name__)
    donated_dead = any(
        getattr(l, "is_deleted", _false)()
        for l in leaves
        if isinstance(l, jax.Array)
    )
    if donated_dead:
        # the failed launch already invalidated donated inputs — nothing to
        # retry with; the checkpoint/sentinel layer owns recovery from here
        attempts.append({"action": "retry", "ok": False,
                         "why": "donated inputs invalidated"})
        path = _hbm.post_mortem("lazy_flush", attempts, exc)
        raise _hbm.HbmExhausted("lazy_flush", attempts, path) from exc
    attempts.append({"action": "free_pressure",
                     **_hbm.free_pressure("lazy_flush")})
    try:
        with _spans().span("execute", retry="hbm_oom"):
            from .dispatch import _fault_inject as _finj

            if _finj is not None:
                # consult again: a persistent injected fault (from=) must
                # defeat the retry the way sustained real pressure would
                _finj.maybe_hbm_oom("lazy_flush")
            results = entry[0](*leaves)
    except Exception as e2:
        if not _hbm.is_oom(e2):
            raise
        attempts.append({"action": "retry", "ok": False})
        path = _hbm.post_mortem("lazy_flush", attempts, e2)
        raise _hbm.HbmExhausted("lazy_flush", attempts, path) from e2
    prof.counter_inc("hbm_oom_recoveries")
    attempts.append({"action": "retry", "ok": True})
    if sp is not None:
        sp.set(hbm_oom_recovered=True)
    return results


def _nan_check(keys, fns, live, results, leaves, descs_all,
               deferred=False, producing=None):
    """Post-flush nan/inf scan (reference operator.cc:1171 semantics adapted
    to fused execution). Default mode scans the LIVE flush outputs — a NaN
    in an intermediate that was fused away AND masked out of every live
    output is invisible (the price of keeping fusion). Opt-in
    FLAGS_check_nan_inf_per_op re-runs the graph UNFUSED on every flush and
    checks EVERY node output — full reference parity (dead intermediates
    included) at the reference's documented debug cost (~2x compute).

    In deferred mode (async runtime) the same scan runs against the retained
    arrays at the NEXT flush/materialization/sync; ``producing`` is the
    closed ``lazy_flush`` span of the step that built these values, threaded
    into the flight-recorder dump so the post-mortem still names it."""
    from .dispatch import _nonfinite_error, _prof

    origin_sfx = " (deferred)" if deferred else ""
    extra = None
    if producing is not None:
        extra = {"producing_span": producing.to_dict()}
    if _flags_mod().flag("FLAGS_check_nan_inf_per_op", False):
        # Unfused replay: same wiring, eager ops, every node output checked,
        # first offender attributed to its producing op.
        def check_node(i2, outs):
            for j2, out in enumerate(outs):
                if hasattr(out, "dtype") and jnp.issubdtype(out.dtype, jnp.floating):
                    if not bool(jnp.isfinite(out).all()):
                        _prof().counter_inc("naninf_trips")
                        raise _nonfinite_error(
                            keys[i2], j2, out,
                            origin="lazy per-op replay" + origin_sfx,
                            extra=extra,
                        )

        _interp(fns, descs_all, leaves, on_node=check_node)
        return
    for (i, j), val in zip(live, results):
        if hasattr(val, "dtype") and jnp.issubdtype(val.dtype, jnp.floating):
            if not bool(jnp.isfinite(val).all()):
                _prof().counter_inc("naninf_trips")
                raise _nonfinite_error(
                    keys[i], j, val, origin="lazy flush" + origin_sfx,
                    hint=True, extra=extra,
                )


# -- helpers for the autograd engine ----------------------------------------
def _no_tracer(*xs):
    return not any(isinstance(x, jax.core.Tracer) for x in xs)


def maybe_lazy_binary(fn, a, b, name="lazy_bin"):
    """jnp-style binary op that stays lazy when lazy mode is on (or when an
    operand is already lazy); used by gradient accumulation."""
    if (lazy_enabled() or is_lazy(a) or is_lazy(b)) and _no_tracer(a, b):
        (out,), _ = record(name, fn, [a, b], key=(name, getattr(fn, "__name__", "fn")))
        return out
    return fn(concrete(a), concrete(b))


def lazy_full(shape, dtype, value, name="lazy_full"):
    """Constant creation that embeds into the flushed graph (no host→device
    transfer per call) when lazy mode is on."""
    shape = tuple(shape)
    if lazy_enabled():
        (out,), _ = record(
            name,
            lambda: jnp.full(shape, value, dtype=dtype),
            [],
            key=(name, shape, str(np.dtype(dtype)), float(value)),
        )
        return out
    return jnp.full(shape, value, dtype=dtype)
