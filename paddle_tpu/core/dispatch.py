"""Eager op dispatch.

TPU-native analogue of the reference's dygraph trace path
(``paddle/fluid/imperative/tracer.cc:170`` TraceOp →
``prepared_operator.cc:129`` kernel select → launch). Here "kernel selection"
is gone — every op is a pure JAX function lowered by XLA — and the trace step
is a ``jax.vjp`` capture that doubles as grad-node creation
(cf. tracer.cc:303 CreateGradOpNode). Non-differentiable paths run through a
per-op ``jax.jit`` cache so repeated eager calls hit compiled executables.

AMP auto-cast hooks into this layer exactly where the reference casts inputs
in the tracer (tracer.cc:207-221).
"""
from __future__ import annotations

import time as _time
import weakref
from typing import Callable, Optional, Sequence

import numpy as np
import jax

from . import lazy as lazy_mod
from .engine import GradNode, grad_enabled
from .tensor import Tensor

# profiler module, bound once at first dispatch (module-level `from .. import`
# would run during partial package init; per-op imports cost the hot path)
_profiler = None


def _prof():
    global _profiler
    if _profiler is None:
        from .. import profiler

        _profiler = profiler
    return _profiler

# AMP hook — set by paddle_tpu.amp.auto_cast; signature (op_name, tensors) -> tensors
_amp_hook: Optional[Callable] = None

# Fault-injection hook — set to the paddle_tpu.fault.inject module by
# inject.arm(), back to None by inject.disarm(). The disarmed hot path pays
# one `is not None` check per op.
_fault_inject = None


def set_amp_hook(hook):
    global _amp_hook
    _amp_hook = hook


def _hashable(v):
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    if isinstance(v, np.dtype):
        return str(v)
    if isinstance(v, (bool, int, float, complex)):
        return lazy_mod._typed(v)  # 1/1.0/True hash-collide but trace differently
    return v


# Per-(op, attrs) jitted executable cache — the analogue of the reference's
# PreparedOp cache (prepared_operator.cc) + program/executable caching.
# Ops define their fn as a per-call lambda/closure, so the key must be the
# code object + closure/default VALUES, not the function identity — otherwise
# every call is a cache miss and the cache grows without bound.
import collections

_jit_cache: "collections.OrderedDict" = collections.OrderedDict()
_JIT_CACHE_MAX = 4096


_fn_key = lazy_mod._fn_key  # one implementation; key includes kw-only defaults

# Per-call-site key memo: ops define their fn at a fixed source location, and
# for the common closure-free/default-free shape the key is fully determined
# by the code object — skip re-hashing () cells and defaults on every call.
# Closures over attr values still hash their cell contents (values vary).
_code_key_cache: dict = {}


def _fast_fn_key(fn):
    try:
        cells = fn.__closure__
        if not fn.__defaults__ and not fn.__kwdefaults__:
            if cells is None:
                code = fn.__code__
                k = _code_key_cache.get(code)
                if k is None:
                    k = _fn_key(fn)
                    if len(_code_key_cache) > _JIT_CACHE_MAX:
                        _code_key_cache.clear()  # exec/notebook-generated code objects
                    _code_key_cache[code] = k
                return k
            # Call-site memo, scalar-closure shape (the common op lambda
            # `lambda *xs: fn(*xs, attr=v)` closing over attr values): build
            # the key inline, skipping _fn_key's getattr chain + kwdefault
            # sort. MUST stay value-compatible with _fn_key's output —
            # scalars as (typename, value), strings verbatim — so both paths
            # hash a given fn to the same executable-cache entry.
            vals = []
            for c in cells:
                v = c.cell_contents
                t = type(v)
                if t in (bool, int, float, complex):
                    vals.append((t.__name__, v))
                elif t is str:
                    vals.append(v)
                else:
                    return _fn_key(fn)
            return (fn.__code__, tuple(vals), (), ())
    except (AttributeError, ValueError):
        pass
    return _fn_key(fn)


def _attrs_key(attrs):
    """Hashable signature of an op's attrs; () for the no-attr fast path.
    Raises TypeError for unhashable attrs (callers fall back)."""
    if not attrs:
        return ()
    key = tuple(sorted((k, _hashable(v)) for k, v in attrs.items()))
    hash(key)
    return key


def _get_jitted(name, fn, attrs):
    try:
        key = (_fast_fn_key(fn), _attrs_key(attrs))
        hash(key)
    except TypeError:  # unhashable attr → run eagerly un-jitted
        return lambda *arrays: fn(*arrays, **attrs)
    jf = _jit_cache.get(key)
    if jf is None:
        def op(*arrays):
            return fn(*arrays, **attrs)

        # the program (and, inside a larger one, the device line's op names)
        # says which op it is, not ``jit__lambda_``
        op.__name__ = op.__qualname__ = name
        jf = jax.jit(op)
        _jit_cache[key] = jf
        if len(_jit_cache) > _JIT_CACHE_MAX:
            _jit_cache.popitem(last=False)
    else:
        _jit_cache.move_to_end(key)
    return jf


def _nonfinite_error(name, idx, arr, origin="eager", hint=False, extra=None):
    """Build the FLAGS_check_nan_inf diagnostic (reference
    nan_inf_utils_detail.cc prints tensor meta + offending values): which
    output, its shape/dtype, how many non-finite elements, and where the
    first one sits."""
    a = np.asarray(arr)
    bad = ~np.isfinite(a)
    cnt = int(bad.sum())
    flat_idx = int(np.flatnonzero(bad.ravel())[0]) if cnt else -1
    first = a.ravel()[flat_idx] if cnt else None
    msg = (
        f"Operator '{name}' output {idx} (shape={tuple(a.shape)}, "
        f"dtype={a.dtype}) contains {cnt} non-finite value(s); first at flat "
        f"index {flat_idx} = {first!r} [{origin}] (FLAGS_check_nan_inf is set)."
    )
    if hint:
        msg += (
            " Set FLAGS_check_nan_inf_per_op=1 to re-run the pending graph "
            "unfused and attribute the first non-finite value to its "
            "producing op."
        )
    # Every non-finite diagnostic (eager, lazy flush, per-op replay) writes a
    # flight-recorder post-mortem BEFORE the raise: the dump's active-span
    # stack names the producing flush span (for a DEFERRED async-mode trip
    # the flush span is already closed, so `extra` carries it instead), and
    # recent spans + counters show what the engine was doing when the value
    # went bad.
    try:
        from ..profiler import flight

        flight.dump(
            "naninf",
            extra={
                "op": name, "output": idx, "origin": origin,
                "nonfinite_count": cnt, "first_flat_index": flat_idx,
                "message": msg, **(extra or {}),
            },
        )
    except Exception:  # lint: ok(oom-handler) — flight-dump guard, nothing dispatches in this try
        pass
    return FloatingPointError(msg)


def _check_nan_inf(name, outs, origin="eager"):
    # FLAGS_check_nan_inf debug scan — the reference checks every op output
    # when the flag is set (operator.cc:1171 → nan_inf_utils_detail.cc).
    # Host-side isfinite forces a device sync per op; that's the documented
    # cost of the debug mode there too.
    import jax.numpy as jnp

    for i, o in enumerate(outs):
        if hasattr(o, "dtype") and jnp.issubdtype(o.dtype, jnp.floating):
            if not bool(jnp.isfinite(o).all()):
                _prof().counter_inc("naninf_trips")
                raise _nonfinite_error(name, i, o, origin=origin)


def eager_call(
    name: str,
    fn: Callable,
    tensor_args: Sequence[Tensor],
    attrs: Optional[dict] = None,
    differentiable: bool = True,
    nondiff_outputs: Sequence[int] = (),
    fn_key=None,
):
    """Run one op eagerly; record a GradNode if any input needs grad.

    ``fn(*arrays, **attrs)`` must be a pure function of JAX arrays returning
    an array or a tuple of arrays. ``nondiff_outputs`` marks integer/bool
    output positions excluded from the vjp capture.
    """
    p = _prof()
    try:
        if p._enabled:
            _t0 = _time.perf_counter_ns()
            try:
                res = _eager_call_impl(
                    name, fn, tensor_args, attrs, differentiable,
                    nondiff_outputs, fn_key,
                )
            finally:
                p._record("op::" + name, _t0)
        else:
            res = _eager_call_impl(
                name, fn, tensor_args, attrs, differentiable, nondiff_outputs, fn_key
            )
    except Exception as e:
        # a RESOURCE_EXHAUSTED on the per-op path is classified (counter +
        # flight context) before it propagates — there is no per-op retry
        # rung; the flush/engine ladders own recovery (fault/memory.py)
        _note_oom(e, "eager:" + name)
        raise
    if _fault_inject is not None and _fault_inject.should_fire("tensor.nan", op=name):
        _fault_inject.poison_first_nan(res)
    return res


def _note_oom(e: BaseException, where: str) -> None:
    """Route a possible device-memory exhaustion through the ONE classifier
    (fault/memory.py). Import is lazy and only on the exception path — the
    unconfigured hot loop never touches the module (inert tripwire)."""
    from ..fault import memory as _mem

    if _mem.is_oom(e):
        _mem.note_oom(where, e)


def _eager_call_impl(
    name: str,
    fn: Callable,
    tensor_args: Sequence[Tensor],
    attrs: Optional[dict] = None,
    differentiable: bool = True,
    nondiff_outputs: Sequence[int] = (),
    fn_key=None,
):
    attrs = attrs or {}
    if _amp_hook is not None:
        tensor_args = _amp_hook(name, tensor_args)
    arrays = tuple(t._data for t in tensor_args)
    need_grad = (
        differentiable
        and grad_enabled()
        and any(not t.stop_gradient for t in tensor_args)
    )

    from ..framework import flags as _flags

    check_naninf = _flags.flag("FLAGS_check_nan_inf", False)

    # Lazy batching path: queue the op; execution happens in one XLA
    # computation at the next materialization point. Bypassed under jit
    # tracing (tracer inputs) and for unhashable attrs (no stable
    # executable-cache key). FLAGS_check_nan_inf does NOT bypass: the guard
    # runs as a post-flush scan (lazy.py), so the fused step keeps its
    # fusion and still raises within the same step the NaN is produced.
    has_tracer = any(isinstance(a, jax.core.Tracer) for a in arrays)
    if not has_tracer and lazy_mod.lazy_enabled():
        try:
            attrs_key = _attrs_key(attrs)
        except TypeError:
            attrs_key = None
        if attrs_key is not None:
            return _lazy_eager_call(
                name, fn, tensor_args, arrays, attrs, attrs_key,
                need_grad, nondiff_outputs, fn_key=fn_key,
            )
    if any(lazy_mod.is_lazy(a) for a in arrays):
        # per-op path (tracing / debug / unhashable attrs): jit args must be
        # real buffers, so pending lazy values materialize here
        arrays = tuple(lazy_mod.concrete(a) for a in arrays)

    if not need_grad:
        outs = _get_jitted(name, fn, attrs)(*arrays)
        single = not isinstance(outs, (tuple, list))
        if check_naninf:
            _check_nan_inf(name, (outs,) if single else outs)
        outs_t = [Tensor(o, stop_gradient=True) for o in ((outs,) if single else outs)]
        return outs_t[0] if single else outs_t

    # Differentiate ONLY wrt inputs that need grad (stop_gradient inputs are
    # closed over as constants). Skips dead grad work and avoids an XLA TPU
    # pathology: one program computing a conv's d/dinput AND d/dweight
    # compiles ~10-100x slower than either alone.
    need_idx = tuple(i for i, t in enumerate(tensor_args) if not t.stop_gradient)
    diff_arrays = tuple(arrays[i] for i in need_idx)

    def _over_diff(base_fn):
        def f(*dxs):
            full = list(arrays)
            for j, i in enumerate(need_idx):
                full[i] = dxs[j]
            return base_fn(*full)

        return f

    if nondiff_outputs:
        nondiff = set(nondiff_outputs)

        # has_aux carries the nondiff outputs out of one forward execution
        # (no double compute); we need the output count first — probe cheaply
        # with eval_shape (no FLOPs).
        probe = jax.eval_shape(lambda *xs: fn(*xs, **attrs), *arrays)
        n_out = len(probe) if isinstance(probe, (tuple, list)) else 1
        diff_idx = [i for i in range(n_out) if i not in nondiff]

        def split_fn(*xs):
            res = fn(*xs, **attrs)
            res = res if isinstance(res, (tuple, list)) else (res,)
            return tuple(res[i] for i in diff_idx), tuple(res[i] for i in sorted(nondiff))

        diff_outs, raw_vjp, aux = jax.vjp(_over_diff(split_fn), *diff_arrays, has_aux=True)
        outs = [None] * n_out
        for j, i in enumerate(diff_idx):
            outs[i] = diff_outs[j]
        for j, i in enumerate(sorted(nondiff)):
            outs[i] = aux[j]
        node_out_idx = {i: j for j, i in enumerate(diff_idx)}
        multi = True
        diff_list = list(diff_outs)
    else:
        # jax.vjp natively handles tuple outputs: cotangent structure matches.
        outs, raw_vjp = jax.vjp(_over_diff(lambda *xs: fn(*xs, **attrs)), *diff_arrays)
        multi = isinstance(outs, (tuple, list))
        outs = list(outs) if multi else [outs]
        node_out_idx = {i: i for i in range(len(outs))}
        diff_list = outs

    def vjp_fn(cts, _raw=raw_vjp, _n=len(arrays), _idx=need_idx):
        gs = _raw(cts)
        if not isinstance(gs, tuple):
            gs = (gs,)
        full = [None] * _n
        for j, i in enumerate(_idx):
            full[i] = gs[j]
        return tuple(full)

    routes = []
    for t in tensor_args:
        if t.stop_gradient:
            routes.append(None)
        elif t._grad_node is not None:
            routes.append(("node", t._grad_node, t._out_index))
        else:
            routes.append(("leaf", t))

    out_avals = [(tuple(o.shape), o.dtype) for o in diff_list]
    node = GradNode(name, vjp_fn, routes, out_avals, multi=multi)
    # Replay info for higher-order grads (create_graph): backward is re-run as
    # a recorded op over the ORIGINAL input tensors so d(grad)/d(input) exists.
    if nondiff_outputs:
        # replay must produce ONLY the differentiable outputs (cotangent
        # structure matches diff_outs): reuse split_fn and drop the aux part
        diff_fn = lambda *xs: split_fn(*xs)[0]
    else:
        diff_fn = lambda *xs: fn(*xs, **attrs)
    node.replay = (diff_fn, list(tensor_args), multi)

    if check_naninf:
        _check_nan_inf(name, outs)
    outs_t = []
    refs = [None] * len(out_avals)
    for i, o in enumerate(outs):
        if i in node_out_idx:
            t = Tensor(o, stop_gradient=False)
            t._grad_node = node
            t._out_index = node_out_idx[i]
            refs[node_out_idx[i]] = weakref.ref(t)
        else:
            t = Tensor(o, stop_gradient=True)
        outs_t.append(t)
    node.out_tensors = refs
    if len(outs_t) == 1 and not multi:
        return outs_t[0]
    return outs_t


def _lazy_eager_call(
    name, fn, tensor_args, arrays, attrs, attrs_key, need_grad, nondiff_outputs,
    fn_key=None,
):
    """Record the op into the lazy graph instead of executing it; autograd
    defers jax.vjp into the graph too (vjp composes under tracing), so a
    whole backward()+optimizer.step()+next-forward chain flushes as ONE
    compiled XLA computation."""
    key = ((fn_key if fn_key is not None else _fast_fn_key(fn)), attrs_key)
    fwd = lambda *xs: fn(*xs, **attrs)

    outs, single = lazy_mod.record(name, fwd, list(arrays), key=key)

    if not need_grad:
        outs_t = [Tensor(o, stop_gradient=True) for o in outs]
        return outs_t[0] if single else outs_t

    n_out = len(outs)
    nondiff = set(nondiff_outputs or ())
    diff_idx = [i for i in range(n_out) if i not in nondiff]
    if nondiff:
        def diff_fn(*xs, _idx=tuple(diff_idx)):
            res = fn(*xs, **attrs)
            res = res if isinstance(res, (tuple, list)) else (res,)
            return tuple(res[i] for i in _idx)

        vjp_multi = True
    else:
        diff_fn = fwd
        vjp_multi = not single

    n_in = len(arrays)
    # Differentiate ONLY wrt inputs that need grad. Besides skipping dead
    # work, this avoids an XLA TPU pathology where a conv that computes
    # d/dinput and d/dweight in one program compiles ~10-100x slower than
    # either alone (data inputs are stop_gradient, so the common case is
    # weight-only).
    need_idx = tuple(i for i, t in enumerate(tensor_args) if not t.stop_gradient)
    vjp_key = ("vjp", key, vjp_multi, n_in, tuple(sorted(nondiff)), need_idx)

    def deferred_vjp(cts):
        cts_list = list(cts) if vjp_multi else [cts]

        def bwd(*flat):
            xs = flat[:n_in]
            c = flat[n_in:]

            def f(*diff_xs):
                full = list(xs)
                for j, i in enumerate(need_idx):
                    full[i] = diff_xs[j]
                return diff_fn(*full)

            _, vjp = jax.vjp(f, *(xs[i] for i in need_idx))
            return vjp(tuple(c) if vjp_multi else c[0])

        outs_b, _ = lazy_mod.record(
            "vjp_" + name, bwd, list(arrays) + cts_list, key=vjp_key
        )
        grads = [None] * n_in
        for j, i in enumerate(need_idx):
            grads[i] = outs_b[j]
        return tuple(grads)

    routes = []
    for t in tensor_args:
        if t.stop_gradient:
            routes.append(None)
        elif t._grad_node is not None:
            routes.append(("node", t._grad_node, t._out_index))
        else:
            routes.append(("leaf", t))

    out_avals = [(tuple(outs[i].shape), outs[i].dtype) for i in diff_idx]
    node = GradNode(name, deferred_vjp, routes, out_avals, multi=vjp_multi)
    node.replay = (diff_fn, list(tensor_args), vjp_multi)
    node.replay_key = ("lz", key, vjp_multi, tuple(sorted(nondiff)))
    node.replay_arrays = list(arrays)  # forward-time input values

    node_out_idx = {i: j for j, i in enumerate(diff_idx)}
    outs_t = []
    refs = [None] * len(diff_idx)
    for i, o in enumerate(outs):
        if i in node_out_idx:
            t = Tensor(o, stop_gradient=False)
            t._grad_node = node
            t._out_index = node_out_idx[i]
            refs[node_out_idx[i]] = weakref.ref(t)
        else:
            t = Tensor(o, stop_gradient=True)
        outs_t.append(t)
    node.out_tensors = refs
    if len(outs_t) == 1 and single:
        return outs_t[0]
    return outs_t


def as_tensor(x, dtype=None):
    """Coerce scalars / numpy arrays / Tensors to Tensor (no copy when Tensor)."""
    if isinstance(x, Tensor):
        return x
    return Tensor(x, dtype=dtype)


def unary(name, fn, x, **attrs):
    return eager_call(name, fn, [as_tensor(x)], attrs)


def binary(name, fn, x, y, **attrs):
    return eager_call(name, fn, [as_tensor(x), as_tensor(y)], attrs)
