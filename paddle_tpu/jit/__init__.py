"""paddle.jit — dygraph→static capture, compiled train steps, AOT save/load.

Parity: reference dygraph_to_static (``python/paddle/fluid/dygraph/
dygraph_to_static/program_translator.py:775`` ProgramTranslator,
``partial_program.py:116`` PartialProgramLayer) and ``paddle.jit.save/load``
(``python/paddle/fluid/dygraph/jit.py:630``).

TPU-native design: instead of AST rewriting into a ProgramDesc, capture runs
the Python forward once under JAX tracing — every paddle_tpu op is already a
pure JAX function, so the whole forward lowers to one XLA computation (the
LazyTensor insight; see PAPERS.md). The compiled executable is cached by
input shape/dtype, like the reference's program cache. ``save``/``load`` use
``jax.export`` StableHLO serialization — the analogue of saving a
ProgramDesc + params, but the artifact is an AOT-compilable module.
"""
from __future__ import annotations

import functools
import json
import os
import pickle
from typing import Callable, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..core import lazy as _lazy
from ..core import random as random_state
from ..core.compat import jax_export as _jax_export
from ..core.engine import GradNode, grad_enabled, no_grad
from ..core.tensor import Parameter, Tensor
from ..static.input import InputSpec


def _conc(a):
    """jax.jit arguments must be real buffers: materialize LazyArrays
    (lazy eager batching) before crossing into a compiled callable."""
    return _lazy.concrete(a)


def _tree_to_arrays(obj):
    if isinstance(obj, Tensor):
        return obj._data
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_to_arrays(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _tree_to_arrays(v) for k, v in obj.items()}
    return obj


def _tree_to_tensors(obj, stop_gradient=True):
    if isinstance(obj, jax.Array):
        return Tensor(obj, stop_gradient=stop_gradient)
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_to_tensors(o, stop_gradient) for o in obj)
    if isinstance(obj, dict):
        return {k: _tree_to_tensors(v, stop_gradient) for k, v in obj.items()}
    return obj


class StaticFunction:
    """A callable whose forward is one cached XLA executable.

    Autograd: forward runs the jitted primal; if any input/param requires
    grad, a GradNode is recorded whose vjp is a second cached executable
    computing the fused forward+backward (XLA dedups the shared subgraph).
    """

    def __init__(self, function, layer=None, input_spec=None):
        self._fn = self._convert_control_flow(function)
        self._layer = layer
        self._input_spec = input_spec
        self._fwd_cache = {}
        self._bwd_cache = {}
        self._last_lowered = None

    @staticmethod
    def _convert_control_flow(function):
        """AST-convert tensor-dependent Python if/while into lax control flow
        (dy2static.py; reference program_translator.py:775). Functions whose
        source can't be rewritten keep trace-only capture."""
        import types as _types

        from . import dy2static

        raw = getattr(function, "__func__", function)
        transformed = dy2static.transform_function(raw)
        if transformed is None:
            return function
        if hasattr(function, "__self__"):
            return _types.MethodType(transformed, function.__self__)
        return transformed

    def program(self, *example_inputs):
        """Program view of the traced computation (reference
        StaticFunction.main_program / ProgramDesc introspection): blocks,
        ops, vars over the captured jaxpr."""
        from ..static.program import Program

        specs = list(example_inputs) or list(self._input_spec or [])
        if not specs:
            raise ValueError("program(): pass example inputs or set input_spec")
        return Program.from_callable(self._fn, specs, layer=self._layer)

    def _params_buffers(self):
        if self._layer is None:
            return [], []
        params = [p for _, p in self._layer.named_parameters()]
        buffers = [b for _, b in self._layer.named_buffers()]
        return params, buffers

    def _pure(self, n_params, n_buffers):
        fn = self._fn
        layer = self._layer

        def pure(args_tuple, key):
            param_arrays = args_tuple[:n_params]
            buffer_arrays = args_tuple[n_params : n_params + n_buffers]
            input_arrays = args_tuple[n_params + n_buffers :]
            params, buffers = self._params_buffers()
            saved = [(t, t._data) for t in list(params) + list(buffers)]
            try:
                for t, arr in zip(list(params) + list(buffers), list(param_arrays) + list(buffer_arrays)):
                    t._data = arr
                inputs = [Tensor(a, stop_gradient=True) for a in input_arrays]
                with random_state.traced_keys(key):
                    out = fn(*inputs) if layer is None else fn(*inputs)
                return _tree_to_arrays(out)
            finally:
                for t, arr in saved:
                    t._data = arr

        return pure

    def __call__(self, *args, **kwargs):
        params, buffers = self._params_buffers()
        input_arrays = [_conc(a._data) if isinstance(a, Tensor) else jnp.asarray(a) for a in args]
        all_arrays = tuple(_conc(p._data) for p in params) + tuple(_conc(b._data) for b in buffers) + tuple(input_arrays)
        key = random_state.next_key()
        shape_key = tuple((tuple(a.shape), str(a.dtype)) for a in all_arrays)

        n_p, n_b = len(params), len(buffers)
        pure = self._pure(n_p, n_b)

        training = self._layer.training if self._layer is not None else False
        cache_key = (shape_key, training)
        if cache_key not in self._fwd_cache:
            self._fwd_cache[cache_key] = jax.jit(pure)
        fwd = self._fwd_cache[cache_key]

        need_grad = grad_enabled() and any(not p.stop_gradient for p in params)
        outs = fwd(all_arrays, key)
        single = not isinstance(outs, (tuple, list))
        out_list = [outs] if single else list(outs)

        if not need_grad:
            result = [Tensor(o, stop_gradient=True) if isinstance(o, jax.Array) else o for o in out_list]
            return result[0] if single else result

        if cache_key not in self._bwd_cache:

            def bwd(arrays_tuple, cts, bwd_key):
                _, vjp_fn = jax.vjp(lambda a: pure(a, bwd_key), arrays_tuple)
                (grads,) = vjp_fn(cts)
                return grads

            self._bwd_cache[cache_key] = jax.jit(bwd)
        bwd = self._bwd_cache[cache_key]

        tensor_inputs = list(params) + list(buffers) + [
            a for a in args if isinstance(a, Tensor)
        ]
        # only params/buffers/inputs that are Tensors get routes; held arrays order = all_arrays
        input_tensors = []
        for a in args:
            input_tensors.append(a if isinstance(a, Tensor) else Tensor(np.asarray(a)))
        graph_inputs = list(params) + list(buffers) + input_tensors

        def vjp_fn(cts):
            if single:
                cts_tree = _conc(cts)
            else:
                cts_tree = tuple(_conc(c) for c in cts)
            grads = bwd(all_arrays, cts_tree, key)
            return tuple(grads)

        routes = []
        for t in graph_inputs:
            if t.stop_gradient:
                routes.append(None)
            elif t._grad_node is not None:
                routes.append(("node", t._grad_node, t._out_index))
            else:
                routes.append(("leaf", t))
        out_avals = [(tuple(o.shape), o.dtype) for o in out_list]
        node = GradNode("jit_fn", vjp_fn, routes, out_avals, multi=not single)
        import weakref

        outs_t, refs = [], []
        for i, o in enumerate(out_list):
            t = Tensor(o, stop_gradient=False)
            t._grad_node = node
            t._out_index = i
            refs.append(weakref.ref(t))
            outs_t.append(t)
        node.out_tensors = refs
        return outs_t[0] if single else outs_t

    # -- introspection -----------------------------------------------------
    def concrete_program(self, *args):
        params, buffers = self._params_buffers()
        input_arrays = [_conc(a._data) if isinstance(a, Tensor) else jnp.asarray(a) for a in args]
        all_arrays = tuple(_conc(p._data) for p in params) + tuple(_conc(b._data) for b in buffers) + tuple(input_arrays)
        pure = self._pure(len(params), len(buffers))
        return jax.jit(pure).lower(all_arrays, jax.random.PRNGKey(0))


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, **kwargs):
    """Decorator/wrapper (reference ``paddle.jit.to_static`` / ``declarative``)."""

    def decorate(fn):
        from ..nn.layer.layers import Layer

        if isinstance(fn, Layer):
            sf = StaticFunction(fn.forward, layer=fn, input_spec=input_spec)
            fn.forward = sf
            return fn
        if hasattr(fn, "__self__") and isinstance(fn.__self__, Layer):
            return StaticFunction(fn, layer=fn.__self__, input_spec=input_spec)
        return StaticFunction(fn, layer=None, input_spec=input_spec)

    if function is not None:
        return decorate(function)
    return decorate


declarative = to_static


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


def enable_to_static(flag=True):
    pass


# ---------------------------------------------------------------------------
# Compiled train step — the TPU-idiomatic hot loop
# ---------------------------------------------------------------------------
class CompiledTrainStep:
    """Compile (params, opt_state, batch) → (loss, params, opt_state) into ONE
    XLA executable: forward + backward + optimizer update, fully fused.

    This replaces the reference's per-op executor hot loop
    (``paddle/fluid/framework/executor.cc:297``) with a single compiled
    program — the architectural answer to TPU dispatch latency.
    """

    def __init__(self, model, loss_fn, optimizer, donate=True):
        from ..optimizer import Optimizer

        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.params = [p for p in model.parameters() if not p.stop_gradient]
        self.buffers = list(model.buffers())
        self._jit = None
        self._opt_state_keys = None
        self._donate = donate

    def _build(self):
        from ..profiler import spans as _spans

        with _spans.kept_span("program_build", kind="train_step"):
            self._build_step()

    def _build_step(self):
        model, loss_fn, optimizer = self.model, self.loss_fn, self.optimizer
        params, buffers = self.params, self.buffers
        opt = optimizer

        def step_fn(param_arrays, opt_state, batch_arrays, lr, key):
            def loss_of(params_arrays):
                saved = [(t, t._data) for t in params + buffers]
                try:
                    for t, a in zip(params, params_arrays):
                        t._data = a
                    inputs = [Tensor(a, stop_gradient=True) for a in batch_arrays]
                    # "loss" in the step's op names: the forward is
                    # jvp(loss), the backward transpose(jvp(loss))
                    with random_state.traced_keys(key), no_grad(), \
                            jax.named_scope("loss"):
                        out = loss_fn(model, *inputs)
                    return out._data if isinstance(out, Tensor) else out
                finally:
                    for t, a in saved:
                        t._data = a

            loss, grads = jax.value_and_grad(loss_of)(list(param_arrays))
            with jax.named_scope("optimizer_update"):
                new_params, new_state = opt._functional_update(param_arrays, grads, opt_state, lr)
            return loss, new_params, new_state

        donate = (0, 1) if self._donate else ()
        self._jit = jax.jit(step_fn, donate_argnums=donate)

    def __call__(self, *batch):
        from ..profiler import spans as _spans

        with _spans.span("train_step", kind="jit"):
            return self._call_impl(*batch)

    def _prepare(self, *batch):
        if self._jit is None:
            self._build()
        batch_arrays = tuple(_conc(b._data) if isinstance(b, Tensor) else jnp.asarray(b) for b in batch)
        param_arrays = [_conc(p._data) for p in self.params]
        opt_state = self.optimizer._functional_state(self.params)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        key = random_state.next_key()
        return param_arrays, opt_state, batch_arrays, lr, key

    def lower(self, *batch):
        """The train step lowered for this batch (``jax.stages.Lowered``):
        ``.as_text()`` is its StableHLO, ``.compile().as_text()`` the
        optimized HLO — introspection of which kernels the step really
        holds. Like ``HybridParallelEngine.lower``, the global RNG stream is
        restored and nothing is donated."""
        st = random_state._get()
        saved_key = st.key
        try:
            args = self._prepare(*batch)
            return self._jit.lower(*args)
        finally:
            st.key = saved_key

    def _call_impl(self, *batch):
        param_arrays, opt_state, batch_arrays, lr, key = self._prepare(*batch)
        loss, new_params, new_state = self._jit(param_arrays, opt_state, batch_arrays, lr, key)
        for p, a in zip(self.params, new_params):
            p._set_data(a)
        self.optimizer._functional_restore(self.params, new_state)
        self.optimizer._step_count += 1
        return Tensor(loss)


def compile_train_step(model, loss_fn, optimizer):
    return CompiledTrainStep(model, loss_fn, optimizer)


# ---------------------------------------------------------------------------
# save / load — AOT StableHLO artifacts
# ---------------------------------------------------------------------------
def save(layer, path, input_spec=None, **configs):
    """paddle.jit.save: serialize an inference program + params.

    Artifact layout: ``{path}.pdmodel`` = jax.export StableHLO bytes;
    ``{path}.pdiparams`` = pickled numpy state dict (cf. reference
    save_inference_model: __model__ + params).
    """
    from ..nn.layer.layers import Layer

    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)

    fn = layer.forward if isinstance(layer, Layer) else layer
    if isinstance(fn, StaticFunction):
        inner_layer = fn._layer
        raw_fn = fn._fn
    else:
        inner_layer = layer if isinstance(layer, Layer) else None
        raw_fn = fn

    if input_spec is None and isinstance(fn, StaticFunction):
        input_spec = fn._input_spec
    if input_spec is None:
        raise ValueError("paddle_tpu.jit.save requires input_spec")

    specs = [
        s if isinstance(s, InputSpec) else InputSpec.from_tensor(s) for s in input_spec
    ]
    if inner_layer is not None:
        inner_layer.eval()
        params = [p for _, p in inner_layer.named_parameters()]
        buffers = [b for _, b in inner_layer.named_buffers()]
        named_state = list(inner_layer.state_dict().items())
    else:
        params, buffers, named_state = [], [], []

    def pure(*input_arrays):
        saved = [(t, t._data) for t in params + buffers]
        try:
            inputs = [Tensor(a, stop_gradient=True) for a in input_arrays]
            with random_state.traced_keys(jax.random.PRNGKey(0)):
                with no_grad():
                    out = raw_fn(*inputs)
            return _tree_to_arrays(out)
        finally:
            for t, a in saved:
                t._data = a

    # Dynamic dims (None/-1) export as symbolic shapes so the reloaded
    # artifact accepts any size there (reference save_inference_model keeps
    # dynamic batch). One shared scope across all inputs.
    has_dynamic = any(d is None or d == -1 for s in specs for d in s.shape)
    if has_dynamic:
        scope = _jax_export().SymbolicScope()
        args = []
        for si, s in enumerate(specs):
            dims = ",".join(
                f"d{si}_{di}" if (d is None or d == -1) else str(d)
                for di, d in enumerate(s.shape)
            )
            shape = _jax_export().symbolic_shape(dims, scope=scope) if dims else ()
            args.append(jax.ShapeDtypeStruct(shape, s.dtype))
    else:
        args = [jax.ShapeDtypeStruct(tuple(s.shape), s.dtype) for s in specs]
    def _export(arg_list):
        # multi-platform so a TPU-saved artifact deploys on CPU hosts too
        # (Config.disable_gpu / CPU-only serving); ops without a multi-
        # platform lowering (e.g. Pallas kernels) fall back to native-only
        try:
            return _jax_export().export(jax.jit(pure), platforms=("cpu", "tpu"))(*arg_list)
        except Exception:
            # no multi-platform lowering (e.g. Pallas kernels): retry native-
            # only; a second failure chains the original via __context__
            return _jax_export().export(jax.jit(pure))(*arg_list)

    try:
        exported = _export(args)
    except Exception:
        if not has_dynamic:
            raise
        # some ops aren't shape-polymorphic: fall back to a static export at
        # size 1 for the dynamic dims (pre-existing behavior)
        args = [
            jax.ShapeDtypeStruct(
                tuple(abs(d) if d is not None and d != -1 else 1 for d in s.shape), s.dtype
            )
            for s in specs
        ]
        exported = _export(args)
    from ..framework.io import atomic_open

    with atomic_open(path + ".pdmodel", "wb") as f:
        f.write(exported.serialize())
    state = {k: np.asarray(v._data) for k, v in named_state}
    from ..framework.io import save as fsave

    fsave({"state": {k: Tensor(v) for k, v in state.items()}, "specs": [(list(s.shape), str(np.dtype(s.dtype)), s.name) for s in specs]}, path + ".pdiparams")

    # Trainable companion artifact: the same program exported with PARAMS AS
    # ARGUMENTS and a serialized VJP, so load→append-loss→train works without
    # the original python model (reference programs are data: append_backward
    # runs on a loaded ProgramDesc, python/paddle/fluid/backward.py:1413).
    # Buffers (BN stats, …) stay baked — finetune freezes them, like eval-mode
    # finetuning on a loaded inference program.
    if inner_layer is not None and params:
        named_params = list(inner_layer.named_parameters())
        p_names = [n for n, _ in named_params]
        p_list = [p for _, p in named_params]

        def pure_train(param_arrays, *input_arrays):
            saved = [(t, t._data) for t in p_list + buffers]
            try:
                for t, a in zip(p_list, param_arrays):
                    t._data = a
                inputs = [Tensor(a, stop_gradient=True) for a in input_arrays]
                with random_state.traced_keys(jax.random.PRNGKey(0)):
                    with no_grad():
                        out = raw_fn(*inputs)
                return _tree_to_arrays(out)
            finally:
                for t, a in saved:
                    t._data = a

        static_args = [
            jax.ShapeDtypeStruct(
                tuple(abs(d) if d is not None and d != -1 else 1 for d in s.shape),
                s.dtype,
            )
            for s in specs
        ]
        p_args = [jax.ShapeDtypeStruct(tuple(p.shape), p.dtype) for p in p_list]
        try:
            try:
                # same (possibly symbolic) feed shapes as the primal export,
                # so load→append_backward→train works at any batch size
                exp_train = _jax_export().export(jax.jit(pure_train))(p_args, *args)
            except Exception:
                # vjp not shape-polymorphic for some op: static fallback
                exp_train = _jax_export().export(jax.jit(pure_train))(p_args, *static_args)
            with atomic_open(path + ".pdtrain", "wb") as f:
                f.write(exp_train.serialize(vjp_order=1))
            with atomic_open(path + ".pdtrain.json", "w") as f:
                json.dump({"param_names": p_names}, f)
        except Exception:
            # not exportable with vjp (e.g. non-differentiable custom calls):
            # the inference artifact above is still complete
            for suffix in (".pdtrain", ".pdtrain.json"):
                if os.path.exists(path + suffix):
                    os.remove(path + suffix)


class TranslatedLayer:
    """Reloaded AOT program (reference dygraph/io.py TranslatedLayer)."""

    def __init__(self, exported, state, specs):
        self._exported = exported
        self._state = state
        self._specs = specs
        self.training = False

    def __call__(self, *args):
        arrays = [_conc(a._data) if isinstance(a, Tensor) else jnp.asarray(a) for a in args]
        outs = self._exported.call(*arrays)
        return _tree_to_tensors(outs)

    forward = __call__

    def eval(self):
        self.training = False
        return self

    def state_dict(self):
        return self._state


def load(path, **configs):
    with open(path + ".pdmodel", "rb") as f:
        blob = f.read()
    exported = _jax_export().deserialize(blob)
    from ..framework.io import load as fload

    meta = fload(path + ".pdiparams")
    return TranslatedLayer(exported, meta["state"], meta["specs"])
