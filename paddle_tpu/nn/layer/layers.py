"""Layer base class.

Parity: reference ``python/paddle/fluid/dygraph/layers.py`` — parameter /
sublayer / buffer registries via __setattr__, state_dict with structured
names, train/eval mode, forward hooks, apply, to().
"""
from __future__ import annotations

import collections
import time
from typing import Callable, Dict, Iterator, Optional, Tuple

import numpy as np

from ...core import dtype as dtypes
from ...core.tensor import Parameter, Tensor
from ...profiler import counter_inc
from .. import initializer as init_mod


class HookRemoveHelper:
    def __init__(self, hooks, hook_id):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


class Layer:
    def __init__(self, name_scope=None, dtype=None):
        self.training = True
        self._dtype = dtypes.convert_dtype(dtype) if dtype else dtypes.get_default_dtype()
        self._parameters: Dict[str, Parameter] = collections.OrderedDict()
        self._sub_layers: Dict[str, "Layer"] = collections.OrderedDict()
        self._buffers: Dict[str, Tensor] = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks: Dict[int, Callable] = collections.OrderedDict()
        self._forward_post_hooks: Dict[int, Callable] = collections.OrderedDict()
        self._hook_id = 0
        self._name_scope = name_scope or self.__class__.__name__.lower()

    # -- construction helpers --------------------------------------------
    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias=False,
        default_initializer=None,
    ) -> Parameter:
        dtype = dtypes.convert_dtype(dtype) if dtype else self._dtype
        initializer = None
        name = None
        trainable = True
        learning_rate = 1.0
        if attr is not None and attr is not False:
            from ..param_attr import ParamAttr

            if isinstance(attr, ParamAttr):
                initializer = attr.initializer
                name = attr.name
                trainable = attr.trainable
                learning_rate = attr.learning_rate
            elif isinstance(attr, init_mod.Initializer):
                initializer = attr
            elif isinstance(attr, str):
                name = attr
        if initializer is None:
            initializer = default_initializer or (
                init_mod._default_bias_init if is_bias else init_mod._default_weight_init
            )
        t0 = time.perf_counter_ns()
        data = initializer(shape, dtype)
        # what the constructors draw, in seconds of host time and bytes: a
        # model that is then handed its weights drew them to be thrown away
        counter_inc("param_init_ns", time.perf_counter_ns() - t0)
        counter_inc("param_init_bytes", int(getattr(data, "nbytes", 0)))
        counter_inc("param_init_leaves")
        p = Parameter(data, name=name, trainable=trainable)
        p.optimize_attr["learning_rate"] = learning_rate
        return p

    def add_parameter(self, name, parameter):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    # -- attribute magic --------------------------------------------------
    def __getattr__(self, name):
        # only called when normal lookup fails: check registries (buffers are
        # registered without setattr, reference layers.py behavior)
        for registry in ("_buffers", "_parameters", "_sub_layers"):
            d = self.__dict__.get(registry)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"'{type(self).__name__}' object has no attribute '{name}'")

    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ first")
            params[name] = value
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            object.__setattr__(self, name, value)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ first")
            layers[name] = value
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            object.__setattr__(self, name, value)
        else:
            if params is not None and name in params and value is None:
                params.pop(name)
            if buffers is not None and isinstance(value, Tensor) and name in buffers:
                buffers[name] = value
            object.__setattr__(self, name, value)

    # -- iteration --------------------------------------------------------
    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, layer_prefix, layer in self._walk(prefix, include_sublayers):
            for pname, p in layer._parameters.items():
                if p is not None and id(p) not in seen:
                    seen.add(id(p))
                    yield ((layer_prefix + "." + pname) if layer_prefix else pname), p

    def _walk(self, prefix="", include_sublayers=True):
        yield None, prefix, self
        if include_sublayers:
            for name, sub in self._sub_layers.items():
                if sub is None:
                    continue
                sub_prefix = (prefix + "." + name) if prefix else name
                for item in sub._walk(sub_prefix, True):
                    yield item

    def sublayers(self, include_self=False):
        out = []
        for _, _, layer in self._walk():
            out.append(layer)
        if not include_self:
            out = out[1:]
        return out

    def named_sublayers(self, prefix="", include_self=False):
        for i, (_, p, layer) in enumerate(self._walk(prefix)):
            if i == 0 and not include_self:
                continue
            yield p, layer

    def children(self):
        return iter(self._sub_layers.values())

    def named_children(self):
        return iter(self._sub_layers.items())

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for _, layer_prefix, layer in self._walk(prefix, include_sublayers):
            for bname, b in layer._buffers.items():
                if b is not None:
                    yield ((layer_prefix + "." + bname) if layer_prefix else bname), b

    # -- mode -------------------------------------------------------------
    def train(self):
        self.training = True
        for layer in self.sublayers():
            layer.training = True
        return self

    def eval(self):
        self.training = False
        for layer in self.sublayers():
            layer.training = False
        return self

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    # -- state dict -------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True, use_hook=True):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(include_sublayers=include_sublayers):
            dest[name] = p
        for name, b in self.named_buffers(include_sublayers=include_sublayers):
            short = name.rsplit(".", 1)[-1]
            owner = self._locate(name)
            if owner is not None and short in owner._non_persistable_buffer_names:
                continue
            dest[name] = b
        return dest

    def _locate(self, qual_name):
        parts = qual_name.split(".")[:-1]
        layer = self
        for p in parts:
            layer = layer._sub_layers.get(p)
            if layer is None:
                return None
        return layer

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for name, value in state_dict.items():
            if name not in own:
                unexpected.append(name)
                continue
            target = own[name]
            arr = value.numpy() if isinstance(value, Tensor) else np.asarray(value)
            if tuple(arr.shape) != tuple(target.shape):
                raise ValueError(f"shape mismatch for {name}: {arr.shape} vs {target.shape}")
            target.set_value(arr.astype(target.dtype))
        for name in own:
            if name not in state_dict:
                missing.append(name)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # -- dtype / device movement -----------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        import jax

        from ...core.place import Place

        for p in self.parameters():
            arr = p._data
            if dtype is not None and dtypes.is_floating_point(p.dtype):
                arr = arr.astype(dtypes.convert_dtype(dtype))
            if device is not None:
                place = device if isinstance(device, Place) else None
                if place is None:
                    name, _, idx = str(device).partition(":")
                    place = Place({"xla": "tpu", "cuda": "gpu"}.get(name, name), int(idx) if idx else 0)
                arr = jax.device_put(arr, place.jax_device())
            p._set_data(arr)
        for b in self.buffers():
            if dtype is not None and dtypes.is_floating_point(b.dtype):
                b._set_data(b._data.astype(dtypes.convert_dtype(dtype)))
        if dtype is not None:
            for layer in self.sublayers(include_self=True):
                layer._dtype = dtypes.convert_dtype(dtype)
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # -- hooks ------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # -- call -------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        out = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, inputs, out)
            if result is not None:
                out = result
        return out

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def full_name(self):
        return self._name_scope

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).split("\n")
            sub_repr = [sub_repr[0]] + ["  " + l for l in sub_repr[1:]]
            lines.append(f"  ({name}): " + "\n".join(sub_repr))
        main = f"{self.__class__.__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"
