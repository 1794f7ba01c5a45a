"""`nn.Layer`: the module base class.

Reference: python/paddle/nn/layer/layers.py:337 (`Layer`). Parameters,
sublayers, and buffers are tracked via `__setattr__`; state_dict round-trips
through `paddle_tpu.save/load`; forward pre/post hooks match the reference's
hook API.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable, Iterator

import jax
import numpy as np

from ..core import dtype as dtypes
from ..core.tensor import Tensor, Parameter
from ..framework.parameter import ParamAttr

__all__ = ["Layer"]

_calls = threading.local()      # .depth: Layer calls open on this thread


class _HookHandle:
    _next_id = 0

    def __init__(self, hooks: OrderedDict):
        self._hooks = hooks
        self._id = _HookHandle._next_id
        _HookHandle._next_id += 1
        hooks[self._id] = None  # placeholder replaced by caller

    def remove(self):
        self._hooks.pop(self._id, None)


class Layer:
    def __init__(self, name_scope: str | None = None, dtype: Any = "float32"):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names", set())
        self.training = True
        self._dtype = dtypes.dtype_from_any(dtype)
        self._name_scope = name_scope or self.__class__.__name__.lower()
        self._forward_pre_hooks: OrderedDict = OrderedDict()
        self._forward_post_hooks: OrderedDict = OrderedDict()
        self._casted_dtype = None

    # -- attribute routing --------------------------------------------------
    def __setattr__(self, name: str, value: Any):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            params[name] = value
            layers is not None and layers.pop(name, None)
            buffers is not None and buffers.pop(name, None)
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            layers[name] = value
            params is not None and params.pop(name, None)
            self.__dict__.pop(name, None)
        elif params is not None and name in params:
            if value is None:
                params.pop(name)
                object.__setattr__(self, name, None)
            elif isinstance(value, Tensor):
                params[name].set_value(value)
            else:
                raise TypeError(f"cannot assign {type(value)} to parameter {name!r}")
        elif buffers is not None and name in buffers:
            if value is None or isinstance(value, Tensor):
                buffers[name] = value
            else:
                object.__setattr__(self, name, value)
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name: str):
        d = self.__dict__
        for store in ("_parameters", "_sub_layers", "_buffers"):
            s = d.get(store)
            if s is not None and name in s:
                return s[name]
        raise AttributeError(
            f"'{self.__class__.__name__}' object has no attribute {name!r}")

    def __delattr__(self, name: str):
        for store in (self._parameters, self._sub_layers, self._buffers):
            if name in store:
                del store[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + \
            list(self._sub_layers) + list(self._buffers)

    # -- registration -------------------------------------------------------
    def add_parameter(self, name: str, parameter: Parameter | None) -> Parameter | None:
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("add_parameter expects a Parameter")
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer") -> "Layer":
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name: str, tensor: Tensor | None,
                        persistable: bool = True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None) -> Parameter:
        from ..framework.parameter import create_parameter as _cp
        if attr is False:
            return None
        dt = dtype or self._dtype
        return _cp(shape, dtype=dt, attr=attr, is_bias=is_bias,
                   default_initializer=default_initializer)

    def create_tensor(self, name=None, persistable=False, dtype=None):
        import jax.numpy as jnp
        return Tensor(jnp.zeros((), dtypes.dtype_from_any(dtype or self._dtype).np_dtype),
                      name=name)

    # -- iteration ----------------------------------------------------------
    def named_parameters(self, prefix: str = "",
                         include_sublayers: bool = True) -> Iterator:
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (f"{prefix}.{name}" if prefix else name), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                for n, p in layer.named_parameters(sub_prefix, True):
                    if id(p) not in seen:
                        seen.add(id(p))
                        yield n, p

    def parameters(self, include_sublayers: bool = True) -> list[Parameter]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix: str = "", include_sublayers: bool = True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                yield from layer.named_buffers(sub_prefix, True)

    def buffers(self, include_sublayers: bool = True) -> list[Tensor]:
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self):
        for name, l in self._sub_layers.items():
            if l is not None:
                yield name, l

    def sublayers(self, include_self: bool = False) -> list["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix: str = "", include_self: bool = False,
                        layers_set=None):
        if layers_set is None:
            layers_set = set()
        if include_self and id(self) not in layers_set:
            layers_set.add(id(self))
            yield prefix, self
        for name, l in self._sub_layers.items():
            if l is None or id(l) in layers_set:
                continue
            layers_set.add(id(l))
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield sub_prefix, l
            yield from l.named_sublayers(sub_prefix, False, layers_set)

    def apply(self, fn: Callable[["Layer"], None]) -> "Layer":
        for l in self.children():
            l.apply(fn)
        fn(self)
        return self

    # -- modes --------------------------------------------------------------
    def train(self):
        self.training = True
        for l in self.sublayers():
            l.training = True
        return self

    def eval(self):
        self.training = False
        for l in self.sublayers():
            l.training = False
        return self

    # -- forward ------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError(
            f"{self.__class__.__name__} must implement forward()")

    def __call__(self, *inputs, **kwargs):
        if getattr(_calls, "depth", 0):
            return self._call_impl(*inputs, **kwargs)
        # the outermost call names the device ops made under it `forward`
        # (metadata only). One scope per model call, not one per layer: the
        # eager discovery step of `to_static` calls layers by the thousand
        _calls.depth = 1
        try:
            with jax.named_scope("forward"):
                return self._call_impl(*inputs, **kwargs)
        finally:
            _calls.depth = 0

    def _call_impl(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            if hook is None:
                continue
            res = hook(self, inputs)
            if res is not None:
                inputs = res if isinstance(res, tuple) else (res,)
        out = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            if hook is None:
                continue
            res = hook(self, inputs, out)
            if res is not None:
                out = res
        return out

    def register_forward_pre_hook(self, hook) -> _HookHandle:
        h = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[h._id] = hook
        return h

    def register_forward_post_hook(self, hook) -> _HookHandle:
        h = _HookHandle(self._forward_post_hooks)
        self._forward_post_hooks[h._id] = hook
        return h

    # -- state dict ---------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers: bool = True,
                   structured_name_prefix: str = "", use_hook: bool = True):
        dest = destination if destination is not None else OrderedDict()
        for name, p in self.named_parameters(include_sublayers=include_sublayers):
            dest[structured_name_prefix + name] = p
        for name, b in self.named_buffers(include_sublayers=include_sublayers):
            short = name.rsplit(".", 1)[-1]
            owner = self
            if "." in name:
                for part in name.split(".")[:-1]:
                    owner = owner._sub_layers[part]
            if short in owner._non_persistable_buffer_names:
                continue
            dest[structured_name_prefix + name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name: bool = True):
        missing, unexpected = [], []
        own = self.state_dict()
        matched = set()
        for k, v in state_dict.items():
            if k in own:
                tgt = own[k]
                v_arr = v._data if isinstance(v, Tensor) else np.asarray(v)
                if tuple(tgt._data.shape) != tuple(np.shape(v_arr)):
                    raise ValueError(
                        f"shape mismatch for {k}: {tuple(tgt._data.shape)} vs "
                        f"{tuple(np.shape(v_arr))}")
                tgt.set_value(v_arr)
                matched.add(k)
            else:
                unexpected.append(k)
        missing = [k for k in own if k not in matched]
        return missing, unexpected

    load_dict = set_state_dict

    # -- dtype / device movement -------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self._cast_all(dtypes.dtype_from_any(dtype))
        return self

    def astype(self, dtype):
        self._cast_all(dtypes.dtype_from_any(dtype))
        return self

    def _cast_all(self, dt: dtypes.DType):
        for p in self.parameters():
            if dtypes.is_floating_point(p.dtype):
                p._data = p._data.astype(dt.np_dtype)
        for b in self.buffers():
            if b is not None and dtypes.is_floating_point(b.dtype):
                b._data = b._data.astype(dt.np_dtype)
        for l in self.sublayers(include_self=True):
            l._dtype = dt

    def float(self):
        return self.astype(dtypes.float32)

    def bfloat16(self):
        return self.astype(dtypes.bfloat16)

    def half(self):
        return self.astype(dtypes.float16)

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def full_name(self) -> str:
        return self._name_scope

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, l in self._sub_layers.items():
            mod_str = repr(l)
            mod_str = "\n".join("  " + ln for ln in mod_str.split("\n"))
            lines.append(f"  ({name}): {mod_str.strip()}")
        main = self.__class__.__name__
        if not lines:
            return f"{main}({extra})"
        return f"{main}({extra}\n" + "\n".join(lines) + "\n)"
