"""nn.Layer base class (reference `python/paddle/fluid/dygraph/layers.py`).

Holds Parameters + buffers + sublayers; supports hooks, state_dict, and —
the TPU-native addition — functional capture (`paddle_tpu.framework
.functional.functionalize`) that turns any Layer into a pure
(params, buffers, inputs) -> (outputs, new_buffers) function for
jit/grad/pjit.
"""
from __future__ import annotations

import collections
from typing import Callable, Iterator, Optional, Tuple

import jax
import numpy as np

from ...framework.dtype import to_jax_dtype
from ...framework.param_attr import ParamAttr
from ...framework.tensor import Parameter, Tensor
from .. import initializer as I

__all__ = ["Layer"]


class HookRemoveHelper:
    _next_id = [0]

    def __init__(self, hooks):
        self._hooks = hooks
        self._id = HookRemoveHelper._next_id[0]
        HookRemoveHelper._next_id[0] += 1

    def remove(self):
        self._hooks.pop(self._id, None)


class Layer:
    # a container that is iterated and never called (LayerList, LayerDict)
    # passes its own name on to its items: `layers/3`
    _scope_passthrough = False

    def __init__(self, name_scope: Optional[str] = None, dtype="float32"):
        self.training = True
        self._dtype = dtype
        self._full_name = name_scope or type(self).__name__.lower()
        self._parameters = collections.OrderedDict()
        self._sub_layers = collections.OrderedDict()
        self._buffers = collections.OrderedDict()
        self._non_persistable_buffer_names_set = set()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()

    # -- construction -------------------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = dtype or self._dtype
        init = attr.initializer or default_initializer
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierUniform()
        value = init(shape, dtype)
        p = Parameter(value, name=attr.name, trainable=attr.trainable,
                      regularizer=attr.regularizer, need_clip=attr.need_clip)
        p.optimize_attr["learning_rate"] = attr.learning_rate
        return p

    def add_parameter(self, name, parameter):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("add_parameter expects a Parameter")
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        self._name_sublayer(str(name), sublayer)
        return sublayer

    def _name_sublayer(self, name, sub):
        """Remember the name under which this layer holds `sub`: the
        sublayer's `forward` runs under `jax.named_scope` of it, so an
        operation's name stack on the device reads
        `ErnieForPretraining/ernie/encoder/layers/3/self_attn/...`
        (`__call__`). A layer held twice answers to the later name."""
        if sub is None:
            return
        own = self.__dict__.get("_scope_name")
        if self._scope_passthrough and own:
            name = f"{own}/{name}"
        object.__setattr__(sub, "_scope_name", name)
        if sub._scope_passthrough:
            for key, item in sub._sub_layers.items():
                sub._name_sublayer(key, item)

    def register_buffer(self, name, tensor, persistable=True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor)
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names_set.add(name)
        else:
            self._non_persistable_buffer_names_set.discard(name)
        return tensor

    # -- attribute routing --------------------------------------------------
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
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ first")
            layers[name] = value
            self._name_sublayer(name, value)
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
        elif buffers is not None and name in buffers:
            # assignment to a registered buffer updates it (BN running stats)
            if value is not None and not isinstance(value, Tensor):
                value = Tensor(value)
            buffers[name] = value
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    # -- traversal ----------------------------------------------------------
    def named_parameters(self, prefix="", include_sublayers=True
                         ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix,
                                                include_self=True):
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (f"{name}.{pname}" if name else pname), p
            if not include_sublayers:
                break

    def parameters(self, include_sublayers=True):
        return [p for _, p in
                self.named_parameters(include_sublayers=include_sublayers)]

    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        layers_set = layers_set if layers_set is not None else set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield from sub.named_sublayers(prefix=sub_prefix,
                                           include_self=True,
                                           layers_set=layers_set)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return [l for _, l in self.named_children()]

    def named_children(self):
        for name, sub in self._sub_layers.items():
            if sub is not None:
                yield name, sub

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix,
                                                include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (f"{name}.{bname}" if name else bname), b
            if not include_sublayers:
                break

    def buffers(self, include_sublayers=True):
        return [b for _, b in
                self.named_buffers(include_sublayers=include_sublayers)]

    def apply(self, fn: Callable):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    def full_name(self):
        return self._full_name

    # -- mode / device ------------------------------------------------------
    def train(self):
        for layer in self.sublayers(include_self=True):
            layer.training = True
        return self

    def eval(self):
        for layer in self.sublayers(include_self=True):
            layer.training = False
        return self

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dt = to_jax_dtype(dtype)
            for p in self.parameters():
                p._value = p._value.astype(dt)
        if device is not None:
            import jax
            from ...framework.place import device_for, parse_place
            pl = parse_place(device) if isinstance(device, str) else device
            dev = device_for(pl)
            for p in self.parameters():
                p._value = jax.device_put(p._value, dev)
            for b in self.buffers():
                b._value = jax.device_put(b._value, dev)
        return self

    def float(self):
        return self.to(dtype="float32")

    def astype(self, dtype):
        return self.to(dtype=dtype)

    # -- state dict ---------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   use_hook=True):
        dest = collections.OrderedDict() if destination is None else destination
        for name, p in self.named_parameters(
                include_sublayers=include_sublayers):
            dest[name] = p
        for name, b in self.named_buffers(
                include_sublayers=include_sublayers):
            # skip non-persistable
            short = name.rsplit(".", 1)[-1]
            owner = self
            if "." in name:
                for part in name.split(".")[:-1]:
                    owner = getattr(owner, part)
            if short in owner._non_persistable_buffer_names_set:
                continue
            dest[name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing = []
        for name, t in own.items():
            if name not in state_dict:
                missing.append(name)
                continue
            v = state_dict[name]
            arr = np.asarray(v.numpy() if isinstance(v, Tensor) else v)
            if tuple(arr.shape) != tuple(t.shape):
                raise ValueError(
                    f"shape mismatch for {name}: {arr.shape} vs {t.shape}")
            t.set_value(arr)
        unexpected = [k for k in state_dict if k not in own]
        return missing, unexpected

    load_dict = set_state_dict
    set_dict = set_state_dict

    # -- hooks --------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        helper = HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[helper._id] = hook
        return helper

    def register_forward_post_hook(self, hook):
        helper = HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[helper._id] = hook
        return helper

    # -- call ---------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        # a name on the device, nothing else: the parent's name for this
        # layer, or the class name at the root (`_name_sublayer`)
        with jax.named_scope(self.__dict__.get("_scope_name")
                             or type(self).__name__):
            outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def __repr__(self):
        extra = []
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).replace("\n", "\n  ")
            extra.append(f"  ({name}): {sub_repr}")
        body = "\n".join(extra)
        head = type(self).__name__
        return f"{head}(\n{body}\n)" if body else f"{head}()"
