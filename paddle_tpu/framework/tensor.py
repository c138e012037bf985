"""Tensor: the imperative value type, and the op dispatch core.

Reference: `paddle/fluid/imperative/layer.h:65` (VarBase) +
`pybind/op_function_generator.cc:488` (the generated `core.ops.*` fast path)
+ `framework/tensor.h:89`.

TPU-native redesign: a Tensor wraps a `jax.Array` (device-resident,
XLA-managed memory — no custom allocator needed; reference components #9-10
are subsumed by the XLA runtime). Op dispatch (`defop`) plays the role of
Tracer::TraceOp: unwrap → run the XLA-lowered op eagerly → optionally record
a TapeNode whose pullback is the op's jax.vjp. In trace mode (functional
capture for jit/pjit) the same ops run on jax tracers with the tape off.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd
from .autograd import TapeNode, is_grad_enabled
from .dtype import DType, convert_dtype, to_jax_dtype
from .place import Place, default_place, device_for

__all__ = ["Tensor", "Parameter", "defop", "apply_op", "to_tensor"]

_name_counter = [0]


def _auto_name(prefix="generated_tensor"):
    _name_counter[0] += 1
    return f"{prefix}_{_name_counter[0]}"


class Tensor:
    """Imperative tensor. stop_gradient defaults True (paddle semantics);
    Parameters default False."""

    __slots__ = ("_value", "stop_gradient", "_node", "_grad", "name",
                 "persistable", "__weakref__", "__dict__")

    def __init__(self, value, stop_gradient: bool = True,
                 name: Optional[str] = None):
        if isinstance(value, Tensor):
            value = value._value
        self._value = value if isinstance(value, jax.Array) else jnp.asarray(value)
        self.stop_gradient = stop_gradient
        self._node: Optional[TapeNode] = None
        self._grad: Optional[jax.Array] = None
        self.name = name or _auto_name()
        self.persistable = False

    # -- basic properties ---------------------------------------------------
    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def ndim(self):
        return self._value.ndim

    @property
    def size(self):
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def dtype(self) -> DType:
        return convert_dtype(self._value.dtype)

    @property
    def place(self) -> str:
        try:
            dev = list(self._value.devices())[0]
            return f"Place({dev.platform}:{dev.id})"
        except Exception:
            return "Place(cpu)"

    @property
    def is_leaf(self):
        return self._node is None

    @property
    def grad(self) -> Optional["Tensor"]:
        if self._grad is None:
            return None
        return Tensor(self._grad, stop_gradient=True, name=self.name + "@GRAD")

    @grad.setter
    def grad(self, value):
        if value is None:
            self._grad = None
        else:
            self._grad = value._value if isinstance(value, Tensor) else jnp.asarray(value)

    def _accumulate_grad(self, g):
        for hook in getattr(self, "_grad_hooks", ()):
            out = hook(Tensor(g))
            if out is not None:
                g = out._value if isinstance(out, Tensor) else jnp.asarray(out)
        self._grad = g if self._grad is None else self._grad + g

    # -- conversions --------------------------------------------------------
    def numpy(self) -> np.ndarray:
        return np.asarray(self._value)

    def __array__(self, dtype=None):
        a = np.asarray(self._value)
        return a.astype(dtype) if dtype is not None else a

    def item(self, *args):
        return np.asarray(self._value).item(*args)

    def tolist(self):
        return np.asarray(self._value).tolist()

    def __float__(self):
        return float(np.asarray(self._value))

    def __int__(self):
        return int(np.asarray(self._value))

    def __bool__(self):
        return bool(np.asarray(self._value))

    def __len__(self):
        if not self._value.shape:
            raise TypeError("len() of a 0-d tensor")
        return self._value.shape[0]

    def __repr__(self):
        grad_s = "" if self.stop_gradient else ", stop_gradient=False"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}"
                f"{grad_s},\n       {np.asarray(self._value)!r})")

    def __hash__(self):
        return id(self)

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        autograd.backward(self, grad_tensor, retain_graph)

    def clear_grad(self):
        self._grad = None

    clear_gradient = clear_grad

    def detach(self) -> "Tensor":
        return Tensor(self._value, stop_gradient=True, name=self.name)

    def clone(self) -> "Tensor":
        return apply_op("clone", lambda x: x + 0, (self,), {})

    def stop_gradient_(self, flag=True):
        self.stop_gradient = flag
        return self

    # in-place value swap (reference VarBase copy_ / set_value)
    def set_value(self, value):
        v = value._value if isinstance(value, Tensor) else jnp.asarray(value)
        if tuple(v.shape) != tuple(self._value.shape):
            raise ValueError(
                f"set_value shape mismatch {v.shape} vs {self._value.shape}")
        self._value = v.astype(self._value.dtype)

    def copy_(self, other, blocking=True):
        self.set_value(other)
        return self

    def register_hook(self, hook):
        """Grad hook (reference `imperative/hooks.h`): called with the
        gradient Tensor during backward; a returned Tensor replaces it."""
        if not hasattr(self, "_grad_hooks"):
            self._grad_hooks = []
        self._grad_hooks.append(hook)

        class _Removable:
            def __init__(self, hooks, h):
                self._hooks, self._h = hooks, h

            def remove(self):
                if self._h in self._hooks:
                    self._hooks.remove(self._h)
        return _Removable(self._grad_hooks, hook)

    def pin_memory(self):
        return self

    def cuda(self, *a, **k):
        return self.to(default_place())

    def cpu(self):
        from .place import CPUPlace
        return self.to(CPUPlace())

    def to(self, place):
        if isinstance(place, str):
            from .place import parse_place
            pl = parse_place(place)
        else:
            pl = place
        return Tensor(jax.device_put(self._value, device_for(pl)),
                      stop_gradient=self.stop_gradient, name=self.name)

    @property
    def T(self):
        from ..ops import manipulation
        return manipulation.t(self)


class Parameter(Tensor):
    """Trainable tensor (reference `framework.py` Parameter): stop_gradient
    defaults False, persistable True, optional regularizer / need_clip."""

    def __init__(self, value, name=None, trainable=True, regularizer=None,
                 need_clip=True):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.persistable = True
        self.regularizer = regularizer
        self.need_clip = need_clip
        self.is_distributed = False
        self.optimize_attr = {"learning_rate": 1.0}

    @property
    def trainable(self):
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, v):
        self.stop_gradient = not v

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


# ---------------------------------------------------------------------------
# op dispatch (the Tracer)
# ---------------------------------------------------------------------------

def _is_inexact(v) -> bool:
    return jnp.issubdtype(jnp.result_type(v), jnp.inexact)


def apply_op(name: str, fn: Callable, args: Sequence[Any], kwargs: dict):
    """Run one op. Mirrors `imperative::Tracer::TraceOp` (tracer.cc:132):
    eager execute + optional grad-node creation."""
    raw_args = []
    diff_pos = []
    for i, a in enumerate(args):
        if isinstance(a, Tensor):
            raw_args.append(a._value)
            if not a.stop_gradient and _is_inexact(a._value):
                diff_pos.append(i)
        else:
            raw_args.append(a)
    raw_kwargs = {k: (v._value if isinstance(v, Tensor) else v)
                  for k, v in kwargs.items()}

    from ..amp import amp_active, maybe_cast_inputs, maybe_wrap_op
    if amp_active():
        raw_args = maybe_cast_inputs(name, raw_args)
        fn = maybe_wrap_op(name, fn)

    # static-graph mode: execute eagerly on placeholder values for
    # shape/dtype propagation AND record the op into the current Program
    # (reference: Python Program building in fluid/framework.py; here the
    # record is replayed through one jax.jit at Executor.run time).
    if not autograd.in_trace_mode():
        from ..static import program as _static
        if _static.in_static_mode():
            def closed_static(*vals):
                full = list(raw_args)
                vi = 0
                for i, a in enumerate(args):
                    if isinstance(a, Tensor):
                        full[i] = vals[vi]
                        vi += 1
                return fn(*full, **raw_kwargs)
            out = fn(*raw_args, **raw_kwargs)
            single = not isinstance(out, (tuple, list))
            flat = [out] if single else list(out)
            outs = [_static.Variable(x) for x in flat]
            tin = [a for a in args if isinstance(a, Tensor)]

            def fn_slots(*vals):
                return closed_static(*vals)
            _static.record_op(name, fn_slots, tin, outs, attrs=raw_kwargs)
            return outs[0] if single else tuple(outs)

    record = bool(diff_pos) and is_grad_enabled()
    if not record:
        out = fn(*raw_args, **raw_kwargs)
        return _wrap_outputs(name, out, None, None)

    def closed(*dvals):
        full = list(raw_args)
        for p, v in zip(diff_pos, dvals):
            full[p] = v
        out = fn(*full, **raw_kwargs)
        # canonicalize sequence outputs (incl. NamedTuples like
        # jnp.linalg's SVDResult) to a plain tuple so the backward walk
        # can feed jax.vjp a matching cotangent pytree
        return tuple(out) if isinstance(out, (tuple, list)) else out

    primals = [raw_args[p] for p in diff_pos]
    out, vjp_fn = jax.vjp(closed, *primals)
    in_tensors = [args[p] for p in diff_pos]
    return _wrap_outputs(name, out, vjp_fn, in_tensors,
                         out_is_seq=isinstance(out, tuple))


def _check_nan_inf(name, out):
    """reference `framework/details/nan_inf_utils_detail.cc` — scan every
    op output when FLAGS_check_nan_inf and abort naming the op."""
    from .flags import flag
    if not flag("FLAGS_check_nan_inf") or autograd.in_trace_mode():
        return
    for x in jax.tree_util.tree_leaves(out):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.inexact):
            if bool(jnp.any(~jnp.isfinite(x))):
                raise FloatingPointError(
                    f"Operator `{name}` output contains NaN/Inf "
                    f"(FLAGS_check_nan_inf is enabled)")


def _wrap_outputs(name, out, vjp_fn, in_tensors, out_is_seq=None):
    _check_nan_inf(name, out)
    single = not isinstance(out, (tuple, list))
    flat = [out] if single else list(out)
    sg = vjp_fn is None
    tensors = [x if isinstance(x, Tensor) else Tensor(x, stop_gradient=sg)
               for x in flat]
    if vjp_fn is not None:
        node = TapeNode(name, vjp_fn, in_tensors, tensors,
                        out_is_seq=out_is_seq)
        for t in tensors:
            t._node = node
            t.stop_gradient = False
    return tensors[0] if single else tuple(tensors)


def defop(name: str = None):
    """Decorator: turn a raw jnp/lax function into a framework op.

    Convention: Tensor-valued arguments are positional; attrs are kwargs
    (mirrors the generated core.ops.* signatures). Output arrays are wrapped
    into Tensors; a TapeNode is recorded when any input requires grad.
    """
    def deco(fn):
        opname = name or fn.__name__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            return apply_op(opname, fn, args, kwargs)

        wrapper.raw = fn
        return wrapper
    return deco


# ---------------------------------------------------------------------------

def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor."""
    if isinstance(data, Tensor):
        v = data._value
        if dtype is not None:
            v = v.astype(to_jax_dtype(dtype))
        t = Tensor(v, stop_gradient=stop_gradient)
        return t
    if isinstance(data, (list, tuple)) and any(
            isinstance(x, Tensor) for x in jax.tree_util.tree_leaves(data)):
        data = jax.tree_util.tree_map(
            lambda x: x._value if isinstance(x, Tensor) else x, data)
        v = jnp.asarray(data)
    else:
        arr = np.asarray(data)
        if dtype is None and arr.dtype == np.float64:
            arr = arr.astype(np.float32)  # paddle default float32
        v = jnp.asarray(arr)
    if dtype is not None:
        v = v.astype(to_jax_dtype(dtype))
    if place is not None:
        v = jax.device_put(v, device_for(place if isinstance(place, Place)
                                         else None))
    return Tensor(v, stop_gradient=stop_gradient)
