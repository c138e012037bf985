"""Functional capture: turn an imperative Layer into a pure function.

This is the TPU-native replacement for the reference's dygraph→static
bridge (`fluid/dygraph/dygraph_to_static/program_translator.py:582`
ConcreteProgram traces the Layer into a ProgramDesc; `partial_program.py`
replays it via the run_program op). Here tracing is jax tracing: run the
Layer's Python forward under `trace_mode` with param/buffer values swapped
for tracers → a jaxpr/HLO. No AST rewriting is needed because data-dependent
Python control flow is disallowed under XLA anyway (use lax.cond/scan —
same constraint the reference's AST transformer enforces by conversion).

functionalize(layer) -> (apply_fn, params, buffers) with
  apply_fn(param_values, buffer_values, rng_key, training, *inputs)
      -> (outputs, new_buffer_values)
pure & jittable; batch-norm style buffer mutation is captured by reading
back the Layer's buffer slots after the traced call.
"""
from __future__ import annotations

import collections
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from .autograd import trace_mode
from .random import rng_scope
from .tensor import Tensor

__all__ = ["functionalize", "tree_unwrap", "tree_wrap", "get_params",
           "get_buffers"]


def tree_unwrap(obj):
    """Tensor→jax.Array on arbitrary nests (None passthrough)."""
    return jax.tree_util.tree_map(
        lambda x: x._value if isinstance(x, Tensor) else x, obj,
        is_leaf=lambda x: isinstance(x, Tensor))


def tree_wrap(obj, stop_gradient=True):
    return jax.tree_util.tree_map(
        lambda x: Tensor(x, stop_gradient=stop_gradient)
        if isinstance(x, (jnp.ndarray, jax.Array)) else x, obj)


def get_params(layer) -> "collections.OrderedDict[str, Tensor]":
    return collections.OrderedDict(
        (n, p) for n, p in layer.named_parameters() if p is not None)


def get_buffers(layer) -> "collections.OrderedDict[str, Tensor]":
    return collections.OrderedDict(
        (n, b) for n, b in layer.named_buffers() if b is not None)


def functionalize(layer, forward: Callable = None):
    """Returns (apply_fn, param_values, buffer_values).

    apply_fn(params: dict, buffers: dict, rng, training: bool, *args,
             **kwargs) -> (out_pytree_of_arrays, new_buffers: dict)
    """
    params = get_params(layer)
    buffers = get_buffers(layer)
    fwd = forward or layer.__call__

    def apply_fn(param_values: Dict[str, Any], buffer_values: Dict[str, Any],
                 rng, training: bool, *args, **kwargs):
        saved_vals = {n: t._value for n, t in params.items()}
        saved_bufs = {n: t._value for n, t in buffers.items()}
        saved_training = [(l, l.training)
                         for l in layer.sublayers(include_self=True)]
        for l, _ in saved_training:
            l.training = training
        for n, t in params.items():
            t._value = param_values[n]
        for n, t in buffers.items():
            t._value = buffer_values[n]
        try:
            with trace_mode(), rng_scope(rng):
                wargs = tree_wrap(args)
                wkwargs = tree_wrap(kwargs)
                out = fwd(*wargs, **wkwargs)
                new_bufs = {n: t._value for n, t in buffers.items()}
                return tree_unwrap(out), new_bufs
        finally:
            for n, t in params.items():
                t._value = saved_vals[n]
            for n, t in buffers.items():
                t._value = saved_bufs[n]
            for l, tr in saved_training:
                l.training = tr

    param_values = {n: t._value for n, t in params.items()}
    buffer_values = {n: t._value for n, t in buffers.items()}
    return apply_fn, param_values, buffer_values
