"""@to_static + jit.save/load (reference `fluid/dygraph/jit.py:160,507,787`,
`dygraph_to_static/program_translator.py`).

TPU-native: "static graph" == XLA computation. to_static(fn) traces the
Python forward with jax (no AST transpiler — the same traced-once contract),
caches one compiled forward per input signature, and a compiled
recompute-backward twin so `loss.backward()` works through it (whole-program
rematerialization: the standard TPU memory/compute trade). jit.save
serializes weights + a StableHLO export (`jax.export`) — the serving
artifact a predictor can load without Python model code.
"""
from __future__ import annotations

import os
import pickle
from typing import Any, Callable, Dict, Optional

import jax
import jax.export
import jax.numpy as jnp
import numpy as np

from ..framework import random as frandom
from ..framework.autograd import TapeNode, is_grad_enabled
from ..framework.functional import functionalize, get_buffers, get_params
from ..framework.tensor import Tensor

__all__ = ["to_static", "declarative", "save", "load", "TranslatedLayer",
           "not_to_static", "ProgramTranslator", "enable_to_static",
           "dy2static"]

from .dy2static import ProgramTranslator, ast_transform, enable_to_static


def _split_tensors(args, kwargs):
    leaves, treedef = jax.tree_util.tree_flatten(
        (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
    t_idx = [i for i, l in enumerate(leaves) if isinstance(l, Tensor)]
    arrays = [leaves[i]._value for i in t_idx]
    statics = [None if isinstance(l, Tensor) else l for l in leaves]
    return treedef, t_idx, arrays, statics


class StaticFunction:
    """reference `program_translator.py:233`."""

    def __init__(self, function: Callable, input_spec=None):
        self._function = function
        self._input_spec = input_spec
        self._layer = None
        obj = getattr(function, "__self__", None)
        from ..nn.layer.layers import Layer
        if isinstance(obj, Layer):
            self._layer = obj
        elif isinstance(function, Layer):
            self._layer = function
            self._function = function.forward
        # dygraph_to_static AST pass: data-dependent python control flow
        # becomes lax.cond/while_loop (reference ast_transformer.py)
        self._function = ast_transform(self._function)
        self._apply_fn = None
        self._fwd_cache: Dict[Any, Callable] = {}
        self._bwd_cache: Dict[Any, Callable] = {}
        # descriptor support: to_static on an unbound method
        self._bound_cache = {}

    def __get__(self, instance, owner):
        if instance is None:
            return self
        key = id(instance)
        if key not in self._bound_cache:
            bound = StaticFunction(self._function.__get__(instance, owner),
                                   self._input_spec)
            self._bound_cache[key] = bound
        return self._bound_cache[key]

    def _get_apply(self):
        if self._apply_fn is None:
            if self._layer is not None:
                self._apply_fn, _, _ = functionalize(self._layer,
                                                     self._function)
            else:
                fn = self._function

                def apply_fn(pv, bv, rng, training, *args, **kwargs):
                    from ..framework.autograd import trace_mode
                    from ..framework.functional import tree_unwrap, tree_wrap
                    from ..framework.random import rng_scope
                    with trace_mode(), rng_scope(rng):
                        out = fn(*tree_wrap(args), **tree_wrap(kwargs))
                        return tree_unwrap(out), bv
                self._apply_fn = apply_fn
        return self._apply_fn

    @property
    def parameters(self):
        return (get_params(self._layer) if self._layer is not None
                else {})

    def __call__(self, *args, **kwargs):
        apply_fn = self._get_apply()
        layer = self._layer
        params = get_params(layer) if layer is not None else {}
        buffers = get_buffers(layer) if layer is not None else {}
        pv = {n: t._value for n, t in params.items()}
        bv = {n: t._value for n, t in buffers.items()}
        training = bool(layer.training) if layer is not None else True
        treedef, t_idx, arrays, statics = _split_tensors(args, kwargs)

        def recon(arrs):
            ls = list(statics)
            for i, a in zip(t_idx, arrs):
                ls[i] = a
            return jax.tree_util.tree_unflatten(treedef, ls)

        key = (str(treedef), tuple(statics[i] is None for i in range(len(statics))),
               tuple((a.shape, str(a.dtype)) for a in arrays), training,
               tuple(repr(s) for s in statics))
        rng = frandom.get_rng_key()

        need_grad = is_grad_enabled() and (
            any(not p.stop_gradient for p in params.values())
            or any(isinstance(l, Tensor) and not l.stop_gradient
                   for l in jax.tree_util.tree_leaves(
                       (args, kwargs))))

        def run(pv_, rng_, *arrs):
            a2, k2 = recon(arrs)
            return apply_fn(pv_, bv, rng_, training, *a2, **k2)

        if not need_grad:
            fwd = self._fwd_cache.get(key)
            if fwd is None:
                fwd = jax.jit(run)
                self._fwd_cache[key] = fwd
            out_raw, new_bufs = fwd(pv, rng, *arrays)
            self._write_buffers(buffers, new_bufs)
            return jax.tree_util.tree_map(
                lambda x: Tensor(x), out_raw)

        # train path: compiled forward + compiled recompute-backward
        fwd = self._fwd_cache.get(key)
        if fwd is None:
            fwd = jax.jit(run)
            self._fwd_cache[key] = fwd
        out_raw, new_bufs = fwd(pv, rng, *arrays)
        self._write_buffers(buffers, new_bufs)

        out_leaves, out_tree = jax.tree_util.tree_flatten(out_raw)

        bwd = self._bwd_cache.get(key)
        if bwd is None:
            def bwd_fn(pv_, rng_, arrs, cots):
                def fwd_only(pv2, *xs):
                    o, _ = run(pv2, rng_, *xs)
                    return jax.tree_util.tree_leaves(o)
                _, vjp = jax.vjp(fwd_only, pv_, *arrs)
                return vjp(list(cots))
            bwd = jax.jit(bwd_fn)
            self._bwd_cache[key] = bwd

        param_list = list(params.values())
        in_tensors = [l for l in jax.tree_util.tree_leaves((args, kwargs))
                      if isinstance(l, Tensor)]
        diff_inputs = param_list + in_tensors
        npar = len(param_list)
        pnames = list(params.keys())

        def vjp_like(cots):
            cots = cots if isinstance(cots, tuple) else (cots,)
            grads = bwd(pv, rng, tuple(arrays), tuple(cots))
            pgrad_dict = grads[0]
            flat = [pgrad_dict[n] for n in pnames] + list(grads[1:])
            return flat

        out_tensors = [Tensor(x, stop_gradient=False) for x in out_leaves]
        node = TapeNode("to_static", vjp_like, diff_inputs, out_tensors)
        for t in out_tensors:
            t._node = node
        return jax.tree_util.tree_unflatten(out_tree, out_tensors)

    @staticmethod
    def _write_buffers(buffers, new_bufs):
        for n, t in buffers.items():
            t._value = new_bufs[n]

    def concrete_program(self):
        return self


def to_static(function=None, input_spec=None, build_strategy=None):
    def decorate(fn):
        from ..nn.layer.layers import Layer
        if isinstance(fn, Layer):
            fn.forward = StaticFunction(fn.forward, input_spec)
            return fn
        return StaticFunction(fn, input_spec)
    if function is not None:
        return decorate(function)
    return decorate


declarative = to_static


def not_to_static(fn):
    fn._not_to_static = True
    return fn


# ---------------------------------------------------------------------------
# save / load: weights + StableHLO export
# ---------------------------------------------------------------------------

def _spec_to_sds(spec, scope=None, idx=0):
    """InputSpec → ShapeDtypeStruct. With a SymbolicScope, None/-1 dims
    become symbolic: dim 0 is the shared batch symbol "b" (every input's
    leading dim covaries — the serving-engine contract), other dynamic
    dims get a per-input name ("in<idx>_d<axis>") so unrelated inputs are
    NOT constrained equal. The StableHLO export is then shape-polymorphic:
    one artifact serves any batch size and `serving.InferenceEngine`
    compiles once per bucket instead of failing on every batch ≠ 1.
    Without a scope they collapse to 1 (the pre-polymorphism behavior,
    kept as the export fallback)."""
    from ..static.input_spec import InputSpec
    if isinstance(spec, InputSpec):
        from ..framework.dtype import to_jax_dtype
        dims = []
        for i, s in enumerate(spec.shape):
            if s is None or s == -1:
                if scope is None:
                    dims.append(1)
                else:
                    name = "b" if i == 0 else f"in{idx}_d{i}"
                    dims.append(jax.export.symbolic_shape(
                        name, scope=scope)[0])
            else:
                dims.append(int(s))
        return jax.ShapeDtypeStruct(tuple(dims), to_jax_dtype(spec.dtype))
    if isinstance(spec, Tensor):
        return jax.ShapeDtypeStruct(spec._value.shape, spec._value.dtype)
    return spec


def _collect_quant(layer, bv):
    """Quant manifest for jit.save: every sublayer exposing
    `quant_weight_spec()` (quantization.WeightOnlyLinear) contributes its
    quantized-weight and scale buffer names. These tensors are exported
    as leading runtime ARGUMENTS of the StableHLO artifact instead of
    baked closure constants: a baked int8 constant is legal StableHLO,
    but XLA's compile-time constant folding would dequantize
    `convert(q) * scale` into a resident fp32 weight — as an argument
    the weight stays integer in HBM and the dequant fuses into the
    matmul at run time. Tied layers appear once (named_sublayers dedups
    by id, the same traversal named_buffers uses)."""
    args, entries = [], []
    for pfx, sub in layer.named_sublayers(include_self=True):
        spec = getattr(sub, "quant_weight_spec", None)
        if spec is None:
            continue
        for qattr, sattr, bits in spec():
            qname = f"{pfx}.{qattr}" if pfx else qattr
            sname = f"{pfx}.{sattr}" if pfx else sattr
            if qname not in bv or sname not in bv:
                continue  # tied layer already collected under its
                # first traversal name
            args += [qname, sname]
            entries.append({"name": qname, "scale": sname,
                            "bits": int(bits)})
    return {"version": 1, "args": args, "entries": entries} \
        if entries else None


def save(layer, path, input_spec=None, **configs):
    """reference `jit.py:507` — writes {path}.pdmodel (StableHLO export),
    {path}.pdiparams (weights), {path}.pdmeta (structure + quant
    manifest). Weight-only-quantized sublayers export their int8/packed
    int4 tensors + scales as leading runtime arguments (see
    _collect_quant); inference.Predictor reads the manifest and feeds
    them device-resident, so the serving artifact is genuinely
    integer-weighted end to end."""
    from ..framework.functional import functionalize
    from ..nn.layer.layers import Layer

    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".", exist_ok=True)
    if isinstance(layer, Layer):
        apply_fn, pv, bv = functionalize(layer)
        fwd = layer.forward
        if isinstance(fwd, StaticFunction):
            apply_fn = fwd._get_apply()
        if input_spec is None:
            raise ValueError("jit.save requires input_spec")
        rng = jax.random.PRNGKey(0)

        quant = _collect_quant(layer, bv)
        if quant is None:
            def infer(*xs):
                out, _ = apply_fn(pv, bv, rng, False, *xs)
                return out
            q_sds = []
        else:
            from ..framework import monitor
            monitor.stat_add("STAT_quant_exports")
            qnames = quant["args"]
            bv_rest = {k: v for k, v in bv.items() if k not in set(qnames)}
            q_sds = [jax.ShapeDtypeStruct(bv[n].shape, bv[n].dtype)
                     for n in qnames]

            def infer(*all_args):
                qvals = all_args[:len(qnames)]
                xs = all_args[len(qnames):]
                bv2 = dict(bv_rest)
                bv2.update(zip(qnames, qvals))
                out, _ = apply_fn(pv, bv2, rng, False, *xs)
                return out

        from ..static.input_spec import InputSpec
        dynamic = any(isinstance(s, InputSpec)
                      and any(d is None or d == -1 for d in s.shape)
                      for s in input_spec)
        exported = None
        if dynamic:
            # shape-polymorphic export: None/-1 dims stay symbolic so the
            # serving engine can batch-bucket one artifact. Some programs
            # reject polymorphic shapes (data-dependent reshapes) — fall
            # back to the concrete dim-1 export rather than failing save.
            try:
                scope = jax.export.SymbolicScope()
                sds = [_spec_to_sds(s, scope=scope, idx=i)
                       for i, s in enumerate(input_spec)]
                exported = jax.export.export(jax.jit(infer))(*q_sds, *sds)
            except Exception as sym_err:  # noqa: BLE001
                import warnings
                warnings.warn(
                    f"jit.save: shape-polymorphic export failed "
                    f"({sym_err!r}); falling back to concrete dims — the "
                    f"artifact will only accept the saved shapes")
                exported = None
        if exported is None:
            sds = [_spec_to_sds(s) for s in input_spec]
            exported = jax.export.export(jax.jit(infer))(*q_sds, *sds)
        with open(path + ".pdmodel", "wb") as f:
            f.write(exported.serialize())
        state = {n: np.asarray(v.numpy()) for n, v in
                 layer.state_dict().items()}
        with open(path + ".pdiparams", "wb") as f:
            pickle.dump(state, f, protocol=4)
        meta = {"input_specs": [
            (tuple(d if isinstance(d, int) else str(d) for d in s.shape),
             str(s.dtype)) for s in sds]}
        if quant is not None:
            meta["quant"] = quant
        with open(path + ".pdmeta", "wb") as f:
            pickle.dump(meta, f, protocol=4)
        return
    raise TypeError("jit.save expects an nn.Layer")


class TranslatedLayer:
    """reference `jit.py:787` TranslatedLayer — runs a saved program.
    Quantized artifacts (a "quant" manifest in .pdmeta) expect their
    int8/int4 weight + scale tensors as leading call arguments; the
    layer keeps them device-resident in integer form and prepends them
    on every call (the dequant happens inside the compiled program)."""

    def __init__(self, exported, state, quant=None):
        self._exported = exported
        self._state = state
        self._quant = quant
        if quant:
            missing = [n for n in quant["args"] if n not in state]
            if missing:
                raise ValueError(
                    f"quantized artifact is missing weight tensors "
                    f"{missing} in its params file")
            self._qargs = [jnp.asarray(state[n]) for n in quant["args"]]
            # this base materialization IS device memory: account it
            # once here; Predictor replicas then count only buffers
            # their device_put actually created (same-device puts alias
            # the base buffer — see Predictor._load_quant_args)
            import weakref
            from ..inference import _note_quant_bytes
            total = sum(int(a.nbytes) for a in self._qargs)
            _note_quant_bytes(total)
            weakref.finalize(self, _note_quant_bytes, -total)
        else:
            self._qargs = []
        self.training = False

    def __call__(self, *args):
        arrays = [a._value if isinstance(a, Tensor) else jnp.asarray(a)
                  for a in args]
        out = self._exported.call(*self._qargs, *arrays)
        return jax.tree_util.tree_map(lambda x: Tensor(x), out)

    forward = __call__

    def eval(self):
        self.training = False
        return self

    def state_dict(self):
        return {k: Tensor(jnp.asarray(v)) for k, v in self._state.items()}


def load_meta(path) -> dict:
    """The .pdmeta sidecar ({} when absent — pre-manifest artifacts)."""
    if not os.path.exists(path + ".pdmeta"):
        return {}
    with open(path + ".pdmeta", "rb") as f:
        return pickle.load(f)


def load(path, **configs):
    with open(path + ".pdmodel", "rb") as f:
        exported = jax.export.deserialize(bytearray(f.read()))
    state = {}
    if os.path.exists(path + ".pdiparams"):
        with open(path + ".pdiparams", "rb") as f:
            state = pickle.load(f)
    return TranslatedLayer(exported, state,
                           quant=load_meta(path).get("quant"))
