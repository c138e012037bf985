"""Legacy (fluid-era) op aliases and tensor-array ops.

Reference surface: `python/paddle/fluid/layers/tensor.py` (fill_constant,
create_array/array_write/array_read, reverse, has_inf/has_nan),
`python/paddle/fluid/layers/nn.py` (reduce_* / elementwise_* families,
crop_tensor, shape, rank), `python/paddle/fluid/lod_tensor.py` (LoDTensor).
TPU-native design: all of these are thin jnp compositions over the modern op
library — one lowering path, no separate legacy kernels; LoD is carried as an
explicit offsets list next to a dense padded array (XLA needs static shapes).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..framework import dtype as _dtype_mod
from ..framework.tensor import Tensor, apply_op, to_tensor
from . import creation, manipulation, math as _math, reduction

__all__ = [
    "add_n", "broadcast_shape", "crop_tensor", "fill_constant",
    "elementwise_add", "elementwise_sub", "elementwise_mul", "elementwise_div",
    "elementwise_floordiv", "elementwise_mod", "elementwise_pow",
    "elementwise_max", "elementwise_min",
    "reduce_sum", "reduce_mean", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_all", "reduce_any", "has_inf", "has_nan", "rank", "shape",
    "reverse", "scatter_nd", "get_tensor_from_selected_rows",
    "merge_selected_rows", "create_array", "array_write", "array_read",
    "array_length", "tensor_array_to_tensor", "LoDTensor", "LoDTensorArray",
    "set_printoptions", "get_default_dtype", "set_default_dtype",
    "create_parameter", "create_global_var",
    # fluid-era op surface (round-5 gap closers; ops/extra_ops.py)
    "affine_channel", "row_conv", "conv_shift", "cvm", "data_norm",
    "space_to_depth", "pad_constant_like", "partial_concat", "partial_sum",
    "l1_norm", "squared_l2_norm", "rank_loss", "bpr_loss", "center_loss",
    "hinge_loss", "im2sequence", "linear_chain_crf", "shuffle_batch",
    "gather_tree", "affine_grid", "temporal_shift", "fsp",
    "cross_entropy2", "psroi_pool", "prroi_pool", "correlation", "nce",
    "deformable_conv", "lod_reset", "sequence_reshape", "sequence_slice",
    "sequence_scatter", "batch_fc", "sample_logits", "filter_by_instag",
    "var_conv_2d", "tree_conv", "bilateral_slice", "Print",
    "rank_attention", "search_pyramid_hash", "pyramid_hash",
]

from .extra_ops import (affine_channel, affine_grid, bpr_loss,  # noqa: E402
                        center_loss, conv_shift, correlation,
                        cross_entropy2, cvm, data_norm, deformable_conv,
                        fsp, gather_tree, hinge_loss, im2sequence,
                        l1_norm, linear_chain_crf, nce, pad_constant_like,
                        partial_concat, partial_sum, prroi_pool,
                        psroi_pool, rank_loss, row_conv, shuffle_batch,
                        space_to_depth, squared_l2_norm, temporal_shift)
from .extra_ops import (batch_fc, bilateral_slice,  # noqa: E402
                        filter_by_instag, rank_attention, sample_logits,
                        tree_conv, var_conv_2d)


# --------------------------------------------------------------------------
# default dtype (paddle.set_default_dtype)

def set_default_dtype(d):
    _dtype_mod.set_default_float_dtype(d)


def get_default_dtype():
    return _dtype_mod.default_float_dtype().name


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Reference: `python/paddle/tensor/to_string.py`. Maps onto numpy's
    printoptions — Tensor repr prints via numpy."""
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    np.set_printoptions(**kw)


# --------------------------------------------------------------------------
# elementwise_* / reduce_* legacy names

def _axis_broadcast(x, y, axis):
    """fluid elementwise ops allowed mid-rank broadcast via `axis`."""
    xv = x._value if isinstance(x, Tensor) else jnp.asarray(x)
    yv = y._value if isinstance(y, Tensor) else jnp.asarray(y)
    if axis != -1 and yv.ndim < xv.ndim:
        shape = [1] * xv.ndim
        shape[axis:axis + yv.ndim] = yv.shape
        y = manipulation.reshape(y, shape)
    return x, y


def _elementwise(name, fn):
    def op(x, y, axis=-1, act=None, name=None):
        x, y = _axis_broadcast(x, y, axis)
        out = apply_op(f"elementwise_{name}", fn, (x, y), {})
        if act is not None:
            from ..nn import functional as F
            out = getattr(F, act)(out)
        return out
    op.__name__ = f"elementwise_{name}"
    return op


elementwise_add = _elementwise("add", jnp.add)
elementwise_sub = _elementwise("sub", jnp.subtract)
elementwise_mul = _elementwise("mul", jnp.multiply)
elementwise_div = _elementwise("div", jnp.divide)
elementwise_floordiv = _elementwise("floordiv", jnp.floor_divide)
elementwise_mod = _elementwise("mod", jnp.mod)
elementwise_pow = _elementwise("pow", jnp.power)
elementwise_max = _elementwise("max", jnp.maximum)
elementwise_min = _elementwise("min", jnp.minimum)


def _reduce(new_fn):
    def op(input, dim=None, keep_dim=False, name=None):
        return new_fn(input, axis=dim, keepdim=keep_dim)
    return op


reduce_sum = _reduce(reduction.sum)
reduce_mean = _reduce(reduction.mean)
reduce_max = _reduce(reduction.max)
reduce_min = _reduce(reduction.min)
reduce_prod = _reduce(reduction.prod)
reduce_all = _reduce(reduction.all)
reduce_any = _reduce(reduction.any)


# --------------------------------------------------------------------------
# misc tensor ops

def add_n(inputs, name=None):
    if isinstance(inputs, Tensor):
        return inputs
    def impl(*vs):
        out = vs[0]
        for v in vs[1:]:
            out = out + v
        return out
    return apply_op("add_n", impl, tuple(inputs), {})


def broadcast_shape(x_shape, y_shape):
    return list(np.broadcast_shapes(tuple(x_shape), tuple(y_shape)))


def fill_constant(shape, dtype, value, force_cpu=False, out=None, name=None):
    t = creation.full(shape, value, dtype=dtype)
    if out is not None:
        out.set_value(t._value)
        return out
    return t


def crop_tensor(x, shape=None, offsets=None, name=None):
    xshape = list(x.shape)
    shape = list(shape) if shape is not None else xshape
    shape = [xshape[i] if s in (-1, None) else int(s)
             for i, s in enumerate(shape)]
    offsets = list(offsets) if offsets is not None else [0] * len(xshape)
    def impl(v):
        sl = tuple(slice(int(o), int(o) + int(s))
                   for o, s in zip(offsets, shape))
        return v[sl]
    return apply_op("crop_tensor", impl, (x,), {})


def has_inf(x, name=None):
    return apply_op("has_inf", lambda v: jnp.isinf(v).any(), (x,), {})


def has_nan(x, name=None):
    return apply_op("has_nan", lambda v: jnp.isnan(v).any(), (x,), {})


def rank(input, name=None):
    return to_tensor(np.asarray(input.ndim, np.int32))


def shape(input, name=None):
    return to_tensor(np.asarray(input.shape, np.int32))


def reverse(x, axis, name=None):
    return manipulation.flip(x, axis)


def scatter_nd(index, updates, shape, name=None):
    zeros = creation.zeros(shape, dtype=updates.dtype)
    return manipulation.scatter_nd_add(zeros, index, updates)


def get_tensor_from_selected_rows(x, name=None):
    """reference `operators/get_tensor_from_selected_rows_op.cc`:
    materialize a SelectedRows into its dense tensor. Dense tensors pass
    through (the in-jit path never produces SelectedRows — scatter-add
    into dense is what XLA fuses)."""
    from ..framework.selected_rows import SelectedRows
    if isinstance(x, SelectedRows):
        return Tensor(jnp.asarray(x.to_dense()))
    return x


def merge_selected_rows(x, name=None):
    """reference `operators/merge_selected_rows_op.cc`: sum duplicate
    row ids."""
    from ..framework.selected_rows import SelectedRows
    if isinstance(x, SelectedRows):
        return x.merge()
    return x


def create_parameter(shape, dtype, name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """Reference: `python/paddle/fluid/layers/tensor.py` create_parameter."""
    from ..framework.tensor import Parameter
    from ..nn import initializer as init
    ini = default_initializer
    if ini is None:
        ini = init.Constant(0.0) if is_bias else init.XavierNormal()
    val = ini(shape, dtype)
    v = val._value if isinstance(val, Tensor) else jnp.asarray(val)
    return Parameter(v, name=name)


def create_global_var(shape, value, dtype, persistable=False,
                      force_cpu=False, name=None):
    from ..static import program as _prog
    t = creation.full(shape, value, dtype=dtype)
    t.persistable = persistable
    if name:
        t.name = name
    return t


# --------------------------------------------------------------------------
# tensor arrays (reference: LoDTensorArray + layers/control_flow array ops)

class LoDTensorArray(list):
    """Python-list-backed tensor array. The reference used a C++
    vector<LoDTensor> variable type for while-loop state; under XLA, loop
    state must be a fixed pytree, so eager mode keeps a list and
    `tensor_array_to_tensor` materialises it for compiled code."""


class LoDTensor(Tensor):
    """Dense tensor + LoD offsets (`framework/lod_tensor.h:114`). Kept for
    API parity; variable-length batches on TPU use padded dense + mask."""

    def __init__(self, value=None, lod=None):
        if value is None:
            value = np.zeros((0,), np.float32)
        super().__init__(jnp.asarray(value))
        self._lod = lod or []

    def lod(self):
        return self._lod

    def set_lod(self, lod):
        self._lod = lod

    def recursive_sequence_lengths(self):
        return [[b - a for a, b in zip(level[:-1], level[1:])]
                for level in self._lod]


def create_array(dtype="float32", initialized_list=None):
    arr = LoDTensorArray()
    if initialized_list:
        arr.extend(initialized_list)
    return arr


def array_write(x, i, array=None):
    if array is None:
        array = create_array()
    idx = int(i)
    while len(array) <= idx:
        array.append(None)
    array[idx] = x
    return array


def array_read(array, i):
    return array[int(i)]


def array_length(array):
    return to_tensor(np.asarray(len(array), np.int64))


def tensor_array_to_tensor(input, axis=1, use_stack=False, name=None):
    op = manipulation.stack if use_stack else manipulation.concat
    out = op(list(input), axis=axis)
    sizes = np.asarray([t.shape[axis] if not use_stack else 1
                        for t in input], np.int32)
    return out, to_tensor(sizes)


# ---------------------------------------------------------------------------
# LoD sequence ops (reference `operators/sequence_ops/*.cc`). Fluid-era
# models run these eagerly over LoDTensor (concat-of-sequences + offsets);
# compiled TPU models use padded-dense + sequence_mask instead, so these
# are host-side conveniences, not jit surfaces.
# ---------------------------------------------------------------------------

def _seq_offsets(x):
    lod = x.lod() if isinstance(x, LoDTensor) else []
    if not lod:
        raise ValueError("sequence op needs a LoDTensor with level-0 LoD")
    return list(lod[0])


def sequence_pad(x, pad_value=0.0, maxlen=None, name=None):
    """(LoDTensor rows) → (padded [N, maxlen, ...], lengths [N])
    (reference `sequence_pad_op.cc`)."""
    offs = _seq_offsets(x)
    v = np.asarray(x._value)
    lens = [b - a for a, b in zip(offs[:-1], offs[1:])]
    m = maxlen or max(lens)
    out = np.full((len(lens), m) + v.shape[1:], pad_value, v.dtype)
    for i, (a, b) in enumerate(zip(offs[:-1], offs[1:])):
        out[i, :b - a] = v[a:b]
    return (Tensor(jnp.asarray(out)),
            Tensor(jnp.asarray(np.asarray(lens, np.int64))))


def sequence_unpad(x, length, name=None):
    """Inverse of sequence_pad (reference `sequence_unpad_op.cc`)."""
    v = np.asarray(x._value if isinstance(x, Tensor) else x)
    lens = np.asarray(length._value if isinstance(length, Tensor)
                      else length).astype(np.int64)
    rows = np.concatenate([v[i, :l] for i, l in enumerate(lens)], axis=0)
    offs = np.concatenate([[0], np.cumsum(lens)]).tolist()
    return LoDTensor(rows, lod=[offs])


def sequence_pool(input, pool_type="average", name=None):
    """Per-sequence pooling (reference `sequence_pool_op.cc`):
    sum/average/sqrt/max/min/last/first."""
    offs = _seq_offsets(input)
    v = np.asarray(input._value)
    p = pool_type.lower()
    if p not in ("sum", "average", "mean", "sqrt", "max", "min", "last",
                 "first"):
        raise ValueError(f"unknown pool_type {pool_type!r}")
    outs = []
    for a, b in zip(offs[:-1], offs[1:]):
        if b == a:
            # empty sequences are legal LoD; reference pads them with 0.0
            outs.append(np.zeros(v.shape[1:], v.dtype))
            continue
        seg = v[a:b]
        if p == "sum":
            outs.append(seg.sum(0))
        elif p in ("average", "mean"):
            outs.append(seg.mean(0))
        elif p == "sqrt":
            outs.append(seg.sum(0) / np.sqrt(b - a))
        elif p == "max":
            outs.append(seg.max(0))
        elif p == "min":
            outs.append(seg.min(0))
        elif p == "last":
            outs.append(seg[-1])
        elif p == "first":
            outs.append(seg[0])
    return Tensor(jnp.asarray(np.stack(outs)))


def sequence_softmax(input, name=None):
    """Softmax within each sequence (reference
    `sequence_softmax_op.cc`)."""
    offs = _seq_offsets(input)
    v = np.asarray(input._value, np.float32)
    if v.ndim > 1 and v.shape[-1] != 1:
        # reference sequence_softmax_op enforces width-1 input
        raise ValueError(
            f"sequence_softmax requires input width 1, got {v.shape}")
    out = np.empty_like(v)
    for a, b in zip(offs[:-1], offs[1:]):
        if b == a:
            continue
        e = np.exp(v[a:b] - v[a:b].max())
        out[a:b] = e / e.sum()
    return LoDTensor(out, lod=input.lod())


def sequence_reverse(x, name=None):
    """Reverse rows inside each sequence (reference
    `sequence_reverse_op.h`)."""
    offs = _seq_offsets(x)
    v = np.asarray(x._value).copy()
    for a, b in zip(offs[:-1], offs[1:]):
        v[a:b] = v[a:b][::-1]
    return LoDTensor(v, lod=x.lod())


def sequence_concat(input, name=None):
    """Concatenate LoDTensors sequence-by-sequence (reference
    `sequence_concat_op.cc`)."""
    all_offs = [_seq_offsets(t) for t in input]
    n = len(all_offs[0]) - 1
    vals = [np.asarray(t._value) for t in input]
    rows, offs = [], [0]
    for i in range(n):
        for v, of in zip(vals, all_offs):
            rows.append(v[of[i]:of[i + 1]])
        offs.append(offs[-1] + sum(of[i + 1] - of[i] for of in all_offs))
    if not rows:
        return LoDTensor(np.zeros((0,) + vals[0].shape[1:],
                                  vals[0].dtype), lod=[offs])
    return LoDTensor(np.concatenate(rows, 0), lod=[offs])


def sequence_expand(x, y, ref_level=0, name=None):
    """Repeat each sequence of x to match y's LoD at ref_level
    (reference `sequence_expand_op.cc`)."""
    x_offs = _seq_offsets(x) if isinstance(x, LoDTensor) and x.lod() \
        else None
    y_offs = list(y.lod()[ref_level])
    v = np.asarray(x._value)
    n = len(y_offs) - 1
    rows, offs = [], [0]
    for i in range(n):
        reps = y_offs[i + 1] - y_offs[i]
        seg = v[x_offs[i]:x_offs[i + 1]] if x_offs is not None \
            else v[i:i + 1]
        for _ in range(reps):
            rows.append(seg)
        offs.append(offs[-1] + reps * seg.shape[0])
    if not rows:
        return LoDTensor(np.zeros((0,) + v.shape[1:], v.dtype), lod=[offs])
    return LoDTensor(np.concatenate(rows, 0), lod=[offs])


__all__ += ["sequence_pad", "sequence_unpad", "sequence_pool",
            "sequence_softmax", "sequence_reverse", "sequence_concat",
            "sequence_expand"]


def lod_reset(x, y=None, target_lod=None):
    """reference `lod_reset_op.cc`: replace x's LoD with y's (or the
    given offsets)."""
    if y is not None:
        lod = [_seq_offsets(y)] if isinstance(y, LoDTensor) else \
            [list(np.asarray(y.numpy()).astype(int))]
    elif target_lod is not None:
        lod = [list(target_lod)]
    else:
        raise ValueError("lod_reset needs y or target_lod")
    return LoDTensor(x._value if isinstance(x, Tensor) else x, lod)


def sequence_reshape(input, new_dim):
    """reference `sequence_reshape_op.cc`: re-chunk each sequence's
    flattened payload to rows of new_dim."""
    offs = _seq_offsets(input)
    v = np.asarray(input._value)
    old_dim = v.shape[1]
    new_offs = [0]
    rows = []
    for a, b in zip(offs[:-1], offs[1:]):
        payload = v[a:b].reshape(-1)
        assert payload.size % new_dim == 0, \
            "sequence payload not divisible by new_dim"
        rows.append(payload.reshape(-1, new_dim))
        new_offs.append(new_offs[-1] + rows[-1].shape[0])
    return LoDTensor(jnp.asarray(np.concatenate(rows, 0)), [new_offs])


def sequence_slice(input, offset, length):
    """reference `sequence_slice_op.cc`: per-sequence [offset, length)
    slices."""
    offs = _seq_offsets(input)
    v = np.asarray(input._value)
    off = np.asarray(offset.numpy() if isinstance(offset, Tensor)
                     else offset).reshape(-1).astype(int)
    ln = np.asarray(length.numpy() if isinstance(length, Tensor)
                    else length).reshape(-1).astype(int)
    rows = []
    new_offs = [0]
    for i, (a, b) in enumerate(zip(offs[:-1], offs[1:])):
        rows.append(v[a + off[i]:a + off[i] + ln[i]])
        new_offs.append(new_offs[-1] + rows[-1].shape[0])
    return LoDTensor(jnp.asarray(np.concatenate(rows, 0)), [new_offs])


def sequence_scatter(input, index, updates):
    """reference `sequence_scatter_op.cc`: add `updates` rows into
    `input` at per-sequence `index` positions (sequence i of the LoD
    pair addresses row i of the dense input)."""
    out = np.array(np.asarray(input._value), copy=True)
    offs = _seq_offsets(index)
    iv = np.asarray(index._value).reshape(-1).astype(int)
    uv = np.asarray(updates._value)
    for i, (a, b) in enumerate(zip(offs[:-1], offs[1:])):
        # np.add.at accumulates duplicate indices (fancy += would not)
        np.add.at(out[i], iv[a:b],
                  uv[a:b] if uv.ndim == 1 else uv[a:b, 0])
    return Tensor(jnp.asarray(out))


def Print(input, first_n=-1, message=None, summarize=20,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=False,
          print_phase="both", name=None):
    """reference `operators/print_op.cc` / fluid.layers.Print: log the
    tensor value as a side effect and pass it through, honoring first_n
    (max print count) and summarize (max elements shown).

    Eager values print directly. Traced values (inside jit / a lowered
    static Program) print at RUN time through a host callback
    (`jax.debug.callback`), once per execution; host callbacks run on the
    TPU v5e under jax 0.9.0 (checked on the chip, PR 21). first_n counts
    prints, eager and compiled alike."""
    import jax

    msg = str(message or getattr(input, "name", None) or "var")
    state = {"n": 0}

    def emit(value):
        if 0 <= first_n <= state["n"]:
            return
        state["n"] += 1
        arr = np.asarray(value)
        parts = [msg] if print_tensor_name else []
        if print_tensor_shape:
            parts.append(f"shape={tuple(arr.shape)}")
        if print_tensor_type:
            parts.append(f"dtype={arr.dtype}")
        # reference contract: negative summarize means "print all"
        flat = arr.ravel() if summarize < 0 else arr.ravel()[:summarize]
        print(f"{' '.join(parts)} value={flat}", flush=True)

    def impl(v):
        from ..static import program as _prog
        if isinstance(v, jax.core.Tracer):
            jax.debug.callback(emit, v)
        elif not _prog.in_static_mode():
            # (Program-build placeholder pass: stay silent, don't count)
            emit(v)
        return v
    return apply_op("print", impl, (input,), {})


def _xxh32(data: bytes, seed: int = 0) -> int:
    """XXH32 (public spec) — the hash pyramid_hash_op.h uses via
    <xxhash.h>; pure-Python so the op works with zero native deps."""
    P1, P2, P3, P4, P5 = (2654435761, 2246822519, 3266489917,
                          668265263, 374761393)
    M = 0xFFFFFFFF

    def rotl(x, r):
        return ((x << r) | (x >> (32 - r))) & M

    n = len(data)
    i = 0
    if n >= 16:
        v1 = (seed + P1 + P2) & M
        v2 = (seed + P2) & M
        v3 = seed & M
        v4 = (seed - P1) & M
        while i <= n - 16:
            for j, v in enumerate((v1, v2, v3, v4)):
                lane = int.from_bytes(data[i + 4 * j:i + 4 * j + 4],
                                      "little")
                v = (v + lane * P2) & M
                v = (rotl(v, 13) * P1) & M
                if j == 0:
                    v1 = v
                elif j == 1:
                    v2 = v
                elif j == 2:
                    v3 = v
                else:
                    v4 = v
            i += 16
        h = (rotl(v1, 1) + rotl(v2, 7) + rotl(v3, 12) + rotl(v4, 18)) & M
    else:
        h = (seed + P5) & M
    h = (h + n) & M
    while i <= n - 4:
        h = (h + int.from_bytes(data[i:i + 4], "little") * P3) & M
        h = (rotl(h, 17) * P4) & M
        i += 4
    while i < n:
        h = (h + data[i] * P5) & M
        h = (rotl(h, 11) * P1) & M
        i += 1
    h ^= h >> 15
    h = (h * P2) & M
    h ^= h >> 13
    h = (h * P3) & M
    h ^= h >> 16
    return h


_PYRAMID_RNGS = {}


def search_pyramid_hash(input, num_emb, space_len, pyramid_layer, rand_len,
                        drop_out_percent=0.0, is_training=False,
                        use_filter=False, white_list=None, black_list=None,
                        seed=0, weights=None, name=None):
    """reference `operators/pyramid_hash_op.cc`
    (fluid.contrib.layers.search_pyramid_hash): hash every n-gram window
    (lengths 2..pyramid_layer) of an int-id LoD sequence with XXH32 and
    assemble a num_emb embedding from rand_len-wide chunks of the flat
    weight table at the chained hash offsets — the massive-vocabulary
    embedding trick of the text-matching models.

    Returns a LoDTensor with one embedding row per surviving n-gram;
    gradients flow to `weights` (the hash positions are host-computed,
    the gather is a recorded differentiable op). Deviation from the
    reference: white/black lists filter by EXACT membership of the
    n-gram hash instead of a bloom filter (no false positives;
    documented simplification)."""
    assert num_emb % rand_len == 0, "num_emb must be divisible by rand_len"
    w_t = weights if isinstance(weights, Tensor) else \
        Tensor(jnp.asarray(np.asarray(weights, np.float32).reshape(-1)))
    W_len = int(np.prod(w_t.shape))
    assert W_len >= space_len + rand_len, \
        "weights must hold space_len + rand_len floats"
    offs = _seq_offsets(input)
    ids = np.asarray(input._value).reshape(-1).astype(np.int32)
    white = set(int(x) for x in np.asarray(white_list).ravel()) \
        if (use_filter and white_list is not None) else None
    black = set(int(x) for x in np.asarray(black_list).ravel()) \
        if (use_filter and black_list is not None) else None
    # persistent per-seed RNG (the reference advances a member seed with
    # rand_r across calls — a fresh RandomState per call would drop the
    # SAME grams every training step)
    rng = _PYRAMID_RNGS.setdefault(int(seed),
                                   np.random.RandomState(int(seed) or 1))

    gather_rows, new_offs = [], [0]
    for a, b in zip(offs[:-1], offs[1:]):
        seq = ids[a:b]
        count = 0
        for win in range(2, int(pyramid_layer) + 1):
            for st in range(0, len(seq) - win + 1):
                gram = seq[st:st + win].astype(np.float32).tobytes()
                key = _xxh32(gram, 0)
                if white is not None and key not in white:
                    continue
                if black is not None and key in black:
                    continue
                # reference scale: drop_out_percent is 0-100
                # (rand % 100 > percent keeps the gram)
                if is_training and drop_out_percent > 0 and \
                        not rng.randint(0, 100) > drop_out_percent:
                    continue
                idx = np.empty(num_emb, np.int64)
                pos1 = key % space_len
                pos2 = _xxh32(gram, rand_len) % space_len
                for j in range(0, num_emb, rand_len):
                    pos3 = _xxh32(gram, j + 2 * rand_len) % space_len
                    idx[j:j + rand_len] = np.arange(pos1, pos1 + rand_len)
                    pos1, pos2 = pos2, pos3
                gather_rows.append(idx)
                count += 1
        new_offs.append(new_offs[-1] + count)

    if gather_rows:
        idx_mat = jnp.asarray(np.stack(gather_rows))

        def impl(w):
            return jnp.take(w.reshape(-1), idx_mat, axis=0)
        out = apply_op("pyramid_hash", impl, (w_t,), {})
    else:
        out = Tensor(jnp.zeros((0, num_emb), jnp.float32))
    # keep the autograd tape: re-class the op output instead of
    # constructing a fresh LoDTensor from raw values
    out.__class__ = LoDTensor
    out._lod = [new_offs]
    return out


pyramid_hash = search_pyramid_hash
