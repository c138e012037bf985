"""Collective communication API (reference
`python/paddle/distributed/collective.py:101-457` and the 54 NCCL kernels
in `paddle/fluid/operators/collective/` — c_allreduce_*, c_broadcast,
c_allgather, c_reducescatter, send_v2/recv_v2…).

TPU-native: there are no eager comm kernels or comm streams. A collective
is an XLA op over a named mesh axis, legal inside compiled SPMD regions
(shard_map / pjit manual axes). The eager API below therefore has two
modes, mirroring how the reference ops behave at their two call sites:
  * inside an SPMD region (a `shard_ctx` axis is active): lowers to
    lax.psum / all_gather / ppermute / all_to_all on that axis;
  * eager at top level: operates on the sharded global array — for a
    1-process runtime the group is this process's devices and the op is
    computed directly (world_size==1 ⇒ identity), matching reference
    semantics where each rank holds its shard.
Ordering/streams (`c_sync_calc_stream`) are unnecessary: XLA's dataflow
already serializes compute↔comm correctly.
"""
from __future__ import annotations

import contextlib
import threading
from typing import List, Optional, Sequence

import jax.numpy as jnp
from jax import lax

from ..framework.tensor import Tensor, apply_op
from .env import get_rank, get_world_size

__all__ = ["ReduceOp", "all_reduce", "all_gather", "broadcast", "reduce",
           "scatter", "barrier", "split", "send", "recv", "alltoall",
           "reduce_scatter", "new_group", "wait", "shard_ctx",
           "current_axis", "get_group"]


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3


class Group:
    def __init__(self, rank, nranks, id=0, axis=None, ranks=None):
        self.rank = rank
        self.nranks = nranks
        self.id = id
        self.axis = axis  # mesh axis name this group maps onto
        self.ranks = ranks or list(range(nranks))

    @property
    def world_size(self):
        return self.nranks


_groups = {}


def new_group(ranks=None, backend=None, axis=None):
    gid = len(_groups) + 1
    g = Group(get_rank(), len(ranks) if ranks else get_world_size(), gid,
              axis=axis, ranks=ranks)
    _groups[gid] = g
    return g


def get_group(gid=0):
    return _groups.get(gid)


class _Ctx(threading.local):
    def __init__(self):
        self.axes: List[str] = []


_ctx = _Ctx()


@contextlib.contextmanager
def shard_ctx(*axes: str):
    """Marks an SPMD region (inside shard_map): collective calls bind to
    the innermost axis (or an explicit group's axis)."""
    _ctx.axes.extend(axes)
    try:
        yield
    finally:
        for _ in axes:
            _ctx.axes.pop()


def current_axis(group=None) -> Optional[str]:
    if group is not None and getattr(group, "axis", None):
        return group.axis
    return _ctx.axes[-1] if _ctx.axes else None


def _spmd(x, fn_axis, fallback, group=None):
    axis = current_axis(group)
    if axis is not None:
        return apply_op("collective", lambda v: fn_axis(v, axis), (x,), {})
    return fallback(x)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True,
               use_calc_stream=True):
    def on_axis(v, axis):
        if op == ReduceOp.SUM:
            return lax.psum(v, axis)
        if op == ReduceOp.MAX:
            return lax.pmax(v, axis)
        if op == ReduceOp.MIN:
            return lax.pmin(v, axis)
        return jnp.exp(lax.psum(jnp.log(v), axis))

    def eager(x):
        # 1-process group: the array already holds every shard this process
        # owns; SUM over group of size world_size==1 is identity.
        return x
    out = _spmd(tensor, on_axis, eager, group)
    if isinstance(tensor, Tensor) and not isinstance(out, Tensor):
        out = Tensor(out)
    tensor._value = out._value if isinstance(out, Tensor) else out
    return tensor


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    def on_axis(v, axis):
        return lax.all_gather(v, axis)

    axis = current_axis(group)
    if axis is not None:
        gathered = apply_op("c_allgather",
                            lambda v: lax.all_gather(v, axis), (tensor,), {})
        if isinstance(tensor_list, list):
            n = gathered.shape[0]
            for i in range(n):
                tensor_list.append(gathered[i])
        return gathered
    tensor_list.append(tensor)
    return tensor


def broadcast(tensor, src=0, group=None, sync_op=True):
    axis = current_axis(group)
    if axis is not None:
        def impl(v):
            # select src's value on every member of the axis
            full = lax.all_gather(v, axis)
            return full[src]
        out = apply_op("c_broadcast", impl, (tensor,), {})
        tensor._value = out._value
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    return all_reduce(tensor, op, group, sync_op)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    axis = current_axis(group)
    if axis is not None and tensor_list:
        from ..ops.manipulation import stack
        stacked = stack(tensor_list, axis=0)

        def impl(v):
            idx = lax.axis_index(axis)
            return lax.dynamic_index_in_dim(v, idx, 0, keepdims=False)
        out = apply_op("c_scatter", impl, (stacked,), {})
        tensor._value = out._value
        return tensor
    if tensor_list:
        tensor._value = tensor_list[src]._value
    return tensor


def reduce_scatter(tensor, input_list_or_tensor, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    axis = current_axis(group)
    src = input_list_or_tensor
    if isinstance(src, (list, tuple)):
        from ..ops.manipulation import concat
        src = concat(list(src), axis=0)
    if axis is not None:
        def impl(v):
            return lax.psum_scatter(v, axis, scatter_dimension=0,
                                    tiled=True)
        out = apply_op("c_reducescatter", impl, (src,), {})
        tensor._value = out._value
        return tensor
    tensor._value = src._value
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    axis = current_axis(group)
    from ..ops.manipulation import stack
    x = (stack(in_tensor_list, axis=0)
         if isinstance(in_tensor_list, (list, tuple)) else in_tensor_list)
    if axis is not None:
        def impl(v):
            return lax.all_to_all(v, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
        out = apply_op("c_alltoall", impl, (x,), {})
    else:
        out = x
    if isinstance(out_tensor_list, list):
        for i in range(out.shape[0]):
            out_tensor_list.append(out[i])
    return out


def send(tensor, dst=0, group=None, sync_op=True):
    """P2P send (reference send_v2, pipeline edges). In SPMD this is a
    ppermute; exposed mainly for the pipeline schedule."""
    axis = current_axis(group)
    if axis is None:
        return tensor
    n = get_world_size()

    def impl(v):
        sz = lax.axis_size(axis)
        perm = [(i, (i + 1) % sz) for i in range(sz)]
        return lax.ppermute(v, axis, perm)
    out = apply_op("send_v2", impl, (tensor,), {})
    return out


def recv(tensor, src=0, group=None, sync_op=True):
    return tensor


def barrier(group=None):
    axis = current_axis(group)
    if axis is not None:
        one = Tensor(jnp.ones(()))
        all_reduce(one, group=group)
    return None


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor):
        tensor._value.block_until_ready()


# ---------------------------------------------------------------------------
# tensor-parallel `split` (reference `distributed/collective.py:566`)
# ---------------------------------------------------------------------------

def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """Build a tensor-parallel layer (parallel embedding / row|col linear).
    TPU-native: returns a layer whose weights carry GSPMD partition specs
    over the 'mp' axis — forward code stays dense; XLA partitions it."""
    from .tensor_parallel import (ColumnParallelLinear, RowParallelLinear,
                                  VocabParallelEmbedding)
    if operation == "embedding":
        layer = VocabParallelEmbedding(size[0], size[1],
                                       weight_attr=weight_attr)
    elif operation == "linear" and axis == 0:
        layer = RowParallelLinear(size[0], size[1], weight_attr=weight_attr,
                                  bias_attr=bias_attr)
    elif operation == "linear":
        layer = ColumnParallelLinear(size[0], size[1],
                                     weight_attr=weight_attr,
                                     bias_attr=bias_attr,
                                     gather_output=gather_out)
    else:
        raise ValueError(f"unsupported split operation {operation}")
    return layer(x)
