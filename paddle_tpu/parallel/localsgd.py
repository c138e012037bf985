"""LocalSGD / adaptive LocalSGD and per-worker DGC as a shard_map step.

Reference: `fleet/meta_optimizers/localsgd_optimizer.py` (plain LocalSGD at
`:24`, adaptive at `:195` whose next-interval rule is
``k = sqrt(lr_0 * avg_loss / (lr * loss_0) * init_k)`` at `:422`) and
`dgc_optimizer.py:19`.

GSPMD cannot express "replicas that *diverge* between syncs" — it owns the
gradient allreduce.  So this builder drops down to `jax.shard_map` over the
'dp' mesh axis: every parameter / optimizer-state leaf carries a leading
replica axis sharded over 'dp', each worker runs an independent SGD
trajectory on its own batch shard (its own dropout rng, its own momentum),
and every ``k_steps`` the replicas are averaged with one `lax.pmean` over
ICI.  Between syncs NO parameter collective is issued — the actual point of
LocalSGD (comm every k steps instead of every step).

With ``dgc=True`` the step instead syncs every step, but each worker
top-k-masks its *local* gradient with error feedback before the explicit
`lax.psum` — the faithful per-worker DGC dataflow (see compression.py).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..framework import random as frandom
from ..framework.functional import functionalize
from ..framework.tensor import Tensor
from .compression import dgc_compress, dgc_init
from .mesh import get_mesh

__all__ = ["make_local_train_step", "local_write_back"]


def make_local_train_step(layer, optimizer, loss_fn: Callable, mesh=None,
                          k_steps=4, begin_step=1, adaptive=False,
                          max_k_steps=16, dgc=False, dgc_momentum=0.9,
                          dgc_sparsity=0.999, dp_axis="dp"):
    """Returns (step, state); same contract as make_sharded_train_step but
    params/opt-state/buffers carry a leading per-replica axis over 'dp'.

    state = {params, buffers, opt_state, dgc?, step_no, since_sync, k,
             loss0, lr0}; step(state, inputs, labels, lr, rng) ->
    (state, loss) with loss already averaged over replicas.
    """
    mesh = mesh or get_mesh()
    dp = int(mesh.shape[dp_axis])
    apply_fn, pv, bv = functionalize(layer)
    opt_state = optimizer.init_state_pytree(pv)

    def stack(v):
        return jnp.broadcast_to(v[None], (dp,) + v.shape)

    shd = NamedSharding(mesh, P(dp_axis))
    rep = NamedSharding(mesh, P())
    put_s = lambda t: jax.tree_util.tree_map(
        lambda v: jax.device_put(stack(v), shd), t)

    state = {
        "params": put_s(pv), "buffers": put_s(bv),
        "opt_state": put_s(opt_state),
        "step_no": jnp.zeros((), "int32"),
        "since_sync": jnp.zeros((), "int32"),
        "k": jnp.asarray(k_steps, "int32"),
        "loss0": jnp.zeros((), "float32"),
        "lr0": jnp.zeros((), "float32"),
    }
    if dgc:
        state["dgc"] = put_s(dgc_init(pv))

    def loss_of(pv_, bv_, rng, inputs, labels):
        from ..framework.autograd import trace_mode
        out, new_bufs = apply_fn(pv_, bv_, rng, True, *inputs)
        with trace_mode():
            wout = jax.tree_util.tree_map(lambda x: Tensor(x), out)
            wlab = [Tensor(x) for x in labels]
            lv = loss_fn(wout, wlab)
        lv_raw = lv._value if isinstance(lv, Tensor) else lv
        return jnp.mean(lv_raw.astype("float32")), new_bufs

    unblk = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
    reblk = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)

    def local_step(state_, inputs, labels, lr, rng):
        pv_ = unblk(state_["params"])
        bv_ = unblk(state_["buffers"])
        ov_ = unblk(state_["opt_state"])
        step_no = state_["step_no"]
        since = state_["since_sync"]
        k = state_["k"]
        widx = lax.axis_index(dp_axis)
        my_rng = jax.random.fold_in(rng, widx)

        (lv, new_bufs), grads = jax.value_and_grad(
            loss_of, has_aux=True)(pv_, bv_, my_rng, inputs, labels)
        avg_loss = lax.pmean(lv, dp_axis)

        new_state = dict(state_)
        if dgc:
            # per-worker top-k + error feedback, then explicit allreduce
            grads, new_dgc = dgc_compress(grads, unblk(state_["dgc"]),
                                          dgc_momentum, dgc_sparsity)
            grads = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, dp_axis), grads)
            new_state["dgc"] = reblk(new_dgc)

        new_pv, new_ov = optimizer.apply_gradients_pytree(
            grads, pv_, ov_, lr, step_no + 1)

        if not dgc:
            do_sync = jnp.logical_and(step_no + 1 >= begin_step,
                                      since + 1 >= k)
            new_pv = lax.cond(
                do_sync,
                lambda t: jax.tree_util.tree_map(
                    lambda p: lax.pmean(p, dp_axis), t),
                lambda t: t, new_pv)
            new_state["since_sync"] = jnp.where(do_sync, 0, since + 1)
            if adaptive:
                # first sync pins (loss0, lr0); later syncs rescale k
                first = state_["loss0"] <= 0.0
                loss0 = jnp.where(jnp.logical_and(do_sync, first),
                                  avg_loss, state_["loss0"])
                lr0 = jnp.where(jnp.logical_and(do_sync, first),
                                lr, state_["lr0"])
                next_k = jnp.ceil(jnp.sqrt(
                    lr0 * avg_loss / (lr * jnp.maximum(loss0, 1e-12))
                    * float(k_steps)))
                next_k = jnp.clip(next_k, 1, max_k_steps).astype("int32")
                new_state["k"] = jnp.where(
                    jnp.logical_and(do_sync, jnp.logical_not(first)),
                    next_k, k)
                new_state["loss0"] = loss0
                new_state["lr0"] = lr0

        new_state["params"] = reblk(new_pv)
        new_state["buffers"] = reblk(new_bufs)
        new_state["opt_state"] = reblk(new_ov)
        new_state["step_no"] = step_no + 1
        return new_state, avg_loss

    blk = lambda t: jax.tree_util.tree_map(lambda _: P(dp_axis), t)
    scalar = P()
    state_spec = {n: (blk(v) if n in ("params", "buffers", "opt_state",
                                      "dgc") else scalar)
                  for n, v in state.items()}

    def sharded(state_, inputs, labels, lr, rng):
        in_specs = (state_spec,
                    tuple(P(dp_axis) for _ in inputs),
                    tuple(P(dp_axis) for _ in labels), scalar, scalar)
        fn = jax.shard_map(local_step, mesh=mesh, in_specs=in_specs,
                           out_specs=(state_spec, scalar),
                           check_vma=False)
        return fn(state_, inputs, labels, lr, rng)

    jit_step = jax.jit(sharded, donate_argnums=(0,))

    def step(state_, inputs, labels, lr=None, rng=None):
        inputs = tuple(
            jax.device_put(x._value if isinstance(x, Tensor)
                           else jnp.asarray(x), shd) for x in inputs)
        labels = tuple(
            jax.device_put(x._value if isinstance(x, Tensor)
                           else jnp.asarray(x), shd) for x in labels)
        lr = jnp.asarray(optimizer.get_lr() if lr is None else lr,
                         "float32")
        rng = rng if rng is not None else frandom.get_rng_key()
        return jit_step(state_, inputs, labels, lr, rng)

    step.jitted = jit_step
    return step, state


def local_write_back(layer, state):
    """Average the per-replica params back into the imperative Layer."""
    from ..framework.functional import get_buffers, get_params
    params = get_params(layer)
    for n, v in state["params"].items():
        params[n]._value = jnp.mean(v, axis=0)
    buffers = get_buffers(layer)
    for n, v in state["buffers"].items():
        buffers[n]._value = jnp.mean(
            v, axis=0).astype(v.dtype) if jnp.issubdtype(
            v.dtype, jnp.floating) else v[0]
