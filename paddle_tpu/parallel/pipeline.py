"""Pipeline parallelism (reference: PipelineOptimizer
`fluid/optimizer.py:3718` + `fleet/meta_optimizers/pipeline_optimizer.py`
+ `framework/section_worker.cc:49-105` F-then-B microbatch schedule over
send_v2/recv_v2).

TPU-native redesign: stages live on the 'pp' mesh axis under shard_map;
stage parameters are STACKED on a leading pp-sharded axis (each device
holds its stage's slice), activations flow around the ring with
lax.ppermute, and the GPipe F-then-B schedule is a lax.fori_loop over
micro-steps. XLA overlaps the ppermute with stage compute (the analogue of
the reference's separate comm stream).

Requires homogeneous stages (same params/activation shapes per stage) —
the standard TPU formulation for transformer stacks.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["gpipe_spmd", "pipeline_forward", "partition_blocks",
           "make_pipeline_train_step"]


def pipeline_forward(stage_fn: Callable, stage_params, x, *, axis_name="pp",
                     n_micro: int):
    """Run inside shard_map over `axis_name`.

    stage_fn(params, micro_x) -> micro_y : one stage's forward.
    stage_params: THIS device's stage params (unstacked leaves).
    x: [n_micro, mb, ...] microbatched input, replicated across pp
       (only stage 0's reads matter).
    Returns [n_micro, mb, ...] outputs valid on the LAST stage.

    GPipe forward schedule: at step t, device d processes microbatch
    t - d (if in range); activations hop d→d+1 each step. Total steps =
    n_micro + pp - 1.
    """
    pp = lax.axis_size(axis_name)
    d = lax.axis_index(axis_name)
    steps = n_micro + pp - 1
    mb_shape = x.shape[1:]

    perm = [(i, (i + 1) % pp) for i in range(pp)]

    def body(t, carry):
        buf_in, outs = carry
        # stage 0 injects microbatch t (if valid); others use ring input
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        inject = lax.dynamic_index_in_dim(x, mb_idx, 0, keepdims=False)
        cur = jnp.where(d == 0, inject, buf_in)
        my_mb = t - d  # which microbatch this device processes now
        active = (my_mb >= 0) & (my_mb < n_micro)
        y = stage_fn(stage_params, cur)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # last stage stores result
        out_idx = jnp.clip(my_mb, 0, n_micro - 1)
        store = (d == pp - 1) & active
        prev = lax.dynamic_index_in_dim(outs, out_idx, 0, keepdims=False)
        outs = lax.dynamic_update_index_in_dim(
            outs, jnp.where(store, y, prev), out_idx, 0)
        nxt = lax.ppermute(y, axis_name, perm)
        return nxt, outs

    buf0 = jnp.zeros(mb_shape, x.dtype)
    outs0 = jnp.zeros((n_micro,) + mb_shape, x.dtype)
    _, outs = lax.fori_loop(0, steps, body, (buf0, outs0))
    return outs[None]  # [1, n_micro, ...] per stage; caller takes [-1]


def gpipe_spmd(stage_fn: Callable, mesh, n_micro: int, axis_name="pp"):
    """Wrap a homogeneous stage function into a pipelined forward over the
    mesh's pp axis.

    Usage:
      fwd = gpipe_spmd(stage_fn, mesh, n_micro=4)
      y = fwd(stacked_params, x)[-1]  # stacked_params leaves: [pp, ...]
                                      # x: [n_micro, mb, ...]
    Output is [pp, n_micro, ...]; index [-1] is the last stage's result.
    Gradients flow through ppermute (its transpose is the reverse
    permute), so jax.grad over this forward IS the backward schedule —
    the reference needs hand-inserted send/recv grad ops
    (`section_worker.cc`), here it's transposition.
    """
    inner = functools.partial(pipeline_forward, stage_fn,
                              axis_name=axis_name, n_micro=n_micro)

    def wrapper(stacked_params, x):
        def shard_fn(params_slice, x_rep):
            params_local = jax.tree_util.tree_map(
                lambda a: jnp.squeeze(a, 0), params_slice)
            return inner(params_local, x_rep)
        param_specs = jax.tree_util.tree_map(
            lambda _: P(axis_name), stacked_params)
        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=P(axis_name),
            check_vma=False)(stacked_params, x)
    return wrapper


# ---------------------------------------------------------------------------
# Heterogeneous pipeline: real models (embedding / blocks / head)
# ---------------------------------------------------------------------------
#
# Reference capability: PipelineOptimizer splits an arbitrary Program by
# device_guard into stages run by PipelineTrainer/SectionWorker
# (`fluid/optimizer.py:3718`, `framework/section_worker.cc:49-105`).
#
# TPU-native redesign: the model declares (pre, blocks, post) sections via
# `pipeline_sections()`. The homogeneous block stack — where the FLOPs
# are — is pipelined over the 'pp' mesh axis (params stacked [pp, k, ...],
# activations hop with ppermute, GPipe microbatch schedule); the cheap
# bookends (embedding, final head) run SPMD on every device with normal
# dp/mp shardings, exactly like praxis-style TPU pipelining. Backward is
# jax.grad through the schedule (ppermute transposes to the reverse ring;
# the reference hand-inserts send/recv grad ops instead).

def partition_blocks(blocks, pp):
    """Stack an nn.LayerList of homogeneous blocks into pp pipeline
    stages of k = len(blocks)/pp blocks each.

    Returns (block_apply, stacked, k) where stacked leaves are
    [pp, k, *param_shape] and block_apply is the functionalized single
    block: block_apply(params, {}, rng, training, h) -> (h', bufs).
    """
    from ..framework.functional import functionalize, get_params
    L = len(blocks)
    if L % pp != 0:
        raise ValueError(f"{L} blocks not divisible into pp={pp} stages")
    k = L // pp
    block_apply, p0, b0 = functionalize(blocks[0])
    if b0:
        raise ValueError(
            "pipelined blocks must be buffer-free (running-stat layers "
            "like BatchNorm belong in the pre/post sections)")
    stacked = {}
    for name in p0:
        vals = [get_params(blocks[i])[name]._value for i in range(L)]
        stacked[name] = jnp.stack(
            [jnp.stack(vals[s * k:(s + 1) * k]) for s in range(pp)])
    return block_apply, stacked, k


def _make_stage_fn(block_apply, training):
    """One pipeline stage = scan over its k blocks. `key` must already be
    folded with (device, microbatch); the block index is folded here so
    every block gets a distinct dropout mask."""
    def stage_fn(params_k, h, key):
        def body(hh, idx_and_p):
            i, p_one = idx_and_p
            out, _ = block_apply(p_one, {}, jax.random.fold_in(key, i),
                                 training, hh)
            return out, None
        k_blocks = jax.tree_util.tree_leaves(params_k)[0].shape[0]
        h2, _ = lax.scan(body, h, (jnp.arange(k_blocks), params_k))
        return h2
    return stage_fn


def _hetero_pipeline_inner(block_apply, stage_params, x, rng, training,
                           axis_name, n_micro, recompute, schedule):
    """Inside shard_map: GPipe schedule over one stage of k blocks.

    stage_params: this device's stage, leaves [k, ...].
    x: [n_micro, mb_local, ...] microbatched activations (replicated
       over pp, sharded over dp by the caller's in_specs).
    Returns [n_micro, mb_local, ...] — the LAST stage's outputs,
    replicated to every pp rank via a masked psum (its transpose routes
    the head's cotangents back to the last stage).
    """
    pp = lax.axis_size(axis_name)
    d = lax.axis_index(axis_name)
    steps = n_micro + pp - 1
    mb_shape = x.shape[1:]
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    stage_fn = _make_stage_fn(block_apply, training)

    if recompute:
        stage_fn = jax.checkpoint(stage_fn)

    def body(t, carry):
        buf_in, outs = carry
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        inject = lax.dynamic_index_in_dim(x, mb_idx, 0, keepdims=False)
        cur = jnp.where(d == 0, inject, buf_in)
        my_mb = t - d
        active = (my_mb >= 0) & (my_mb < n_micro)
        key_t = jax.random.fold_in(jax.random.fold_in(rng, d),
                                   jnp.clip(my_mb, 0, n_micro - 1))
        y = stage_fn(stage_params, cur, key_t)
        y = jnp.where(active, y, jnp.zeros_like(y))
        out_idx = jnp.clip(my_mb, 0, n_micro - 1)
        store = (d == pp - 1) & active
        prev = lax.dynamic_index_in_dim(outs, out_idx, 0, keepdims=False)
        outs = lax.dynamic_update_index_in_dim(
            outs, jnp.where(store, y, prev), out_idx, 0)
        nxt = lax.ppermute(y, axis_name, perm)
        return nxt, outs

    buf0 = jnp.zeros(mb_shape, x.dtype)
    outs0 = jnp.zeros((n_micro,) + mb_shape, x.dtype)
    _, outs = lax.fori_loop(0, steps, body, (buf0, outs0))
    # replicate the last stage's outputs across pp (masked psum; only the
    # last stage contributed non-zeros)
    return lax.psum(jnp.where(d == pp - 1, outs, jnp.zeros_like(outs)),
                    axis_name)


def make_pipeline_train_step(model, optimizer, loss_fn, *, n_micro,
                             mesh=None, pp_axis="pp", dp_axis="dp",
                             recompute=True, schedule="gpipe",
                             donate=True):
    """Build a jit'd pp×dp training step for a model exposing
    `pipeline_sections() -> (pre, blocks, post)`.

    Returns (step, state) with the same contract as
    `make_sharded_train_step`: state = {params, buffers, opt_state,
    step_no}; step(state, inputs, labels[, lr, rng]) -> (state, loss).
    Block-stack params live in state["params"] under "pp::<name>" keys,
    stacked [pp, k, ...] and sharded over the pp mesh axis.
    """
    from jax.sharding import NamedSharding
    from ..framework import random as frandom
    from ..framework.functional import functionalize
    from ..framework.tensor import Tensor
    from .. import nn as _nn
    from .mesh import get_mesh
    from .spmd import batch_sharding, param_sharding

    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    mesh = mesh or get_mesh()
    pp = mesh.shape[pp_axis]
    pre, blocks, post = model.pipeline_sections()

    class _Outer(_nn.Layer):
        def __init__(self):
            super().__init__()
            self.pre = pre
            self.post = post

    outer = _Outer()
    pre_apply, opv, obv = functionalize(
        outer, forward=lambda *a, **k: outer.pre(*a, **k))
    if schedule == "1f1b" and obv:
        # the manual-vjp 1F1B loop replays pre/post per microbatch and has
        # no way to thread buffer mutations through the schedule; refuse
        # loudly rather than silently serving stale running stats
        raise ValueError(
            "schedule='1f1b' requires buffer-free pre/post sections "
            f"(found buffers: {sorted(obv)}); use schedule='gpipe' or "
            "move running-stat layers out of the pipelined model")
    post_apply, _, _ = functionalize(
        outer, forward=lambda *a, **k: outer.post(*a, **k))
    block_apply, bpv, k = partition_blocks(blocks, pp)

    # -- shardings ----------------------------------------------------------
    o_shard = param_sharding(outer, mesh)
    opv = {n: jax.device_put(v, o_shard[n]) for n, v in opv.items()}
    repl = NamedSharding(mesh, P())
    obv = {n: jax.device_put(v, repl) for n, v in obv.items()}
    bp_shard = {n: NamedSharding(mesh, P(pp_axis))
                for n in bpv}
    bpv = {n: jax.device_put(v, bp_shard[n]) for n, v in bpv.items()}

    pv_all = {**opv, **{f"pp::{n}": v for n, v in bpv.items()}}
    pv_shard = {**o_shard, **{f"pp::{n}": bp_shard[n] for n in bpv}}
    opt_state = optimizer.init_state_pytree(pv_all)
    os_shard = {
        n: jax.tree_util.tree_map(
            lambda leaf: (pv_shard[n]
                          if getattr(leaf, "ndim", 0) == pv_all[n].ndim
                          else repl), st)
        for n, st in opt_state.items()}
    opt_state = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, s), opt_state, os_shard,
        is_leaf=lambda x: hasattr(x, "shape"))

    bp_specs = {n: P(pp_axis) for n in bpv}

    def pipelined(bpv_, x, rng, training):
        def shard_fn(bp_local, x_local, rng_):
            bp = jax.tree_util.tree_map(lambda a: jnp.squeeze(a, 0),
                                        bp_local)
            return _hetero_pipeline_inner(
                block_apply, bp, x_local, rng_, training, pp_axis,
                n_micro, recompute, schedule)
        x_spec = (P(None, dp_axis) if dp_axis in mesh.axis_names
                  else P())
        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(bp_specs, x_spec, P()),
            out_specs=x_spec,
            check_vma=False)(bpv_, x, rng)

    def loss_of(pv_all_, bv_, rng, inputs, labels):
        from ..framework.autograd import trace_mode
        opv_ = {n: pv_all_[n] for n in opv}
        bpv_ = {n: pv_all_[f"pp::{n}"] for n in bpv}
        h, pre_bufs = pre_apply(opv_, bv_, rng, True, *inputs)
        b = h.shape[0]
        dp = mesh.shape.get(dp_axis, 1)
        if b % (n_micro * dp) != 0:
            raise ValueError(
                f"global batch {b} must be divisible by "
                f"n_micro*dp = {n_micro}*{dp}")
        hm = h.reshape((n_micro, b // n_micro) + h.shape[1:])
        y = pipelined(bpv_, hm, rng, True)
        y = y.reshape((b,) + y.shape[2:])
        # thread pre-section buffer updates through post so running-stat
        # layers in either bookend section persist their mutations
        out, new_bufs = post_apply(opv_, pre_bufs, rng, True, y)
        with trace_mode():
            wout = jax.tree_util.tree_map(lambda v: Tensor(v), out)
            wlab = [Tensor(v) for v in labels]
            lv = loss_fn(wout, wlab)
        lv_raw = lv._value if isinstance(lv, Tensor) else lv
        return jnp.mean(lv_raw.astype("float32")), new_bufs

    pp_count = pp
    has_dp = dp_axis in mesh.axis_names and mesh.shape[dp_axis] > 1

    def grads_1f1b(pv_all_, bv_, rng, inputs, labels):
        """Manual-gradient 1F1B: returns (loss, grads dict) without
        jax.grad — activation stash capped at pp microbatches."""
        opv_ = {n: pv_all_[n] for n in opv}
        bpv_ = {n: pv_all_[f"pp::{n}"] for n in bpv}
        dp = mesh.shape.get(dp_axis, 1) if has_dp else 1
        b = inputs[0].shape[0]
        if b % (n_micro * dp) != 0:
            raise ValueError(
                f"global batch {b} must be divisible by "
                f"n_micro*dp = {n_micro}*{dp}")

        def micro(x):
            return x.reshape((n_micro, x.shape[0] // n_micro)
                             + x.shape[1:])

        ids_m = tuple(micro(x) for x in inputs)
        lab_m = tuple(micro(x) for x in labels)
        mb_spec = (P(None, dp_axis) if has_dp else P())

        def shard_fn(bp_local, opv_in, bv_in, ids_in, lab_in, rng_):
            bp = jax.tree_util.tree_map(lambda a: jnp.squeeze(a, 0),
                                        bp_local)
            loss, g_stage, g_outer = _one_f_one_b_inner(
                block_apply, pre_apply, post_apply, loss_fn, bp, opv_in,
                bv_in, ids_in, lab_in, rng_, pp_axis, n_micro, pp_count,
                dp_axis=dp_axis if has_dp else None)
            # restore the leading stage axis stripped by squeeze(0) above:
            # out_specs P(pp) concatenates per-shard leaves on axis 0, so
            # each shard must contribute [1, k, ...], not [k, ...]
            g_stage = jax.tree_util.tree_map(lambda g: g[None], g_stage)
            return loss, g_stage, g_outer

        loss, g_stage, g_outer = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(bp_specs, P(), P(),
                      tuple(mb_spec for _ in ids_m),
                      tuple(mb_spec for _ in lab_m), P()),
            out_specs=(P(), {n: P(pp_axis) for n in bpv}, P()),
            check_vma=False)(bpv_, opv_, bv_, ids_m, lab_m, rng)
        grads = {**g_outer, **{f"pp::{n}": g_stage[n] for n in g_stage}}
        return loss, grads

    def step_fn(state, inputs, labels, lr, rng):
        pv_, bv_, opt_state_, step_no = (state["params"], state["buffers"],
                                         state["opt_state"],
                                         state["step_no"])
        if schedule == "1f1b":
            lv, grads = grads_1f1b(pv_, bv_, rng, inputs, labels)
            new_bufs = bv_  # buffer mutation not tracked under 1f1b
        else:
            (lv, new_bufs), grads = jax.value_and_grad(
                loss_of, has_aux=True)(pv_, bv_, rng, inputs, labels)
        new_pv, new_opt = optimizer.apply_gradients_pytree(
            grads, pv_, opt_state_, lr, step_no + 1)
        return {"params": new_pv, "buffers": new_bufs,
                "opt_state": new_opt, "step_no": step_no + 1}, lv

    state_sharding = {"params": pv_shard, "buffers": {n: repl for n in obv},
                      "opt_state": os_shard, "step_no": repl}
    jit_step = jax.jit(step_fn, out_shardings=(state_sharding, repl),
                       donate_argnums=(0,) if donate else ())
    state = {"params": pv_all, "buffers": obv, "opt_state": opt_state,
             "step_no": jnp.zeros((), "int32")}

    def step(state, inputs, labels, lr=None, rng=None):
        inputs = tuple(
            jax.device_put(x._value if isinstance(x, Tensor)
                           else jnp.asarray(x),
                           batch_sharding(
                               np.ndim(x._value if isinstance(x, Tensor)
                                       else x), mesh, dp_axis))
            for x in inputs)
        labels = tuple(
            jax.device_put(x._value if isinstance(x, Tensor)
                           else jnp.asarray(x),
                           batch_sharding(
                               np.ndim(x._value if isinstance(x, Tensor)
                                       else x), mesh, dp_axis))
            for x in labels)
        lr = jnp.asarray(optimizer.get_lr() if lr is None else lr,
                         "float32")
        rng = rng if rng is not None else frandom.get_rng_key()
        return jit_step(state, inputs, labels, lr, rng)

    step.jitted = jit_step
    step.state_sharding = state_sharding
    return step, state


# ---------------------------------------------------------------------------
# 1F1B schedule (manual-gradient interleaved pipeline)
# ---------------------------------------------------------------------------
#
# Reference: SectionWorker's F-then-B is GPipe; Megatron-style 1F1B caps
# in-flight activations at pp instead of n_micro. Here the whole
# fwd+bwd+grad-accumulation runs as ONE SPMD loop with manual vjps —
# jax.grad is not used, so no AD residuals accumulate across the loop;
# the only activation storage is an x-stash of pp microbatch inputs.
#
# Schedule (derived; makespan-optimal 2*(n_micro+pp-1) half-steps):
#   device d runs F of microbatch m at step tau = d + 2m
#                 B of microbatch m at step tau = 2pp - 1 - d + 2m
# F and B slots have opposite parity per device (never collide), every
# ring hop lands exactly one step before its consumer, and in-flight
# microbatches never exceed pp (stash slot = m mod pp).

def _one_f_one_b_inner(block_apply, pre_apply, post_apply, loss_fn,
                       stage_params, opv, obv, ids_micro, labels_micro,
                       rng, axis_name, n_micro, pp, dp_axis=None):
    from ..framework.autograd import trace_mode
    from ..framework.tensor import Tensor

    d = lax.axis_index(axis_name)
    steps = 2 * (n_micro + pp - 1)
    perm_f = [(i, (i + 1) % pp) for i in range(pp)]
    perm_b = [(i, (i - 1) % pp) for i in range(pp)]

    stage_fn = _make_stage_fn(block_apply, True)

    def stage_key(m):
        return jax.random.fold_in(jax.random.fold_in(rng, d), m)

    def pre_of(m):
        xs = [lax.dynamic_index_in_dim(x, m, 0, keepdims=False)
              for x in ids_micro]
        out, _ = pre_apply(opv, obv, jax.random.fold_in(rng, m), True, *xs)
        return out

    def head_loss(opv_, y, labels_m, key):
        out, _ = post_apply(opv_, obv, key, True, y)
        with trace_mode():
            wout = jax.tree_util.tree_map(lambda v: Tensor(v), out)
            wlab = [Tensor(v) for v in labels_m]
            lv = loss_fn(wout, wlab)
        lv_raw = lv._value if isinstance(lv, Tensor) else lv
        return jnp.mean(lv_raw.astype("float32"))

    # probe shapes with abstract eval only
    act = jax.eval_shape(pre_of, 0)
    mb_shape, act_dtype = act.shape, act.dtype

    zeros_act = jnp.zeros(mb_shape, act_dtype)
    g_stage0 = jax.tree_util.tree_map(jnp.zeros_like, stage_params)
    g_outer0 = jax.tree_util.tree_map(jnp.zeros_like, opv)

    def f_branch(op):
        (tau, ring_f, ring_b, x_stash, y_prev, g_stage, g_outer,
         loss_acc) = op
        m_f = (tau - d) // 2
        m_safe = jnp.clip(m_f, 0, n_micro - 1)
        x_in = jnp.where(d == 0, pre_of(m_safe), ring_f)
        y = stage_fn(stage_params, x_in, stage_key(m_safe))
        x_stash = lax.dynamic_update_index_in_dim(
            x_stash, x_in, m_safe % pp, 0)
        y_prev = jnp.where(d == pp - 1, y, y_prev)
        return (y, jnp.zeros_like(ring_b), x_stash, y_prev,
                g_stage, g_outer, loss_acc)

    def b_branch(op):
        (tau, ring_f, ring_b, x_stash, y_prev, g_stage, g_outer,
         loss_acc) = op
        m_b = (tau - (2 * pp - 1 - d)) // 2
        m_safe = jnp.clip(m_b, 0, n_micro - 1)
        labels_m = [lax.dynamic_index_in_dim(l, m_safe, 0, keepdims=False)
                    for l in labels_micro]
        # cotangent into this stage's output: loss head on the last
        # stage (y from the previous step), ring hop elsewhere
        lv_m, (g_post, dy_head) = jax.value_and_grad(
            head_loss, argnums=(0, 1))(opv, y_prev, labels_m,
                                       jax.random.fold_in(rng, m_safe))
        dy = jnp.where(d == pp - 1, dy_head / n_micro, ring_b)
        x_in = lax.dynamic_index_in_dim(x_stash, m_safe % pp, 0,
                                        keepdims=False)
        key_m = stage_key(m_safe)
        _, stage_vjp = jax.vjp(
            lambda p, h: stage_fn(p, h, key_m), stage_params, x_in)
        dstage, dx = stage_vjp(dy)
        g_stage = jax.tree_util.tree_map(jnp.add, g_stage, dstage)
        # pre-section grads: replay pre's vjp with the stage-0 input
        # cotangent (non-zero contribution only on device 0)
        xs_m = [lax.dynamic_index_in_dim(x, m_safe, 0, keepdims=False)
                for x in ids_micro]
        _, pre_vjp = jax.vjp(
            lambda ov: pre_apply(ov, obv, jax.random.fold_in(rng, m_safe),
                                 True, *xs_m)[0], opv)
        (g_pre,) = pre_vjp(dx)
        is_first = (d == 0).astype("float32")
        is_last = (d == pp - 1).astype("float32")
        g_outer = jax.tree_util.tree_map(
            lambda g, a, b: g + is_first * a + is_last * b / n_micro,
            g_outer, g_pre, g_post)
        loss_acc = loss_acc + is_last * lv_m / n_micro
        return (jnp.zeros_like(ring_f), dx, x_stash, y_prev,
                g_stage, g_outer, loss_acc)

    def idle_branch(op):
        (tau, ring_f, ring_b, x_stash, y_prev, g_stage, g_outer,
         loss_acc) = op
        return (jnp.zeros_like(ring_f), jnp.zeros_like(ring_b), x_stash,
                y_prev, g_stage, g_outer, loss_acc)

    def body(tau, carry):
        ring_f, ring_b, x_stash, y_prev, g_stage, g_outer, loss_acc = carry
        mf2 = tau - d
        is_f = (mf2 % 2 == 0) & (mf2 >= 0) & (mf2 < 2 * n_micro)
        mb2 = tau - (2 * pp - 1 - d)
        is_b = (mb2 % 2 == 0) & (mb2 >= 0) & (mb2 < 2 * n_micro)
        idx = jnp.int32(0) + is_f.astype("int32") + 2 * is_b.astype("int32")
        op = (tau, ring_f, ring_b, x_stash, y_prev, g_stage, g_outer,
              loss_acc)
        (y_send, dx_send, x_stash, y_prev, g_stage, g_outer,
         loss_acc) = lax.switch(idx, [idle_branch, f_branch, b_branch], op)
        # collectives run unconditionally (identical program on all ranks)
        ring_f = lax.ppermute(y_send, axis_name, perm_f)
        ring_b = lax.ppermute(dx_send, axis_name, perm_b)
        return (ring_f, ring_b, x_stash, y_prev, g_stage, g_outer,
                loss_acc)

    x_stash0 = jnp.zeros((pp,) + mb_shape, act_dtype)
    carry = (zeros_act, zeros_act, x_stash0, zeros_act, g_stage0, g_outer0,
             jnp.zeros((), "float32"))
    carry = lax.fori_loop(0, steps, body, carry)
    _, _, _, _, g_stage, g_outer, loss_acc = carry
    # outer grads / loss live on one stage each — replicate across pp
    g_outer = lax.psum(g_outer, axis_name)
    loss = lax.psum(loss_acc, axis_name)
    if dp_axis is not None:
        g_stage = lax.pmean(g_stage, dp_axis)
        g_outer = lax.pmean(g_outer, dp_axis)
        loss = lax.pmean(loss, dp_axis)
    return loss, g_stage, g_outer
