"""SPMD sharded training step builder.

This is the TPU-native replacement for the whole reference multi-device
execution stack: ParallelExecutor's SSA graphs + allreduce op handles
(`framework/details/`), the dygraph Reducer (`imperative/reducer.cc`), the
sharding meta-optimizer (`fleet/meta_optimizers/sharding_optimizer.py`) and
TP split — collapsed into ONE function: lay params/opt-state/batch onto a
mesh with NamedShardings and jit the whole train step; XLA/GSPMD inserts
every collective (grad allreduce over 'dp', TP collectives over 'mp',
ZeRO gather/scatter over 'dp') on ICI.

Sharding rules:
  * params: honor `param.partition_spec` (set by TP layers / user), else
    replicated.
  * optimizer state (ZeRO-1/2, reference sharding_optimizer.py:33): each
    state leaf inherits the param spec, and — when zero_stage >= 1 — its
    largest unsharded divisible axis is additionally sharded over 'dp'.
  * batch: axis 0 over 'dp'; optional sequence axis over 'sp'.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..framework import random as frandom
from ..framework.functional import functionalize, get_buffers, get_params
from ..framework.monitor import STAT_ADD
from ..framework.tensor import Tensor
from .mesh import get_mesh

__all__ = ["param_sharding", "zero_sharding", "batch_sharding",
           "batch_placement", "make_sharded_train_step", "shard_params",
           "sharded_splash_attention", "tp_mesh"]


def tp_mesh(tp, axis="tp", devices=None):
    """A 1-D mesh of `tp` devices for tensor-parallel serving lanes.

    Takes the FIRST `tp` visible devices (a mesh-slice lane is a
    contiguous slice, and the router addresses whole engines, not
    devices). Raises if the host exposes fewer than `tp` devices.
    """
    from jax.sharding import Mesh
    devs = list(devices if devices is not None else jax.devices())
    if len(devs) < tp:
        raise RuntimeError(
            f"tp={tp} needs {tp} devices, host exposes {len(devs)} "
            f"(CPU smoke: XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={tp})")
    return Mesh(np.asarray(devs[:tp]), (axis,))


def _spec_of(param) -> PartitionSpec:
    return getattr(param, "partition_spec", None) or PartitionSpec()


def param_sharding(layer, mesh=None) -> Dict[str, NamedSharding]:
    mesh = mesh or get_mesh()
    out = {}
    for name, p in get_params(layer).items():
        spec = _spec_of(p)
        spec = _filter_spec(spec, mesh)
        out[name] = NamedSharding(mesh, spec)
    return out


def _filter_spec(spec, mesh):
    """Drop axes not present in the mesh (lets TP layers run on dp-only
    meshes unchanged)."""
    parts = []
    for s in tuple(spec):
        if s is None:
            parts.append(None)
        elif isinstance(s, str) and s in mesh.axis_names and \
                mesh.shape[s] > 1:
            parts.append(s)
        else:
            parts.append(None)
    return PartitionSpec(*parts)


def zero_sharding(layer, opt_state, mesh=None, zero_stage=1,
                  dp_axis="dp") -> Dict:
    """Sharding pytree for optimizer state (ZeRO over the dp axis)."""
    mesh = mesh or get_mesh()
    params = get_params(layer)
    dp = mesh.shape.get(dp_axis, 1) if dp_axis in mesh.axis_names else 1

    def one(name):
        p = params[name]
        base = tuple(_filter_spec(_spec_of(p), mesh))
        shape = tuple(p._value.shape)

        def leaf_sharding(leaf):
            if not hasattr(leaf, "shape") or leaf.ndim == 0:
                return NamedSharding(mesh, PartitionSpec())
            spec = list(base[:leaf.ndim]) + [None] * (leaf.ndim - len(base))
            if zero_stage >= 1 and dp > 1:
                for ax in np.argsort([-d for d in leaf.shape]):
                    ax = int(ax)
                    if spec[ax] is None and leaf.shape[ax] % dp == 0:
                        spec[ax] = dp_axis
                        break
            return NamedSharding(mesh, PartitionSpec(*spec))
        return leaf_sharding

    out = {}
    for name, st in opt_state.items():
        f = one(name)
        out[name] = jax.tree_util.tree_map(f, st)
    return out


def batch_sharding(ndim, mesh=None, dp_axis="dp", sp_axis=None,
                   seq_dim=1) -> NamedSharding:
    mesh = mesh or get_mesh()
    spec = [None] * ndim
    if dp_axis in mesh.axis_names and mesh.shape[dp_axis] > 1:
        spec[0] = dp_axis
    if sp_axis and sp_axis in mesh.axis_names and mesh.shape[sp_axis] > 1 \
            and ndim > seq_dim:
        spec[seq_dim] = sp_axis
    return NamedSharding(mesh, PartitionSpec(*spec))


def batch_placement(mesh=None, dp_axis="dp", sp_axis=None, seq_dim=1):
    """Per-leaf placement callable for io.DeviceFeeder: leaf -> the
    NamedSharding a training batch of that rank gets on `mesh`.

    Handing this to the feeder moves the batch split/upload onto the
    feeder thread, so the sharded train step receives arrays already in
    their dp/sp layout and skips its synchronous per-step device_put
    (the step's pre-placed fast path below). Every leaf — labels
    included — gets the same policy; GSPMD reshards inside the step if
    the computation wants a different layout.

    A dimension that does not divide its mesh axis is left unsharded
    (jax.device_put hard-fails on uneven shards). A leaf with no
    shardable dimension at all — e.g. the raw drop_last=False tail
    batch before Model.fit pads it — returns None: it stays on the
    default device and the step (or the padded re-placement) lays it
    out once it is even.
    """
    mesh = mesh or get_mesh()

    def place(x):
        sh = batch_sharding(np.ndim(x), mesh, dp_axis, sp_axis, seq_dim)
        shape = np.shape(x)
        spec = []
        for d, a in enumerate(tuple(sh.spec)):
            if a is not None and shape[d] % mesh.shape[a] != 0:
                a = None
            spec.append(a)
        if not any(s is not None for s in spec):
            return None
        return NamedSharding(mesh, PartitionSpec(*spec))

    return place


def _place_batch(x, mesh, dp_axis, sp_axis):
    """Lay one batch leaf onto the mesh — unless the feeder already did.

    An array that is committed to a NamedSharding on this mesh is consumed
    as-is (zero re-placement; STAT_sharded_batch_puts stays flat), which is
    what makes the sharding-aware DeviceFeeder a true overlap instead of a
    double transfer.
    """
    v = x._value if isinstance(x, Tensor) else x
    if not isinstance(v, jax.Array):
        v = jnp.asarray(v)
    sh = getattr(v, "sharding", None)
    if isinstance(sh, NamedSharding) and sh.mesh == mesh and \
            getattr(v, "committed", False):
        return v
    STAT_ADD("STAT_sharded_batch_puts")
    return jax.device_put(v, batch_sharding(np.ndim(v), mesh, dp_axis,
                                            sp_axis))


def shard_params(layer, mesh=None):
    """Physically lay the layer's parameters out on the mesh."""
    mesh = mesh or get_mesh()
    shardings = param_sharding(layer, mesh)
    for name, p in get_params(layer).items():
        p._value = jax.device_put(p._value, shardings[name])
    return shardings


def make_sharded_train_step(layer, optimizer, loss_fn: Callable,
                            mesh=None, zero_stage=1, dp_axis="dp",
                            sp_axis=None, recompute=False,
                            donate=True, grad_dtype=None,
                            dgc=False, dgc_momentum=0.9,
                            dgc_sparsity=0.999):
    """Returns (step, state) where
      state = {params, buffers, opt_state, step_no}
      step(state, inputs, labels, lr, rng) -> (state, loss)
    fully jit-compiled over the mesh with every parallelism expressed as
    shardings. `loss_fn(outputs, labels)` operates on framework Tensors.
    """
    mesh = mesh or get_mesh()
    apply_fn, pv, bv = functionalize(layer)
    p_shard = param_sharding(layer, mesh)
    pv = {n: jax.device_put(v, p_shard[n]) for n, v in pv.items()}
    repl = NamedSharding(mesh, PartitionSpec())
    bv = {n: jax.device_put(v, repl) for n, v in bv.items()}
    opt_state = optimizer.init_state_pytree(pv)
    o_shard = zero_sharding(layer, opt_state, mesh, zero_stage, dp_axis)
    opt_state = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, s), opt_state, o_shard,
        is_leaf=lambda x: hasattr(x, "shape"))

    if recompute:
        inner_apply = apply_fn

        def apply_remat(pv_, bv_, rng, training, *xs):
            def f(pv2, *xs2):
                return inner_apply(pv2, bv_, rng, training, *xs2)
            return jax.checkpoint(f)(pv_, *xs)
        fwd = apply_remat
    else:
        fwd = apply_fn

    def loss_of(pv_, bv_, rng, inputs, labels):
        from ..framework.autograd import trace_mode
        # the same phase names as the one-device step (hapi/model.py)
        with jax.named_scope("forward"):
            out, new_bufs = fwd(pv_, bv_, rng, True, *inputs)
        with trace_mode(), jax.named_scope("loss"):
            wout = jax.tree_util.tree_map(lambda x: Tensor(x), out)
            wlab = [Tensor(x) for x in labels]
            lv = loss_fn(wout, wlab)
        lv_raw = lv._value if isinstance(lv, Tensor) else lv
        return jnp.mean(lv_raw.astype("float32")), new_bufs

    def train_step(state, inputs, labels, lr, rng):
        pv_, bv_, opt_state_, step_no = (state["params"], state["buffers"],
                                         state["opt_state"],
                                         state["step_no"])
        (lv, new_bufs), grads = jax.value_and_grad(loss_of, has_aux=True)(
            pv_, bv_, rng, inputs, labels)
        new_dgc = None
        if dgc:
            # DGC on the global gradient: top-k + momentum correction +
            # error feedback (see compression.py for the dataflow note)
            from .compression import dgc_compress
            grads, new_dgc = dgc_compress(grads, state["dgc"],
                                          dgc_momentum, dgc_sparsity)
        if grad_dtype is not None:
            # fp16/bf16-allreduce strategy (reference
            # fp16_allreduce_optimizer.py): compress grads before the
            # (XLA-inserted) dp allreduce, restore for the update
            from ..framework.dtype import to_jax_dtype
            gd = to_jax_dtype(grad_dtype)
            grads = jax.tree_util.tree_map(
                lambda g, p: g.astype(gd).astype(p.dtype), grads, pv_)
        with jax.named_scope("optimizer"):
            new_pv, new_opt = optimizer.apply_gradients_pytree(
                grads, pv_, opt_state_, lr, step_no + 1)
        new_state = {"params": new_pv, "buffers": new_bufs,
                     "opt_state": new_opt, "step_no": step_no + 1}
        if new_dgc is not None:
            new_state["dgc"] = new_dgc
        return new_state, lv

    state_sharding = {
        "params": p_shard, "buffers": {n: repl for n in bv},
        "opt_state": o_shard, "step_no": repl,
    }
    if dgc:
        from .compression import dgc_init
        dgc_state = dgc_init(pv)
        dgc_shard = {n: {"u": p_shard[n], "v": p_shard[n]}
                     for n in dgc_state}
        dgc_state = jax.tree_util.tree_map(
            lambda v, s: jax.device_put(v, s), dgc_state, dgc_shard)
        state_sharding["dgc"] = dgc_shard
    jit_step = jax.jit(
        train_step,
        out_shardings=(state_sharding, repl),
        donate_argnums=(0,) if donate else ())

    state = {"params": pv, "buffers": bv, "opt_state": opt_state,
             "step_no": jnp.zeros((), "int32")}
    if dgc:
        state["dgc"] = dgc_state

    cost_noted = set()  # batch signatures whose FLOPs were estimated

    def step(state, inputs, labels, lr=None, rng=None):
        inputs = tuple(_place_batch(x, mesh, dp_axis, sp_axis)
                       for x in inputs)
        labels = tuple(_place_batch(x, mesh, dp_axis, None)
                       for x in labels)
        lr = jnp.asarray(optimizer.get_lr() if lr is None else lr,
                         "float32")
        rng = rng if rng is not None else frandom.get_rng_key()
        out = jit_step(state, inputs, labels, lr, rng)
        # per-step FLOPs for the MFU gauge, scaled by mesh size (the
        # cost analysis sees the global program; peak = per-device peak
        # x participating devices). Keyed per batch signature — jit_step
        # recompiles when batch shapes change and the gauge must track
        # the CURRENT step's cost, not the first-ever one — and gated on
        # the sampler being live, so telemetry enabled mid-training
        # still gets FLOPs while inactive processes never pay the
        # retrace. New state shares the donated input's avals so
        # lowering never touches consumed buffers.
        key = tuple((tuple(x.shape), str(x.dtype))
                    for x in inputs + labels)
        if key not in cost_noted:
            from ..profiler import device_telemetry
            if device_telemetry.active():
                cost_noted.add(key)
                device_telemetry.note_train_step_lowering(
                    jit_step, (out[0], inputs, labels, lr, rng),
                    n_devices=int(mesh.devices.size))
        return out

    step.jitted = jit_step
    step.state_sharding = state_sharding
    return step, state


def sharded_splash_attention(mesh=None, causal=False, scale=None,
                             dropout_p=0.0, dp_axis="dp"):
    """shard_map-wrapped splash attention for packed batches on a mesh.

    GSPMD cannot partition a pallas_call — under plain pjit the kernel
    would be gathered onto every device — so the kernel is wrapped in
    `shard_map` with the batch axis split over `dp_axis` and segment ids
    riding the same split (the SNIPPETS [1]/[3] pattern): each shard
    runs the kernel on its local rows only, which is exactly right
    because packing never creates cross-row attention.

    Returns f(q, k, v, q_seg, kv_seg, seed=None) with q/k/v
    [B, H, S, D] and segment ids [B, S] (B divisible by the dp degree).
    `scale` defaults to 1/sqrt(D) at call time. With dropout_p > 0 a
    fresh int32 seed is drawn per call from the framework RNG stream
    (pass `seed` explicitly for reproducible replay) — the seed is a
    traced argument, NOT baked into the jit, so every step gets a new
    keep mask.
    """
    from ..framework import random as frandom
    from ..ops.splash_ops import splash_attention_raw
    mesh = mesh or get_mesh()
    if mesh is None:
        raise RuntimeError("sharded_splash_attention needs a live mesh "
                           "(parallel.mesh.set_mesh / fleet.init)")
    dp = dp_axis if dp_axis in mesh.axis_names and \
        mesh.shape[dp_axis] > 1 else None
    qkv_spec = PartitionSpec(dp, None, None, None)
    seg_spec = PartitionSpec(dp, None)

    def call(q, k, v, q_seg, kv_seg, seed):
        sc = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
        if dp is not None and dropout_p > 0.0:
            # the kernel keys its keep mask on SHARD-LOCAL grid indices
            # (pl.program_id over the local batch*heads), so a replicated
            # seed would hand every dp shard the identical dropout
            # pattern — fold the shard index in for independent draws
            seed = seed + jax.lax.axis_index(dp)
        return splash_attention_raw(q, k, v, q_seg, kv_seg, seed, causal,
                                    sc, dropout_p)

    jitted = jax.jit(jax.shard_map(
        call, mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, seg_spec, seg_spec,
                  PartitionSpec()),
        out_specs=qkv_spec, check_vma=False))

    def f(q, k, v, q_seg, kv_seg, seed=None):
        if seed is None:
            if dropout_p > 0.0:
                seed = jax.random.randint(
                    frandom.get_rng_key(), (), 0,
                    np.int32(2 ** 31 - 1), dtype=jnp.int32)
            else:
                seed = jnp.zeros((), jnp.int32)
        return jitted(q, k, v, q_seg, kv_seg,
                      jnp.asarray(seed, jnp.int32))

    return f


def write_back(layer, state):
    """Copy trained param/buffer values back into the imperative Layer."""
    params = get_params(layer)
    for n, v in state["params"].items():
        params[n]._value = v
    buffers = get_buffers(layer)
    for n, v in state["buffers"].items():
        buffers[n]._value = v
