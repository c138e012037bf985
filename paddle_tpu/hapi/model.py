"""High-level Model API (reference `python/paddle/hapi/model.py:810`:
Model.fit:1299 / evaluate / predict / save:1043, dual Static/Dynamic
adapters :224/:609).

TPU-native: ONE adapter — the functional train step. prepare() captures
the network functionally; fit() drives a jax.jit-compiled
carry -> carry step — forward, backward and the optimizer update fused
into a single XLA program per input signature (what the reference needs
CompiledProgram + ParallelExecutor for). When fleet is initialized the
same step is pjit'ed over the device mesh (see distributed/fleet).

Training hot-loop contract (the zero-copy / async-dispatch design):

* The whole model state — (params, buffers, opt_state) — travels as ONE
  donated carry pytree: `jax.jit(step, donate_argnums=(0,))`. XLA updates
  parameters in place; no second copy of the model state is allocated per
  step (mirrors parallel/spmd.py and parallel/pipeline.py donation).
  `FLAGS_train_step_donate=0` turns donation off for A/B checks.
* While a fit() epoch is running, `Tensor._value` on the network is STALE
  (the donated buffers are consumed). The carry is written back by
  `_sync_carry()` on epoch boundaries, save(), load(), parameters(),
  summary() — eval/predict read the live carry directly without a flush.
  Standalone train_batch calls (custom loops, outside fit) write back
  every call, preserving the public contract that direct Layer reads —
  net(x), state_dict() — stay fresh.
* `train_batch` returns a device-resident DeferredScalar loss; fit() only
  forces host floats every `log_freq` steps, so the Python loop runs ahead
  of the accelerator (async dispatch) instead of blocking every batch.
  CAVEAT: prepared Metrics update on host (`_update_metrics` pulls the
  step outputs with np.asarray), so a model with metrics still syncs once
  per batch — the deferred-sync win currently applies to metric-less
  training; moving metric accumulation into the jitted step is the
  follow-up that lifts this.
* Input batches are staged onto the device one step ahead by
  io.DeviceFeeder (double buffer) when the DataLoader has
  `use_buffer_reader=True` (the default). Under fleet the feeder gets the
  mesh's batch placement (parallel.spmd.batch_placement), so each batch
  lands directly in its dp/sp-sharded layout and the sharded step's
  synchronous per-step device_put disappears (STAT_sharded_batch_puts
  stays flat).
* The fleet path keeps `_sharded_state` device-resident across fit steps
  exactly like the single-device donated carry: `write_back` to the
  network's Tensors runs on epoch boundaries / save / load / parameters
  only (STAT_sharded_carry_syncs), with the same poisoned-carry
  validation. `FLAGS_train_step_donate=0` restores per-step write-back.
* `FLAGS_train_tail_bucketing` (default on): with `drop_last=False` the
  last partial batch is padded up to the loader's batch size (rows
  replicated from the last real sample) and a row mask is folded into the
  loss mean, so the tail reuses the full-batch executable — exactly one
  train-step compile per epoch instead of one per tail shape. The mask
  zero-weights padded rows and divides by the real-row count; per-row
  losses on the real rows are untouched (requires a row-independent
  forward — the serving engine's contract — and a loss that reduces
  rows by mean/sum; otherwise the model falls back to the unpadded step
  once and warns). eval_batch/predict_batch share the same padding so
  their per-exact-shape jit caches stop growing one entry per tail shape.
* Sequence packing (io.packing.PackingCollator as the loader's
  collate_fn, marked by `emits_token_mask`): batches arrive as
  fixed-shape packs whose last leaf is a [rows, max_tokens] token
  validity mask. fit/evaluate pop it and fold it into the loss as a
  TOKEN mask — per-token losses normalize by real tokens only — while
  the network masks attention per segment
  (F.scaled_dot_product_attention(segment_ids=...) → splash kernel).
  The row-mask tail machinery is bypassed: a short tail is just a pack
  with more masked tokens, so one-compile-per-epoch carries over and a
  batch is never double-masked.

Monitor counters (framework/monitor.py): STAT_train_steps,
STAT_train_step_compiles (one per input-shape key),
STAT_train_host_syncs (DeferredScalar materializations),
STAT_sharded_carry_syncs (fleet write-backs),
STAT_tail_pad_batches / STAT_tail_pad_compiles_avoided (tail bucketing).
"""
from __future__ import annotations

import os
import pickle
import warnings
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import random as frandom
from ..framework.deferred import DeferredScalar, materialize_many
from ..framework.flags import flag
from ..framework.functional import functionalize, get_buffers, get_params
from ..framework.monitor import STAT_ADD, STAT_SUB, stat_get
from ..framework.tensor import Tensor
from ..io import DataLoader, Dataset
from ..io.device_loader import DeviceFeeder
from ..metric import Metric
from ..profiler import device_telemetry, flight_recorder, step_log
from . import callbacks as cbks_mod

__all__ = ["Model"]


_NO_BATCH = object()     # the fit loop's end-of-epoch sentinel


def _flatten_batch(data):
    if isinstance(data, dict):
        return list(data.values())
    if isinstance(data, (list, tuple)):
        return list(data)
    return [data]


class _TailMaskError(TypeError):
    """The prepared loss cannot expose per-row values, so a padded tail's
    row mask cannot be folded into it (raised at trace time)."""


def _batch_rows(leaves):
    for x in leaves:
        v = x._value if isinstance(x, Tensor) else x
        if hasattr(v, "shape") and getattr(v, "ndim", 0) >= 1:
            return int(v.shape[0])
    return None


def _pad_leaf(x, rows, target):
    """Grow a batch-major leaf to `target` rows by replicating its last
    real row (a real sample: stays in-distribution and finite, unlike
    zeros which can be invalid labels)."""
    v = x._value if isinstance(x, Tensor) else x
    if not (hasattr(v, "shape") and getattr(v, "ndim", 0) >= 1
            and v.shape[0] == rows):
        return x
    v = jnp.asarray(v)
    v = jnp.concatenate([v, jnp.repeat(v[-1:], target - rows, axis=0)],
                        axis=0)
    return Tensor(v) if isinstance(x, Tensor) else v


def _real_rows(mask):
    """(padded_rows, real-row index array) for a loss mask. fit's own
    row masks are ones-prefixes, but loss_mask is a public train_batch/
    eval_batch parameter and may have holes. A token-level mask
    [rows, T] (packing) counts a row as real when ANY of its tokens is
    real — metrics then see whole packed rows, pad positions included
    (per-token metric masking is the packing contract's caveat)."""
    m = np.asarray(mask)
    if m.ndim > 1:
        m = (m.reshape(m.shape[0], -1) > 0).any(axis=1)
    return int(m.shape[0]), np.flatnonzero(m)


def _select_rows(leaves, padded_rows, idx):
    """Keep only the real rows of every batch-major leaf (host-side view
    for metrics / fallback reruns). A contiguous prefix uses a cheap
    slice; arbitrary masks gather by index."""
    n = len(idx)
    prefix = bool(np.array_equal(idx, np.arange(n)))
    out = []
    for x in leaves:
        v = x._value if isinstance(x, Tensor) else x
        if hasattr(v, "shape") and getattr(v, "ndim", 0) >= 1 and \
                v.shape[0] == padded_rows:
            sel = v[:n] if prefix else v[idx]
            out.append(Tensor(sel) if isinstance(x, Tensor) else sel)
        else:
            out.append(x)
    return out


def _steps_of(loader):
    """len(loader) or None — a generator has no __len__ and a DataLoader
    over an IterableDataset raises TypeError from its own; both mean the
    progress display falls back to countless mode."""
    if not hasattr(loader, "__len__"):
        return None
    try:
        return len(loader)
    except TypeError:
        return None


class Model:
    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics: List[Metric] = []
        self._amp_level = None
        self._apply_fn = None
        self._opt_state = None
        self._train_carry = None  # donated {params,buffers,opt_state} pytree
        self._in_fit = False  # fit() defers carry write-back to epoch ends
        self._sharded_state = None  # fleet device-resident donated carry
        self._sharded_dirty = False  # sharded state ahead of the Tensors
        self._sharded_mask_live = False  # trace-time: mask rides labels[-1]
        self._tail_maskable = True  # cleared once the loss refuses a mask
        self._mask_cache = {}  # (mask bytes, sharded) -> placed device mask
        self._train_step_cache = {}
        self._eval_step_cache = {}
        self._pred_step_cache = {}
        self.stop_training = False
        self._dist_ctx = None  # set by fleet.distributed_model

    # -- preparation --------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        if metrics is None:
            self._metrics = []
        elif isinstance(metrics, Metric):
            self._metrics = [metrics]
        else:
            self._metrics = list(metrics)
        if amp_configs is not None:
            self._amp_level = (amp_configs if isinstance(amp_configs, str)
                               else amp_configs.get("level", "O1"))
        self._apply_fn, _, _ = functionalize(self.network)
        if optimizer is not None and getattr(
                optimizer, "_parameter_list", None) is None:
            optimizer._parameter_list = self.network.parameters()
        # fleet-distributed: route training through the SPMD sharded step
        # (reference `hapi/model.py:165` prepare_distributed_context)
        try:
            from ..distributed.fleet import fleet as _fleet
            from ..parallel.mesh import get_mesh
            mesh = get_mesh()
            if _fleet._inited and mesh is not None and \
                    mesh.devices.size > 1:
                self._dist_ctx = _fleet
        except Exception:
            self._dist_ctx = None
        return self

    # -- internals ----------------------------------------------------------
    def _loss_value(self, outputs, labels):
        outs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
        if self._loss is None:
            # network returns the loss directly
            v = outs[0]
            return v
        if callable(self._loss):
            return self._loss(*outs, *labels)
        raise TypeError("loss must be callable")

    def _masked_loss(self, outputs, labels, mask):
        """User loss folded with a validity mask.

        A 1-D mask [rows] is the tail row mask: padded rows get zero
        weight and the mean divides by the real-row count, so the scalar
        equals the loss of the unpadded batch (for losses that reduce
        rows by mean/sum). A 2-D mask [rows, T] is a TOKEN mask (the
        packing collator's last leaf): the loss must expose per-token
        values [rows, T(, ...)], padded tokens get zero weight and the
        mean divides by the REAL-TOKEN count — per-token losses
        normalize by real tokens only, which is the packing contract.

        Losses with a `reduction` attribute are traced with
        reduction='none' to expose per-element values; a loss that only
        yields a scalar raises _TailMaskError at trace time and the
        caller falls back.

        CAVEAT (row masks only): a loss whose mean has a data-dependent
        denominator (e.g. cross_entropy with ignore_index labels
        present) is reduced here as a mean of per-row means, which
        weights rows uniformly instead of by valid-element count. Token
        masks don't have the problem — the denominator IS the
        valid-token count.
        """
        m = mask._value if isinstance(mask, Tensor) else mask
        red = getattr(self._loss, "reduction", None)
        if red in ("mean", "sum"):
            self._loss.reduction = "none"
            try:
                lv = self._loss_value(outputs, labels)
            finally:
                self._loss.reduction = red
        else:
            lv = self._loss_value(outputs, labels)
        lv_raw = (lv._value if isinstance(lv, Tensor) else lv)
        lv_raw = lv_raw.astype("float32")
        rows = int(m.shape[0])
        if m.ndim == 2:
            T = int(m.shape[1])
            if lv_raw.ndim < 2 or tuple(lv_raw.shape[:2]) != (rows, T):
                raise _TailMaskError(
                    f"loss produced shape "
                    f"{tuple(getattr(lv_raw, 'shape', ()))} — not "
                    f"per-token over the ({rows}, {T}) pack, so the "
                    "token mask cannot be folded in; packed training "
                    "needs a per-token-maskable loss (e.g. "
                    "CrossEntropyLoss over [rows, T, C] logits)")
            per_tok = lv_raw.reshape((rows, T, -1))
            per_tok = (per_tok.sum(axis=2) if red == "sum"
                       else per_tok.mean(axis=2))
            # where, not multiply: a non-finite pad-token value must not
            # poison the sum through NaN * 0
            per_tok = jnp.where(m > 0, per_tok, jnp.zeros_like(per_tok))
            if red == "sum":
                return jnp.sum(per_tok)
            return jnp.sum(per_tok) / jnp.maximum(
                jnp.sum(m.astype("float32")), 1.0)
        if lv_raw.ndim < 1 or lv_raw.shape[0] != rows:
            raise _TailMaskError(
                f"loss produced shape {tuple(getattr(lv_raw, 'shape', ()))}"
                f" — not per-row over the {rows}-row batch, so the tail "
                "row mask cannot be folded in; set "
                "FLAGS_train_tail_bucketing=0 or use a loss with a "
                "mean/sum `reduction`")
        per_row = lv_raw.reshape((rows, -1))
        per_row = (per_row.sum(axis=1) if red == "sum"
                   else per_row.mean(axis=1))
        # where, not multiply: a non-finite padded-row value must not
        # poison the sum through NaN * 0
        per_row = jnp.where(m > 0, per_row, jnp.zeros_like(per_row))
        if red == "sum":
            return jnp.sum(per_row)
        return jnp.sum(per_row) / jnp.sum(m.astype("float32"))

    def _make_train_step(self):
        apply_fn = self._apply_fn
        opt = self._optimizer
        amp_level = self._amp_level

        def loss_fn(pv, bv, rng, inputs, labels, mask):
            def fwd():
                wrapped_in = [Tensor(x) for x in inputs]
                wrapped_lb = [Tensor(x) for x in labels]
                # the step's phases as names on the device (`forward`,
                # `loss`, `optimizer`; the backward pass reads
                # `transpose(jvp(forward/...))` by the transform itself)
                with jax.named_scope("forward"):
                    out, new_bufs = apply_fn(
                        pv, bv, rng, True, *[w._value for w in wrapped_in])
                with jax.named_scope("loss"):
                    wout = jax.tree_util.tree_map(
                        lambda x: Tensor(x), out)
                    if mask is None:
                        lv = self._loss_value(wout, wrapped_lb)
                    else:
                        lv = self._masked_loss(wout, wrapped_lb, mask)
                return lv, (out, new_bufs)
            if amp_level:
                from .. import amp as amp_mod
                from ..framework.autograd import trace_mode
                with trace_mode(), amp_mod.auto_cast(level=amp_level):
                    lv, aux = fwd()
            else:
                from ..framework.autograd import trace_mode
                with trace_mode():
                    lv, aux = fwd()
            lv_raw = lv._value if isinstance(lv, Tensor) else lv
            return jnp.mean(lv_raw.astype("float32")), aux

        # the name is the program's: a trace reads `jit_train_step(...)`
        def train_step(carry, rng, step_no, lr, inputs, labels, mask=None):
            pv, bv, opt_state = (carry["params"], carry["buffers"],
                                 carry["opt_state"])
            (lv, (out, new_bufs)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(pv, bv, rng, inputs, labels, mask)
            with jax.named_scope("optimizer"):
                new_pv, new_state = opt.apply_gradients_pytree(
                    grads, pv, opt_state, lr, step_no)
            return {"params": new_pv, "buffers": new_bufs,
                    "opt_state": new_state}, lv, out
        return train_step

    # -- carry management ----------------------------------------------------
    def _ensure_carry(self):
        """Device-resident {params, buffers, opt_state} pytree that the
        donated train step consumes and reproduces each step."""
        if self._train_carry is None:
            pv = {n: t._value
                  for n, t in get_params(self.network).items()}
            bv = {n: t._value
                  for n, t in get_buffers(self.network).items()}
            if self._opt_state is None:
                self._opt_state = self._optimizer.init_state_pytree(pv)
            self._train_carry = {"params": pv, "buffers": bv,
                                 "opt_state": self._opt_state}
        return self._train_carry

    def _sync_carry(self, validate=False):
        """Write the training carry back into the network's Tensors.

        Called on epoch boundaries, save(), load() and parameters() —
        NOT per step. After the first donated step of an epoch the
        Tensors' old buffers are consumed; anything that reads
        `Tensor._value` directly mid-epoch must flush through here first.

        `validate=True` (epoch boundaries and fit's error path) blocks
        until the carry is ready and DROPS it if the device computation
        failed: with async dispatch a step's XLA error surfaces at a
        later host sync, after the poisoned output carry was already
        installed — writing it back would leave the network's Tensors
        re-raising the XLA error on every read. NOTE: with donation
        active the Tensors' pre-epoch buffers were consumed by the first
        step, so after a drop the model state is NOT recoverable from the
        live network (reads raise "Array has been deleted") — recovery is
        via ModelCheckpoint epoch saves, which flush to host files. With
        FLAGS_train_step_donate=0 the Tensors keep valid pre-carry values.
        """
        self._sync_sharded_carry(validate=validate)
        carry = self._train_carry
        if carry is None:
            return
        if validate:
            try:
                jax.block_until_ready(jax.tree_util.tree_leaves(carry))
            except Exception as e:
                # device-side failure only (XLA runtime errors are
                # Exception subclasses): drop the poisoned carry.
                # KeyboardInterrupt/SystemExit propagate with the carry
                # kept installed — it is healthy, and a later
                # _sync_carry() still writes it back.
                self._train_carry = None
                self._opt_state = None  # rode the same poisoned step
                # the raised error says WHAT failed; the flight record
                # keeps the step/feeder timeline + counters around WHEN
                flight_recorder.dump("poisoned_carry", {
                    "error": repr(e),
                    "donate": bool(flag("FLAGS_train_step_donate")),
                    "train_steps": stat_get("STAT_train_steps")})
                return
        for n, t in get_params(self.network).items():
            t._value = carry["params"][n]
        for n, t in get_buffers(self.network).items():
            t._value = carry["buffers"][n]
        self._opt_state = carry["opt_state"]
        self._train_carry = None

    def _sync_sharded_carry(self, validate=False):
        """Fleet analogue of the single-device carry flush: write the
        device-resident `_sharded_state` params/buffers back into the
        network's Tensors. Unlike the single-device carry the state stays
        live (it carries the sharded optimizer moments across epochs);
        only the dirty bit clears. Same poisoned-carry rule: with
        `validate` a state whose async step failed is DROPPED, not
        written back (recovery is via checkpoint saves — the donated
        pre-epoch buffers were already consumed)."""
        if not getattr(self, "_sharded_dirty", False):
            return
        state = self._sharded_state
        if validate:
            try:
                jax.block_until_ready(jax.tree_util.tree_leaves(state))
            except Exception as e:
                # poisoned: never write failed arrays into the Tensors.
                # With donation off the Tensors are still healthy, so a
                # rebuilt step can restart from them; with donation on
                # the pre-epoch buffers are consumed — the next sharded
                # step raises until a checkpoint is loaded.
                self._sharded_state = None
                self._sharded_dirty = False
                if not getattr(self, "_sharded_donate", True) and \
                        hasattr(self, "_sharded_step"):
                    del self._sharded_step
                flight_recorder.dump("poisoned_sharded_carry", {
                    "error": repr(e),
                    "donate": getattr(self, "_sharded_donate", True),
                    "train_steps": stat_get("STAT_train_steps")})
                return
        from ..parallel.spmd import write_back
        write_back(self.network, state)
        STAT_ADD("STAT_sharded_carry_syncs")
        self._sharded_dirty = False

    def _current_values(self):
        """(params, buffers) value dicts for eval/predict: the live carry
        when training is in flight (no flush — eval doesn't donate), else
        the network's Tensors."""
        carry = self._train_carry
        if carry is not None:
            return carry["params"], carry["buffers"]
        state = getattr(self, "_sharded_state", None)
        if state is not None and getattr(self, "_sharded_dirty", False):
            # sharded training in flight: Tensors are stale until the
            # epoch-boundary write_back — read the live carry directly
            return state["params"], state["buffers"]
        return ({n: t._value for n, t in get_params(self.network).items()},
                {n: t._value for n, t in get_buffers(self.network).items()})

    def _placed_mask(self, loss_mask):
        """Device-resident loss mask, cached per exact ROW-mask pattern.

        fit passes the same handful of row masks every epoch (all-ones
        per full batch, one tail pattern); caching their placement keeps
        the hot loop free of per-step host->device mask uploads — and on
        the fleet path the dp-sharded placement lets the step's
        pre-placed fast path skip the mask too. Keyed by the exact byte
        pattern: train_batch's loss_mask parameter is public, and two
        masks with the same population count need not select the same
        rows. Token-level masks [rows, T] (packing) differ on every
        batch — they are placed but NOT cached (a byte-keyed cache
        would grow one entry per batch forever); they ride to the
        device like any other batch leaf, and one that is ALREADY a
        device array (the DeviceFeeder staged it with the rest of the
        pack) passes straight through instead of a device→host→device
        round trip in the hot loop."""
        mv = loss_mask._value if isinstance(loss_mask, Tensor) else loss_mask
        if isinstance(mv, jax.Array) and getattr(mv, "ndim", 0) > 1:
            return mv if mv.dtype == jnp.float32 \
                else mv.astype(jnp.float32)
        m = np.ascontiguousarray(np.asarray(loss_mask, "float32"))
        sharded = self._dist_ctx is not None
        key = None
        if m.ndim == 1:
            key = (m.tobytes(), sharded)
            hit = self._mask_cache.get(key)
            if hit is not None:
                return hit
        arr = jnp.asarray(m, "float32")
        if sharded:
            from ..parallel.mesh import get_mesh
            from ..parallel.spmd import batch_placement
            mesh = get_mesh()
            if mesh is not None:
                # batch_placement leaves a row count that does not
                # divide dp unsharded instead of hard-failing device_put
                sh = batch_placement(mesh)(m)
                if sh is not None:
                    arr = jax.device_put(arr, sh)
        if key is not None:
            self._mask_cache[key] = arr
        return arr

    @staticmethod
    def _is_token_mask(loss_mask):
        m = loss_mask._value if isinstance(loss_mask, Tensor) else loss_mask
        return m is not None and getattr(m, "ndim", 1) > 1

    def _mask_fallback(self, inputs, labels, loss_mask):
        """A loss that cannot fold the tail row mask: warn once, pin the
        model to unpadded tails, and rerun this batch on its real rows.

        Row masks only — a TOKEN mask (packing) has no unpadded shape to
        fall back to (the pack IS the batch), so its _TailMaskError
        propagates: packed training requires a per-token-maskable loss."""
        if getattr(self, "_tail_maskable", True):
            self._tail_maskable = False
            warnings.warn(
                "FLAGS_train_tail_bucketing: the prepared loss does not "
                "expose per-row values; falling back to unpadded tail "
                "batches (one extra XLA compile per tail shape)",
                stacklevel=3)
        rows, idx = _real_rows(loss_mask)
        return (_select_rows(inputs, rows, idx),
                _select_rows(labels, rows, idx))

    def train_batch(self, inputs, labels=None, update=True, loss_mask=None):
        if self._dist_ctx is not None:
            return self._train_batch_sharded(inputs, labels,
                                             loss_mask=loss_mask)
        inputs = [t._value if isinstance(t, Tensor) else jnp.asarray(t)
                  for t in _flatten_batch(inputs)]
        labels = [t._value if isinstance(t, Tensor) else jnp.asarray(t)
                  for t in _flatten_batch(labels or [])]
        carry = self._ensure_carry()
        donate = bool(flag("FLAGS_train_step_donate"))
        mask = None if loss_mask is None else self._placed_mask(loss_mask)
        key = (donate,
               tuple((tuple(a.shape), str(a.dtype)) for a in inputs),
               tuple((tuple(a.shape), str(a.dtype)) for a in labels),
               None if mask is None else tuple(mask.shape))
        fn = self._train_step_cache.get(key)
        if fn is None:
            fn = jax.jit(self._make_train_step(),
                         donate_argnums=(0,) if donate else ())
            self._train_step_cache[key] = fn
            STAT_ADD("STAT_train_step_compiles")
        rng = frandom.get_rng_key()
        step_no = getattr(self, "_global_step", 0) + 1
        self._global_step = step_no
        try:
            new_carry, lv, out = fn(
                carry, rng, jnp.asarray(step_no, "int32"),
                jnp.asarray(self._optimizer.get_lr(), "float32"),
                tuple(inputs), tuple(labels), mask)
        except _TailMaskError:
            # trace-time failure: the carry was never dispatched into —
            # rerun the real rows through the plain (unpadded) step. The
            # evicted entry never produced an executable, so it does not
            # count against the compile budget either.
            if self._train_step_cache.pop(key, None) is not None:
                STAT_SUB("STAT_train_step_compiles")
            self._global_step = step_no - 1
            if self._is_token_mask(loss_mask):
                raise  # packing: no unpadded shape to fall back to
            ins, lbs = self._mask_fallback(inputs, labels, loss_mask)
            return self.train_batch(ins, lbs, update=update)
        except BaseException:
            # a step that died mid-call may have consumed the donated
            # carry (XLA error after dispatch). Keep the carry when its
            # buffers are intact (trace-time error, Ctrl-C before
            # dispatch, donation inactive) — that preserves the last
            # completed step — but drop it once consumed so the
            # epoch-boundary _sync_carry never writes deleted buffers
            # back into the network's Tensors.
            if any(getattr(leaf, "is_deleted", lambda: False)()
                   # lint: allow(use-after-donate): is_deleted() probes buffer liveness metadata without touching the (possibly deleted) data — detecting a consumed carry is this handler's whole purpose
                   for leaf in jax.tree_util.tree_leaves(carry)):
                self._train_carry = None
                self._opt_state = None  # its arrays rode the same donation
            raise
        self._train_carry = new_carry
        STAT_ADD("STAT_train_steps")
        if device_telemetry.active() and \
                key not in getattr(self, "_flops_noted_keys", ()):
            # estimated per-step FLOPs for the MFU gauge — HLO cost
            # analysis on the lowered module, no second backend compile;
            # new_carry shares the (possibly donated) carry's avals.
            # Keyed on the compile-cache key and gated on the sampler
            # being live, so telemetry enabled mid-training still gets
            # FLOPs on the next step while inactive processes never pay
            # the retrace.
            if not hasattr(self, "_flops_noted_keys"):
                self._flops_noted_keys = set()
            self._flops_noted_keys.add(key)
            device_telemetry.note_train_step_lowering(
                fn, (new_carry, rng, jnp.asarray(step_no, "int32"),
                     jnp.asarray(self._optimizer.get_lr(), "float32"),
                     tuple(inputs), tuple(labels), mask))
        if not self._in_fit:
            # public custom-loop contract: a standalone train_batch call
            # writes updated params back to the network's Tensors (cheap
            # reference stores), so direct Layer reads — net(x),
            # state_dict() — stay valid. Only fit() keeps the carry live
            # across steps.
            self._sync_carry()
        outs = jax.tree_util.tree_leaves(out)
        if loss_mask is not None and self._metrics and \
                not self._is_token_mask(loss_mask):
            # metrics must never see the masked-out rows. Token masks
            # (packing) skip this: metrics see whole packed rows by
            # contract (pad positions included — README caveat), and a
            # per-batch _real_rows would force a device->host copy of a
            # feeder-staged mask in the hot loop
            rows, idx = _real_rows(loss_mask)
            if len(idx) < rows:
                outs = _select_rows(outs, rows, idx)
                labels = _select_rows(labels, rows, idx)
        metrics = self._update_metrics(outs, labels)
        loss = DeferredScalar(lv)
        return (loss, metrics) if self._metrics else ([loss], metrics)

    def _train_batch_sharded(self, inputs, labels, loss_mask=None):
        """fleet path: one pjit'ed step over the mesh (dp/tp/zero per
        strategy). The state is a device-resident donated carry like the
        single-device path: inside fit it stays live across steps and is
        written back to the network's Tensors on epoch boundaries only
        (`_sync_sharded_carry`); standalone calls — and
        FLAGS_train_step_donate=0 — keep the per-call write-back
        contract. A padded tail's row mask rides along as an extra
        dp-sharded "label" so the pjit signature (and the single
        compiled executable) is shared with full batches."""
        donate = bool(flag("FLAGS_train_step_donate"))
        if not hasattr(self, "_sharded_step"):
            def loss_fn(outs, lbs):
                out = outs[0] if isinstance(outs, (list, tuple)) else outs
                if self._sharded_mask_live:
                    mask = lbs[-1]
                    lv = self._masked_loss(out, list(lbs[:-1]), mask)
                    return Tensor(lv)
                return self._loss_value(out, lbs)
            self._sharded_donate = donate
            self._sharded_step, self._sharded_state = \
                self._dist_ctx.build_sharded_train_step(
                    self.network, self._optimizer, loss_fn, donate=donate)
        if self._sharded_state is None:
            raise RuntimeError(
                "sharded training state was dropped after a failed step "
                "and the donated pre-epoch buffers are consumed; restore "
                "from a checkpoint (Model.load) before training on")
        ins = [t._value if isinstance(t, Tensor) else jnp.asarray(t)
               for t in _flatten_batch(inputs)]
        lbs = [t._value if isinstance(t, Tensor) else jnp.asarray(t)
               for t in _flatten_batch(labels or [])]
        if loss_mask is not None:
            lbs = lbs + [self._placed_mask(loss_mask)]
        # read at trace time by loss_fn; consistent because pjit retraces
        # exactly when the label structure (mask present/absent) changes
        self._sharded_mask_live = loss_mask is not None
        state = self._sharded_state
        try:
            new_state, lv = self._sharded_step(
                state, tuple(ins), tuple(lbs))
        except _TailMaskError:
            if self._is_token_mask(loss_mask):
                raise  # packing: no unpadded shape to fall back to
            ins, lbs = self._mask_fallback(ins, lbs[:-1], loss_mask)
            return self._train_batch_sharded(ins, lbs)
        except BaseException:
            # same donated-carry hygiene as the single-device path: a
            # step that consumed the donated state mid-failure must not
            # leave deleted buffers where the epoch-end write_back (or
            # the next step) will read them
            if any(getattr(leaf, "is_deleted", lambda: False)()
                   for leaf in jax.tree_util.tree_leaves(state)):
                self._sharded_state = None
                self._sharded_dirty = False
            raise
        self._sharded_state = new_state
        self._sharded_dirty = True
        STAT_ADD("STAT_train_steps")
        if not (self._in_fit and getattr(self, "_sharded_donate", donate)):
            # standalone contract / donation off: Tensors stay fresh
            self._sync_sharded_carry()
        loss = DeferredScalar(lv)
        return (loss, []) if self._metrics else ([loss], [])

    def eval_batch(self, inputs, labels=None, loss_mask=None):
        pv, bv = self._current_values()
        inputs = [t._value if isinstance(t, Tensor) else jnp.asarray(t)
                  for t in _flatten_batch(inputs)]
        labels = [t._value if isinstance(t, Tensor) else jnp.asarray(t)
                  for t in _flatten_batch(labels or [])]
        mask = None if loss_mask is None else self._placed_mask(loss_mask)
        key = (tuple((tuple(a.shape), str(a.dtype))
                     for a in inputs + labels),
               None if mask is None else tuple(mask.shape))
        fn = self._eval_step_cache.get(key)
        if fn is None:
            apply_fn = self._apply_fn

            def eval_step(pv_, bv_, rng, ins, lbs, mask_=None):
                from ..framework.autograd import trace_mode
                with jax.named_scope("forward"):
                    out, _ = apply_fn(pv_, bv_, rng, False, *ins)
                with trace_mode(), jax.named_scope("loss"):
                    wout = jax.tree_util.tree_map(lambda x: Tensor(x), out)
                    if self._loss is not None and lbs:
                        wlbs = [Tensor(x) for x in lbs]
                        lv = (self._loss_value(wout, wlbs) if mask_ is None
                              else Tensor(self._masked_loss(wout, wlbs,
                                                            mask_)))
                    else:
                        lv = None
                lv_raw = (jnp.mean(lv._value.astype("float32"))
                          if isinstance(lv, Tensor) else
                          (lv if lv is not None else jnp.zeros(())))
                return lv_raw, out
            fn = jax.jit(eval_step)
            self._eval_step_cache[key] = fn
        rng = frandom.get_rng_key()
        try:
            lv, out = fn(pv, bv, rng, tuple(inputs), tuple(labels), mask)
        except _TailMaskError:
            self._eval_step_cache.pop(key, None)
            if self._is_token_mask(loss_mask):
                raise  # packing: no unpadded shape to fall back to
            ins, lbs = self._mask_fallback(inputs, labels, loss_mask)
            return self.eval_batch(ins, lbs)
        outs = jax.tree_util.tree_leaves(out)
        if loss_mask is not None and not self._is_token_mask(loss_mask):
            # token masks skip row filtering — same contract and hot-loop
            # reasoning as train_batch above
            rows, idx = _real_rows(loss_mask)
            if len(idx) < rows:
                outs = _select_rows(outs, rows, idx)
                labels = _select_rows(labels, rows, idx)
        metrics = self._update_metrics(outs, labels)
        return DeferredScalar(lv), metrics

    def predict_batch(self, inputs, nreal=None):
        """`nreal` (tail bucketing): the batch was padded; only the first
        `nreal` output rows are returned — and the padded shape means the
        per-exact-shape jit cache gets no tail-shape entry."""
        pv, bv = self._current_values()
        inputs = [t._value if isinstance(t, Tensor) else jnp.asarray(t)
                  for t in _flatten_batch(inputs)]
        key = tuple((tuple(a.shape), str(a.dtype)) for a in inputs)
        fn = self._pred_step_cache.get(key)
        if fn is None:
            apply_fn = self._apply_fn
            fn = jax.jit(lambda pv_, bv_, rng, ins: apply_fn(
                pv_, bv_, rng, False, *ins)[0])
            self._pred_step_cache[key] = fn
        out = fn(pv, bv, frandom.get_rng_key(), tuple(inputs))
        rows = _batch_rows(inputs)
        out = jax.tree_util.tree_map(lambda x: np.asarray(x), out)
        if nreal is not None and rows is not None and nreal < rows:
            out = jax.tree_util.tree_map(
                lambda x: (x[:nreal] if (hasattr(x, "shape")
                                         and getattr(x, "ndim", 0) >= 1
                                         and x.shape[0] == rows) else x),
                out)
        return out

    def _update_metrics(self, outputs, labels):
        res = []
        for m in self._metrics:
            inp = m.compute(Tensor(outputs[0]),
                            *[Tensor(l) for l in labels])
            r = m.update(inp if not isinstance(inp, tuple) else inp[0])
            res.append(r)
        return res

    # -- loops --------------------------------------------------------------
    def _as_loader(self, data, batch_size, shuffle, num_workers, drop_last):
        if data is None or isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                              num_workers=num_workers, drop_last=drop_last)
        return data

    def _buffered(self, loader):
        """Wrap a DataLoader with the async DeviceFeeder double buffer
        (host->device transfer of batch N+1 overlaps batch N's compute)
        when the loader opted into buffering (`use_buffer_reader`).

        Under fleet the feeder gets the strategy's batch placement, so
        the background thread lays every batch directly into its
        dp/sp-sharded layout and the sharded step consumes it without a
        synchronous re-placement."""
        if isinstance(loader, DataLoader) and \
                getattr(loader, "use_buffer_reader", False):
            placement = None
            if self._dist_ctx is not None:
                try:
                    placement = self._dist_ctx.batch_placement()
                except Exception:
                    placement = None
            return DeviceFeeder(loader, device=placement)
        return loader

    def _token_masked(self, loader):
        """True when the loader's collator is a packing collator
        (io.packing.PackingCollator or anything with emits_token_mask):
        every batch's LAST leaf is a [rows, max_tokens] token validity
        mask that fit/evaluate pop off the labels and fold into the loss
        as a token-level mask. Packs are always full-shape — a short
        tail is just a pack with more masked tokens — so the row-mask
        tail machinery (_tail_target/_pad_tail) is bypassed entirely:
        one compiled step per epoch, and never BOTH masks on one batch.

        The model must be constructed with explicit `inputs=` specs so
        _split_batch knows how many leading pack leaves (tokens,
        segment_ids, position_ids, ...) feed the network."""
        cf = getattr(loader, "collate_fn", None)
        return bool(getattr(cf, "emits_token_mask", False))

    def _pop_token_mask(self, lbs):
        """Split the collator-emitted token mask off the label leaves.
        The mask stays whatever the feeder made it (host numpy or an
        already-placed device array) — never forced through the host
        here."""
        if not lbs:
            raise ValueError(
                "packing collator batches must carry at least the token "
                "mask after the input leaves — construct the Model with "
                "inputs= specs matching the pack layout")
        tm = lbs[-1]
        return lbs[:-1], (tm._value if isinstance(tm, Tensor) else tm)

    def _tail_target(self, loader, need_mask=True):
        """The loader's batch size when its epochs can actually produce a
        partial tail batch (unknown-length loaders count as "can"), else
        None. Gating on this keeps datasets that only ever emit full
        batches on the exact maskless step they always had — the masked
        reduction is numerically identical for row-uniform losses but
        weights rows (not valid elements) for losses with data-dependent
        denominators like cross_entropy ignore_index, so it must not be
        paid where it buys nothing. `need_mask=False` (predict: no loss,
        rows just sliced off the output) pads even when the prepared
        loss refused the mask."""
        if not flag("FLAGS_train_tail_bucketing"):
            return None
        if need_mask and not getattr(self, "_tail_maskable", True):
            return None
        bs = getattr(loader, "batch_size", None)
        if not bs:
            return None
        sampler = getattr(loader, "batch_sampler", None)
        if getattr(sampler, "drop_last", False):
            return None  # the sampler already drops the tail
        ds = getattr(loader, "dataset", None)
        if ds is not None and sampler is not None:
            try:
                if len(ds) % bs == 0:
                    return None  # every batch is full
            except TypeError:
                pass  # unsized dataset: a tail is possible
        return bs

    def _pad_tail(self, ins, lbs, target):
        """Tail bucketing: grow a partial batch to `target` rows and
        return (ins, lbs, row_mask, nreal). Full batches pass through
        with an all-ones mask (same jit signature -> same executable as
        the padded tail: exactly one train-step compile per epoch)."""
        rows = _batch_rows(ins + lbs)
        if rows is None:
            return ins, lbs, None, None
        if rows >= target:
            return ins, lbs, np.ones((rows,), "float32"), rows
        mask = np.zeros((target,), "float32")
        mask[:rows] = 1.0
        ins = [_pad_leaf(x, rows, target) for x in ins]
        lbs = [_pad_leaf(x, rows, target) for x in lbs]
        STAT_ADD("STAT_tail_pad_batches")
        return ins, lbs, mask, rows

    def _split_batch(self, batch):
        data = _flatten_batch(batch)
        n_in = len(self._inputs) if self._inputs else 1
        if len(data) == 1:
            return data, []
        return data[:n_in], data[n_in:]

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None):
        assert self._optimizer is not None, "call prepare() first"
        loader = self._as_loader(train_data, batch_size, shuffle, num_workers,
                                 drop_last)
        eval_loader = self._as_loader(eval_data, batch_size, False,
                                      num_workers, False)
        cbks = cbks_mod.config_callbacks(
            callbacks, model=self, epochs=epochs,
            steps=_steps_of(loader),
            log_freq=log_freq, save_freq=save_freq, save_dir=save_dir,
            verbose=verbose,
            metrics=["loss"] + [n for m in self._metrics
                                for n in (m.name() if isinstance(m.name(),
                                                                 list)
                                          else [m.name()])])
        cbks.on_begin("train")
        self.stop_training = False
        step_count = 0
        logs = {}  # stays bound for on_end even with epochs=0
        feed = self._buffered(loader)
        clock = step_log.FitClock()
        self._in_fit = True  # keep the carry live; write back at epoch ends
        flight_recorder.touch()  # periodic counter snapshots while training
        device_telemetry.touch()  # HBM/compile/MFU gauges while training
        try:
            for epoch in range(epochs):
                if hasattr(loader, "batch_sampler") and hasattr(
                        loader.batch_sampler, "set_epoch"):
                    loader.batch_sampler.set_epoch(epoch)
                cbks.on_epoch_begin(epoch)
                for m in self._metrics:
                    m.reset()
                logs = {}
                # tail bucketing: pad the drop_last=False partial batch
                # to the loader's batch size and fold a row mask into the
                # loss, so every batch of the epoch shares ONE compiled
                # step (the mask rides the signature even on full
                # batches; epochs that cannot produce a tail skip the
                # mask entirely and keep the plain step). A packing
                # collator replaces all of this with its own token mask:
                # packs are already fixed-shape, so the tail machinery
                # must stay OFF (no row padding, no double-masking).
                token_masked = self._token_masked(loader)
                pad_to = None if token_masked else self._tail_target(loader)
                # the step record (profiler/step_log.py FitRecord): each
                # stretch of the loop goes to a bucket, the waits under a
                # span on the profiler's clock, and `close` writes one
                # record a step whose buckets tile its wall exactly
                clock.restart()
                batches, step = iter(feed), -1
                while True:
                    with clock.span("input_wait_ms", "fit::input_wait"):
                        batch = next(batches, _NO_BATCH)
                    if batch is _NO_BATCH:
                        break
                    step += 1
                    with clock.span("callback_ms", "fit::callbacks"):
                        cbks.on_batch_begin("train", step, logs)
                    with clock.span("prep_ms"):
                        ins, lbs = self._split_batch(batch)
                        mask, nreal = None, None
                        if token_masked:
                            lbs, mask = self._pop_token_mask(lbs)
                        elif pad_to and self._tail_maskable:
                            # _tail_maskable re-checked per batch: a
                            # mid-epoch fallback stops the masked attempts
                            ins, lbs, mask, nreal = self._pad_tail(
                                ins, lbs, pad_to)
                    padded = not token_masked and mask is not None and \
                        nreal is not None and nreal < len(mask)
                    c0 = (stat_get("STAT_train_step_compiles") if padded
                          else 0)
                    # argument preparation and the launch (either step
                    # path); device time is in the jax.profiler trace
                    with clock.span("dispatch_ms", "fit::train_step"):
                        loss, metrics = self.train_batch(ins, lbs,
                                                         loss_mask=mask)
                    if padded and self._dist_ctx is None and \
                            stat_get("STAT_train_step_compiles") == c0:
                        # the padded tail rode an executable some full
                        # batch already compiled — the win this is for.
                        # (single-device only: pjit compiles are not
                        # observable through this counter, so the fleet
                        # path makes no claim here)
                        STAT_ADD("STAT_tail_pad_compiles_avoided")
                    lv = loss[0] if isinstance(loss, (list, tuple)) else loss
                    # deferred host sync: the loss stays a device handle
                    # except on the log cadence (one sync per log_freq),
                    # where the loop waits for the chip
                    if log_freq and step % log_freq == 0 and \
                            isinstance(lv, DeferredScalar):
                        with clock.span("sync_ms", "fit::sync"):
                            lv = float(lv)
                    logs = {"loss": lv, "step": step, "batch_size":
                            nreal if nreal is not None else
                            (ins[0].shape[0] if hasattr(ins[0], "shape")
                             else batch_size)}
                    for m, r in zip(self._metrics, metrics):
                        names = m.name() if isinstance(m.name(), list) else \
                            [m.name()]
                        vals = r if isinstance(r, list) else [r]
                        for n, v in zip(names, vals):
                            logs[n] = v
                    with clock.span("callback_ms", "fit::callbacks"):
                        cbks.on_batch_end("train", step, logs)
                    clock.close(step)
                    step_count += 1
                    if num_iters is not None and step_count >= num_iters:
                        self.stop_training = True
                        break
                del batches  # a feeder cut short stops its thread now
                # epoch boundary: params/opt state back into Tensors, loss
                # to a host float (callbacks may checkpoint / early-stop).
                # validate: an async step failure from the un-synced tail
                # of the epoch must not be written back as poisoned arrays
                self._sync_carry(validate=True)
                if isinstance(logs.get("loss"), DeferredScalar):
                    logs["loss"] = float(logs["loss"])
                # epoch-level metric accumulation
                for m in self._metrics:
                    names = m.name() if isinstance(m.name(), list) else \
                        [m.name()]
                    vals = m.accumulate()
                    vals = vals if isinstance(vals, list) else [vals]
                    for n, v in zip(names, vals):
                        logs[n] = v
                cbks.on_epoch_end(epoch, logs)
                if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                    self.evaluate(eval_loader, batch_size=batch_size,
                                  verbose=0, num_workers=num_workers,
                                  callbacks=None)
                if self.stop_training:
                    break
        except BaseException:
            # an async device failure surfaces at a deferred float() sync
            # or in a callback, AFTER train_batch installed the (possibly
            # poisoned) output carry — validate before write-back so the
            # network keeps its last synced values instead of arrays that
            # re-raise the XLA error on every read
            self._in_fit = False
            self._sync_carry(validate=True)
            try:
                # on_end still fires: VisualDL flushes its buffered
                # scalars; ModelCheckpoint's "final" save succeeds when
                # the carry survived (or donation is off) and fails
                # loudly-but-contained when donated state was consumed
                cbks.on_end("train", logs)
            except Exception:
                pass  # never mask the original error
            raise
        self._in_fit = False
        self._sync_carry()
        cbks.on_end("train", logs)
        return self

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_samples=None):
        loader = self._as_loader(eval_data, batch_size, False, num_workers,
                                 False)
        for m in self._metrics:
            m.reset()
        losses = []
        weights = []
        token_masked = self._token_masked(loader)
        pad_to = None if token_masked else self._tail_target(loader)
        for batch in self._buffered(loader):
            ins, lbs = self._split_batch(batch)
            mask = None
            if token_masked:
                lbs, mask = self._pop_token_mask(lbs)
                # each pack's loss is already real-token-normalized;
                # weight packs by their real-token count so the pass
                # mean is the TRUE per-token mean over the dataset (a
                # near-empty tail pack must not count like a full one).
                # A device-resident mask's count stays a deferred
                # handle — it rides the same single stacked transfer
                # as the losses below instead of a per-batch sync
                mv = mask._value if isinstance(mask, Tensor) else mask
                weights.append(DeferredScalar(jnp.sum(mv))
                               if isinstance(mv, jax.Array)
                               else float(np.asarray(mv).sum()))
            elif pad_to and self._tail_maskable:
                ins, lbs, mask, _ = self._pad_tail(ins, lbs, pad_to)
            lv, _ = self.eval_batch(ins, lbs, loss_mask=mask)
            losses.append(lv)
        # one device->host sync for the whole pass: every per-batch handle
        # rides a single stacked transfer (framework.deferred)
        vals = materialize_many(losses + weights)
        vals, weights = vals[:len(losses)], vals[len(losses):]
        if token_masked and vals and sum(weights) > 0:
            logs = {"loss": float(np.average(vals, weights=weights))}
        else:
            logs = {"loss": float(np.mean(vals)) if vals else 0.0}
        for m in self._metrics:
            names = m.name() if isinstance(m.name(), list) else [m.name()]
            vals = m.accumulate()
            vals = vals if isinstance(vals, list) else [vals]
            for n, v in zip(names, vals):
                logs[n] = v
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, verbose=1, callbacks=None):
        loader = self._as_loader(test_data, batch_size, False, num_workers,
                                 False)
        outputs = []
        # packing collators emit fixed-shape packs whose row count is
        # unrelated to the loader's sequences-per-pack batch_size — row
        # padding would corrupt them (and is never needed)
        pad_to = None if self._token_masked(loader) else \
            self._tail_target(loader, need_mask=False)
        for batch in self._buffered(loader):
            ins, _ = self._split_batch(batch)
            nreal = None
            if pad_to:
                rows = _batch_rows(ins)
                if rows is not None and rows < pad_to:
                    ins = [_pad_leaf(x, rows, pad_to) for x in ins]
                    nreal = rows
                    STAT_ADD("STAT_tail_pad_batches")
            outputs.append(self.predict_batch(ins, nreal=nreal))
        if stack_outputs and outputs:
            if isinstance(outputs[0], (list, tuple)):
                outputs = [np.concatenate([o[i] for o in outputs])
                           for i in range(len(outputs[0]))]
            else:
                outputs = np.concatenate(outputs)
        return outputs

    # -- persistence --------------------------------------------------------
    def save(self, path, training=True):
        from ..framework.io_state import save as psave
        self._sync_carry()
        if training:
            psave(self.network.state_dict(), path + ".pdparams")
            if self._optimizer is not None:
                opt_state = {"global_step": getattr(self, "_global_step", 0)}
                if self._opt_state is not None:
                    opt_state["state"] = jax.tree_util.tree_map(
                        lambda x: np.asarray(x), self._opt_state)
                psave(opt_state, path + ".pdopt")
        else:
            from .. import jit as pjit
            specs = self._inputs
            pjit.save(self.network, path, input_spec=specs)

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework.io_state import load as pload
        self._train_carry = None  # loaded values supersede any live carry
        # the sharded step closed over the pre-load param placements;
        # rebuild it (and its state) from the freshly loaded Tensors
        self._sharded_state = None
        self._sharded_dirty = False
        if hasattr(self, "_sharded_step"):
            del self._sharded_step
        state = pload(path + ".pdparams")
        self.network.set_state_dict(state)
        opt_path = path + ".pdopt"
        if not reset_optimizer and os.path.exists(opt_path):
            opt_state = pload(opt_path)
            self._global_step = opt_state.get("global_step", 0)
            # no "state" key (checkpoint saved before any step) must still
            # drop the previous run's moments, not keep them
            self._opt_state = (jax.tree_util.tree_map(
                lambda x: jnp.asarray(x), opt_state["state"])
                if "state" in opt_state else None)
        else:
            # actually reset: otherwise _ensure_carry would resume with the
            # previous run's optimizer moments against the loaded weights
            self._opt_state = None
            self._global_step = 0
        return self

    def parameters(self, *args, **kwargs):
        self._sync_carry()  # expose fresh values, not donated buffers
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        from .model_summary import summary
        self._sync_carry()  # summary forwards through Tensor._value
        return summary(self.network, input_size, dtype)
