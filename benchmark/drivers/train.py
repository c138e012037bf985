"""Driver of kind `train`: a configuration through `paddle.Model.fit`, with
DataLoader workers and the DeviceFeeder, timed by a callback of the
benchmark's own.

`Model.fit` leaves an epoch early only through `num_iters`, so the window is
ended by the data: a first short fit compiles the step and probes its time
(set-up), and the second fit's dataset is sized from the probe to outlast
the window by a little. The clock starts at a forced loss and stops at the
forced loss of the last counted step; fit's own log cadence (`log_freq`, 10
as users run it) keeps the host at most that many steps ahead of the chip.
"""
import math
import multiprocessing
import time

import jax
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import fleet
from paddle_tpu.framework.monitor import stat_get

from benchmark import trace_reduce, trafficgen


def mlm_nsp_loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels):
    """Masked-LM cross-entropy over the labelled positions plus the NSP
    cross-entropy, from the program's own ops."""
    v = mlm_logits.shape[-1]
    return (F.cross_entropy(mlm_logits.reshape([-1, v]),
                            mlm_labels.reshape([-1]), ignore_index=-100)
            + F.cross_entropy(nsp_logits, nsp_labels))


class PretrainData(paddle.io.Dataset):
    """`steps` batches of seeded samples, made in the DataLoader workers."""

    def __init__(self, mix, cfg, seed, steps, offset):
        self.mix, self.seed, self.offset = mix, seed, offset
        self.n = steps * mix["sequences_per_step"]
        self.first = min(cfg["run"]["first_token_id"], cfg["vocab_size"] // 2)
        self.mask_id = min(cfg["run"]["mask_token_id"], self.first - 1)
        self.cdf = trafficgen.zipf_cdf(cfg["vocab_size"],
                                       mix["zipf_exponent"], self.first)

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return trafficgen.pretrain_sample(self.mix, self.cdf, self.first,
                                          self.mask_id, self.seed,
                                          self.offset + i)


class Window(paddle.callbacks.Callback):
    """Times the steps of one fit from outside the program.

    The clock starts when the loss of step `skip` has reached the host and
    stops when, `seconds` later (or at `last_step`, the last the data
    has), the loss of the then-current step has: `steps` steps lie between
    the two, all their work and all that time.
    With `trace_steps`, a profiler trace covers that many steps inside the
    window, between two forced losses of its own."""

    def __init__(self, skip, seconds, last_step=None, trace_dir=None,
                 trace_steps=0):
        super().__init__()
        self.skip, self.seconds, self.last_step = skip, seconds, last_step
        self.trace_dir, self.trace_steps = trace_dir, trace_steps
        self.t_start = self.t_end = None
        self.steps = 0
        self.losses = []
        self.traced = None      # steps inside the traced sub-window
        self.trace_cost_s = 0.0  # its time, starting and stopping included
        self._span = None

    def on_train_batch_end(self, step, logs=None):
        if self.t_end is not None or step < self.skip:
            return
        with jax.profiler.TraceAnnotation("bench:fit_callback"):
            loss = logs["loss"]
            if step == self.skip:
                self.losses.append(float(loss))
                self.t_start = time.perf_counter()
                return
            self.steps += 1
            if isinstance(loss, float):      # fit forced it: log cadence
                self.losses.append(loss)
            if self.trace_dir and self.traced is None:
                self._trace(loss)
            if time.perf_counter() - self.t_start >= self.seconds \
                    or step == self.last_step:
                self.losses.append(float(loss))
                self.t_end = time.perf_counter()

    def _trace(self, loss):
        if self.steps == 2:
            float(loss)                      # the chip has caught up
            self._t = time.perf_counter()
            self._span = trace_reduce.open_window(self.trace_dir)
        elif self.steps == 2 + self.trace_steps:
            float(loss)
            trace_reduce.close_window(self._span)
            self.traced = self.trace_steps
            self.trace_cost_s = time.perf_counter() - self._t


def run(ctx):
    cfg, mix, say = ctx.config, ctx.traffic, ctx.say
    if ctx.chips > 1:
        fleet.init(is_collective=True)
        mesh = paddle.parallel.get_mesh()
        if mesh.devices.size != ctx.chips:
            raise RuntimeError(f"fleet laid a mesh of {mesh.devices.size} "
                               f"devices, the cell asks for {ctx.chips}")
    paddle.seed(ctx.seed % trafficgen.SEED_MOD)
    mcfg = ctx.resolve(cfg["run"]["config_class"])(**ctx.model_kwargs)
    net = ctx.resolve(cfg["run"]["model_class"])(mcfg)
    model = paddle.Model(net)
    model.prepare(
        paddle.optimizer.AdamW(cfg["run"]["learning_rate"],
                               parameters=net.parameters()),
        ctx.resolve(cfg["run"]["loss"]), amp_configs=cfg["run"]["amp"])
    batch = mix["sequences_per_step"]
    tokens_per_step = batch * mix["seq_len"]

    # correct, part 1: the untrained weights' eval-mode loss on the first
    # rows of the first batch against the plain reference
    rows = min(cfg["run"]["check_rows"], batch)
    first = PretrainData(mix, cfg, ctx.seed, 1, 0)
    ids, mlm, nsp = (np.stack(x) for x in zip(*(first[i]
                                                for i in range(rows))))
    ours = float(model.eval_batch([ids], [mlm, nsp])[0])
    ref = ctx.load("reference", ctx.cell["config"])
    want = float(ref.loss(ref.weights(net.state_dict()), ids, mlm, nsp,
                          cfg["num_attention_heads"]))
    tol = cfg["run"]["loss_tolerance"] * (20 if ctx.rehearse else 1)
    loss_ok = abs(ours - want) <= tol
    say(f"train: untrained eval-mode loss on {rows} rows {ours:.6f}, plain "
        f"reference {want:.6f}, |diff| {abs(ours - want):.2e} "
        f"(tolerance {tol}) -> {'ok' if loss_ok else 'WRONG'}")

    def fit(data, cb):
        model.fit(data, batch_size=batch, epochs=1, shuffle=False,
                  drop_last=True, num_workers=mix["workers"], verbose=0,
                  log_freq=mix["log_freq"], callbacks=[cb])

    # set-up: compile the step, probe its time
    probe = Window(mix["skip_steps"], ctx.seconds,
                   last_step=mix["probe_steps"] - 1)
    t = time.perf_counter()
    fit(PretrainData(mix, cfg, ctx.seed, mix["probe_steps"], 0), probe)
    step_s = (probe.t_end - probe.t_start) / probe.steps
    say(f"train: first fit ({mix['probe_steps']} steps, compile or cache "
        f"included) {time.perf_counter() - t:.1f}s; probed step "
        f"{step_s * 1e3:.1f} ms")
    # the data outlasts the window by a tenth and the log cadence; should
    # the probe have read far too fast, the window closes with the data
    steps = (mix["skip_steps"] + math.ceil(ctx.seconds / step_s * 1.1)
             + mix["log_freq"])
    compiles0 = stat_get("STAT_train_step_compiles")
    win = Window(mix["skip_steps"], ctx.seconds, steps - 1,
                 ctx.trace_dir if ctx.trace else None, mix["trace_steps"])
    fit(PretrainData(mix, cfg, ctx.seed, steps, mix["probe_steps"]), win)
    if ctx.trace and win.traced is None:
        raise RuntimeError("the window closed before the traced steps")
    compiled = stat_get("STAT_train_step_compiles") - compiles0
    window_s = win.t_end - win.t_start
    rate = win.steps * tokens_per_step / window_s
    finite = bool(np.all(np.isfinite(win.losses)))
    fell = win.losses[-1] < win.losses[0]
    say(f"train: window {window_s:.3f}s, {win.steps} steps of "
        f"{tokens_per_step} tokens ({window_s / win.steps * 1e3:.2f} "
        f"ms/step), forced losses {win.losses[0]:.4f} -> "
        f"{win.losses[-1]:.4f} ({len(win.losses)} read, finite {finite}), "
        f"train-step compiles in the window {compiled}")
    left = multiprocessing.active_children()
    if left:
        raise RuntimeError(f"DataLoader workers still alive: {left}")
    return {
        "correct": loss_ok and finite and fell and compiled == 0,
        "attempted": win.steps, "failed": 0,
        "setup_end": win.t_start,
        "end_to_end": {"train_tokens_per_s": rate},
        # the rate over the steps outside the traced ones, which starting
        # and stopping the profiler slow: what the per-layer readers use
        "record": {"tokens_per_s": (win.steps - (win.traced or 0))
                   * tokens_per_step / (window_s - win.trace_cost_s),
                   "tokens_per_step": tokens_per_step,
                   "seq_len": mix["seq_len"], "traced": win.traced,
                   "step_ms_host": window_s / win.steps * 1e3},
    }
