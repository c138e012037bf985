"""chip_smoke.py — the quickest proof that paddle_tpu starts on the chip.

One process drives the two normal entry points once each, at the full width
of models the repo supports, through the public API (`import paddle_tpu as
paddle`), on seeded synthetic data made here:

  train    paddle.Model(ERNIE-base).fit — bf16 AMP, batch 32, seq 128,
           DataLoader workers, the donated carry, the DeviceFeeder.
  serve    serving.GenerationEngine(GPT-2 small) — two prefill buckets,
           futures and streams, checked against the eager forward and
           `net.generate`.
  kernels  compiled (never interpreted) Pallas kernels: a GPT causal train
           step at seq 2048 through the flash kernel, and flash / splash /
           paged attention against the repo's dense references.
  latent   GLM-4.7-Flash at its published widths and the benchmark's depth
           (benchmark/configs/glm-4.7-flash.json: 1 dense + 6 expert
           layers, 64 experts, vocabulary 154,880, bfloat16): the engine's
           own prefill program over 2,048-token buckets and 16 decode steps
           through the latent pages for 4 slots, LOGITS against the plain
           float32 reference (benchmark/reference/glm-4.7-flash.py), and
           the same steps over a cache rounded to 8 bits failing the
           tolerance.
  hybrid   Falcon-H1-34B at its published widths and the benchmark's depth
           (benchmark/configs/falcon-h1-34b.json: 6 blocks of Mamba-2 mixer
           beside grouped-query attention, vocabulary 261,120, bfloat16):
           the engine's own prefill program over prompts of 1,024 / 777 /
           129 / 40 tokens and 32 decode steps through K/V pages and slot
           state, LOGITS at every decoded position against the plain
           float32 reference (benchmark/reference/falcon-h1-34b.py) in
           units of the logits' std, and the same steps with the state
           pool held in bfloat16 failing the tolerance.
  multi    only with >= 4 devices: ERNIE-base dp=4 through fleet.init,
           GenerationEngine(tp=4), Router(num_replicas=4).

Every line names the platform, device kind and device count. Any failed check
or exception ends the run with a traceback and a non-zero exit code; nothing
is caught and carried past. Without a TPU the script exits 2 before doing any
work, and a copy of it outside the repo dies on its first import. The last
line of stdout is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The engine keeps one decode step in flight ahead of the host (ISSUE 34): it
launches step n+1 from the tokens the device still holds and only then reads
step n, so what a step teaches the host — an EOS, a poison flag — is learned
one step late (the token computed meanwhile is dropped), and whatever needs
a step's tokens on the host first (speculation, a test hook, an armed step
failpoint) settles the step in flight before it launches. A prefill's first
token stays on the device the same way: the first-token program writes it
into the next step's token input, and the prefill is read after that step
is launched. `serve`, `latent` and `hybrid` each end a loaded run through
the loop and fail unless `stats()["lookahead"]["ahead"]` is over 0.9 of that
run's decode steps and `prefills_ahead` over 0.9 of its prefills, with
nothing settled first and no token dropped, and the tokens still match the
phase's reference.

`--cpu-rehearsal` runs the same control flow at tiny sizes on the CPU with
the kernels in the Pallas interpreter, to debug the script itself. It says
platform=cpu in every line, is never the default, and proves nothing about
a chip. `--phases` picks phases (a four-chip run need not pay for all of
them again).
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import multiprocessing
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import serving
from paddle_tpu.distributed import fleet
from paddle_tpu.framework.monitor import stat_get
from paddle_tpu.models import (ErnieConfig, ErnieForSequenceClassification,
                               GPTConfig, GPTForCausalLM)
from paddle_tpu.ops import paged_ops

PHASES = ("train", "serve", "kernels", "latent", "hybrid", "multi")
HERE = os.path.dirname(os.path.abspath(__file__))

# A generated token "agrees" with the eager forward when it scores within
# this much of the eager argmax. About a tenth of the spread of a random
# GPT-2 small's logits (std ~0.55 over 50k entries): a wrong position, mask
# or page fails it, rounding between two compiled programs does not.
NEAR = 0.25
# Shares of generated tokens that must be exactly the eager argmax, and
# that must match `net.generate`. The framework pins f32 matmuls to
# "highest" precision (framework/__init__.py) and the models here are f32,
# so on the v5e both measured 1.000 (PR 21); the margin is for near-ties
# flipping between differently ordered reductions (the tp=4 psum). A PR
# that moves serving to bf16 restates these with what it measures.
EXACT = 0.95
# The latent phase compares LOGITS of the bfloat16 programs with the float32
# reference over the same bfloat16 weights, position by position, as the
# root-mean-square difference over the vocabulary divided by the logits'
# standard deviation. Two kinds of position (on the v5e, PR 27; PERF.md):
# nearly all read 0.007-0.009 — bfloat16 operands through 7 layers — and
# about one in ten reads 0.1-0.3, where rounding flipped the router's choice
# between the 4th and 5th of 64 near-tied scores in some layer (the same
# positions under the gather and the pool-dense attention; none in
# float32). So the limit is on the MEDIAN position, which a lower precision
# moves (a cache kept in float8 has to fail it), and the flips are bounded
# by their share.
LATENT_MEDIAN = 0.02
LATENT_FLIPPED = 0.05      # a position past this had an expert flipped
LATENT_FLIP_SHARE = 0.3
# The hybrid phase compares LOGITS the same way (the rms difference of a
# position over the vocabulary, in units of the reference's logits' std: with
# the muP multipliers that std is about 0.01, so nothing here is absolute).
# The limit is on the MEDIAN over the DECODED positions, which is where a
# carried state shows: 0.0086 on the v5e under the configuration's draws
# (PR 36, second round; PERF.md section 6) and 0.0130 with the state pool
# rounded to bfloat16 at prefill and before every step: 32 steps in, the
# rounding has moved the logits by half, too little to put a limit between
# (the benchmark's cell, whose requests decode 180 steps, holds it by the
# tokens' mean shortfall). So the STATE is compared too, in units of its own
# spread: the rms difference of a slot's state of one layer from the
# reference's positional scan over the rms of that state, the median over
# slots and layers: 0.00092 sound, 0.00783 with the pool held in bfloat16;
# the limit is 2.2 times the one and 3.9 times under the other.
HYBRID_MEDIAN = 0.02
HYBRID_STATE = 0.002


class SmokeFailure(AssertionError):
    pass


def max_err(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


class RuleDataset(paddle.io.Dataset):
    """A learnable synthetic rule: a sample's tokens all come from the lower
    half of the vocabulary (label 0) or all from the upper half (label 1)."""

    def __init__(self, n, seq, vocab, seed):
        rng = np.random.RandomState(seed)
        self.labels = (np.arange(n) % 2).astype("int64")
        rng.shuffle(self.labels)
        half = vocab // 2
        self.ids = (rng.randint(0, half, size=(n, seq))
                    + self.labels[:, None] * half).astype("int32")

    def __len__(self):
        return len(self.labels)

    def __getitem__(self, i):
        return self.ids[i], self.labels[i]


class LossTrace(paddle.callbacks.Callback):
    """Keeps every step's (lazy) loss, and at the last step — while the
    donated carry is still live; fit writes it back afterwards — records
    what the devices hold."""

    def __init__(self, steps, devices):
        super().__init__()
        self.steps = steps
        self.devices = devices
        self.losses = []
        self.live = None
        self.in_use = None

    def on_train_batch_end(self, step, logs=None):
        self.losses.append(logs["loss"])
        if step == self.steps - 1:
            self.live = jax.live_arrays()
            self.in_use = bytes_in_use(self.devices)


def bytes_in_use(devices):
    """Per-device bytes in use; None where the backend keeps no such
    statistic (the CPU rehearsal)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return [int(s["bytes_in_use"]) for s in stats]


def prompts_for(cfg, lengths, seed):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, size=(n,)).astype("int32")
            for n in lengths]


class Smoke:
    def __init__(self, rehearsal, phases):
        self.rehearsal = rehearsal
        self.phases = phases
        self.devices = jax.devices()
        self.platform = self.devices[0].platform
        self.kind = self.devices[0].device_kind
        self.ndev = len(self.devices)
        self.tag = (f'[chip_smoke platform={self.platform} '
                    f'device_kind="{self.kind}" devices={self.ndev}]')
        self.t0 = time.perf_counter()

    # -- output and checks --------------------------------------------------

    def say(self, msg):
        print(f"{self.tag} +{time.perf_counter() - self.t0:6.1f}s {msg}",
              flush=True)

    def check(self, ok, what):
        """A failed check ends the run: it raises, nothing catches it."""
        if not ok:
            raise SmokeFailure(f"{self.tag} FAILED: {what}")
        self.say(f"ok: {what}")

    def on_device(self, t):
        return t.place.startswith(f"Place({self.platform}:")

    def spans_all(self, x):
        return len(x.sharding.device_set) == self.ndev

    def grew_everywhere(self, before, what):
        after = bytes_in_use(self.devices)
        if after is None:
            self.say(f"{what}: this backend keeps no memory_stats "
                     f"(rehearsal)")
            return
        self.check(all(a > b for a, b in zip(after, before)),
                   f"{what}: bytes_in_use grew on all {self.ndev} devices "
                   f"(by {[a - b for a, b in zip(after, before)]})")

    def cache_entries(self):
        d = paddle.device.compilation_cache_dir()
        return d, (len(os.listdir(d)) if os.path.isdir(d) else 0)

    # -- train --------------------------------------------------------------

    def fit_ernie(self, cfg, batch, seq, steps, num_workers):
        """One seeded ERNIE fine-tune through Model.fit. Returns the net,
        the per-step losses, the LossTrace, and the loss of the UNTRAINED
        weights on the first batch from a forward-only one-device program
        (`Model.eval_batch`: eval mode, f32)."""
        paddle.seed(0)
        net = ErnieForSequenceClassification(cfg, num_classes=2)
        model = paddle.Model(net)
        model.prepare(
            paddle.optimizer.AdamW(1e-4, parameters=net.parameters()),
            paddle.nn.CrossEntropyLoss(),
            amp_configs={"level": "O1", "dtype": "bfloat16"})
        data = RuleDataset(batch * steps, seq, cfg.vocab_size, seed=0)
        loss0, _ = model.eval_batch([data.ids[:batch]],
                                    [data.labels[:batch]])
        trace = LossTrace(steps, self.devices)
        model.fit(data, batch_size=batch, epochs=1, shuffle=False,
                  num_workers=num_workers, verbose=0, log_freq=steps,
                  callbacks=[trace])
        return net, [float(x) for x in trace.losses], trace, float(loss0)

    def phase_train(self):
        say, check = self.say, self.check
        cfg = ErnieConfig.tiny() if self.rehearsal else ErnieConfig.base()
        batch, seq, steps = (8, 32, 24) if self.rehearsal else (32, 128, 24)
        c0 = stat_get("STAT_train_step_compiles")
        o0 = stat_get("STAT_device_feeder_overlap")
        t = time.perf_counter()
        net, losses, trace, loss0 = self.fit_ernie(cfg, batch, seq, steps,
                                                   num_workers=2)
        say(f"train: {steps} steps of ERNIE hidden={cfg.hidden_size} "
            f"layers={cfg.num_hidden_layers} batch={batch} seq={seq} with 2 "
            f"DataLoader workers in {time.perf_counter() - t:.1f}s wall "
            f"(compile included); loss {losses[0]:.4f} -> {losses[-1]:.4f} "
            f"(untrained eval-mode loss on the first batch {loss0:.4f})")
        check(len(losses) == steps and bool(np.all(np.isfinite(losses))),
              f"train: {steps} finite losses")
        first, last = np.mean(losses[:4]), np.mean(losses[-4:])
        check(last < first,
              f"train: loss fell, mean of first 4 steps {first:.4f} -> "
              f"mean of last 4 {last:.4f}")
        check(all(self.on_device(p) for p in net.parameters())
              and all(self.on_device(b) for b in net.buffers()),
              f"train: every parameter and buffer lives on a "
              f"{self.platform} device")
        pbytes = sum(int(np.prod(p.shape)) * 4 for p in net.parameters())
        live_bytes = sum(a.nbytes for a in trace.live)
        check(all(d.platform == self.platform
                  for a in trace.live for d in a.devices())
              and live_bytes >= 3 * pbytes,
              f"train: during fit {live_bytes} bytes of arrays were live, "
              f"all on {self.platform} devices, >= 3x the {pbytes} "
              f"parameter bytes (the carry: parameters and both AdamW "
              f"moments)")
        if trace.in_use is not None:
            check(trace.in_use[0] >= 3 * pbytes,
                  f"train: device 0 reported {trace.in_use[0]} bytes in "
                  f"use during fit")
        check(stat_get("STAT_train_step_compiles") - c0 == 1,
              "train: STAT_train_step_compiles == 1")
        check(stat_get("STAT_device_feeder_overlap") - o0 > 0,
              "train: STAT_device_feeder_overlap > 0")
        check(not multiprocessing.active_children(),
              "train: every DataLoader worker has exited")

    # -- serve --------------------------------------------------------------

    def gpt2_small(self):
        cfg = (GPTConfig.tiny(dropout=0.0) if self.rehearsal
               else GPTConfig(dropout=0.0))
        paddle.seed(1)
        net = GPTForCausalLM(cfg)
        net.eval()
        return cfg, net

    @property
    def serve_new(self):
        """New tokens a request of the serve and multi phases: enough decode
        steps for the share launched ahead of the host to mean something."""
        return 24 if self.rehearsal else 32

    def serve_requests(self, eng, prompts, new):
        """Half the requests through submit (futures), half through
        submit_stream; returns the full sequences, in prompt order."""
        handles = [eng.submit_stream(p, max_new_tokens=new) if i % 2
                   else eng.submit(p, max_new_tokens=new)
                   for i, p in enumerate(prompts)]
        outs = []
        for i, h in enumerate(handles):
            streamed = [int(t) for t in h] if i % 2 else None
            full = np.asarray(h.result(timeout=600))
            if streamed is not None and \
                    list(full[len(prompts[i]):]) != streamed:
                raise SmokeFailure(
                    f"{self.tag} FAILED: request {i} streamed {streamed} "
                    f"but resolved to {list(full[len(prompts[i]):])}")
            outs.append(full)
        return outs

    def check_ahead(self, phase, stats):
        """The loaded run kept one decode step in flight ahead of the host
        (ISSUE 34): all but a few of its steps were launched before the
        step before them was read, all but a few of its prefills were read
        after the next step was launched behind them, and no token was
        dropped."""
        look, steps = stats["lookahead"], stats["steps"]
        prefills = stats["prefills"]
        self.say(f"{phase}: lookahead {look} over {steps} decode steps "
                 f"and {prefills} prefills")
        self.check(look["ahead"] > 0.9 * steps
                   and look["prefills_ahead"] > 0.9 * prefills
                   and not look["settled"] and look["dropped_tokens"] == 0,
                   f"{phase}: over 0.9 of the {steps} decode steps were "
                   f"launched ahead of the last one's read-back "
                   f"({look['ahead']}) and over 0.9 of the {prefills} "
                   f"prefills read behind the next launch "
                   f"({look['prefills_ahead']}), none settled first, no "
                   f"token dropped")

    def near_argmax_rate(self, net, outs, prompts):
        """Teacher-forced agreement with the eager forward: every sequence
        the engine produced goes through ONE eager `net(ids)` (right-padded,
        which a causal model cannot see), and each generated token is scored
        against the eager argmax at its position. Returns (share exactly the
        argmax, share within NEAR of it, largest shortfall, the first
        generated token's shortfall per request)."""
        width = max(len(o) for o in outs)
        ids = np.zeros((len(outs), width), "int32")
        for i, o in enumerate(outs):
            ids[i, :len(o)] = o
        logits = np.asarray(net(paddle.to_tensor(ids)).numpy(), "float32")
        short = [[float(logits[i, t - 1].max() - logits[i, t - 1, o[t]])
                  for t in range(len(p), len(o))]
                 for i, (o, p) in enumerate(zip(outs, prompts))]
        flat = np.concatenate(short)
        return (float(np.mean(flat == 0.0)), float(np.mean(flat <= NEAR)),
                float(flat.max()), [s[0] for s in short])

    def phase_serve(self):
        say, check = self.say, self.check
        cfg, net = self.gpt2_small()
        buckets = (16, 32) if self.rehearsal else (32, 128)
        new = self.serve_new
        lengths = ([3, 9, 14, 20, 27, 31, 9, 20] if self.rehearsal
                   else [5, 24, 40, 72, 100, 120, 24, 72])
        prompts = prompts_for(cfg, lengths, seed=2)
        k0, p0 = (stat_get("STAT_paged_attn_kernel"),
                  stat_get("STAT_paged_attn_pool"))
        t = time.perf_counter()
        eng = serving.GenerationEngine(
            net, name="smoke", prefill_buckets=buckets, max_slots=8,
            page_size=16, num_pages=128, max_new_tokens=new)
        say(f"serve: GenerationEngine(GPT hidden={cfg.hidden_size} "
            f"layers={cfg.num_layers} heads={cfg.num_heads} "
            f"vocab={cfg.vocab_size}) warmed in "
            f"{time.perf_counter() - t:.1f}s wall")
        warm = dict(eng.stats()["compiles"])
        say(f"serve: compile ledger after warm-up {warm}")
        outs = self.serve_requests(eng, prompts, new)
        stats = eng.stats()
        eng.shutdown()
        check(all(len(o) == len(p) + new for o, p in zip(outs, prompts)),
              f"serve: all {len(prompts)} requests (lengths {lengths}, half "
              f"streamed) resolved with {new} new tokens each, streams "
              f"token-for-token equal to their futures")
        expected = {f"prefill[b={b}]" for b in buckets} | {"decode[m=8]"}
        check(expected <= set(warm) and all(v == 1 for v in warm.values()),
              f"serve: exactly one compile per program ({sorted(warm)})")
        check(stats["compiles"] == warm,
              "serve: zero compiles after warm-up")
        check(stats["pages"]["pages_in_use"] == 0,
              "serve: pages_in_use == 0 after drain")
        self.check_ahead("serve", stats)
        kern = stat_get("STAT_paged_attn_kernel") - k0
        pool = stat_get("STAT_paged_attn_pool") - p0
        entries = stats["pages"]["pages_per_seq"]
        head_dim = cfg.hidden_size // cfg.num_heads
        # heads narrower than a lane tile lie fused in one row, and the
        # fused-row kernel takes them: GPT-2 small's 12 x 64 in 768 lanes,
        # pages of 16, a 64-entry table (the rehearsal's 4 x 16 in the
        # interpreter). Anything else here is a regression of the rule.
        want = "kernel"
        say(f"serve: attention path at head_dim={head_dim}, 128 pages "
            f"against 8 slots x {entries} entries: "
            f"{stats['decode_attention']}; STAT_paged_attn_kernel={kern} "
            f"STAT_paged_attn_pool={pool} (traces)")
        check(stats["decode_attention"] == want
              and kern == cfg.num_layers and pool == 0,
              f"serve: the decode program's attention is `{want}`")

        # what the engine holds, and in which layout (PR 28)
        pools = stats["pools"]
        say(f"serve: pools ({stats['pages']['pool_form']}) {pools}")
        check(all(p["layout"] == p["compiled_for"] == p["preferred"]
                  for p in pools),
              "serve: the pools lie in the layout the decode program was "
              "compiled for, and the compiler, left to choose, chose it "
              "(no program relays a pool, at its boundary or inside)")
        if cfg.hidden_size // cfg.num_heads == 64:  # not the rehearsal's 16
            check(all(p["device_bytes"] <= 1.1 * p["logical_bytes"]
                      for p in pools),
                  "serve: 64-wide heads in one dense row: every pool's "
                  "device bytes within 1.1 times the bytes it stores")

        exact, near, worst, first = self.near_argmax_rate(net, outs, prompts)
        say(f"serve: vs the eager forward, teacher-forced: {exact:.3f} of "
            f"generated tokens are the eager argmax, {near:.3f} within "
            f"{NEAR} of it, largest shortfall {worst:.4f}; first-step "
            f"shortfalls {[round(x, 4) for x in first]}")
        check(max(first) <= NEAR,
              f"serve: every first-step token scores within {NEAR} of the "
              f"eager argmax")
        check(near >= 0.99 and exact >= EXACT,
              f"serve: >= 99% of generated tokens within {NEAR} of the "
              f"eager argmax and >= {EXACT:.0%} exactly it")

        # against net.generate (its own compiled program); a sequence
        # agrees up to its first flipped near-tie and differs after it
        agree = total = 0
        for idx in ((1, 6), (3, 7)):   # two prompt lengths -> two programs
            batch = np.stack([prompts[i] for i in idx])
            gen = np.asarray(net.generate(paddle.to_tensor(batch),
                                          max_new_tokens=new).numpy())
            for row, i in zip(gen, idx):
                agree += int(np.sum(row[len(prompts[i]):]
                                    == outs[i][len(prompts[i]):]))
                total += new
        say(f"serve: token agreement with net.generate {agree}/{total} = "
            f"{agree / total:.3f}")
        check(agree / total >= EXACT,
              f"serve: token agreement with net.generate >= {EXACT}")
        return prompts, outs

    # -- kernels ------------------------------------------------------------

    def attention_pair(self, flag_name, shape, seed, **kw):
        """(kernel out, dense out, kernel dq, dense dq) for one bf16 causal
        call through F.scaled_dot_product_attention, kernel on and off."""
        rng = np.random.RandomState(seed)
        base = [rng.standard_normal(shape).astype("float32")
                for _ in range(3)]
        res = []
        for use_kernel in (True, False):
            paddle.set_flags({flag_name: use_kernel})
            q, k, v = (paddle.to_tensor(x).astype("bfloat16") for x in base)
            q.stop_gradient = False
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                                 **kw)
            out.astype("float32").sum().backward()
            res.append((out.numpy(), q.grad.numpy()))
        paddle.set_flags({flag_name: True})
        return res[0][0], res[1][0], res[0][1], res[1][1]

    def phase_kernels(self):
        say, check = self.say, self.check
        interp = bool(paddle.get_flags(["FLAGS_flash_attention_interpret"])[
            "FLAGS_flash_attention_interpret"])
        check(interp == self.rehearsal,
              "kernels: Pallas interpret mode is "
              + ("on (rehearsal)" if interp
                 else "off: every kernel below is compiled by Mosaic"))

        # (a) one GPT causal train step at seq 2048, 768 wide, bf16 AMP
        seq = 512 if self.rehearsal else 2048
        cfg = (GPTConfig.tiny(max_position_embeddings=seq, dropout=0.0)
               if self.rehearsal else
               GPTConfig(num_layers=2, max_position_embeddings=seq,
                         dropout=0.0))
        paddle.seed(3)
        net = GPTForCausalLM(cfg)
        model = paddle.Model(net)
        model.prepare(
            paddle.optimizer.AdamW(1e-4, parameters=net.parameters()),
            paddle.nn.CrossEntropyLoss(),
            amp_configs={"level": "O1", "dtype": "bfloat16"})
        ids = np.random.RandomState(3).randint(
            0, cfg.vocab_size, size=(2, seq)).astype("int32")
        f0, b0 = (stat_get("STAT_flash_attention_fwd"),
                  stat_get("STAT_flash_attention_bwd"))
        t = time.perf_counter()
        loss, _ = model.train_batch([ids],
                                    [np.roll(ids, -1, 1).astype("int64")])
        lv = float(loss[0])
        say(f"kernels: GPT hidden={cfg.hidden_size} layers="
            f"{cfg.num_layers} causal train step at seq {seq}: loss "
            f"{lv:.4f} in {time.perf_counter() - t:.1f}s wall (compile "
            f"included)")
        check(bool(np.isfinite(lv)),
              f"kernels: the seq-{seq} train step's loss is finite")
        check(stat_get("STAT_flash_attention_fwd") > f0
              and stat_get("STAT_flash_attention_bwd") > b0,
              "kernels: that step traced the flash kernel forward and "
              "backward (STAT_flash_attention_fwd/bwd > 0)")

        # (b) flash against dense, forward and dq
        B, H, S, D = (1, 2, 512, 64) if self.rehearsal else (2, 12, 1024, 64)
        of, od, gf, gd = self.attention_pair("FLAGS_use_flash_attention",
                                             (B, H, S, D), seed=4)
        say(f"kernels: flash [{B},{H},{S},{D}] bf16 causal: max|out-dense| "
            f"{max_err(of, od):.4f}, max|dq-dense| {max_err(gf, gd):.4f}")
        check(max_err(of, od) < 0.05 and max_err(gf, gd) < 0.3,
              "kernels: flash matches dense attention at bf16 tolerance "
              "(out < 0.05, dq < 0.3)")

        # (c) splash over packed segment ids against dense, forward and dq
        seg = np.zeros((B, S), "int32")
        seg[0, S // 4:] += 1
        seg[0, S // 2 + 64:] += 1
        seg[-1, S // 8:] += 1
        s0 = stat_get("STAT_splash_attention_fwd")
        of, od, gf, gd = self.attention_pair(
            "FLAGS_use_splash_attention", (B, H, S, D), seed=5,
            segment_ids=seg)
        check(stat_get("STAT_splash_attention_fwd") > s0,
              "kernels: the packed call traced the splash kernel")
        say(f"kernels: splash [{B},{H},{S},{D}] bf16 causal, packed "
            f"segments: max|out-dense| {max_err(of, od):.4f}, "
            f"max|dq-dense| {max_err(gf, gd):.4f}")
        check(max_err(of, od) < 0.05 and max_err(gf, gd) < 0.3,
              "kernels: splash matches dense segment-masked attention at "
              "bf16 tolerance (out < 0.05, dq < 0.3)")

        # (d) paged attention at head dim 128, page 16, against the gather
        # reference — the shape the rule admits to the repo's head-pool
        # kernel `head_decode_attention`
        Bq, Hq, Dq, P, N, PP = 8, 8, 128, 16, 72, 8
        rng = np.random.RandomState(6)
        q = jnp.asarray(rng.standard_normal((Bq, Hq, Dq)), jnp.bfloat16)
        kp = jnp.asarray(rng.standard_normal((Hq, N, P, Dq)), jnp.bfloat16)
        vp = jnp.asarray(rng.standard_normal((Hq, N, P, Dq)), jnp.bfloat16)
        table = jnp.asarray(
            rng.permutation(N - 1)[:Bq * PP].reshape(Bq, PP) + 1, jnp.int32)
        pos = jnp.asarray(rng.randint(0, PP * P, size=(Bq,)), jnp.int32)
        scale = 1.0 / Dq ** 0.5
        check(paged_ops.paged_kernel_supported(q.shape, kp.shape,
                                               table.shape)
              and not paged_ops.paged_kernel_supported(
                  (Bq, Hq, 64), (Hq, N, P, 64), table.shape),
              "kernels: the paged-attention rule admits head dim 128 and "
              "sends head dim 64 to the reference")
        k0, r0 = (stat_get("STAT_paged_attn_kernel"),
                  stat_get("STAT_paged_attn_reference"))
        out = jax.jit(lambda *a: paged_ops.paged_attention(*a, scale))(
            q, kp, vp, table, pos)
        kern = stat_get("STAT_paged_attn_kernel") - k0
        refc = stat_get("STAT_paged_attn_reference") - r0
        want = jax.jit(lambda q, kp, vp, t, pos: paged_ops.cached_attention(
            q.astype(jnp.float32),
            paged_ops.paged_gather(kp, t).astype(jnp.float32),
            paged_ops.paged_gather(vp, t).astype(jnp.float32), pos, scale))(
                q, kp, vp, table, pos)
        say(f"kernels: paged attention q[{Bq},{Hq},{Dq}] page {P}: "
            f"STAT_paged_attn_kernel={kern} STAT_paged_attn_reference="
            f"{refc}, max|out-reference| {max_err(out, want):.4f}")
        check((kern, refc) == (1, 0),
              "kernels: head dim 128 took the head-pool kernel "
              "head_decode_attention"
              + (" (interpreted: rehearsal)" if self.rehearsal else ""))
        check(max_err(out, want) < 0.05,
              "kernels: paged attention matches the gather reference at "
              "bf16 tolerance (< 0.05)")

    # -- multi: four devices, one process -----------------------------------

    def phase_multi(self, served):
        say, check, n = self.say, self.check, self.ndev
        # (a) ERNIE-base dp=n through fleet.init + the sharded train step.
        # Dropout is off here (and only here): the first step's loss is
        # then a function of weights and batch alone, and can be held
        # against a one-device forward of the same weights and batch —
        # with dropout on it moves by +-0.1 with the draw.
        cfg = ErnieConfig.tiny() if self.rehearsal else ErnieConfig.base()
        cfg.hidden_dropout_prob = cfg.attention_probs_dropout_prob = 0.0
        batch, seq, steps = (8, 32, 8) if self.rehearsal else (32, 128, 8)
        before = bytes_in_use(self.devices)
        fleet.init(is_collective=True)
        mesh = paddle.parallel.get_mesh()
        check(dict(mesh.shape) == {"dp": n},
              f"multi: fleet.init laid a dp={n} mesh over the devices")
        t = time.perf_counter()
        net, losses, trace, loss0 = self.fit_ernie(cfg, batch, seq, steps,
                                                   num_workers=0)
        say(f"multi: dp={n} ERNIE hidden={cfg.hidden_size} fit, {steps} "
            f"steps in {time.perf_counter() - t:.1f}s wall (compile "
            f"included); loss {losses[0]:.4f} -> {losses[-1]:.4f}")
        check(bool(np.all(np.isfinite(losses))) and losses[-1] < losses[0],
              "multi: dp losses are finite and fell")
        check(abs(losses[0] - loss0) < 0.05,
              f"multi: the sharded step's first loss {losses[0]:.4f} (bf16 "
              f"AMP, {n} devices) agrees with the one-device forward of "
              f"the same weights and batch {loss0:.4f} (f32) within 0.05")
        pbytes = sum(int(np.prod(p.shape)) * 4 for p in net.parameters())
        sharded = sum(a.nbytes for a in trace.live if self.spans_all(a))
        check(sharded >= 3 * pbytes,
              f"multi: during the dp fit {sharded} bytes of live arrays "
              f"spanned all {n} devices (>= 3x the {pbytes} parameter "
              f"bytes: parameters and optimizer state)")
        sh = fleet.fleet.batch_placement()(np.zeros((batch, seq), "int32"))
        check(len(sh.device_set) == n
              and sh.shard_shape((batch, seq)) == (batch // n, seq),
              f"multi: the feeder places a batch {batch // n} rows per "
              f"device across {n} devices")
        self.grew_everywhere(before, "multi: dp fit")
        paddle.parallel.set_mesh(None)

        # (b) GenerationEngine(tp=n) on GPT-2 small
        cfg, net = self.gpt2_small()
        new = self.serve_new    # (its tokens are compared with `serve`'s)
        buckets = (16, 32) if self.rehearsal else (32, 128)
        if served is None:
            lengths = [3, 9, 14, 20] if self.rehearsal else [5, 24, 72, 120]
            prompts, base = prompts_for(cfg, lengths, seed=2), None
        else:
            prompts, base = served
        before = bytes_in_use(self.devices)
        t = time.perf_counter()
        eng = serving.GenerationEngine(
            net, name="smoke-tp", tp=n, prefill_buckets=buckets,
            max_slots=8, page_size=16, num_pages=128, max_new_tokens=new)
        say(f"multi: GenerationEngine(tp={n}) warmed in "
            f"{time.perf_counter() - t:.1f}s wall")
        warm = dict(eng.stats()["compiles"])
        outs = self.serve_requests(eng, prompts, new)
        stats = eng.stats()
        self.grew_everywhere(before, f"multi: tp={n} engine")
        eng.shutdown()
        check(stats["tp"] == n and stats["pages"]["shard_hbm_bytes"] * n
              == stats["pages"]["hbm_bytes"],
              f"multi: KV pools are sharded {n} ways (one shard holds "
              f"{stats['pages']['shard_hbm_bytes']} of "
              f"{stats['pages']['hbm_bytes']} bytes)")
        check(stats["compiles"] == warm
              and all(v == 1 for v in warm.values())
              and stats["pages"]["pages_in_use"] == 0,
              f"multi: tp engine compiled each program once "
              f"({sorted(warm)}), none after warm-up, and drained its pages")
        exact, near, worst, _ = self.near_argmax_rate(net, outs, prompts)
        say(f"multi: tp={n} vs the eager forward, teacher-forced: exact "
            f"{exact:.3f}, within {NEAR} {near:.3f}, largest shortfall "
            f"{worst:.4f}")
        check(near >= 0.99 and exact >= EXACT,
              f"multi: tp engine tokens agree with the eager forward "
              f"(>= 99% within {NEAR} of the argmax, >= {EXACT:.0%} exactly "
              f"it)")
        if base is not None:
            same = np.mean([np.mean(a[len(p):] == b[len(p):])
                            for a, b, p in zip(outs, base, prompts)])
            say(f"multi: tp={n} token agreement with the tp=1 engine "
                f"{same:.3f}")

        # (c) Router(num_replicas=n): replica i on device i. Depth cut to 2
        # layers, width full: four replicas each compile their own programs
        rcfg = (GPTConfig.tiny(dropout=0.0) if self.rehearsal
                else GPTConfig(num_layers=2, dropout=0.0))
        paddle.seed(7)
        rnet = GPTForCausalLM(rcfg)
        rnet.eval()
        before = bytes_in_use(self.devices)
        t = time.perf_counter()
        router = serving.Router(
            rnet, num_replicas=n, name="smoke-router",
            prefill_buckets=buckets, max_slots=4, page_size=16,
            num_pages=64, max_new_tokens=new)
        say(f"multi: Router(num_replicas={n}) over GPT hidden="
            f"{rcfg.hidden_size} layers={rcfg.num_layers} built in "
            f"{time.perf_counter() - t:.1f}s wall")
        rprompts = prompts_for(
            rcfg, [7 + (3 * i) % 20 for i in range(4 * n)], seed=8)
        futs = [router.submit(p, max_new_tokens=new) for p in rprompts]
        routs = [np.asarray(f.result(timeout=600)) for f in futs]
        replicas = router.stats()["router"]["replicas"]
        self.grew_everywhere(before, f"multi: router x{n}")
        router.shutdown()
        placements = [r["placements"] for r in replicas.values()]
        check(all(len(o) == len(p) + new for o, p in zip(routs, rprompts)),
              f"multi: router resolved all {len(rprompts)} requests")
        check(len(placements) == n and all(c > 0 for c in placements),
              f"multi: router placements reached all {n} replicas "
              f"({placements})")

    # -- latent: GLM-4.7-Flash, logits against the plain reference ----------

    def phase_latent(self):
        """Prefill + 16 decode steps through the latent pages at the
        published widths, logits against the float32 reference."""
        import importlib.util
        from paddle_tpu.models import GlmMoeLiteConfig, GlmMoeLiteForCausalLM
        from paddle_tpu.serving.latent_family import latent_decode
        say, check = self.say, self.check
        with open(os.path.join(HERE, "benchmark", "configs",
                               "glm-4.7-flash.json")) as f:
            data = json.load(f)
        if self.rehearsal:
            data.update({k: v for k, v in data["rehearsal"].items()
                         if k != "run"})
        run = data["run"]
        kwargs = {kw: data[key] for kw, key in run["config_kwargs"].items()}
        kwargs.update(run["config_overrides"])
        mcfg = GlmMoeLiteConfig(**kwargs)
        spec = importlib.util.spec_from_file_location(
            "glm_reference", os.path.join(HERE, "benchmark", "reference",
                                          "glm-4.7-flash.py"))
        ref = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ref)

        paddle.seed(27)
        t = time.perf_counter()
        net = GlmMoeLiteForCausalLM(mcfg)
        net.eval()
        n_par = sum(int(np.prod(p.shape)) for p in net.parameters())
        say(f"latent: GlmMoeLite hidden={mcfg.hidden_size} layers="
            f"{mcfg.num_hidden_layers} heads={mcfg.num_heads} experts="
            f"{mcfg.n_routed_experts} top-{mcfg.num_experts_per_tok} vocab="
            f"{mcfg.vocab_size} {mcfg.dtype}: {n_par} parameters built in "
            f"{time.perf_counter() - t:.1f}s wall")
        bucket, page, steps = (32, 16, 6) if self.rehearsal else (2048, 16, 16)
        # a prompt that fills its bucket and ends ON a page boundary, two
        # that end inside a page, one short: different lengths in one step
        lengths = ([32, 27, 14, 5] if self.rehearsal
                   else [2048, 1777, 1021, 300])
        # the table's width a power of two (256 entries, the benchmark's):
        # whole rounds of the decode kernel's page copies, whatever their
        # size (`paged_ops.paged_latent_kernel_supported`)
        pps = 1 << (-(-(bucket + steps) // page) - 1).bit_length()
        eng = serving.GenerationEngine(
            net, name="smoke_latent", max_slots=4, page_size=page,
            num_pages=4 * pps, pages_per_seq=pps,
            prefill_buckets=(bucket,), max_new_tokens=steps, warmup=False)
        check(eng.stats()["decode_attention"] == "latent_kernel",
              "latent: the decode attention is `latent_kernel` (one Pallas "
              "kernel walks each slot's own pages for its 20 heads: no "
              "gather of the slot's whole table)")
        W = eng._W
        prompts = prompts_for(mcfg, lengths, seed=27)
        pt = np.stack([eng._cache.alloc(i, n + steps)
                       for i, n in enumerate(lengths)])
        t = time.perf_counter()
        logits = []
        for i, pr in enumerate(prompts):        # the engine's own program
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :len(pr)] = pr
            out = eng._prefill_jit(W, *eng._pools(), pt[i], ids,
                                   np.int32(len(pr)))
            eng._set_pools(out[:-1])
            logits.append(np.asarray(out[-1]))
        got = [np.stack(logits)]                                # [4, V]
        say(f"latent: {len(prompts)} prefills of lengths {lengths} in a "
            f"{bucket} bucket in {time.perf_counter() - t:.1f}s wall "
            f"(compile included)")
        step = jax.jit(lambda W, pool, pt, tok, pos: latent_decode(
            W, pool, pt, tok, pos, jnp.ones(tok.shape, bool), mcfg,
            page))
        after_prefill = eng._pools()[0]

        def decode(pool, forced=None):
            """`steps` greedy steps; with `forced` (a first run's tokens)
            the same tokens over a cache rounded to 8 bits."""
            rounded = forced is not None
            toks = [got[0].argmax(-1).astype(np.int32)]
            outs, hits = [], []
            for k in range(steps):
                pos = np.asarray(lengths, np.int32) + k
                if rounded:     # the stand-in: the cache kept in 8 bits
                    pool = pool.astype(jnp.float8_e4m3fn).astype(pool.dtype)
                lg, pool, hit, rows = step(W, pool, pt, toks[-1], pos)
                outs.append(np.asarray(lg))
                hits.append((int(hit), int(rows)))
                toks.append(forced[k + 1] if rounded
                            else outs[-1].argmax(-1).astype(np.int32))
            return np.stack(outs, 1), toks, hits       # [4, steps, V]

        t = time.perf_counter()
        dec, toks, hits = decode(after_prefill)
        say(f"latent: {steps} decode steps for 4 slots through the pages in "
            f"{time.perf_counter() - t:.1f}s wall (compile included); "
            f"experts hit a step (of {mcfg.n_routed_experts} x "
            f"{mcfg.num_expert_layers}) {[h for h, _ in hits][:4]}..., rows "
            f"attended {hits[0][1]} -> {hits[-1][1]}")
        check(hits[-1][1] == sum(lengths) + 4 * steps,
              "latent: latent_rows counts every cached position of the 4 "
              "slots")
        system = np.concatenate([got[0][:, None], dec], 1)  # [4, steps+1, V]

        # the reference: each sequence whole, teacher-forced with the
        # system's own tokens, logits at the positions the system produced
        t = time.perf_counter()
        RW = ref.weights(net.state_dict())
        width = -(-(bucket + steps) // 128) * 128
        want = []
        for i, pr in enumerate(prompts):
            seq = np.concatenate([pr, [tk[i] for tk in toks[:steps]]])
            ids = jnp.zeros((width,), jnp.int32).at[:len(seq)].set(
                jnp.asarray(seq, jnp.int32))
            x = ref.hidden(RW, ids, mcfg.num_heads,
                           top_k=mcfg.num_experts_per_tok)
            lo = len(pr) - 1
            want.append(np.asarray(ref._head(
                x[lo:lo + steps + 1], RW["model.norm.weight"],
                RW["lm_head.weight"], ref.EPS)))
        want = np.stack(want)
        say(f"latent: plain float32 reference over {len(prompts)} sequences "
            f"of up to {width} positions in {time.perf_counter() - t:.1f}s "
            f"wall")

        def compare(sys_logits, what):
            d = sys_logits.astype(np.float64) - want
            std = float(want.std())
            each = np.sqrt((d * d).mean(-1)).ravel() / std   # per position
            med, flipped = float(np.median(each)), float(
                (each > LATENT_FLIPPED).mean())
            agree = float((sys_logits.argmax(-1) == want.argmax(-1)).mean())
            # what the benchmark's token test would read of these positions:
            # how far the system's token falls short of the reference's best
            short = want.max(-1) - np.take_along_axis(
                want, sys_logits.argmax(-1)[..., None], -1)[..., 0]
            say(f"latent: {what}: logits std {std:.4f}; rms difference of a "
                f"position / std: median {med:.4f} (limit {LATENT_MEDIAN}), "
                f"largest {each.max():.4f}, {flipped:.3f} of {each.size} "
                f"positions past {LATENT_FLIPPED} (limit "
                f"{LATENT_FLIP_SHARE}); prefill rows {each[::steps + 1]}"
                f"; largest single difference {np.abs(d).max():.4f}; same "
                f"argmax at {agree:.3f} of the positions, largest shortfall "
                f"of the system's token {short.max():.4f}")
            return med, flipped

        med, flipped = compare(system, "bfloat16 programs vs the reference")
        check(np.isfinite(system).all() and med <= LATENT_MEDIAN
              and flipped <= LATENT_FLIP_SHARE,
              f"latent: prefill + {steps} paged decode steps agree with the "
              f"reference's full forward (median position <= "
              f"{LATENT_MEDIAN} of the logits' std, flipped positions <= "
              f"{LATENT_FLIP_SHARE})")
        low, _, _ = decode(after_prefill, forced=toks)
        med8, _ = compare(np.concatenate([got[0][:, None], low], 1),
                          "the same steps over a cache rounded to float8 "
                          "(e4m3) before every step")
        if self.rehearsal:
            say("latent: the 8-bit stand-in is not held to the limit at "
                "the rehearsal's toy widths")
        else:
            check(med8 > LATENT_MEDIAN,
                  f"latent: a cache kept in 8 bits FAILS the limit (median "
                  f"{med8:.4f} > {LATENT_MEDIAN}): the tolerance tells "
                  f"bfloat16 from a lower precision")

        # the same prompts through the engine's own loop, one decode step
        # in flight ahead of the host (ISSUE 34): its tokens against the
        # direct steps' above, which the reference has just vouched for
        freed = [pg for i in range(len(lengths))
                 for pg in eng._cache.free(i)]
        eng._zero_pages(freed)
        new = 24 if self.rehearsal else 32
        t = time.perf_counter()
        outs = [np.asarray(f.result(timeout=600)) for f in
                [eng.submit(pr, max_new_tokens=new) for pr in prompts]]
        stats = eng.stats()
        direct = np.stack(toks[:steps + 1], 1)              # [4, steps + 1]
        looped = np.stack([o[len(pr):len(pr) + steps + 1]
                           for o, pr in zip(outs, prompts)])
        same = float((direct == looped).mean())
        say(f"latent: {len(prompts)} requests of {new} tokens through the "
            f"loop in {time.perf_counter() - t:.1f}s wall; their first "
            f"{steps + 1} tokens against the direct steps': {same:.3f} "
            f"equal")
        check(all(len(o) == len(pr) + new for o, pr in zip(outs, prompts))
              and same >= EXACT,
              f"latent: the loop's tokens are the direct steps' (>= "
              f"{EXACT})")
        self.check_ahead("latent", stats)
        eng.shutdown(drain=False)

    def phase_hybrid(self):
        """Prefill + 32 decode steps through K/V pages and slot state at
        the published widths, logits against the float32 reference; the
        state pool held in bfloat16 has to fail."""
        import importlib.util
        from paddle_tpu.models import FalconH1Config, FalconH1ForCausalLM
        from paddle_tpu.serving.hybrid_family import hybrid_decode
        say, check = self.say, self.check
        with open(os.path.join(HERE, "benchmark", "configs",
                               "falcon-h1-34b.json")) as f:
            data = json.load(f)
        if self.rehearsal:
            data.update({k: v for k, v in data["rehearsal"].items()
                         if k != "run"})
        run = data["run"]
        kwargs = {kw: data[key] for kw, key in run["config_kwargs"].items()}
        kwargs.update(run["config_overrides"])
        mcfg = FalconH1Config(**kwargs)
        spec = importlib.util.spec_from_file_location(
            "falcon_h1_reference", os.path.join(
                HERE, "benchmark", "reference", "falcon-h1-34b.py"))
        ref = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ref)
        # what the reference does not read from shapes, at this run's sizes
        rkw = {"mamba_n_groups": mcfg.mamba_n_groups}

        paddle.seed(36)
        t = time.perf_counter()
        net = FalconH1ForCausalLM(mcfg)
        net.eval()
        n_par = sum(int(np.prod(p.shape)) for p in net.parameters())
        say(f"hybrid: FalconH1 hidden={mcfg.hidden_size} layers="
            f"{mcfg.num_hidden_layers} heads={mcfg.num_heads}/"
            f"{mcfg.num_key_value_heads} mixer={mcfg.mamba_n_heads}x"
            f"{mcfg.mamba_d_head}x{mcfg.mamba_d_state} vocab="
            f"{mcfg.vocab_size} {mcfg.dtype}: {n_par} parameters built in "
            f"{time.perf_counter() - t:.1f}s wall")
        page = 4 if self.rehearsal else 16
        steps = 6 if self.rehearsal else 32
        # through the loop at the end: enough decode steps that all but a
        # few are launched ahead
        new = 24 if self.rehearsal else steps
        # a prompt that fills the largest bucket, one inside it, one a
        # token past a chunk of the scan (129 in a 256 bucket), one short
        lengths = [32, 27, 9, 5] if self.rehearsal else [1024, 777, 129, 40]
        buckets = (8, 16, 32) if self.rehearsal else (128, 256, 1024)
        # the table's width in whole rounds of the head-pool kernel (32
        # pages of 32 KB: `latent_attention_kernel.head_block_pages`)
        pps = -(-(-(-(max(lengths) + new) // page)) // 32) * 32
        eng = serving.GenerationEngine(
            net, name="smoke_hybrid", max_slots=4, page_size=page,
            num_pages=4 * pps + 1, pages_per_seq=pps,
            prefill_buckets=buckets, max_new_tokens=new, warmup=False)
        st = eng.stats()
        want_attn, want_ssm = (("reference", "reference") if self.rehearsal
                               else ("kernel", "kernel"))
        check(st["decode_attention"] == want_attn
              and st["ssm_decode_path"] == want_ssm,
              f"hybrid: decode attention is `{want_attn}` (the head-pool "
              f"kernel over 4 K/V heads under 20 query heads on the chip) "
              f"and the state update `{want_ssm}` (one Pallas kernel over "
              f"the slot pool in place); got {st['decode_attention']} / "
              f"{st['ssm_decode_path']}")
        check([p["kind"] for p in st["pools"]]
              == ["pages", "pages", "slots", "slots"],
              "hybrid: two page pools and two slot pools")
        W = eng._W
        prompts = prompts_for(mcfg, lengths, seed=36)
        pt = np.stack([eng._cache.alloc(i, n + steps)
                       for i, n in enumerate(lengths)])
        t = time.perf_counter()
        logits = []
        for i, pr in enumerate(prompts):        # the engine's own program
            b = eng._bucket_for(len(pr))
            ids = np.zeros((1, b), np.int32)
            ids[0, :len(pr)] = pr
            out = eng._prefill_jit(W, *eng._pools(), pt[i], ids,
                                   np.int32(len(pr)), np.int32(i))
            eng._set_pools(out[:-1])
            logits.append(np.asarray(out[-1]))
        first = np.stack(logits)                                # [4, V]
        say(f"hybrid: {len(prompts)} prefills of lengths {lengths} in "
            f"buckets {buckets} in {time.perf_counter() - t:.1f}s wall "
            f"(compile included)")
        live = jnp.ones((4,), bool)
        step = jax.jit(lambda W, pools, pt, tok, pos: hybrid_decode(
            W, pools, pt, tok, pos, live, mcfg, page))
        after_prefill = eng._pools()

        def to_bf16(pools):
            kp, vp, sp, cp = pools
            return kp, vp, sp.astype(jnp.bfloat16).astype(sp.dtype), cp

        def decode(pools, forced=None):
            """`steps` greedy steps; with `forced` (a first run's tokens)
            the same tokens over a state pool held in bfloat16."""
            rounded = forced is not None
            toks = [first.argmax(-1).astype(np.int32)]
            outs, counts = [], []
            for k in range(steps):
                pos = np.asarray(lengths, np.int32) + k
                if rounded:
                    pools = to_bf16(pools)
                lg, pools, slots, rows = step(W, pools, pt, toks[-1], pos)
                outs.append(np.asarray(lg))
                counts.append((int(slots), int(rows)))
                toks.append(forced[k + 1] if rounded
                            else outs[-1].argmax(-1).astype(np.int32))
            if rounded:
                pools = to_bf16(pools)
            # [4, steps, V], the tokens, the counters, the states [L, 4, ..]
            return np.stack(outs, 1), toks, counts, np.asarray(pools[2])

        t = time.perf_counter()
        dec, toks, counts, state = decode(after_prefill)
        say(f"hybrid: {steps} decode steps for 4 slots through pages and "
            f"state in {time.perf_counter() - t:.1f}s wall (compile "
            f"included); (state_slots, kv_rows) {counts[0]} -> {counts[-1]}")
        check(counts[-1] == (4, sum(lengths) + 4 * steps),
              "hybrid: state_slots counts the 4 live slots, kv_rows every "
              "cached position of theirs")
        system = np.concatenate([first[:, None], dec], 1)  # [4, steps+1, V]

        t = time.perf_counter()
        RW = ref.weights(net.state_dict())
        want, want_state = [], []
        width = -(-(max(lengths) + steps) // 128) * 128   # one compile
        for i, pr in enumerate(prompts):
            seq = np.concatenate([pr, [tk[i] for tk in toks[:steps]]])
            ids = np.zeros((width,), np.int32)
            ids[:len(seq)] = seq
            lo = len(pr) - 1
            lg, st = ref.logits_at(
                RW, ids, np.arange(lo, lo + steps + 1), mcfg.num_heads,
                states_at=len(seq) - 1, **rkw)
            want.append(np.asarray(lg))
            want_state.append(np.asarray(st))
        want = np.stack(want)
        want_state = np.stack(want_state, 1)            # [L, 4, H, P, N]
        say(f"hybrid: plain float32 reference (positional scan) over "
            f"{len(prompts)} sequences in {time.perf_counter() - t:.1f}s "
            f"wall")

        def compare(sys_logits, what):
            d = sys_logits.astype(np.float64) - want
            std = float(want.std())
            each = np.sqrt((d * d).mean(-1)) / std      # [4, steps + 1]
            med = float(np.median(each[:, 1:]))
            agree = float((sys_logits.argmax(-1) == want.argmax(-1)).mean())
            say(f"hybrid: {what}: logits std {std:.5f}; rms difference of a "
                f"position / std: median over the decoded positions "
                f"{med:.4f} (limit {HYBRID_MEDIAN}), largest "
                f"{each.max():.4f}; prefill rows {each[:, 0]}; by slot, "
                f"first and last decoded {each[:, 1]} {each[:, -1]}; same "
                f"argmax at {agree:.3f} of the positions")
            return med

        def compare_state(sys_state, what):
            d = sys_state.astype(np.float64) - want_state
            rms = np.sqrt((want_state.astype(np.float64) ** 2)
                          .mean((2, 3, 4)))
            each = np.sqrt((d * d).mean((2, 3, 4))) / rms    # [L, 4]
            med = float(np.median(each))
            say(f"hybrid: {what}: a slot's state after {steps} steps, rms "
                f"difference / rms, by layer and slot: median {med:.5f} "
                f"(limit {HYBRID_STATE}), largest {each.max():.5f}, by "
                f"slot {np.median(each, 0)}; the states' rms "
                f"{float(rms.mean()):.5f}")
            return med

        med = compare(system, f"{mcfg.dtype} programs vs the reference")
        smed = compare_state(state, "float32 state pool vs the reference's "
                             "positional scan")
        low, _, _, state16 = decode(to_bf16(after_prefill), forced=toks)
        compare(np.concatenate([first[:, None], low], 1),
                "the same steps with the state pool held in bfloat16")
        smed16 = compare_state(state16, "the state pool held in bfloat16")
        check(np.isfinite(system).all() and med <= HYBRID_MEDIAN
              and smed <= HYBRID_STATE,
              f"hybrid: prefill + {steps} decode steps through pages and "
              f"state agree with the reference's full forward (median "
              f"decoded position <= {HYBRID_MEDIAN} of the logits' std, "
              f"median state <= {HYBRID_STATE} of its rms)")
        if self.rehearsal:
            say("hybrid: the bfloat16-state control is not held to the "
                "limit at the rehearsal's toy widths")
        else:
            check(smed16 > HYBRID_STATE,
                  f"hybrid: a state pool held in bfloat16 FAILS the limit "
                  f"(median state {smed16:.5f} > {HYBRID_STATE}): the "
                  f"tolerance tells a float32 state from the precision "
                  f"below")

        # the same prompts through the engine's own loop, one decode step
        # in flight ahead of the host (ISSUE 34), their slots taken in
        # whatever order admission gives them
        freed = [pg for i in range(len(lengths))
                 for pg in eng._cache.free(i)]
        eng._zero_pages(freed)
        t = time.perf_counter()
        outs = [np.asarray(f.result(timeout=600)) for f in
                [eng.submit(pr, max_new_tokens=new) for pr in prompts]]
        stats = eng.stats()
        direct = np.stack(toks[:steps], 1)                  # [4, steps]
        looped = np.stack([o[len(pr):len(pr) + steps]
                           for o, pr in zip(outs, prompts)])
        same = float((direct == looped).mean())
        say(f"hybrid: {len(prompts)} requests of {new} tokens through the "
            f"loop in {time.perf_counter() - t:.1f}s wall; their first "
            f"{steps} tokens against the direct steps': {same:.3f} equal")
        check(all(len(o) == len(pr) + new for o, pr in zip(outs, prompts))
              and same >= EXACT,
              f"hybrid: the loop's tokens are the direct steps' (>= "
              f"{EXACT})")
        self.check_ahead("hybrid", stats)
        eng.shutdown(drain=False)

    # -- the run ------------------------------------------------------------

    def run(self):
        cdir, n0 = self.cache_entries()
        envset = "set" if os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            else "unset"
        self.say(f"start: jax {jax.__version__}, jaxlib "
                 f"{importlib.metadata.version('jaxlib')}, libtpu "
                 f"{importlib.metadata.version('libtpu')}, phases "
                 f"{self.phases}; compile cache {cdir} holds {n0} entries "
                 f"(JAX_COMPILATION_CACHE_DIR {envset})")
        if self.rehearsal:
            paddle.set_flags({"FLAGS_flash_attention_interpret": True})
        served = None
        if "train" in self.phases:
            self.phase_train()
        if "serve" in self.phases:
            served = self.phase_serve()
        if "kernels" in self.phases:
            self.phase_kernels()
        if "latent" in self.phases:
            self.phase_latent()
        if "hybrid" in self.phases:
            self.phase_hybrid()
        if "multi" in self.phases:
            if self.ndev >= 4:
                self.phase_multi(served)
            else:
                self.say(f"multi: skipped, it needs >= 4 devices and this "
                         f"host has {self.ndev}")
        self.check(not multiprocessing.active_children(),
                   "end: no child process is left running")
        _, n1 = self.cache_entries()
        self.say(f"end: all phases passed in "
                 f"{time.perf_counter() - self.t0:.1f}s wall; compile cache "
                 f"{cdir} holds {n1} entries ({n1 - n0} added by this run)")
        print(json.dumps({"ok": True, "device": {
            "platform": self.platform, "kind": self.kind,
            "count": self.ndev}}), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU backend, interpret-mode "
                         "kernels; prints platform=cpu; debugging only")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of " + ",".join(PHASES)
                         + " (multi is skipped with fewer than 4 devices)")
    args = ap.parse_args()
    phases = [p for p in args.phases.split(",") if p]
    if set(phases) - set(PHASES):
        ap.error(f"unknown phases {sorted(set(phases) - set(PHASES))}")
    if args.cpu_rehearsal:
        jax.config.update("jax_platforms", "cpu")
    smoke = Smoke(args.cpu_rehearsal, phases)
    if smoke.platform != "tpu" and not args.cpu_rehearsal:
        print(f"{smoke.tag} no TPU: jax found only {smoke.platform} "
              f"devices; refusing to run (--cpu-rehearsal debugs the "
              f"control flow on the CPU)", file=sys.stderr, flush=True)
        sys.exit(2)
    smoke.run()


if __name__ == "__main__":
    main()
