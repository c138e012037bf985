"""The program's own spans, step records and device names (ISSUE 25).

Host side: the fit loop's step ring (`profiler/step_log.py` FitRecord) tiles
each step's mark-to-mark wall exactly; the engine's StepRecord splits launch
from wait inside `decode_ms` / `prefill_ms` and carries how long the admitted
had queued, with the six attribution buckets as they were; every bucket that
had no span has one. Device side: the jitted programs of the two benchmark
cells carry names fixed on purpose and their operations carry scopes. And
`Profiler(log_dir=...)` fails loudly.
"""
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn, serving
from paddle_tpu.models import (ErnieConfig, ErnieForPretraining, GPTConfig,
                               GPTForCausalLM)
from paddle_tpu.profiler import Profiler, spans, step_log, tracer

FIT_BUCKETS = ("input_wait_ms", "prep_ms", "dispatch_ms", "sync_ms",
               "callback_ms", "other_ms")


class Rows(paddle.io.Dataset):
    def __init__(self, n, sleep_s=0.0):
        self.n, self.sleep_s = n, sleep_s

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if self.sleep_s:
            time.sleep(self.sleep_s)
        rng = np.random.RandomState(i)
        return rng.rand(8).astype("float32"), np.array([i % 2], "int64")


def tiny_fit(workers, sleep_s=0.0, rows=48):
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.Adam(1e-3, parameters=net.parameters()),
                  nn.CrossEntropyLoss())
    before = {r["fit"] for r in step_log.fit_records()}
    model.fit(Rows(rows, sleep_s), batch_size=8, epochs=2, verbose=0,
              num_workers=workers, log_freq=3, shuffle=False)
    recs = step_log.fit_records()
    ours = max(r["fit"] for r in recs)
    assert ours not in before
    return [r for r in recs if r["fit"] == ours]


# -- the fit ring ------------------------------------------------------------

@pytest.mark.parametrize("workers", [0, 2])
def test_fit_buckets_sum_to_the_wall_exactly(workers):
    recs = tiny_fit(workers)
    assert len(recs) == 12                      # 2 epochs x 6 steps
    assert [r["step"] for r in recs] == list(range(6)) * 2
    for r in recs:
        assert abs(sum(r[k] for k in FIT_BUCKETS) - r["wall_ms"]) < 1e-9, r
        assert all(r[k] >= 0 for k in FIT_BUCKETS if k != "other_ms"), r
        assert r["other_ms"] > -0.01, r         # the rounding's slack only
        assert r["dispatch_ms"] > 0
    # the log cadence is the only place the loop waits for the device
    assert all(r["sync_ms"] == 0 for r in recs if r["step"] % 3)
    assert all(a["t"] < b["t"] for a, b in zip(recs, recs[1:]))


def test_input_wait_is_the_larger_part_when_the_dataset_sleeps():
    recs = tiny_fit(0, sleep_s=0.01)[1:]        # the first step compiles
    wall = sum(r["wall_ms"] for r in recs)
    waited = sum(r["input_wait_ms"] for r in recs)
    assert waited > 0.5 * wall, (waited, wall)


def test_the_rings_share_one_bounded_ring():
    log = step_log.FitLog(capacity=4)
    clock = step_log.FitClock(log)
    for step in range(10):
        with clock.span("prep_ms"):
            pass
        clock.close(step)
    kept = log.snapshot()
    assert [r.step for r in kept] == [6, 7, 8, 9] and log.recorded == 10
    assert isinstance(log, step_log._Ring)
    assert issubclass(step_log.StepLog, step_log._Ring)


# -- the engine's step record ------------------------------------------------

@pytest.fixture(scope="module")
def gpt():
    paddle.seed(25)
    net = GPTForCausalLM(GPTConfig.tiny(dropout=0.0))
    net.eval()
    return net


def tiny_engine(gpt, name, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("prefill_buckets", (16,))
    return serving.GenerationEngine(gpt, name=name, **kw)


def test_new_step_fields_are_appended_after_the_old():
    # the device timeline's four after the hybrid family's three (its two
    # device counters, the engine's prefill tokens) after `ahead` after the
    # latent family's two device counters after the three waits, each era
    # appended to the one before
    assert step_log._FIELDS[-4:] == ("decode_dev_ms", "prefill_dev_ms",
                                     "dev_idle_ms", "dev_idle_by")
    assert step_log._FIELDS[-7:-4] == ("state_slots", "kv_rows",
                                       "prefill_tokens")
    assert step_log._FIELDS[-8] == "ahead"
    assert step_log._FIELDS[-10:-8] == ("experts_hit", "latent_rows")
    assert step_log._FIELDS[-13:-10] == ("decode_wait_ms", "prefill_wait_ms",
                                         "admit_wait_ms")
    assert step_log._FIELDS[-14] == "attr_wall_ms"
    assert list(step_log.StepRecord().to_dict()) == list(step_log._FIELDS)


def test_waits_are_sub_splits_and_admit_wait_matches_the_stamps(
        gpt, monkeypatch):
    finished = []
    real = spans.GenSpan.finish

    def finish(self, *a, **kw):
        finished.append(dict(self.stamps))
        return real(self, *a, **kw)
    monkeypatch.setattr(spans.GenSpan, "finish", finish)
    eng = tiny_engine(gpt, "spans_waits")
    try:
        futs = [eng.submit(np.arange(6, dtype=np.int64) + i,
                           max_new_tokens=6) for i in range(5)]
        for f in futs:
            f.result(timeout=120)
        recs = step_log.steps_payload()["engines"]["spans_waits"]["records"]
    finally:
        eng.shutdown()
    assert len(finished) == 5
    for r in recs:
        assert 0 <= r["decode_wait_ms"] <= r["decode_ms"], r
        assert 0 <= r["prefill_wait_ms"] <= r["prefill_ms"], r
        if r["attr_wall_ms"] > 0:       # the six buckets, as they were
            total = (r["attr_admit_ms"] + r["prefill_ms"]
                     + r["attr_promote_ms"] + r["decode_ms"]
                     + r["attr_bookkeep_ms"] + r["attr_idle_ms"])
            assert abs(total - r["attr_wall_ms"]) < 1e-9, r
        assert (r["admit_wait_ms"] > 0) == (r["admitted"] > 0), r
    assert sum(r["decode_wait_ms"] for r in recs) > 0
    assert sum(r["prefill_wait_ms"] for r in recs) > 0
    assert sum(r["admitted"] for r in recs) == 5
    stamped = sum(s["admitted"] - s["queued"] for s in finished) * 1e3
    recorded = sum(r["admit_wait_ms"] for r in recs)
    # each iteration's sum is rounded to a microsecond once
    assert abs(recorded - stamped) < 1e-3 * len(recs), (recorded, stamped)


# -- span names --------------------------------------------------------------

def test_every_new_span_shows_in_the_tracer_after_a_fit_and_a_request(gpt):
    tracer.clear()
    tiny_fit(2, rows=16)
    eng = tiny_engine(gpt, "spans_names")
    try:
        eng.submit(np.arange(5, dtype=np.int64), max_new_tokens=4) \
            .result(timeout=120)
    finally:
        eng.shutdown()
    names = {n for n, _, _ in tracer.events()}
    for want in ("fit::input_wait", "fit::train_step", "fit::sync",
                 "fit::callbacks", "feeder::fetch", "feeder::stage",
                 "generation::admit", "generation::prepare",
                 "generation::idle",
                 "generation::record", "generation::deliver",
                 "generation::submit"):
        assert want in names, (want, sorted(names))


# -- names on the device -----------------------------------------------------

def scopes_of(lowered):
    txt = lowered.as_text(debug_info=True)
    module = re.search(r"module @(\S+)", txt).group(1)
    return module, set(re.findall(r'loc\("([^"]+)"', txt))


def has(stacks, part):
    return any(part in s for s in stacks)


def test_the_train_step_carries_its_name_and_scopes():
    paddle.seed(0)
    cfg = ErnieConfig(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                      num_attention_heads=2, intermediate_size=64,
                      max_position_embeddings=32)
    net = ErnieForPretraining(cfg)

    def loss(mlm, nsp, mlm_labels, nsp_labels):
        v = mlm.shape[-1]
        return (F.cross_entropy(mlm.reshape([-1, v]),
                                mlm_labels.reshape([-1]), ignore_index=-100)
                + F.cross_entropy(nsp, nsp_labels))
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.AdamW(1e-3, parameters=net.parameters()),
                  loss)
    rng = np.random.RandomState(0)
    ids = rng.randint(5, 96, (4, 16)).astype("int64")
    mlm = np.where(rng.rand(4, 16) < 0.2, ids, -100).astype("int64")
    nsp = np.zeros((4,), "int64")
    model._in_fit = True            # keep the carry live, as fit does
    try:
        model.train_batch([ids], [mlm, nsp])
        (fn,) = model._train_step_cache.values()
        module, stacks = scopes_of(fn.lower(
            model._train_carry, jax.random.PRNGKey(0),
            jnp.asarray(1, "int32"), jnp.asarray(1e-3, "float32"),
            (jnp.asarray(ids),), (jnp.asarray(mlm), jnp.asarray(nsp)), None))
    finally:
        model._in_fit = False
    assert module == "jit_train_step"
    root = "jit(train_step)/jvp(forward)/ErnieForPretraining/ernie/encoder/"
    assert has(stacks, root + "layers/0/self_attn/")
    assert has(stacks, root + "layers/1/linear1/")
    assert has(stacks, "transpose(jvp(forward))/ErnieForPretraining/ernie/"
                       "encoder/layers/0/self_attn/")
    assert has(stacks, "jit(train_step)/optimizer/")
    assert has(stacks, "jvp(loss)/")
    model.eval_batch([ids], [mlm, nsp])
    (fn,) = model._eval_step_cache.values()
    assert fn.__name__ == "eval_step"


def test_a_list_of_layers_passes_its_name_on_to_its_items():
    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.blocks = nn.LayerList([nn.Linear(4, 4), nn.Linear(4, 4)])
            self.head = nn.Linear(4, 2)

        def forward(self, x):
            for b in self.blocks:
                x = b(x)
            return self.head(x)
    net = Net()
    net.blocks.append(nn.Linear(4, 4))
    assert [b._scope_name for b in net.blocks] == [
        "blocks/0", "blocks/1", "blocks/2"]
    assert net.head._scope_name == "head"
    from paddle_tpu.framework.functional import functionalize
    apply_fn, pv, bv = functionalize(net)
    _, stacks = scopes_of(jax.jit(
        lambda p, x: apply_fn(p, bv, jax.random.PRNGKey(0), False, x)[0]
    ).lower(pv, jnp.ones((2, 4))))
    assert has(stacks, "Net/blocks/2/") and has(stacks, "Net/head/")


@pytest.fixture(scope="module")
def lowered_programs(gpt):
    eng = tiny_engine(gpt, "spans_lower", prefix_cache=True, spec_k=2,
                      kv_tier=True)
    try:
        W, pools = eng._W, eng._pools()
        C = eng._cfg.kv_tier_chunk_pages
        # a chunk of C whole pages stacked in front, as the host tier
        # holds them: a page is the pool with its page axis cut out
        page = eng._cache.form.pages(pools[0], 0).shape
        chunk = np.zeros((C,) + page, pools[0].dtype)
        ids = np.zeros((1, 16), np.int32)
        row = np.zeros((eng._cfg.pages_per_seq,), np.int32)
        five, four = np.int32(5), np.int32(4)
        return {
            "gen_decode": scopes_of(eng._decode_jit.lower(
                W, *pools, *eng._step_arrays())),
            "gen_prefill": scopes_of(eng._prefill_jit.lower(
                W, *pools, row, ids, five)),
            "gen_prefill_tail": scopes_of(eng._tail_jit.lower(
                W, *pools, row, ids, five, four)),
            "gen_zero_pages": scopes_of(eng._zero_jit.lower(*pools, row)),
            "gen_cow_copy": scopes_of(eng._cow_jit.lower(
                *pools, np.int32(1), np.int32(2))),
            "gen_verify": scopes_of(eng._verify_jit.lower(
                W, *pools, *eng._spec_arrays()[0])),
            "gen_tier_gather": scopes_of(eng._tier_gather_jit.lower(
                *pools, np.int32(1))),
            "gen_tier_write": scopes_of(eng._tier_write_jit.lower(
                *pools, np.zeros((C,), np.int32), chunk, chunk)),
        }
    finally:
        eng.shutdown()


def test_gen_decode_on_the_gather_side_of_the_pool_rule_keeps_its_scopes(gpt):
    """A pool of more pages than max_slots x pages_per_seq takes the
    gather reference (`ops/paged_ops.py`): `kv_gather` under each layer,
    no `kv_mask`."""
    eng = tiny_engine(gpt, "spans_gather", num_pages=72, warmup=False)
    try:
        assert eng.stats()["decode_attention"] == "reference"
        module, stacks = scopes_of(eng._decode_jit.lower(
            eng._W, *eng._pools(), *eng._step_arrays()))
    finally:
        eng.shutdown()
    assert module == "jit_gen_decode"
    for s in ("layer_0/attn/kv_write/", "layer_0/attn/kv_gather/",
              "layer_1/attn/kv_attend/"):
        assert has(stacks, f"/{s}"), (s, sorted(stacks)[:40])
    assert not has(stacks, "kv_mask/")


@pytest.mark.parametrize("program, scopes", [
    # the fixture's engine (64 pages = 2 slots x 32 entries) is pool-dense:
    # one `kv_mask` at the program's top, no gather
    ("gen_decode", ("embed/", "layer_0/attn/kv_write/", "kv_mask/",
                    "layer_0/attn/kv_attend/", "layer_1/mlp/", "lm_head/",
                    "sample/")),
    ("gen_prefill", ("embed/", "layer_0/attn/", "layer_1/mlp/", "kv_write/",
                     "lm_head/")),
    ("gen_prefill_tail", ("kv_gather/", "layer_0/attn/kv_attend/",
                          "layer_0/mlp/", "kv_write/", "lm_head/")),
    ("gen_verify", ("embed/", "layer_0/attn/kv_gather/",
                    "layer_0/attn/kv_attend/", "layer_1/mlp/", "lm_head/",
                    "kv_write/")),
    ("gen_zero_pages", ()),
    ("gen_cow_copy", ()),
    ("gen_tier_gather", ()),
    ("gen_tier_write", ()),
])
def test_an_engine_program_carries_its_name_and_scopes(lowered_programs,
                                                       program, scopes):
    module, stacks = lowered_programs[program]
    assert module == "jit_" + program
    if program == "gen_decode":
        assert not has(stacks, "kv_gather/") \
            and not has(stacks, "attn/kv_mask/")
    for s in scopes:
        assert has(stacks, f"jit({program})/{s}") \
            or has(stacks, f"/{s}"), (s, sorted(stacks)[:40])


# -- Profiler(log_dir=...) ---------------------------------------------------

def test_a_device_trace_that_cannot_start_raises(tmp_path, monkeypatch):
    def refuse(*a, **kw):
        raise RuntimeError("no trace for you")
    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    prof = Profiler(log_dir=str(tmp_path))
    with pytest.raises(RuntimeError, match="no trace for you"):
        prof.start()
    assert not tracer.profiler_enabled()


def test_the_python_tracer_is_off_and_stop_survives_a_failing_step(
        tmp_path, monkeypatch):
    seen = {}

    def start(log_dir, profiler_options=None, **kw):
        seen["dir"], seen["options"] = log_dir, profiler_options
    monkeypatch.setattr(jax.profiler, "start_trace", start)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: seen.__setitem__("stopped", True))
    prof = Profiler(log_dir=str(tmp_path)).start()
    assert seen["dir"] == str(tmp_path)
    assert seen["options"].python_tracer_level == 0

    def boom():
        raise ValueError("step failed")
    monkeypatch.setattr(prof, "step", boom)
    with pytest.raises(ValueError, match="step failed"):
        prof.stop()
    assert seen.get("stopped") and not tracer.profiler_enabled()
