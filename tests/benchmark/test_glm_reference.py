"""CPU tests of what PR 27 added to the yardstick: the plain float32
reference of GLM-4.7-Flash against the repo's model (the Layer's forward, and
the engine's prefill + paged decode programs, LOGITS), the tolerance that
bfloat16 earns and a lower precision fails, the byte counts against
hand-worked numbers, the two new readers on hand-made records, the
configuration file against the catalog's published numbers, and both
rehearsals of the new cell. Nothing here measures anything.
"""
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check_line, flops_moe_mla, run  # noqa: E402
from benchmark.program_records import NO_RECORD  # noqa: E402

CELL = "glm-4.7-flash.reasoning-saturated"


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config():
    return run.load_json("benchmark/configs/glm-4.7-flash.json")


def model_kwargs():
    c = config()
    kw = {k: c[v] for k, v in c["run"]["config_kwargs"].items()}
    kw.update(c["run"]["config_overrides"])
    return kw


@pytest.fixture(scope="module")
def ref():
    return run.load_by_name("reference", "glm-4.7-flash")


def build(dtype, seed=27, **kw):
    import paddle_tpu as paddle
    from paddle_tpu.models import GlmMoeLiteConfig, GlmMoeLiteForCausalLM
    paddle.seed(seed)
    cfg = GlmMoeLiteConfig.tiny(dtype=dtype, **kw)
    net = GlmMoeLiteForCausalLM(cfg)
    net.eval()
    return cfg, net


def ids_for(cfg, shape, seed=0):
    return np.random.RandomState(seed).randint(
        0, cfg.vocab_size, shape).astype("int32")


def rel_rms(got, want):
    d = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    return float(np.sqrt((d * d).mean()) / np.asarray(want).std())


# -- the Layer's forward against the reference --------------------------------

def test_forward_agrees_with_the_reference_in_float32(ref):
    """Same weights, same float32: the two differ only in the order of
    float32 sums (the model's grouped expert product against the
    reference's loop over all experts, einsum contractions), about 1e-7
    per sum on logits of std 0.16 — 1e-4 is a thousand roundings wide and
    a hundred times under what a wrong expert, gate or rope pairing gives
    (0.01 and up)."""
    import paddle_tpu as paddle
    cfg, net = build("float32")
    ids = ids_for(cfg, (2, 24))
    got = net(paddle.to_tensor(ids)).numpy()
    want = np.asarray(ref.forward(ref.weights(net.state_dict()), ids,
                                  cfg.num_heads,
                                  top_k=cfg.num_experts_per_tok))
    assert got.dtype == np.float32 and got.shape == (2, 24, cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=1e-4)
    # the reference's published constants are the configuration's
    c = config()
    assert (ref.TOP_K, ref.ROUTED_SCALE, ref.ROPE_THETA, ref.EPS) == (
        c["num_experts_per_tok"], c["routed_scaling_factor"],
        c["rope_theta"], c["rms_norm_eps"])


def test_bfloat16_earns_its_tolerance_and_eight_bits_fail_it(ref):
    """The served precision: bfloat16 weights and activations, float32
    accumulation, against the float32 reference over the SAME bfloat16
    weights. The difference is activation rounding (2^-9 relative, a few
    dozen roundings deep) plus the odd expert flipped at a near-tie: read
    here 0.5-0.8% of the logits' std, limit 2%. The stand-in for a lower
    precision — the same model with every weight rounded to float8 (e4m3:
    3 mantissa bits against bfloat16's 7) — reads 4-6% and fails it."""
    import paddle_tpu as paddle
    cfg, net = build("bfloat16")
    ids = ids_for(cfg, (2, 24))
    W = ref.weights(net.state_dict())
    assert W["model.layers.1.mlp.experts.gate_proj"].dtype == jnp.bfloat16
    want = np.asarray(ref.forward(W, ids, cfg.num_heads,
                                  top_k=cfg.num_experts_per_tok))
    got = net(paddle.to_tensor(ids)).numpy()
    err = rel_rms(got, want)
    assert 0 < err < 0.02, err
    for p in net.parameters():       # the stand-in: 8-bit weights
        p._value = p._value.astype(jnp.float8_e4m3fn).astype(p._value.dtype)
    low = rel_rms(net(paddle.to_tensor(ids)).numpy(), want)
    assert low > 0.02 and low > 3 * err, (err, low)


# -- prefill + paged decode against the reference's full forward --------------

def test_prefill_then_paged_decode_give_the_references_logits(ref):
    """The engine's own prefill program, then `latent_decode` (the decode
    program's body before sampling) token by token through the latent
    pages — LOGITS, against the reference's one full forward. Three slots
    of different lengths in every step: a prompt that ends inside a page,
    one that ends ON a page boundary, one that fills its bucket; float32,
    so the tolerance is the forward's."""
    from paddle_tpu import serving
    from paddle_tpu.serving.latent_family import latent_decode
    cfg, net = build("float32")
    page, bucket, steps, lengths = 4, 16, 6, (7, 12, 16)
    prompts = [ids_for(cfg, (n,), seed=n) for n in lengths]
    eng = serving.GenerationEngine(
        net, name="glm_logits", max_slots=3, page_size=page, num_pages=18,
        pages_per_seq=6, prefill_buckets=(bucket,), max_new_tokens=steps,
        warmup=False)
    try:
        assert eng.stats()["decode_attention"] == "latent_gather"
        W = eng._W
        pt = np.stack([eng._cache.alloc(i, n + steps)
                       for i, n in enumerate(lengths)])
        first = []
        for i, p in enumerate(prompts):
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :len(p)] = p
            out = eng._prefill_jit(W, *eng._pools(), pt[i], ids,
                                   np.int32(len(p)))
            eng._set_pools(out[:-1])
            first.append(np.asarray(out[-1]))
        pool = eng._pools()[0]
        table = jnp.asarray(pt)
        step = jax.jit(lambda W, pool, tok, pos: latent_decode(
            W, pool, table, tok, pos, jnp.ones(3, bool), cfg, page))
        logits, toks = [np.stack(first)], []
        for k in range(steps):
            toks.append(logits[-1].argmax(-1).astype(np.int32))
            lg, pool, hit, rows = step(W, pool, toks[-1],
                                       np.asarray(lengths, np.int32) + k)
            logits.append(np.asarray(lg))
            assert int(rows) == sum(lengths) + 3 * (k + 1)
            assert 0 < int(hit) <= cfg.n_routed_experts \
                * cfg.num_expert_layers
    finally:
        eng.shutdown(drain=False)
    got = np.stack(logits, 1)                          # [3, steps + 1, V]
    RW = ref.weights(net.state_dict())
    for i, p in enumerate(prompts):
        seq = np.concatenate([p, [t[i] for t in toks]])[None]
        want = np.asarray(ref.forward(RW, seq, cfg.num_heads,
                                      top_k=cfg.num_experts_per_tok))[0]
        np.testing.assert_allclose(got[i], want[len(p) - 1:], atol=1e-4)


def test_shortfalls_of_the_models_own_greedy_tokens_are_zero(ref):
    import paddle_tpu as paddle
    cfg, net = build("float32")
    W = ref.weights(net.state_dict())
    ids = ids_for(cfg, (1, 20))
    logits = net(paddle.to_tensor(ids)).numpy()[0]
    seq = list(ids[0, :10]) + [int(logits[9].argmax())]
    wrong = list(ids[0, :10]) + [int(logits[9].argmin())]
    s = ref.token_shortfalls(W, [seq, wrong], [10, 10], cfg.num_heads,
                             pad_to=16, top_k=cfg.num_experts_per_tok)
    assert len(s) == 2 and s[0].shape == (1,) and s[0][0] == 0.0
    assert s[1][0] == pytest.approx(logits[9].max() - logits[9].min(),
                                    abs=1e-3)
    # a sequence longer than one head block is cut into blocks, not lost
    long = list(ids_for(cfg, (40,), seed=3))
    ref.HEAD_BLOCK, was = 16, ref.HEAD_BLOCK
    try:
        a = ref.token_shortfalls(W, [long], [3], cfg.num_heads, pad_to=16,
                                 top_k=cfg.num_experts_per_tok)[0]
    finally:
        ref.HEAD_BLOCK = was
    full = np.asarray(ref.forward(W, np.asarray([long]), cfg.num_heads,
                                  top_k=cfg.num_experts_per_tok))[0]
    want = full[2:-1].max(-1) - full[np.arange(2, 39), long[3:]]
    assert a.shape == (37,)
    np.testing.assert_allclose(a, want, atol=1e-5)


def test_the_second_limit_rides_in_what_the_driver_takes_the_largest_of(
        ref, capsys):
    """`drivers/serve.py` holds `max(concatenate(shortfalls(...)))` to
    `near_margin` and knows no second limit: the share of tokens that are
    not the reference's argmax comes as one more entry that passes
    `near_margin` exactly when the share passes `disagree_limit`."""
    import paddle_tpu as paddle
    cfg, net = build("float32")
    W = ref.weights(net.state_dict())
    margin, limit = ref.limits()
    assert (margin, limit) == (config()["run"]["near_margin"],
                               config()["run"]["disagree_limit"])
    prompt = ids_for(cfg, (10,))
    kw = dict(pad_to=16, top_k=cfg.num_experts_per_tok)

    def folded(n_wrong):
        # 20 tokens generated after a prompt of 10, each the model's own
        # greedy choice given what came before, but for the first `n_wrong`,
        # which are the runner-up: the share is n_wrong / 20 exactly
        seq = list(prompt)
        for t in range(20):
            logits = net(paddle.to_tensor(np.asarray([seq]))).numpy()[0, -1]
            seq.append(int(np.argsort(logits)[-2 if t < n_wrong else -1]))
        out = ref.shortfalls(W, [seq], [10], cfg.num_heads, **kw)
        tokens = ref.token_shortfalls(W, [seq], [10], cfg.num_heads, **kw)
        assert len(out) == 2 and out[1].shape == (1,)
        np.testing.assert_array_equal(out[0], tokens[0])
        assert float(np.mean(tokens[0] > 0)) == n_wrong / 20
        return float(out[1][0])

    assert folded(0) == 0.0
    passes = folded(3)
    assert passes == pytest.approx(margin * 0.15 / limit) and passes < margin
    assert folded(5) > margin                     # the driver says WRONG
    assert "are not the reference's argmax" in capsys.readouterr().out


# -- operations and bytes -----------------------------------------------------

def test_decode_bytes_against_hand_worked_numbers():
    m = model_kwargs()
    # 2048x768 + 768 + 768x20x256 + 2048x576 + 512 + 512x20x448 + 5120x2048
    assert flops_moe_mla.mla_params(m) == (
        1_572_864 + 768 + 3_932_160 + 1_179_648 + 512 + 4_587_520
        + 10_485_760) == 21_759_232
    assert flops_moe_mla.expert_params(m) == 3 * 2048 * 1536 == 9_437_184
    assert flops_moe_mla.expert_bytes(m) == 18_874_368        # 18.87 MB
    assert flops_moe_mla.latent_row_bytes(m) == 1_152         # 576 x 2 B
    assert flops_moe_mla.expert_layers(m) == 6
    # 7 x (MLA + two norms) + dense 3 x 2048 x 10240 + 6 x (shared expert
    # + 2048 x 64 router) + final norm + 2048 x 154880 head
    fixed = (7 * (21_759_232 + 4_096) + 62_914_560
             + 6 * (9_437_184 + 131_072) + 2_048 + 317_194_240)
    assert flops_moe_mla.fixed_decode_params(m) == fixed == 589_863_680
    # a step that hits 55 experts in each of 6 layers over 50,000 rows
    assert flops_moe_mla.decode_step_bytes(m, 330, 50_000) == (
        2 * fixed + 330 * 18_874_368 + 50_000 * 7 * 1_152) == 7_811_468_800


def step(decode_ms, hit, rows, live=30):
    return {"decode_ms": decode_ms, "experts_hit": hit, "latent_rows": rows,
            "live": live}


def test_the_two_readers_on_hand_made_records():
    m = model_kwargs()
    rec = {"model": m, "device_kind": "TPU v5 lite", "steps": [
        step(20.0, 330, 50_000), step(40.0, 330, 50_000),
        step(10.0, 330, 50_000), step(0.0, 0, 0)]}
    roof = run.load_by_name("metrics", "moe_decode_roofline.serve")
    hits = run.load_by_name("metrics", "experts_hit.serve")
    # 7,811,468,800 B / 819e9 B/s = 9.5378 ms; median over 20, 40, 10 ms
    assert roof.read(rec) == pytest.approx(100 * 9.53781 / 20.0, rel=1e-5)
    assert hits.read(rec) == pytest.approx(100 * 330 / 384)
    idle = dict(rec, steps=[step(0.0, 0, 0)])
    assert roof.read(idle) is None and hits.read(idle) is None
    old = dict(rec, steps=[{"decode_ms": 20.0, "live": 3}])
    assert roof.read(old) == NO_RECORD and hits.read(old) == NO_RECORD


# -- the configuration and the manifest ---------------------------------------

def test_the_configuration_keeps_every_published_number():
    catalog = {  # the catalog row's `config` (model-configs guide)
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    c = config()
    differ = sorted(k for k, v in catalog.items() if c.get(k, "absent") != v)
    assert differ == sorted(c["reduced"]) == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    assert c["published"] == {k: catalog[k] for k in c["reduced"]}
    assert c["num_hidden_layers"] == 7 and c["kind"] == "serve"
    entry = next(e for e in manifest()["configs"]
                 if e["name"] == "glm-4.7-flash")
    assert entry["reduced"] == c["reduced"] and len(c["source"]) <= 200
    assert entry["source"] in c["source"]
    assert {"assumed", "deployment", "precision"} <= set(c)
    assert "num_heads" in c["run"]["config_kwargs"]       # serve.py reads it
    eng = c["run"]["engine"]
    assert (eng["max_slots"], eng["page_size"], eng["num_pages"]) == \
        (32, 16, 8192)
    assert eng["num_pages"] == eng["max_slots"] * eng["pages_per_seq"]


def test_the_cell_and_its_metrics_are_in_the_manifest():
    m = manifest()
    check_line.check_manifest(m, ROOT)
    cell = check_line.cell_of(m, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reasoning-saturated"
    names = [x["name"] for x in check_line.metrics_of(m, CELL, "per_layer")]
    assert names == ["prefill_share.serve", "batch_occupancy.serve",
                     "decode_step_ms.serve", "host_step_ms.serve",
                     "queue_wait_ms.serve", "moe_decode_roofline.serve",
                     "experts_hit.serve"]
    by = {p["name"]: p for p in m["per_layer"]}
    assert CELL not in by["decode_roofline.serve"]["workloads"]
    for name in ("moe_decode_roofline.serve", "experts_hit.serve"):
        assert by[name]["workloads"] == [CELL]
        assert by[name]["moves"] == "serve_tokens_per_s"
    mix = run.load_json("benchmark/traffic/reasoning-saturated.json")
    assert (mix["clients"], mix["pool"], mix["max_total_tokens"]) == \
        (48, 192, 4096)
    from benchmark import trafficgen
    prompt, out = trafficgen.size_pool(mix, mix["pool"])
    assert prompt.min() >= 32 and prompt.max() <= 2048
    assert out.min() >= 64 and (prompt + out).max() <= 4096


# -- run.py --rehearse of the new cell ----------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_of_the_cell_prints_a_line_the_driver_reads(trace):
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 27), "--seconds", "2",
         "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, PYTHONPATH=ROOT), cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    m = manifest()
    check_line.check_line(m, CELL, trace, line)
    assert line["correct"] and line["attempted"] > 0 and not line["failed"]
    group = "per_layer" if trace else "end_to_end"
    for x in check_line.metrics_of(m, CELL, group):
        v = line["metrics"][x["name"]]["value"]
        assert math.isfinite(v) and v > 0, (x["name"], v)
