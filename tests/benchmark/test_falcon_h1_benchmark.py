"""CPU tests of what PR 36 adds to the benchmark: the `falcon-h1-34b`
configuration against the catalog's row, the `chat-saturated` mix, the byte
and FLOP counts of `benchmark/flops_hybrid.py` against hand-worked numbers,
the two readers on hand-made records, on an empty window and on an older
program's records, the plain reference against the repo's model at a small
size, and the cell's rehearsal end to end. Nothing here measures anything.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check_line, flops_hybrid, run, trafficgen  # noqa: E402
from benchmark.program_records import NO_RECORD  # noqa: E402

CELL, CONFIG = "falcon-h1-34b.chat-saturated", "falcon-h1-34b"
SHARED = ("prefill_share.serve", "batch_occupancy.serve",
          "decode_step_ms.serve", "host_step_ms.serve", "queue_wait_ms.serve")
NEW = ("hybrid_decode_roofline.serve", "prefill_mfu.serve")
# the catalog's row (model-configs guide, architectures.jsonl): every number
# the published config.json gives
PUBLISHED = {
    "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_d_conv": 4, "mamba_d_head": 128, "mamba_d_ssm": 4096,
    "mamba_d_state": 256, "mamba_expand": 2, "mamba_n_groups": 2,
    "mamba_n_heads": 32, "max_position_embeddings": 262144,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "num_attention_heads": 20, "num_hidden_layers": 72,
    "num_key_value_heads": 4, "num_logits_to_keep": 1,
    "rms_norm_eps": 1e-05, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "vocab_size": 261120}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config():
    return run.load_json(f"benchmark/configs/{CONFIG}.json")


def model_kwargs():
    cfg = config()
    m = {kw: cfg[key] for kw, key in cfg["run"]["config_kwargs"].items()}
    m.update(cfg["run"]["config_overrides"])
    return m


# -- the manifest and the data files -----------------------------------------

def test_the_cell_its_configuration_and_its_metrics_are_entries_at_the_end():
    m = manifest()
    check_line.check_manifest(m, ROOT)
    assert m["configs"][-1]["name"] == CONFIG
    assert m["configs"][-1]["reduced"] == ["num_hidden_layers"]
    cell = m["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, CONFIG, "chat-saturated", 1)
    assert [p["name"] for p in m["per_layer"][-2:]] == list(NEW)
    for p in m["per_layer"][-2:]:
        assert p == {"name": p["name"], "unit": "%", "better": "higher",
                     "source": "program_span", "layer": "kernels",
                     "moves": "serve_tokens_per_s", "workloads": [CELL]}
    reported = {x["name"] for x in check_line.metrics_of(m, CELL,
                                                         "per_layer")}
    assert reported == set(SHARED) | set(NEW)
    assert {x["name"] for x in check_line.metrics_of(m, CELL, "end_to_end")} \
        == {"serve_tokens_per_s", "setup_s"}
    assert not any(w["chips"] == 4 for w in m["workloads"])


def test_the_configuration_has_every_published_number_and_cuts_only_depth():
    cfg = config()
    for key, value in PUBLISHED.items():
        if key == "num_hidden_layers":
            assert (cfg[key], cfg["published"][key]) == (6, value)
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["model_type"] == "falcon_h1"
    for key in ("assumed", "deployment", "precision", "run", "rehearsal"):
        assert cfg[key], key
    assert "float32" in cfg["assumed"]["state_dtype"]
    # the constructor takes each width under its published key
    m = model_kwargs()
    assert (m["num_heads"], m["num_key_value_heads"], m["head_dim"]) \
        == (20, 4, 128)
    from paddle_tpu.models import FalconH1Config
    c = FalconH1Config(**m)
    assert (c.conv_dim, c.in_proj_dim, c.state_shape) \
        == (5120, 9248, (32, 128, 256))


def test_the_assumed_draws_are_the_files_and_reach_the_parameters():
    """What the published config leaves open is drawn as the file's
    `assumed` says (`run.config_overrides`), so that the state a sequence
    carries is most of the mixer's output and the cell's token test sees
    it: D small, the in-projection at four times `initializer_range`, dt
    log-uniform over one decade."""
    cfg = config()
    over = cfg["run"]["config_overrides"]
    assert over == {"dtype": "bfloat16", "mamba_in_proj_range": 0.08,
                    "mamba_d_init": 0.02, "mamba_dt_range": [0.001, 0.01]}
    for key, said in (("mamba_in_proj_range", "mixer_in_proj"),
                      ("mamba_d_init", "mixer_scalars"),
                      ("mamba_dt_range", "mixer_scalars")):
        assert key in cfg["assumed"][said], key
    import paddle_tpu as paddle
    from paddle_tpu.models import FalconH1Config, FalconH1ForCausalLM
    draws = {k: v for k, v in over.items() if k != "dtype"}
    paddle.seed(36)
    net = FalconH1ForCausalLM(FalconH1Config.tiny(
        hidden_size=256, mamba_n_heads=32, mamba_d_head=2, **draws))
    mixer = net.model.layers[0].mamba
    assert np.asarray(mixer.in_proj.weight._value).std() \
        == pytest.approx(0.08, rel=0.05)
    assert np.asarray(net.model.layers[0].self_attn.q_proj.weight._value) \
        .std() == pytest.approx(0.02, rel=0.05)
    np.testing.assert_array_equal(np.asarray(mixer.scalars.D._value),
                                  np.float32(0.02))
    dt = np.log1p(np.exp(np.asarray(mixer.scalars.dt_bias._value,
                                    np.float64)))
    assert 0.001 <= dt.min() and dt.max() <= 0.01 and dt.max() > 2 * dt.min()
    # the constructor's own defaults stay the Mamba-2 convention
    c = FalconH1Config.tiny()
    assert (c.mamba_in_proj_range, c.mamba_d_init, c.mamba_dt_range) \
        == (None, 1.0, (1e-3, 1e-1))


def test_the_cell_is_a_deployment_in_which_slots_bind_and_the_chip_is_full():
    cfg, mix = config(), run.load_json("benchmark/traffic/chat-saturated.json")
    eng = cfg["run"]["engine"]
    assert (eng["max_slots"], eng["page_size"], eng["num_pages"],
            eng["pages_per_seq"]) == (96, 16, 3456, 96)
    assert eng["gc_freeze"] is True and eng["max_new_tokens"] == 512
    assert eng["pages_per_seq"] % 4 == 0        # the paged kernel's block
    assert all(b % cfg["mamba_chunk_size"] == 0
               for b in eng["prefill_buckets"])
    assert (mix["loop"], mix["clients"], mix["pool"], mix["pool_seed"]) \
        == ("closed", 144, 288, 36)
    assert mix["clients"] > eng["max_slots"]          # a queue always waits
    prompt, out = trafficgen.size_pool(mix, mix["pool"])
    total = prompt + out
    assert total.max() <= mix["max_total_tokens"] \
        == eng["pages_per_seq"] * eng["page_size"]
    assert prompt.max() <= max(eng["prefill_buckets"])
    assert out.max() <= eng["max_new_tokens"]
    pages = np.ceil(total / eng["page_size"])
    # slots bind, not pages: every slot's mean reservation and a fifth
    assert eng["max_slots"] * pages.mean() * 1.2 <= eng["num_pages"] - 1
    # what the arguments hold: weights, state, windows, pages
    m = model_kwargs()
    held = (flops_hybrid.decode_weight_bytes(m)
            + 2 * m["hidden_size"] * m["vocab_size"]      # the embedding
            + flops_hybrid.state_bytes(m) * eng["max_slots"]
            + eng["num_pages"] * eng["page_size"] * m["num_hidden_layers"]
            * flops_hybrid.kv_row_bytes(m))
    assert 0.8 * 16e9 < held < 15.75e9


# -- bytes and operations from shapes ----------------------------------------

def test_hybrid_bytes_and_flops_against_hand_worked_numbers():
    m = model_kwargs()
    d, f, V = 5120, 21504, 261120
    attention = d * 2560 + 2 * d * 512 + 2560 * d            # 31,457,280
    mixer = d * 9248 + 4096 * d                               # in, out
    small = 5120 * 4 + 5120 + 4096                            # conv, norm
    assert flops_hybrid.attention_params(m) == attention == 31457280
    assert flops_hybrid.mixer_matmul_params(m) == mixer == 68321280
    assert flops_hybrid.mixer_small_params(m) == small
    assert flops_hybrid.mlp_params(m) == 3 * d * f == 330301440
    layer = attention + mixer + small + 3 * d * f + 2 * d
    assert flops_hybrid.decode_weight_bytes(m) == \
        2 * (6 * layer + d + d * V) + 4 * 96 * 6
    assert flops_hybrid.state_bytes(m) == 6 * 32 * 128 * 256 * 4 == 25165824
    assert flops_hybrid.kv_row_bytes(m) * 6 == 12288
    step = flops_hybrid.decode_step_bytes(m, 96, 96 * 360)
    assert step == flops_hybrid.decode_weight_bytes(m) \
        + 2 * 96 * 25165824 + 96 * 360 * 12288
    assert 13.0e9 < step < 13.2e9           # 16 ms at 819 GB/s
    # prefill: matrices a token, pairs of causal attention, the scan's
    # products, the head once a request; nothing for padding
    per_token = 2 * (attention + mixer + 3 * d * f) + (
        2 * 128 * 2 * 256 + 2 * 128 * 32 * 128 + 4 * 32 * 128 * 256)
    one = flops_hybrid.prefill_flops(m, 200, 1)
    assert one == 6 * (per_token * 200 + 4 * 20 * 128 * 200 * 200 / 2) \
        + 2 * d * V
    # two prompts of 100 have half the pairs of one of 200, two heads
    two = flops_hybrid.prefill_flops(m, 200, 2)
    assert two == one - 6 * 4 * 20 * 128 * 200 * 200 / 4 + 2 * d * V
    assert flops_hybrid.prefill_flops(m, 0, 0) == 0.0


# -- the two readers ----------------------------------------------------------

def step(decode_ms=0.0, slots=0, rows=0, prefill_ms=0.0, tokens=0,
         admitted=0):
    return {"decode_ms": decode_ms, "state_slots": slots, "kv_rows": rows,
            "prefill_ms": prefill_ms, "prefill_tokens": tokens,
            "admitted": admitted, "attr_wall_ms": decode_ms + prefill_ms}


def record(steps):
    return {"steps": steps, "model": model_kwargs(),
            "device_kind": "TPU v5 lite", "engine": config()["run"]["engine"]}


def test_the_decode_roofline_is_the_median_share_of_the_steps_own_bytes():
    m = model_kwargs()
    read = run.load_by_name("metrics", NEW[0]).read
    steps = [step(20.0, 96, 30000), step(25.0, 90, 28000),
             step(40.0, 96, 31000), step(prefill_ms=12.0, tokens=100,
                                         admitted=1)]
    shares = sorted(flops_hybrid.decode_step_bytes(m, s["state_slots"],
                                                   s["kv_rows"]) / 819e9
                    / (s["decode_ms"] / 1e3) for s in steps[:3])
    assert read(record(steps)) == pytest.approx(100.0 * shares[1])
    assert 40 < read(record(steps)) < 100
    assert read(record([steps[-1]])) is None          # no decode step
    old = [{k: v for k, v in s.items() if k not in ("state_slots", "kv_rows")}
           for s in steps]
    assert read(record(old)) == NO_RECORD


def test_prefill_mfu_is_the_windows_flops_over_its_prefill_time():
    m = model_kwargs()
    read = run.load_by_name("metrics", NEW[1]).read
    steps = [step(prefill_ms=14.0, tokens=250, admitted=1),
             step(prefill_ms=30.0, tokens=900, admitted=2),
             step(20.0, 96, 30000)]
    done = (flops_hybrid.prefill_flops(m, 250, 1)
            + flops_hybrid.prefill_flops(m, 900, 2))
    assert read(record(steps)) == pytest.approx(
        100.0 * done / 44e-3 / 197e12)
    assert 0 < read(record(steps)) < 100
    assert read(record([steps[-1]])) is None          # no prefill
    old = [{k: v for k, v in s.items() if k != "prefill_tokens"}
           for s in steps]
    assert read(record(old)) == NO_RECORD


# -- the reference and the rehearsal ------------------------------------------

def test_falcon_reference_agrees_with_the_model_at_the_rehearsals_size():
    """The model in float32 at the rehearsal's widths against the plain
    reference: logits, to rounding (tests/test_falcon_h1.py holds the paged
    path and a heavier state to it)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import FalconH1Config, FalconH1ForCausalLM
    cfg = config()
    small = {k: v for k, v in cfg["rehearsal"].items() if k != "run"}
    m = {kw: small.get(key, cfg[key])
         for kw, key in cfg["run"]["config_kwargs"].items()}
    paddle.seed(3)
    net = FalconH1ForCausalLM(FalconH1Config(**m, dtype="float32"))
    net.eval()
    ref = run.load_by_name("reference", CONFIG)
    ids = np.random.RandomState(3).randint(0, m["vocab_size"], (2, 19))
    got = np.asarray(net(paddle.to_tensor(ids.astype(np.int32)))._value)
    want = np.asarray(ref.forward(ref.weights(net.state_dict()), ids,
                                  m["num_heads"]))
    assert np.abs(got - want).max() < 1e-5 * max(1.0, want.std())
    short = ref.token_shortfalls(
        ref.weights(net.state_dict()),
        [np.concatenate([r, want[i, -1:].argmax(-1)])
         for i, r in enumerate(ids)], [19, 19], m["num_heads"])
    assert [s.tolist() for s in short] == [[0.0], [0.0]]


def test_rehearsal_of_the_cell_prints_a_line_the_driver_reads():
    env = dict(os.environ, PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 36), "--seconds", "2",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    m = manifest()
    check_line.check_line(m, CELL, 1, line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    for name in SHARED + NEW:
        assert np.isfinite(line["metrics"][name]["value"]), name
    for name in NEW:
        assert 0 < line["metrics"][name]["value"] < 100


def test_the_reference_folds_the_mean_limit_into_what_the_driver_holds(
        monkeypatch):
    """The driver holds the LARGEST returned entry to `near_margin`; the
    last entry passes it exactly when the mean shortfall passes
    `mean_limit`."""
    ref = run.load_by_name("reference", CONFIG)
    margin, mean_limit = ref.limits()
    cfg = config()["run"]
    assert (margin, mean_limit) == (cfg["near_margin"], cfg["mean_limit"])
    assert 0 < mean_limit < margin and cfg["check_requests"] == 32
    for scale, passes in ((0.9, True), (1.1, False)):
        tokens = [np.full((50,), scale * mean_limit, np.float32),
                  np.full((150,), scale * mean_limit, np.float32)]
        monkeypatch.setattr(ref, "token_shortfalls", lambda *a, **kw: tokens)
        out = ref.shortfalls(None, None, None, 20)
        assert len(out) == 3 and out[-1].shape == (1,)
        assert float(out[-1][0]) == pytest.approx(scale * margin, rel=1e-5)
        assert bool(np.concatenate(out).max() <= margin) is passes
