"""CPU tests of the benchmark's own yardstick (benchmark/): the manifest and
last-line checks, the trace reduction on hand-made planes, the FLOP and byte
counts against hand-worked numbers, the traffic generator, the plain
references against the repo's models at tiny sizes, and `run.py --rehearse`
end to end. Nothing here measures anything.
"""
import copy
import json
import os
import shutil
import subprocess
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check_line, flops, run, trace_reduce, trafficgen  # noqa: E402


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def data(folder, name):
    with open(os.path.join(ROOT, "benchmark", folder, name + ".json")) as f:
        return json.load(f)


# -- BENCHMARK.json ----------------------------------------------------------

def test_manifest_keeps_the_contract():
    m = manifest()
    check_line.check_manifest(m, ROOT)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    for e in m["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    for c in m["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        cfg = run.load_json(c["file"])
        assert cfg["reduced"] == c["reduced"] and "assumed" in cfg


@pytest.mark.parametrize("breakage, message", [
    (lambda m: m["per_layer"][0].update(moves="setup_x"), "moves"),
    (lambda m: m["workloads"][0].update(chips=4) or
     m["workloads"][1].update(chips=4), "four chips"),
    (lambda m: m["workloads"][0].update(traffic="no-such-mix"), "no file"),
    (lambda m: m["per_layer"][0].update(name="no_such.metric"), "no file"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per s"), "bad unit"),
])
def test_manifest_check_refuses(breakage, message):
    m = manifest()
    breakage(m)
    with pytest.raises(check_line.BadManifest, match=message):
        check_line.check_manifest(m, ROOT)


# -- the last line -----------------------------------------------------------

def good_line(m, cell, trace):
    group = "per_layer" if trace else "end_to_end"
    line = {"correct": True, "attempted": 10, "failed": 0,
            "metrics": {x["name"]: {"value": 1.5, "unit": x["unit"]}
                        for x in check_line.metrics_of(m, cell, group)},
            "device": {"platform": "tpu", "kind": "TPU v5 lite",
                       "count": check_line.cell_of(m, cell)["chips"],
                       "memory_peak_bytes": 1 << 30}}
    if trace:
        line["device"].update(busy_s=0.9, window_s=1.0)
        line["breakdown"] = {"device_ops": [["%fusion.1", 0.5]],
                             "idle_gaps": [["inside the program", 0.1]]}
    return line


@pytest.mark.parametrize("trace", [0, 1])
def test_check_line_accepts_a_good_line(trace):
    m = manifest()
    for w in m["workloads"]:
        check_line.check_line(m, w["name"], trace,
                              good_line(m, w["name"], trace))


def _drop_metric(line):
    line["metrics"].pop(next(iter(line["metrics"])))


@pytest.mark.parametrize("breakage, message", [
    (_drop_metric, "missing from the line"),
    (lambda ln: ln["device"].update(busy_s=0.0), "busy_s"),
    (lambda ln: ln["device"].update(busy_s=3.7), "busy_s"),
    (lambda ln: ln["device"].update(count=4), "count"),
    (lambda ln: ln["device"].pop("memory_peak_bytes"), "memory_peak_bytes"),
    (lambda ln: ln.update(platform="tpu"), "unknown"),
    (lambda ln: next(iter(ln["metrics"].values())).update(value=float("nan")),
     "finite"),
    (lambda ln: next(iter(ln["metrics"].values())).update(unit="furlongs"),
     "unit"),
])
def test_check_line_refuses(breakage, message):
    m = manifest()
    cell = m["workloads"][0]["name"]
    line = good_line(m, cell, 1)
    breakage(line)
    with pytest.raises(check_line.BadLine, match=message):
        check_line.check_line(m, cell, 1, line)


# -- trace reduction ---------------------------------------------------------

MS = 1e6   # nanoseconds


def planes(device_ops, window=(0.0, 100 * MS)):
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ("bench:window", *window),
        ("bench:submit", 40 * MS, 60 * MS)]}]}
    devs = [{"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Modules", "events": [("jit_step", 0.0, 100 * MS)]},
        {"name": "XLA Ops", "events": ops},
        {"name": "Async XLA Ops", "events": [       # in flight, not busy
            ("%copy-start.9", 0.0, 100 * MS),
            ("%all-reduce-start.3", 55 * MS, 70 * MS)]}]}
        for i, ops in enumerate(device_ops)]
    return [host] + devs


def test_busy_is_a_union_clipped_to_the_window():
    ops = [("%fusion.1 = f32[8]", 10 * MS, 30 * MS),
           ("%fusion.2 = f32[8]", 20 * MS, 40 * MS),      # overlaps the first
           ("%all-reduce.3 = f32[8]", 60 * MS, 70 * MS),
           ("%fusion.1 = f32[8]", 90 * MS, 150 * MS),     # runs past the end
           ("%fusion.4 = f32[8]", 200 * MS, 300 * MS)]    # outside
    r = trace_reduce.reduce(planes([ops]), 1)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.050)    # 30 + 10 + 10, not 60 + ...
    assert r["collective0_s"] == pytest.approx(0.015)   # 55..70 in flight
    assert "%copy-start.9" not in dict(map(tuple, r["device_ops"]))
    assert dict(map(tuple, r["device_ops"]))["%fusion.1"] == \
        pytest.approx(0.030)
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["bench:submit"] == pytest.approx(0.020)      # 40..60
    assert gaps["inside the program"] == pytest.approx(0.030)


def test_busy_over_four_devices_is_a_mean_not_a_sum():
    one = [("%fusion.1", 0.0, 80 * MS)]
    r = trace_reduce.reduce(planes([one, one, one, one]), 4)
    assert r["busy_s"] == pytest.approx(0.080) and r["busy_s"] <= \
        r["window_s"]
    assert len(r["per_device_busy_s"]) == 4


@pytest.mark.parametrize("device_ops, n", [
    ([], 1),                                         # no device plane
    ([[("%fusion.1", 0.0, 80 * MS)]], 4),            # fewer planes than chips
    ([[("%fusion.1", 200 * MS, 300 * MS)]], 1),      # nothing in the window
])
def test_no_device_time_is_an_error_not_a_zero(device_ops, n):
    with pytest.raises(trace_reduce.NoDeviceTrace):
        trace_reduce.reduce(planes(device_ops), n)


# -- operations and bytes ----------------------------------------------------

def test_flops_and_bytes_against_hand_worked_numbers():
    e = run.load_json("benchmark/configs/ernie-base.json")
    # 12 x (4 x 768^2 + 2 x 768 x 3072) + 768^2 + 30522 x 768
    assert flops.encoder_matmul_params(e) == 108_965_376
    # 6 x that + 12 layers x 12 x 512 x 768
    assert flops.train_flops_per_token(e, 512) == 710_415_360
    g = run.load_json("benchmark/configs/gpt2-xl.json")
    kw = {k: g[v] for k, v in g["run"]["config_kwargs"].items()}
    # 4 B x (48 x (4 x 1600^2 + 2 x 1600 x 6400) + 50257 x 1600)
    assert flops.decoder_weight_bytes(kw) == 6_219_884_800
    assert flops.kv_bytes_per_token(kw) == 614_400
    assert flops.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")


# -- traffic -----------------------------------------------------------------

def serve_mix(loop):
    """The repo's closed-loop mix, or the same sizes offered as an open loop
    (no open-loop cell is in BENCHMARK.json yet: PERF.md, Open questions)."""
    mix = data("traffic", "batch-saturated")
    if loop == "open":
        mix.update(loop="open", rate_per_s=3.0, drain_seconds=20)
        mix["rehearsal"].update(rate_per_s=6.0)
    return mix


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_serve_traffic_is_seeded_clipped_and_the_same_work(loop):
    mix = serve_mix(loop)

    def draw(seed):
        reqs = trafficgen.serve_requests(mix, seed, 20.0, 50257)
        if mix["loop"] == "closed":     # one generator per client
            assert len(reqs) == mix["clients"]
            reqs = [next(own) for _ in range(8) for own in reqs]
        return reqs
    a, b, c = draw(7), draw(7), draw(2 ** 31 + 9)
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["max_new"] == y["max_new"] and x["due_s"] == y["due_s"]
               for x, y in zip(a, b))
    sizes = lambda rs: sorted((len(r["prompt"]), r["max_new"]) for r in rs)
    assert sizes(a) == sizes(c)            # another seed: same work,
    order = lambda rs: [len(r["prompt"]) for r in rs]
    assert (order(a) != order(c)) == (loop == "open")   # open: rotated
    assert not np.array_equal(a[0]["prompt"], c[0]["prompt"])  # other ids
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    for r in a:
        assert p["min"] <= len(r["prompt"]) <= p["max"]
        assert o["min"] <= r["max_new"] <= o["max"]
        assert len(r["prompt"]) + r["max_new"] <= mix["max_total_tokens"]
        assert 0 <= r["prompt"].min() and r["prompt"].max() < 50257
    if mix["loop"] == "open":
        due = [r["due_s"] for r in a]
        assert len(a) == round(mix["rate_per_s"] * 20.0)
        assert due[0] == 0.0 and due == sorted(due) and due[-1] < 20.0


CLOSED_MIXES = [
    name for name in sorted(
        os.path.splitext(f)[0] for f in os.listdir(
            os.path.join(ROOT, "benchmark", "traffic")) if f.endswith(".json"))
    if data("traffic", name).get("loop") == "closed"]


@pytest.mark.parametrize("name", CLOSED_MIXES)
def test_a_closed_loop_mix_drains_what_is_in_flight(name):
    """A closed loop always ends with its clients' requests in flight, and
    `serve.window` counts over the readers that are finished when the drain
    ends: without a drain the count moves by a whole request with which one
    ends just before the window does (PR 27: 2.9%; PR 35: 2-3%)."""
    mix = data("traffic", name)
    assert mix["drain_seconds"] > 0 and len(mix["drain_why"]) > 100


def test_the_gpt2_xl_cell_is_a_deployment_in_which_slots_bind():
    cfg = run.load_json("benchmark/configs/gpt2-xl.json")
    mix = data("traffic", "batch-saturated")
    eng = cfg["run"]["engine"]
    assert eng["gc_freeze"] is True
    assert (eng["max_slots"], eng["page_size"]) == (16, 16)
    assert mix["clients"] > eng["max_slots"]          # a queue always waits
    assert (mix["pool"], mix["pool_seed"]) == (64, 24)    # the ledger's ring
    prompt, out = trafficgen.size_pool(mix, mix["pool"])
    reserved = np.ceil((prompt + out) / eng["page_size"])
    assert reserved.mean() == pytest.approx(16.65625) and reserved.max() == 42
    # every slot's mean reservation fits, so slots and not pages bind ...
    assert eng["num_pages"] >= eng["max_slots"] * reserved.mean()
    # ... and the pool is no larger than the tables, so decode stays
    # pool-dense (`paged_ops.paged_pool_dense_supported`)
    pages_per_seq = eng.get("pages_per_seq",
                            -(-cfg["n_positions"] // eng["page_size"]))
    assert eng["num_pages"] <= eng["max_slots"] * pages_per_seq
    # with 24 clients every entry of the ring is still sent
    sent = {(c + k * mix["clients"]) % mix["pool"]
            for c in range(mix["clients"]) for k in range(mix["pool"])}
    assert sent == set(range(mix["pool"]))


class PacedEngine:
    """Stands in for the engine under `serve.window`: a request's tokens
    come one every `gap` seconds, whatever else is in flight."""

    def __init__(self, gap):
        self.gap = gap

    def stats(self):
        return {"compiles": {}}

    def submit_stream(self, prompt, max_new_tokens, timeout_ms):
        for tok in range(max_new_tokens):
            time.sleep(self.gap)
            yield tok


@pytest.mark.parametrize("drain, tokens, requests", [
    (0, 10, 2),      # no drain: the requests in flight at t1 are left out
    (3, 14, 4),      # drained: what they were given inside the window counts
])
def test_a_drain_counts_the_tokens_stamped_in_the_window_whoever_ends_when(
        monkeypatch, drain, tokens, requests):
    """Two clients, requests of 5 tokens 0.2 s apart, a window of 1.5 s:
    each client finishes one request at 1.0 s and has its second stamped at
    1.2 and 1.4 s inside the window and at 1.6-2.0 s after it."""
    from benchmark.drivers import serve
    mix = {"loop": "closed", "clients": 2, "pool": 2, "pool_seed": 1,
           "prompt_tokens": {"median": 4, "sigma": 0.0, "min": 4, "max": 4},
           "output_tokens": {"median": 5, "sigma": 0.0, "min": 5, "max": 5},
           "max_total_tokens": 16, "drain_seconds": drain, "trace_seconds": 1,
           "request_timeout_s": 30}
    record = dict.fromkeys((
        "attr_admit_ms", "prefill_ms", "attr_promote_ms", "decode_ms",
        "attr_bookkeep_ms", "attr_idle_ms", "attr_wall_ms", "queue_depth"),
        0.0)
    monkeypatch.setattr(serve.step_log, "steps_payload", lambda: {
        "engines": {serve.ENGINE: {
            "records": [dict(record, t=time.perf_counter() - 0.5)],
            "recorded_total": 1, "ring_capacity": 8}}})
    ctx = types.SimpleNamespace(seed=2 ** 31 + 35, say=lambda msg: None,
                                config={"vocab_size": 512})
    w = serve.window(ctx, PacedEngine(0.2), mix, 1.5, False)
    assert w["end_to_end"]["serve_tokens_per_s"] == tokens / 1.5
    assert (w["attempted"], w["failed"], len(w["done"])) == \
        (requests, 0, requests)
    assert not w["compiled"]


def test_pretrain_samples_are_seeded_and_masked():
    mix = data("traffic", "pretrain-s512")
    cdf = trafficgen.zipf_cdf(30522, mix["zipf_exponent"], 1000)
    ids, labels, nsp = trafficgen.pretrain_sample(mix, cdf, 1000, 103, 5, 3)
    again = trafficgen.pretrain_sample(mix, cdf, 1000, 103, 5, 3)
    other = trafficgen.pretrain_sample(mix, cdf, 1000, 103, 6, 3)
    assert np.array_equal(ids, again[0]) and not np.array_equal(ids, other[0])
    assert ids.shape == labels.shape == (mix["seq_len"],) and nsp in (0, 1)
    masked = labels != -100
    assert 0 < masked.sum() < 0.3 * mix["seq_len"]
    assert (ids[masked] == 103).all() and (labels[masked] >= 1000).all()
    assert (ids[~masked] >= 1000).all() and ids.max() < 30522


# -- the plain references against the repo's models --------------------------

def test_ernie_reference_agrees_with_the_model():
    import paddle_tpu as paddle
    from paddle_tpu.models import ErnieConfig, ErnieForPretraining
    paddle.seed(3)
    net = ErnieForPretraining(ErnieConfig.tiny())
    net.eval()
    ids = np.random.RandomState(0).randint(0, 1024, (2, 24)).astype("int32")
    logits, nsp = net(paddle.to_tensor(ids))
    ref = run.load_by_name("reference", "ernie-base")
    want, want_nsp = ref.forward(ref.weights(net.state_dict()), ids, 4)
    np.testing.assert_allclose(logits.numpy(), want, atol=2e-4)
    np.testing.assert_allclose(nsp.numpy(), want_nsp, atol=2e-4)


def test_gpt2_reference_agrees_with_the_model():
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    paddle.seed(4)
    net = GPTForCausalLM(GPTConfig.tiny(dropout=0.0))
    net.eval()
    ids = np.random.RandomState(1).randint(0, 512, (2, 20)).astype("int32")
    logits = net(paddle.to_tensor(ids)).numpy()
    ref = run.load_by_name("reference", "gpt2-xl")
    W = ref.weights(net.state_dict())
    np.testing.assert_allclose(logits, ref.forward(W, ids, 4), atol=2e-4)
    # a sequence continued by the model's own argmax falls short by nothing;
    # one continued by another token falls short by the logit difference
    seq = list(ids[0, :10]) + [int(logits[0, 9].argmax())]
    wrong = list(ids[0, :10]) + [int(logits[0, 9].argmin())]
    s = ref.shortfalls(W, [seq, wrong], [10, 10], 4, pad_to=16)
    assert s[0].shape == (1,) and s[0][0] == 0.0
    assert s[1][0] == pytest.approx(logits[0, 9].max() - logits[0, 9].min(),
                                    abs=1e-3)


# -- run.py --rehearse, end to end -------------------------------------------

def rehearse(root, cell, trace):
    env = dict(os.environ, PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2 ** 31 + 5), "--seconds", "2",
         "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, timeout=300, env=env, cwd=root)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_rehearsal_of_a_train_cell_prints_a_line_the_driver_reads():
    m = manifest()
    cell = next(w["name"] for w in m["workloads"]
                if run.load_json(next(c["file"] for c in m["configs"]
                                      if c["name"] == w["config"]))["kind"]
                == "train")
    line = rehearse(ROOT, cell, 1)
    check_line.check_line(m, cell, 1, line)
    assert line["device"]["platform"] == "cpu"
    # `correct` includes "the last forced loss is below the first": with
    # dropout on, a loaded CPU that fits only a few tiny steps into two
    # seconds need not show that, so it is held to it from 60 steps on
    assert line["correct"] or line["attempted"] < 60


def test_a_cell_a_mix_and_metrics_are_added_by_files_alone(tmp_path):
    """A copy of the benchmark in a temporary directory gains an open-loop
    traffic mix, its two end-to-end tails, a per-layer metric and a cell by
    NEW files and entries only, and runs."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = manifest()
    old = next(w for w in m["workloads"] if w["traffic"] == "batch-saturated")
    with open(tmp_path / "benchmark/traffic/chat-fast.json", "w") as f:
        json.dump(serve_mix("open"), f)
    with open(tmp_path / "benchmark/metrics/iterations.serve.py", "w") as f:
        f.write("def read(rec):\n    return float(len(rec['steps']))\n")
    new = dict(copy.deepcopy(old), name="tiny.chat-fast", traffic="chat-fast")
    m["workloads"].append(new)
    for e in m["end_to_end"]:
        if old["name"] in e.get("workloads", []):
            e["workloads"].append(new["name"])
    m["end_to_end"] += [
        {"name": n, "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": [new["name"]]}
        for n in ("ttft_p95_ms", "gap_p95_ms")]
    m["per_layer"].append({
        "name": "iterations.serve", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "ttft_p95_ms", "workloads": [new["name"]]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(m, f)
    line = rehearse(str(tmp_path), new["name"], 1)
    check_line.check_line(m, new["name"], 1, line)
    assert line["correct"] and line["attempted"] == 12 and not line["failed"]
    assert line["metrics"]["iterations.serve"]["value"] > 0
    assert line["metrics"]["ttft_p95_ms"]["value"] > 0


def test_rehearsal_of_a_closed_loop_cell_counts_outcomes_only():
    m = manifest()
    cell = next(w["name"] for w in m["workloads"]
                if w["traffic"] == "batch-saturated")
    line = rehearse(ROOT, cell, 0)
    check_line.check_line(m, cell, 0, line)
    assert line["correct"] and line["attempted"] > 0 and not line["failed"]
