"""Warm start (ISSUE 16): the on-disk AOT program store.

The load-bearing anchors:

- **Cold-process warm start** — process A builds a store; process B
  with the same config serves with an EMPTY compile ledger (every
  covered program `loaded`, zero XLA compiles), token-identical to a
  store-less run. Proven across real processes, not just engines.
- **Never wrong, never failed** — a corrupt payload is a miss, a
  tampered alias spec fails the self-check (counter + flight dump) and
  falls back to live compile; both paths still produce the store-off
  tokens.
- **The CPU gate** — on XLA:CPU the store refuses without
  `force=True` (`device.serialization_unsafe_backend()`), and a forced
  store warns once per process.
"""
import json
import os
import subprocess
import sys
import warnings
from contextlib import contextmanager

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import device as pdevice
from paddle_tpu import serving
from paddle_tpu.framework import monitor
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.profiler import flight_recorder
from paddle_tpu.serving.program_store import ProgramStore, read_manifest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "program_store_worker.py")
INSPECT = os.path.join(REPO, "tools", "pack_inspect.py")


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = GPTConfig.tiny(dropout=0.0)
    net = GPTForCausalLM(cfg)
    net.eval()
    return net


def _prompts(n=2, S=7, seed=0, vocab=512):
    return np.random.RandomState(seed).randint(
        0, vocab, size=(n, S)).astype("int64")


def _engine(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("prefill_buckets", (8,))
    kw.setdefault("max_new_tokens", 5)
    kw.setdefault("request_timeout_ms", 0)
    return serving.GenerationEngine(model, **kw)


def _serve(eng, ids, max_new=5):
    return [np.asarray(f.result(timeout=300)) for f in
            [eng.submit(p, max_new_tokens=max_new) for p in ids]]


def _build_store(model, store, **kw):
    """One engine lifetime with the store on (forced: tests run on
    CPU); returns (outputs, stats) after shutdown."""
    with _engine(model, program_store=str(store),
                 program_store_force=True, **kw) as eng:
        outs = _serve(eng, _prompts())
        stats = eng.stats()
    return outs, stats


def _only_key_dir(store):
    dirs = [d for d in os.listdir(store)
            if os.path.isdir(os.path.join(store, d))]
    assert len(dirs) == 1, dirs
    return os.path.join(store, dirs[0])


def _run_worker(out_path, store="", extra=()):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, WORKER, "--out", str(out_path)]
    if store:
        cmd += ["--store", str(store), "--force"]
    cmd += list(extra)
    proc = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    with open(out_path, "r", encoding="utf-8") as f:
        return json.load(f)


# -- serde helpers (jit layer) ----------------------------------------------

def test_serialize_round_trip_preserves_alias_and_math():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.jit import (compiled_alias_spec, deserialize_compiled,
                                serialize_compiled)
    fn = jax.jit(lambda a, b: (a + b, a * 2.0), donate_argnums=(0,))
    a = jnp.arange(8, dtype=jnp.float32)
    b = jnp.ones((8,), jnp.float32)
    compiled = fn.lower(a, b).compile()
    alias = compiled_alias_spec(compiled)
    assert alias.strip()                      # donation survived compile
    loaded = deserialize_compiled(serialize_compiled(compiled))
    assert compiled_alias_spec(loaded) == alias
    out = loaded(jnp.arange(8, dtype=jnp.float32), b)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.arange(8, dtype=np.float32) + 1.0)


def test_key_material_digest_is_canonical_and_sensitive():
    from paddle_tpu.jit import key_material_digest
    base = {"model": {"n_layer": 2, "n_head": 2}, "knobs": [8, 4]}
    same = {"knobs": [8, 4], "model": {"n_head": 2, "n_layer": 2}}
    assert key_material_digest(base) == key_material_digest(same)
    bumped = {"model": {"n_layer": 2, "n_head": 2}, "knobs": [8, 8]}
    assert key_material_digest(base) != key_material_digest(bumped)


# -- cold-process warm start (the acceptance test) --------------------------

def test_cold_process_warm_start(tmp_path):
    """Process A compiles + persists; process B (same config, fresh
    process) serves with ZERO live compiles — every covered program
    `loaded` — and is token-identical to a store-less process."""
    store = tmp_path / "store"
    cold = _run_worker(tmp_path / "a.json", store=store)
    assert cold["compiles"], "cold process must live-compile"
    assert cold["loaded"] == {}
    assert cold["program_store"]["active"] is True

    warm = _run_worker(tmp_path / "b.json", store=store)
    assert warm["compiles"] == {}, warm["compiles"]
    assert set(warm["loaded"]) == set(cold["compiles"])
    assert warm["programs"] == {k: "loaded" for k in warm["loaded"]}
    assert warm["program_store"]["key"] == cold["program_store"]["key"]

    off = _run_worker(tmp_path / "c.json")
    assert off["program_store"]["configured"] is False
    assert warm["outputs"] == cold["outputs"] == off["outputs"]


def test_warm_engine_same_process(model, tmp_path):
    """In-process replay of the same invariant (cheap, no subprocess):
    a second engine over the same store loads everything it would have
    compiled, and the pack_load_ms histogram saw the loads."""
    store = tmp_path / "store"
    _, cold_stats = _build_store(model, store)
    before = monitor.histogram("pack_load_ms").snapshot()["count"]
    with _engine(model, program_store=str(store),
                 program_store_force=True) as eng:
        outs = _serve(eng, _prompts())
        stats = eng.stats()
    assert stats["compiles"] == {}
    assert set(stats["loaded"]) == set(cold_stats["compiles"])
    assert monitor.histogram("pack_load_ms").snapshot()["count"] > before
    with _engine(model) as eng:
        ref = _serve(eng, _prompts())
    for got, want in zip(outs, ref):
        np.testing.assert_array_equal(got, want)


def test_full_pack_coverage_prefix_and_spec(model, tmp_path):
    """With the prefix cache and speculation on, the covered set grows
    to prefill + prefill_tail + cow_copy + verify[k] (+ decode when the
    degrade path pre-warms): EVERY one must warm-start from the store,
    not just the two defaults."""
    store = tmp_path / "store"
    kw = dict(prefix_cache=True, spec_k=2)
    _, cold_stats = _build_store(model, store, **kw)
    for name in ("prefill[b=8]", "prefill_tail[b=8]", "cow_copy",
                 "verify[k=2]"):
        assert name in cold_stats["compiles"], cold_stats["compiles"]
    with _engine(model, program_store=str(store),
                 program_store_force=True, **kw) as eng:
        _serve(eng, _prompts())
        stats = eng.stats()
    assert stats["compiles"] == {}
    assert set(stats["loaded"]) == set(cold_stats["compiles"])


# -- corruption / staleness: a bad entry costs a compile, never a wrong answer

def test_corrupt_payload_is_a_miss_not_an_error(model, tmp_path):
    store = tmp_path / "store"
    _build_store(model, store)
    key_dir = _only_key_dir(store)
    victim = os.path.join(key_dir, "decode_m_2.bin")
    assert os.path.isfile(victim)
    with open(victim, "wb") as f:
        f.write(b"not a serialized executable")
    misses = monitor.stat_get("STAT_pack_store_misses")
    with _engine(model, program_store=str(store),
                 program_store_force=True) as eng:
        outs = _serve(eng, _prompts())
        stats = eng.stats()
    # the corrupted program live-compiled (and was re-persisted); the
    # intact one still loaded
    assert stats["compiles"] == {"decode[m=2]": 1}
    assert set(stats["loaded"]) == {"prefill[b=8]"}
    assert monitor.stat_get("STAT_pack_store_misses") > misses
    with _engine(model) as eng:
        ref = _serve(eng, _prompts())
    for got, want in zip(outs, ref):
        np.testing.assert_array_equal(got, want)
    # the write-back healed the store: a third engine loads everything
    with _engine(model, program_store=str(store),
                 program_store_force=True) as eng:
        _serve(eng, _prompts())
        assert eng.stats()["compiles"] == {}


def test_alias_tamper_fails_selfcheck_and_falls_back(model, tmp_path):
    store = tmp_path / "store"
    _build_store(model, store)
    key_dir = _only_key_dir(store)
    mf = read_manifest(key_dir)
    mf["programs"]["decode[m=2]"]["alias"] = "{0}: (99, {}, may-alias)"
    with open(os.path.join(key_dir, "manifest.json"), "w",
              encoding="utf-8") as f:
        json.dump(mf, f)
    fails = monitor.stat_get("STAT_pack_selfcheck_failures")
    dumps = len(flight_recorder.dump_records())
    with _engine(model, program_store=str(store),
                 program_store_force=True) as eng:
        outs = _serve(eng, _prompts())
        stats = eng.stats()
    assert stats["compiles"] == {"decode[m=2]": 1}
    assert monitor.stat_get("STAT_pack_selfcheck_failures") > fails
    recs = flight_recorder.dump_records()[dumps:]
    assert any(r["reason"] == "program_store_selfcheck" for r in recs)
    with _engine(model) as eng:
        ref = _serve(eng, _prompts())
    for got, want in zip(outs, ref):
        np.testing.assert_array_equal(got, want)


def test_stale_key_is_a_clean_miss(model, tmp_path):
    """Any trace-shaping knob change → different content key → a fresh
    key directory and a full live compile; the old entries are never
    consulted (and so can never be wrong)."""
    store = tmp_path / "store"
    _, first = _build_store(model, store)
    with _engine(model, program_store=str(store),
                 program_store_force=True, num_pages=32) as eng:
        _serve(eng, _prompts())
        stats = eng.stats()
    assert stats["loaded"] == {}
    assert set(stats["compiles"]) == set(first["compiles"])
    assert stats["program_store"]["key"] != first["program_store"]["key"]
    key_dirs = [d for d in os.listdir(store)
                if os.path.isdir(os.path.join(store, d))]
    assert len(key_dirs) == 2


# -- the PR 1 CPU gate ------------------------------------------------------

def test_cpu_refusal_without_force(model, tmp_path):
    """On XLA:CPU the store refuses to engage unless forced: the engine
    runs exactly as store-off and the directory stays empty."""
    assert pdevice.serialization_unsafe_backend()
    store = tmp_path / "store"
    with _engine(model, program_store=str(store)) as eng:
        _serve(eng, _prompts())
        stats = eng.stats()
    assert stats["program_store"]["configured"] is True
    assert stats["program_store"]["active"] is False
    assert stats["loaded"] == {}
    assert stats["compiles"] != {}
    assert not os.path.exists(store)


def test_forced_serialization_warns_once(model, tmp_path, monkeypatch):
    """Every force path shares ONE per-process warning naming the
    corruption class — an override of the CPU gate is never silent."""
    monkeypatch.setattr(pdevice, "_force_warned", False)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        ProgramStore(str(tmp_path / "s1"), {"k": 1}, force=True)
        ProgramStore(str(tmp_path / "s2"), {"k": 2}, force=True)
    msgs = [str(x.message) for x in w
            if issubclass(x.category, RuntimeWarning)
            and "corruption class" in str(x.message)]
    assert len(msgs) == 1
    assert ProgramStore(str(tmp_path / "s3"), {"k": 3}).refused


# -- tools/pack_inspect.py --------------------------------------------------

def test_pack_inspect_cli(model, tmp_path):
    store = tmp_path / "store"
    _build_store(model, store)
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")

    proc = subprocess.run(
        [sys.executable, INSPECT, str(store), "--verify"], env=env,
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "decode[m=2]" in proc.stdout and "[ok]" in proc.stdout
    assert "[FAIL]" not in proc.stdout

    proc = subprocess.run(
        [sys.executable, INSPECT, str(store), "--verify", "--json"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    report = json.loads(proc.stdout)
    assert report["problems"] == []
    key_dir = _only_key_dir(store)
    with open(os.path.join(key_dir, "prefill_b_8.bin"), "wb") as f:
        f.write(b"garbage")
    proc = subprocess.run(
        [sys.executable, INSPECT, str(store), "--verify"], env=env,
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1
    assert "PROBLEM" in proc.stderr

    proc = subprocess.run(
        [sys.executable, INSPECT, str(tmp_path / "nope")], env=env,
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1


# -- supervisor: rebuilds prefer the store ----------------------------------

def test_supervised_restart_keeps_zero_compiles(model, tmp_path):
    """A supervised engine with a warm store resurrects without minting
    compiles: the replacement engine adopts the pack (PR 14) or reloads
    from the store — either way the ledger stays empty."""
    from paddle_tpu.serving import failpoints

    @contextmanager
    def flags(**kw):
        old = paddle.get_flags(list(kw))
        paddle.set_flags(kw)
        try:
            yield
        finally:
            paddle.set_flags(old)

    store = tmp_path / "store"
    _build_store(model, store)
    failpoints.reset()
    with flags(FLAGS_failpoints="decode_step_raise@2"):
        sup = serving.EngineSupervisor(
            model, max_slots=2, page_size=4, num_pages=64,
            prefill_buckets=(8,), max_new_tokens=5,
            request_timeout_ms=0, program_store=str(store),
            program_store_force=True)
        try:
            outs = _serve(sup, _prompts())
            sstats = sup.stats()
        finally:
            sup.shutdown()
            failpoints.reset()
    assert sstats["supervisor"]["restarts"] >= 1
    assert sstats["compiles"] == {}
    assert sstats["supervisor"]["program_store"] == str(store)
    with _engine(model) as eng:
        ref = _serve(eng, _prompts())
    for got, want in zip(outs, ref):
        np.testing.assert_array_equal(got, want)
