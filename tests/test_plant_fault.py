"""tools/plant_fault.py: every plant is LIVE. A plant that silently did
nothing would read as "the check cannot see this fault", so each one is
shown, at tiny size, to move the logits of the engine's own prefill program
and of `latent_decode` (the decode program's body) away from the sound
program's, and to leave them finite."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models import GlmMoeLiteConfig, GlmMoeLiteForCausalLM, glm_moe
from paddle_tpu.ops import paged_ops

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import plant_fault  # noqa: E402

PAGE, BUCKET, STEPS, LENGTHS = 4, 16, 3, (7, 12, 16)


@pytest.fixture(scope="module")
def net():
    paddle.seed(27)
    model = GlmMoeLiteForCausalLM(GlmMoeLiteConfig.tiny(dtype="float32"))
    model.eval()
    return model


def paged_logits(net):
    """Three prefills, then STEPS decode steps on fixed tokens through the
    pages: [3, STEPS, V] logits of the decode steps."""
    from paddle_tpu.serving.latent_family import latent_decode
    cfg = net.config
    rs = np.random.RandomState(5)
    eng = serving.GenerationEngine(
        net, name="planted", max_slots=3, page_size=PAGE, num_pages=18,
        pages_per_seq=6, prefill_buckets=(BUCKET,), max_new_tokens=STEPS,
        warmup=False)
    try:
        pt = np.stack([eng._cache.alloc(i, n + STEPS)
                       for i, n in enumerate(LENGTHS)])
        for i, n in enumerate(LENGTHS):
            ids = np.zeros((1, BUCKET), np.int32)
            ids[0, :n] = rs.randint(0, cfg.vocab_size, n)
            out = eng._prefill_jit(eng._W, *eng._pools(), pt[i], ids,
                                   np.int32(n))
            eng._set_pools(out[:-1])
        pool, table = eng._pools()[0], jnp.asarray(pt)
        step = jax.jit(lambda W, pool, tok, pos: latent_decode(
            W, pool, table, tok, pos, jnp.ones(3, bool), cfg, PAGE))
        logits = []
        for k in range(STEPS):
            tok = rs.randint(0, cfg.vocab_size, 3).astype(np.int32)
            lg, pool, _, _ = step(eng._W, pool, tok,
                                  np.asarray(LENGTHS, np.int32) + k)
            logits.append(np.asarray(lg))
    finally:
        eng.shutdown(drain=False)
    return np.stack(logits, 1)


@pytest.fixture()
def restored(monkeypatch):
    """The plants assign module attributes; put the originals back."""
    monkeypatch.setattr(paged_ops, "paged_latent_write",
                        paged_ops.paged_latent_write)
    monkeypatch.setattr(paged_ops, "paged_latent_attention",
                        paged_ops.paged_latent_attention)
    monkeypatch.setattr(paged_ops, "paged_attention",
                        paged_ops.paged_attention)
    monkeypatch.setattr(glm_moe, "moe_route", glm_moe.moe_route)


# the latent family's plants (the hybrid family's: tests/test_falcon_h1.py)
LATENT_PLANTS = ("dropped_expert", "float8_cache", "wrong_page",
                 "wrong_table")


def test_every_plant_has_a_test_that_shows_it_live():
    from test_falcon_h1 import HYBRID_PLANTS
    from test_row_decode_kernel import GPT_PLANTS
    # `wrong_page` and `wrong_table` wrap every family's decode attention
    assert set(plant_fault.PLANTS) - {"none"} == \
        set(LATENT_PLANTS) | set(HYBRID_PLANTS) | set(GPT_PLANTS)


@pytest.mark.parametrize("fault", LATENT_PLANTS)
def test_a_plant_moves_the_logits(net, restored, fault):
    sound = paged_logits(net)
    plant_fault.PLANTS[fault]()
    planted = paged_logits(net)
    assert np.isfinite(planted).all()
    d = np.sqrt(((planted - sound) ** 2).mean()) / sound.std()
    assert d > 1e-3, (fault, d)


def test_nothing_planted_moves_nothing(net, restored):
    sound = paged_logits(net)
    plant_fault.PLANTS["none"]()
    np.testing.assert_array_equal(paged_logits(net), sound)
