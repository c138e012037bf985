"""Pallas flash-attention kernel parity tests (interpreter mode on CPU).

The reference has no fused attention op (MultiHeadAttention is composed in
Python, `python/paddle/nn/layer/transformer.py:87`); these tests guard OUR
kernel (paddle_tpu/ops/pallas_ops.py) against the reference math: fwd +
dq/dk/dv parity vs the dense jnp path across causal / padding-mask /
cross-attention shapes, plus dispatch-gate rules and dropout semantics.
Runs via FLAGS_flash_attention_interpret so CPU CI exercises the exact
kernel code the TPU runs.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework.flags import set_flags, get_flags
from paddle_tpu.ops import pallas_ops as po


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = get_flags(["FLAGS_flash_attention_interpret",
                     "FLAGS_use_flash_attention",
                     "FLAGS_flash_attention_min_seq"])
    set_flags({"FLAGS_flash_attention_interpret": True,
               "FLAGS_use_flash_attention": True,
               "FLAGS_flash_attention_min_seq": 128})
    yield
    set_flags(old)


def _mk(shape, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32), dtype)


def _dense_ref(q, k, v, bias, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias[:, None, None, :]
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((Sq, Sk), bool)), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def _flash(q, k, v, bias, causal, scale):
    seed = jnp.zeros((), jnp.int32)
    return po.flash_attention_raw(q, k, v, bias, seed, causal, scale, 0.0)


CASES = [
    # (Sq, Sk, causal, padded)
    (128, 128, False, False),
    (128, 128, True, False),
    (256, 128, False, False),   # cross-attention, S_q != S_kv
    (128, 256, False, False),   # decoder memory attention shape
    (128, 128, False, True),
    (256, 256, True, True),
]


@pytest.mark.parametrize("sq,sk,causal,padded", CASES)
def test_flash_forward_parity(sq, sk, causal, padded):
    B, H, D = 2, 2, 32
    q = _mk((B, H, sq, D), 1)
    k = _mk((B, H, sk, D), 2)
    v = _mk((B, H, sk, D), 3)
    scale = 1.0 / D ** 0.5
    if padded:
        valid = np.ones((B, sk), np.float32)
        valid[0, sk // 2:] = 0.0       # half of batch-0's keys padded out
        bias = jnp.asarray(np.where(valid, 0.0, -1e30).astype(np.float32))
    else:
        bias = jnp.zeros((B, sk), jnp.float32)
    out = _flash(q, k, v, bias, causal, scale)
    ref = _dense_ref(q, k, v, bias, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sq,sk,causal,padded", CASES)
def test_flash_grad_parity(sq, sk, causal, padded):
    B, H, D = 1, 2, 16
    q = _mk((B, H, sq, D), 4)
    k = _mk((B, H, sk, D), 5)
    v = _mk((B, H, sk, D), 6)
    scale = 1.0 / D ** 0.5
    if padded:
        valid = np.ones((B, sk), np.float32)
        valid[0, sk - sk // 4:] = 0.0
        bias = jnp.asarray(np.where(valid, 0.0, -1e30).astype(np.float32))
    else:
        bias = jnp.zeros((B, sk), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(_flash(q, k, v, bias, causal, scale)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(_dense_ref(q, k, v, bias, causal, scale)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(gf, gr, "q k v".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{nm} mismatch")


def test_flash_bf16_forward_close():
    B, H, S, D = 2, 2, 128, 64
    q = _mk((B, H, S, D), 7, jnp.bfloat16)
    k = _mk((B, H, S, D), 8, jnp.bfloat16)
    v = _mk((B, H, S, D), 9, jnp.bfloat16)
    bias = jnp.zeros((B, S), jnp.float32)
    out = _flash(q, k, v, bias, True, 0.125)
    ref = _dense_ref(q, k, v, bias, True, 0.125)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# dispatch gate
# ---------------------------------------------------------------------------

def test_gate_min_seq_default():
    # at seq 128 the dense path must win the dispatch: a flash kernel
    # has one 128-row block a head there and nothing to stream, so it only
    # adds its launch and softmax bookkeeping — regression guard
    assert not po.flash_supported((8, 12, 128, 64), min_seq=512)
    assert po.flash_supported((8, 12, 512, 64), min_seq=512)


def test_gate_reads_flag():
    set_flags({"FLAGS_flash_attention_min_seq": 256})
    assert not po.flash_supported((2, 2, 128, 64))
    assert po.flash_supported((2, 2, 256, 64))


def test_gate_cross_attention_shapes():
    q, kv = (2, 4, 256, 64), (2, 4, 128, 64)
    assert po.flash_supported(q, kv, kv, min_seq=128)
    # causal with S_q != S_kv: diagonals don't align — refuse
    assert not po.flash_supported(q, kv, kv, is_causal=True, min_seq=128)
    # k/v disagree
    assert not po.flash_supported(q, kv, (2, 4, 256, 64), min_seq=128)
    # head-count mismatch (GQA) unsupported
    assert not po.flash_supported(q, (2, 2, 128, 64), (2, 2, 128, 64),
                                  min_seq=128)
    # non-multiple-of-block kv length
    assert not po.flash_supported(q, (2, 4, 100, 64), (2, 4, 100, 64),
                                  min_seq=128)


def test_gate_mask_keyed_on_kv_length():
    q, kv = (2, 4, 256, 64), (2, 4, 128, 64)
    good = jnp.zeros((2, 1, 1, 128), jnp.float32)
    bad = jnp.zeros((2, 1, 1, 256), jnp.float32)   # q-length mask: refuse
    assert po.flash_supported(q, kv, kv, good, min_seq=128)
    assert not po.flash_supported(q, kv, kv, bad, min_seq=128)


def test_fallback_causal_decode_bottom_right_aligned():
    """is_causal with S_q < S_kv (KV-cache decode) must attend the whole
    prefix — bottom-right aligned diagonal, not jnp.tril's top-left."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.tensor import Tensor
    set_flags({"FLAGS_use_flash_attention": False})
    B, H, Sk, D = 1, 1, 16, 8
    q = Tensor(_mk((B, H, 1, D), 20))       # one new token
    k = Tensor(_mk((B, H, Sk, D), 21))
    v = Tensor(_mk((B, H, Sk, D), 22))
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    ref = _dense_ref(q._value, k._value, v._value, None, False,
                     1.0 / D ** 0.5)        # full attention over the cache
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_functional_cross_attention_no_crash():
    """Regression: maskless cross-attention S_q != S_kv used to pass the
    gate and die inside _flash_call's reshape."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.tensor import Tensor
    q = Tensor(_mk((1, 2, 256, 32), 10))
    kv = Tensor(_mk((1, 2, 128, 32), 11))
    out = F.scaled_dot_product_attention(q, kv, kv)
    ref = _dense_ref(q._value, kv._value, kv._value, None, False,
                     1.0 / 32 ** 0.5)
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# dropout semantics
# ---------------------------------------------------------------------------

def test_fallback_dropout_on_probabilities():
    """The fallback must drop softmax PROBABILITIES (kernel semantics), not
    attention outputs: with p=0.5 an output row is a sub-sum of upscaled
    prob*V terms — its expectation matches the dense output, and rows are
    NOT exactly zero/2x-scaled copies (which output-dropout would give)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.tensor import Tensor
    set_flags({"FLAGS_use_flash_attention": False})
    B, H, S, D = 1, 1, 8, 4
    q = Tensor(_mk((B, H, S, D), 12))
    k = Tensor(_mk((B, H, S, D), 13))
    v = Tensor(jnp.ones((B, H, S, D), jnp.float32))
    paddle.seed(123)
    out = F.scaled_dot_product_attention(q, k, v, dropout_p=0.5,
                                         training=True)
    a = np.asarray(out._value)
    base = np.asarray(
        F.scaled_dot_product_attention(q, k, v, dropout_p=0.0)._value)
    # v == ones → dense output rows are exactly 1.0; prob-dropout rows are
    # sums of a random subset of upscaled probs — generically neither 0,
    # 1, nor 2 exactly, and different across rows
    assert not np.allclose(a, base)          # dropout did something
    zero_or_double = np.isclose(a, 0.0) | np.isclose(a, 2.0 * base)
    assert not zero_or_double.all(), \
        "looks like output-dropout, not probability-dropout"


def test_kernel_dropout_keep_rate_and_determinism():
    B, H, S, D = 1, 2, 128, 32
    q = _mk((B, H, S, D), 14)
    k = _mk((B, H, S, D), 15)
    v = jnp.ones((B, H, S, D), jnp.float32)
    bias = jnp.zeros((B, S), jnp.float32)
    seed = jnp.asarray(42, jnp.int32)
    call = functools.partial(po.flash_attention_raw, causal=False,
                             scale=1.0 / D ** 0.5, dropout_p=0.5)
    try:
        o1 = call(q, k, v, bias, seed)
    except Exception as e:  # TPU PRNG primitives may not interpret on CPU
        pytest.skip(f"in-kernel PRNG not interpretable here: {e}")
    o2 = call(q, k, v, bias, seed)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    o3 = call(q, k, v, bias, jnp.asarray(7, jnp.int32))
    assert not np.allclose(np.asarray(o1), np.asarray(o3))
    # keep-rate: with v=1 each output element is sum(upscaled kept probs);
    # mean over all rows ≈ 1.0 (unbiased estimator)
    assert abs(float(jnp.mean(o1)) - 1.0) < 0.15


@pytest.mark.chip
def test_bf16_parity_on_tpu():
    """bf16 COMPILED-kernel parity vs dense SDPA on REAL TPU hardware
    (`chip` marker: PADDLE_TPU_TEST_ON_CHIP=1 pytest -m chip). Must defeat
    the module fixture's interpret flag or it would validate interpreter
    math, not the Mosaic kernel."""
    assert jax.default_backend() == "tpu"
    set_flags({"FLAGS_flash_attention_interpret": False})

    B, H, S, D = 2, 4, 1024, 64
    q = _mk((B, H, S, D), 0, jnp.bfloat16)
    k = _mk((B, H, S, D), 1, jnp.bfloat16)
    v = _mk((B, H, S, D), 2, jnp.bfloat16)
    bias = jnp.zeros((B, S), jnp.float32)
    scale = 1.0 / D ** 0.5

    out_f = jax.jit(lambda q, k, v: _flash(q, k, v, bias, True,
                                           scale))(q, k, v)
    out_d = jax.jit(lambda q, k, v: _dense_ref(q, k, v, None, True,
                                               scale))(q, k, v)
    err = float(jnp.max(jnp.abs(out_f.astype(jnp.float32)
                                - out_d.astype(jnp.float32))))
    assert err < 0.05, err

    gf = jax.jit(jax.grad(lambda q, k, v: _flash(
        q, k, v, bias, True, scale).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(lambda q, k, v: _dense_ref(
        q, k, v, None, True, scale).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gd):
        e = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32))))
        assert e < 0.3, e
