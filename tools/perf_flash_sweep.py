"""Flash sweep v3: on-device iteration chaining.

One RPC dispatch per measurement; the op repeats CHAIN times inside the
jit with a data dependency (q := out), so dispatch overhead is
amortized and the per-iteration time is the kernel's own.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import pallas_ops as P

B, H, S, D = 4, 12, 2048, 64
CAUSAL = True
SCALE = 1.0 / (D ** 0.5)
CHAIN = 16


def _sync(out):
    leaves = jax.tree_util.tree_leaves(out)
    return float(jnp.sum(leaves[0].astype(jnp.float32).ravel()[:8]))


def time_chained(one_step, q, k, v, reps=3):
    """one_step(q, k, v) -> out with out.shape == q.shape."""
    def chained(q, k, v):
        def body(_, qq):
            return one_step(qq, k, v)
        return jax.lax.fori_loop(0, CHAIN, body, q)
    fn = jax.jit(chained)
    _sync(fn(q, k, v))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        _sync(fn(q, k, v))
        best = min(best, time.perf_counter() - t0)
    return best / CHAIN * 1e3


def dense_step(q, k, v):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * SCALE
    if CAUSAL:
        idx = jnp.arange(S)
        s = jnp.where(idx[None, None, :, None] >= idx[None, None, None, :],
                      s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def main():
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(4096, 4096), jnp.bfloat16)
    t = time_chained(lambda x, _k, _v: x @ a, a, a, a)
    print(f"calib 4096^3 matmul: {t:8.3f} ms "
          f"({2*4096**3/(t/1e3)/1e12:.0f} TFLOP/s)")

    q = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    bias = jnp.zeros((B, S), jnp.float32)
    seed = jnp.zeros((), jnp.int32)

    t = time_chained(dense_step, q, k, v)
    print(f"dense fwd:           {t:8.3f} ms")

    def dense_gstep(qq, k, v):
        g = jax.grad(lambda q_: dense_step(q_, k, v).astype(
            jnp.float32).sum())(qq)
        return g.astype(qq.dtype)
    t = time_chained(dense_gstep, q, k, v)
    print(f"dense dq-grad step:  {t:8.3f} ms")

    for bq, bk in [(128, 128), (256, 512), (512, 512), (512, 2048),
                   (256, 2048)]:
        def fstep(qq, k, v, bq=bq, bk=bk):
            out, _ = P._flash_call(qq, k, v, bias, seed, CAUSAL, SCALE,
                                   0.0, bq, bk)
            return out
        try:
            t = time_chained(fstep, q, k, v)
        except Exception as e:  # noqa: BLE001
            print(f"flash bq={bq:4d} bk={bk:4d}: FAILED "
                  f"{str(e)[:100]}")
            continue

        orig_pick = P._pick_blocks
        P._pick_blocks = lambda Sq, Sk, bq=bq, bk=bk: (bq, bk)

        def gstep(qq, k, v):
            g = jax.grad(lambda q_: P.flash_attention_raw(
                q_, k, v, bias, seed, CAUSAL, SCALE, 0.0).astype(
                    jnp.float32).sum())(qq)
            return g.astype(qq.dtype)
        try:
            tg = time_chained(gstep, q, k, v)
        except Exception:  # noqa: BLE001
            tg = float("nan")
        finally:
            P._pick_blocks = orig_pick
        print(f"flash bq={bq:4d} bk={bk:4d}: fwd {t:8.3f} ms   "
              f"dq-grad step {tg:8.3f} ms")


if __name__ == "__main__":
    main()
