"""10B-parameter hybrid-parallel lowering proof (an ERNIE-3.0-scale
configuration; the reference trains it with sharding + pipeline
meta-optimizers).

No weights are materialized: parameters enter as sharded
ShapeDtypeStructs and `jit(...).lower()` runs GSPMD partitioning on the
virtual 8-device mesh. The assertions check what matters at scale — the
partitioner accepted the shardings and inserted ICI collectives for the
tensor-parallel contractions and data-parallel grad reduction."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.parallel import create_mesh

H = 4608
L = 40
V = 50304
FF = 4 * H
B, S = 8, 512
N_PARAMS = V * H + L * (12 * H * H)          # ~10.2B


def _abstract(shape, spec, mesh, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def test_10b_tp_dp_train_step_lowers_with_collectives():
    mesh = create_mesh({"dp": 2, "mp": 4})
    assert N_PARAMS > 10_000_000_000

    params = {
        "emb": _abstract((V, H), P("mp", None), mesh),
        "qkv": _abstract((L, H, 3 * H), P(None, None, "mp"), mesh),
        "proj": _abstract((L, H, H), P(None, "mp", None), mesh),
        "ff1": _abstract((L, H, FF), P(None, None, "mp"), mesh),
        "ff2": _abstract((L, FF, H), P(None, "mp", None), mesh),
    }
    ids = _abstract((B, S), P("dp", None), mesh, jnp.int32)

    def forward(pv, ids):
        h = jnp.take(pv["emb"], ids, axis=0)          # [B,S,H]

        def layer(h, lw):
            qkv, proj, ff1, ff2 = lw
            q, k, v = jnp.split(h @ qkv, 3, axis=-1)

            def heads(x):
                return x.reshape(B, S, 32, H // 32).transpose(0, 2, 1, 3)
            s_ = jnp.einsum("bhqd,bhkd->bhqk", heads(q), heads(k))
            mask = jnp.tril(jnp.ones((S, S), bool))
            p_ = jax.nn.softmax(jnp.where(mask, s_ / np.sqrt(H // 32),
                                          -1e30), axis=-1)
            att = jnp.einsum("bhqk,bhkd->bhqd", p_, heads(v))
            att = att.transpose(0, 2, 1, 3).reshape(B, S, H)
            h = h + att @ proj
            h = h + jax.nn.gelu(h @ ff1) @ ff2
            return h, None

        h, _ = jax.lax.scan(layer, h,
                            (pv["qkv"], pv["proj"], pv["ff1"],
                             pv["ff2"]))
        return h @ pv["emb"].T                        # tied head

    def step(pv, ids):
        def loss_fn(pv_):
            logits = forward(pv_, ids)
            tgt = jnp.roll(ids, -1, axis=1)
            lse = jax.nn.logsumexp(logits, axis=-1)
            pick = jnp.take_along_axis(logits, tgt[..., None],
                                       axis=-1)[..., 0]
            return jnp.mean(lse - pick)
        loss, grads = jax.value_and_grad(loss_fn)(pv)
        new_pv = jax.tree_util.tree_map(lambda p, g: p - 1e-4 * g,
                                        pv, grads)
        return loss, new_pv

    with mesh:
        lowered = jax.jit(step).lower(params, ids)
    text = lowered.as_text()
    # the partitioner accepted the 10B layout (8-way SPMD over dp×mp)
    assert "num_partitions = 8" in text or "num_partitions=8" in text, \
        text[:400]
    # mesh axis names only appear in the pre-partitioning text on jax
    # versions that lower through the shardy dialect; GSPMD-era jax
    # records the layout as mhlo.sharding device assignments instead —
    # accept either spelling of "the mesh layout reached the compiler"
    assert ('"mp"' in text and '"dp"' in text) or "mhlo.sharding" in text

    # collectives appear after SPMD partitioning — compile (no weights
    # materialize; XLA only codegens) and inspect the partitioned module
    compiled = lowered.compile()
    ctext = compiled.as_text()
    assert "all-reduce" in ctext or "all-gather" in ctext or \
        "reduce-scatter" in ctext, \
        "no ICI collective emitted for TP contractions / DP grads"

    # per-device parameter bytes fit one v5e HBM (16GB): 10.2B f32 / 4
    # mp shards ≈ 10.2GB — the layout is deployable, unsharded it isn't
    shard_bytes = 4 * (V * H // 4 + L * 12 * H * H // 4)
    assert shard_bytes < 16e9 < 4 * N_PARAMS
