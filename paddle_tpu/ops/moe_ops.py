"""Routed experts: sigmoid router with a selection bias, top-k, tokens
sorted by expert, one grouped matmul per projection over the experts held,
no dropped tokens.

The router is DeepSeek-V3's `noaux_tc` with one group (GLM-4.7-Flash,
`models/glm_moe.py`): scores `s = sigmoid(x . W_r)` in float32, the k
experts are the top-k of `s + b` (`b` a selection bias that is a buffer,
not a trained weight), and the combine weights are `scale * s_i / sum s_j`
over the chosen experts — from `s` WITHOUT `b`.

The experts are SwiGLUs whose weights are stacked `[E, d, f]` / `[E, f, d]`.
Each (token, expert) pair is one row: rows are sorted by expert, the three
projections are `jax.lax.ragged_dot` over the sorted rows (XLA:TPU lowers it
to its own grouped-matmul kernel, which reads the weights of the experts
that got rows and does the flops of the rows there are; on the CPU it is a
plain reference), and the rows go back to token order by a gather — no
scatter, no capacity, nothing dropped. A row marked dead (`live` false: an
empty decode slot) is given to no expert: it sorts past the last group and
reads no weight.

Scopes `router`, `dispatch`, `experts`, `combine` are names on the device
(tools/trace_report.py); `STAT_moe_grouped` counts traces, not calls.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework import monitor

__all__ = ["moe_route", "moe_grouped_experts", "moe_dense_experts"]


def moe_route(x, w_router, bias, top_k, scale):
    """x [T, d]; w_router [d, E]; bias [E] float32. Returns
    (idx [T, k] int32, gates [T, k] float32). Logits, sigmoid, top-k and
    the normalisation are float32 whatever `x` is: the operands are upcast
    (exact) and the product is taken at "highest"."""
    with jax.named_scope("router"):
        logits = jnp.matmul(x.astype(jnp.float32),
                            w_router.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
        picked = jnp.take_along_axis(s, idx, axis=-1)
        gates = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), gates


def _swiglu_rows(rows, wg, wu, wd, sizes):
    def gmm(a, w):
        return jax.lax.ragged_dot(a, w, sizes,
                                  preferred_element_type=jnp.float32)
    act = (jax.nn.silu(gmm(rows, wg)) * gmm(rows, wu)).astype(rows.dtype)
    return gmm(act, wd)


def moe_grouped_experts(x, idx, gates, wg, wu, wd, live=None):
    """The routed part of the layer: sum_i gates_i * E_idx_i(x).

    x [T, d]; idx/gates [T, k]; wg/wu [E, d, f]; wd [E, f, d]; live [T]
    bool or None. Returns (y [T, d] float32, hit int32 — the number of
    distinct experts that got at least one live row)."""
    monitor.stat_add("STAT_moe_grouped")       # traces, not calls
    T, k = idx.shape
    E = wg.shape[0]
    with jax.named_scope("dispatch"):
        flat = idx.reshape(-1)
        if live is not None:
            # a dead row's expert is E: past every group
            flat = jnp.where(jnp.repeat(live, k), flat, E)
        order = jnp.argsort(flat, stable=True)
        rows = x[order // k]                                 # [T*k, d]
        sizes = jnp.bincount(flat, length=E + 1)[:E].astype(jnp.int32)
    with jax.named_scope("experts"):
        y = _swiglu_rows(rows, wg, wu, wd, sizes)            # [T*k, d]
        # rows past the last group belong to no expert; whatever the
        # grouped product left there is dropped, never multiplied
        y = jnp.where((jnp.arange(T * k) < sizes.sum())[:, None], y, 0.0)
    with jax.named_scope("combine"):
        back = jnp.argsort(order)                            # token order
        y = y[back].reshape(T, k, -1) * gates[..., None]
        return y.sum(1), jnp.sum(sizes > 0).astype(jnp.int32)


def moe_dense_experts(x, idx, gates, wg, wu, wd):
    """The same sum as a plain loop over ALL experts, each run on every
    token and weighted by its gate (0 where the token did not choose it):
    the oracle `moe_grouped_experts` is tested against."""
    E = wg.shape[0]
    weight = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32)
                     * gates[..., None], axis=1)             # [T, E]

    def mm(a, w):
        return jnp.matmul(a, w, preferred_element_type=jnp.float32)

    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(E):
        act = (jax.nn.silu(mm(x, wg[e])) * mm(x, wu[e])).astype(x.dtype)
        y = y + weight[:, e:e + 1] * mm(act, wd[e])
    return y
