"""Run one benchmark cell with ONE fault planted in the program, to read
what the cell's `correct` makes of it.

    python tools/plant_fault.py <fault> [--dump DIR] [--drain S] -- \\
        --workload glm-4.7-flash.reasoning-saturated --seed 1 --seconds 10

Everything after `--` goes to `benchmark/run.py` unchanged: the driver, the
traffic, the reference and the comparison are the benchmark's own, so the
last line's `correct` is what the driver's check would have read had the
program carried the fault. The limits of a configuration's comparison are
set between two readings (PERF.md section 6): what the sound program gives
over its seeds, and what these plants give.

Faults of the latent family (models/glm_moe.py, serving/latent_family.py):

    none            nothing planted (with --dump: the sound program's arrays)
    float8_cache    every cached latent row rounded to float8 (e4m3) when it
                    is written: the nearest precision below the bfloat16 cache
    dropped_expert  the weakest of a token's top-k experts contributes
                    nothing (its gate is 0, the others keep theirs)
    wrong_page      decode attention reads, as each slot's FIRST page, the
                    first page of the slot before it
    wrong_table     decode attention reads every slot through the page table
                    of the slot before it: a whole context that is another's

Faults of the hybrid family (models/falcon_h1.py, serving/hybrid_family.py):

    bf16_state      the mixer state rounded to bfloat16 at every write
                    (prefill's and each decode step's): the nearest
                    precision below the float32 state pool
    neighbour_state each slot decodes from the state of the slot before it
                    (and updates that one): a whole state that is another's
    unmasked_pad    prefill takes the state and the window at the BUCKET's
                    end, not at the prompt's: padding runs through the scan
    no_window       the convolution's carried window is zero when decode
                    starts: the three positions before it are forgotten
    float8_window   K/V rows and the convolution's window rounded to float8
                    (e4m3) when they are written: the nearest precision
                    below the bfloat16 pages and window
    wrong_page, wrong_table
                    as for the latent family, through `paged_attention` over
                    the K/V head pools: a first page, or a whole context,
                    that is the neighbour's
    wrong_group     query head i reads K/V head i % 4 (striped) where the
                    grouped-query map is i // 5 (blocked)

Faults of the GPT family (models/gpt.py, serving/gpt_family.py), whose
64-wide heads lie side by side in the lanes of one fused K and one fused V
row a token:

    wrong_page, wrong_table
                    as above, through `paged_attention` over the fused pools
    wrong_lanes     query head i reads the 64 lanes of head (i + 1) mod H
                    in every K and V row

`--dump DIR` writes the reference's per-token shortfalls (`short.npy`) and
the window's step records (`steps.json`) there; `--drain S` shortens the
mix's drain for a short `--seconds` (a plant needs finished requests to
check, not a steady rate).
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def float8_cache():
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_ops
    write = paged_ops.paged_latent_write

    def rounded(pool, layer, page_ids, offsets, rows):
        rows = rows.astype(jnp.float8_e4m3fn).astype(rows.dtype)
        return write(pool, layer, page_ids, offsets, rows)
    paged_ops.paged_latent_write = rounded


def dropped_expert():
    from paddle_tpu.models import glm_moe
    route = glm_moe.moe_route

    def three_of_four(*args):
        idx, gates = route(*args)       # top-k comes sorted, weakest last
        return idx, gates.at[:, -1].set(0.0)
    glm_moe.moe_route = three_of_four


def _attend_through(wrong):
    """Decode attention of either family through `wrong(page_table)`: the
    latent family's `paged_latent_attention`, the hybrid family's
    `paged_attention` over its K/V head pools."""
    from paddle_tpu.ops import paged_ops
    latent, heads = paged_ops.paged_latent_attention, paged_ops.paged_attention

    def latent_through(q, pool, page_table, *args, **kw):
        return latent(q, pool, wrong(page_table), *args, **kw)

    def heads_through(q, k_pages, v_pages, page_table, *args, **kw):
        return heads(q, k_pages, v_pages, wrong(page_table), *args, **kw)
    paged_ops.paged_latent_attention = latent_through
    paged_ops.paged_attention = heads_through


def wrong_page():
    import jax.numpy as jnp
    _attend_through(lambda pt: pt.at[:, 0].set(jnp.roll(pt[:, 0], 1)))


def wrong_table():
    import jax.numpy as jnp
    _attend_through(lambda pt: jnp.roll(pt, 1, axis=0))


def wrong_group():
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_ops
    attend = paged_ops.paged_attention

    def striped(q, k_pages, v_pages, *args, kv_heads=None, **kw):
        # the true map is query head i -> K/V head i // (H / Hkv); query
        # head i sits at (i % Hkv) * (H / Hkv) + i // Hkv here, so that it
        # reads K/V head i % Hkv
        H = q.shape[1]
        Hkv = kv_heads or k_pages.shape[0]
        at = jnp.asarray([(i % Hkv) * (H // Hkv) + i // Hkv
                          for i in range(H)])
        out = attend(q.at[:, at].set(q), k_pages, v_pages, *args,
                     kv_heads=kv_heads, **kw)
        return out[:, at]
    paged_ops.paged_attention = striped


def wrong_lanes():
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_ops
    attend = paged_ops.paged_attention

    def next_heads_lanes(q, *args, **kw):
        # query head i sits where head i + 1 is read, so that it reads the
        # (i + 1) mod H-th 64 lanes of every fused K and V row
        return jnp.roll(attend(jnp.roll(q, 1, axis=1), *args, **kw), -1,
                        axis=1)
    paged_ops.paged_attention = next_heads_lanes


def bf16_state():
    import jax
    from paddle_tpu.ops import ssm_ops
    from paddle_tpu.serving import hybrid_family
    store, update = hybrid_family.store_state, ssm_ops.ssm_decode_update

    def rounded(x):
        # not `x.astype(bfloat16).astype(x.dtype)`: inside a program
        # XLA:TPU drops that pair as excess precision it may keep (read on
        # the v5e, PR 36: the decode program's states came out unrounded)
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def store_rounded(pool, slot, states):
        return store(pool, slot, rounded(states))

    def update_rounded(pool, layer, *args):
        pool, y = update(pool, layer, *args)
        return pool.at[layer].set(rounded(pool[layer])), y
    hybrid_family.store_state = store_rounded
    ssm_ops.ssm_decode_update = update_rounded


def neighbour_state():
    import jax.numpy as jnp
    from paddle_tpu.ops import ssm_ops
    update = ssm_ops.ssm_decode_update

    def through_neighbour(pool, layer, *inputs):
        # state j takes slot j + 1's inputs; slot i reads state i - 1
        pool, y = update(pool, layer,
                         *(jnp.roll(x, -1, axis=0) for x in inputs))
        return pool, jnp.roll(y, 1, axis=0)
    ssm_ops.ssm_decode_update = through_neighbour


def unmasked_pad():
    from paddle_tpu.models import falcon_h1
    prefill = falcon_h1.fh1_prefill

    def to_the_buckets_end(W, ids, cfg, length=None):
        return prefill(W, ids, cfg, None)
    falcon_h1.fh1_prefill = to_the_buckets_end


def no_window():
    import jax.numpy as jnp
    from paddle_tpu.serving import hybrid_family
    store = hybrid_family.store_window

    def store_zero(pool, slot, windows):
        return store(pool, slot, jnp.zeros_like(windows))
    hybrid_family.store_window = store_zero


def float8_window():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_ops, ssm_ops
    from paddle_tpu.serving import hybrid_family
    write, store = paged_ops.paged_write, hybrid_family.store_window
    step = ssm_ops.causal_conv_window_step

    def rounded(x):
        # the barrier keeps the pair of conversions in the program (see
        # `bf16_state`: XLA:TPU may drop a round trip it takes for excess
        # precision)
        return jax.lax.optimization_barrier(
            x.astype(jnp.float8_e4m3fn)).astype(x.dtype)

    def step_rounded(window, x, w, b):
        out, window = step(window, x, w, b)
        return out, rounded(window)
    paged_ops.paged_write = lambda pages, layer, ids, offs, values: write(
        pages, layer, ids, offs, rounded(values))
    hybrid_family.store_window = lambda pool, slot, windows: store(
        pool, slot, rounded(windows))
    ssm_ops.causal_conv_window_step = step_rounded


PLANTS = {"none": lambda: None, "float8_cache": float8_cache,
          "dropped_expert": dropped_expert, "wrong_page": wrong_page,
          "wrong_table": wrong_table, "wrong_group": wrong_group,
          "wrong_lanes": wrong_lanes, "bf16_state": bf16_state,
          "neighbour_state": neighbour_state, "unmasked_pad": unmasked_pad,
          "no_window": no_window, "float8_window": float8_window}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("fault", choices=sorted(PLANTS))
    ap.add_argument("--dump", default=None)
    ap.add_argument("--drain", type=float, default=None)
    cut = sys.argv.index("--") if "--" in sys.argv else len(sys.argv)
    args, rest = ap.parse_args(sys.argv[1:cut]), sys.argv[cut + 1:]
    from benchmark import run

    load_json, load_by_name = run.load_json, run.load_by_name

    def json_with_drain(path):
        data = load_json(path)
        if args.drain is not None and path.startswith("benchmark/traffic/"):
            data["drain_seconds"] = args.drain
        return data

    def by_name_with_dump(folder, name):
        import numpy as np
        mod = load_by_name(folder, name)
        if folder == "drivers":
            # here and not before: run.main() has chosen the platform
            PLANTS[args.fault]()
            print(f"plant_fault: {args.fault} planted", flush=True)
        if args.dump and folder == "drivers" and hasattr(mod, "window"):
            window = mod.window

            def dumped(*a, **kw):
                w = window(*a, **kw)
                with open(os.path.join(args.dump, "steps.json"), "w") as f:
                    json.dump(w["steps"], f)
                return w
            mod.window = dumped
        if args.dump and folder == "reference":
            shortfalls = mod.shortfalls

            def dumped(*a, **kw):
                out = shortfalls(*a, **kw)
                np.save(os.path.join(args.dump, "short.npy"),
                        np.concatenate(out))
                return out
            mod.shortfalls = dumped
        return mod

    run.load_json, run.load_by_name = json_with_drain, by_name_with_dump
    if args.dump:
        os.makedirs(args.dump, exist_ok=True)
    sys.argv = ["benchmark/run.py"] + rest
    run.main()


if __name__ == "__main__":
    main()
