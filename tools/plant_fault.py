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
    from paddle_tpu.ops import paged_ops
    attend = paged_ops.paged_latent_attention

    def through_wrong_table(q, pool, page_table, *args):
        return attend(q, pool, wrong(page_table), *args)
    paged_ops.paged_latent_attention = through_wrong_table


def wrong_page():
    import jax.numpy as jnp
    _attend_through(lambda pt: pt.at[:, 0].set(jnp.roll(pt[:, 0], 1)))


def wrong_table():
    import jax.numpy as jnp
    _attend_through(lambda pt: jnp.roll(pt, 1, axis=0))


PLANTS = {"none": lambda: None, "float8_cache": float8_cache,
          "dropped_expert": dropped_expert, "wrong_page": wrong_page,
          "wrong_table": wrong_table}


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
