"""Subprocess worker for tests/test_warm_start.py: one fresh process that
builds a tiny engine under a supervisor over the compile cache its
environment names (`JAX_COMPILATION_CACHE_DIR`), serves two fixed greedy
prompts, kills the engine once and serves them again, and prints one JSON
line. A ledger read inside one process cannot show that a warm start
survives the process; two runs of this script can.

    python tests/warm_start_worker.py gpt|latent

Model and prompts are deterministic (paddle.seed(11), RandomState(0)): two
processes build the same weights and the same programs.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def cache_entries():
    """The compiled programs in the cache directory (JAX keeps a
    `<key>-atime` file beside each, rewritten on every read)."""
    d = os.environ["JAX_COMPILATION_CACHE_DIR"]
    if not os.path.isdir(d):
        return []
    return sorted(f for f in os.listdir(d) if not f.endswith("-atime"))


def main(family: str) -> int:
    before = cache_entries()

    import paddle_tpu as paddle
    from paddle_tpu import device, serving
    from paddle_tpu.models import (GlmMoeLiteConfig, GlmMoeLiteForCausalLM,
                                   GPTConfig, GPTForCausalLM)
    from paddle_tpu.serving import failpoints

    assert device.compilation_cache_dir() == \
        os.environ["JAX_COMPILATION_CACHE_DIR"]
    paddle.seed(11)
    if family == "gpt":
        net, kw = GPTForCausalLM(GPTConfig.tiny(dropout=0.0)), {}
    else:
        net, kw = GlmMoeLiteForCausalLM(GlmMoeLiteConfig.tiny()), \
            {"pages_per_seq": 8}
    net.eval()
    prompts = np.random.RandomState(0).randint(
        0, 200, size=(2, 7)).astype("int64")

    def serve():
        futs = [sup.submit(p, max_new_tokens=5) for p in prompts]
        return [np.asarray(f.result(timeout=120)).tolist() for f in futs]

    sup = serving.EngineSupervisor(
        net, name=f"warm_{family}", max_slots=2, page_size=4, num_pages=32,
        prefill_buckets=(8,), max_new_tokens=5, request_timeout_ms=0, **kw)
    try:
        tokens = serve()
        stats = sup.stats()
        served = cache_entries()
        # one engine death, as tests/test_engine_resurrection.py arms it
        failpoints.reset()
        paddle.set_flags({"FLAGS_failpoints": "decode_step_raise@2",
                          "FLAGS_gen_restart_backoff_ms": 5.0})
        replayed = serve()
        after = sup.stats()
    finally:
        sup.shutdown()

    print(json.dumps({
        "tokens": tokens,
        "compiles": stats["compiles"],
        "pools": [{k: p[k] for k in ("layout", "compiled_for", "preferred")}
                  for p in stats["pools"]],
        "cache_before": before,
        "cache_added": sorted(set(served) - set(before)),
        "restart": {
            "restarts": after["supervisor"]["restarts"],
            "tokens": replayed,
            "compiles": after["compiles"],
            "pools": [{k: p[k] for k in ("layout", "compiled_for")}
                      for p in after["pools"]],
            "cache_added": sorted(set(cache_entries()) - set(served)),
        }}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
