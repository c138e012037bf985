"""The one general traffic generator: a mix is a JSON file of parameters
under benchmark/traffic/, and everything a run sends is made here from that
file and `--seed`.

Every seed gets the SAME work. The sequence of request sizes and of gaps
between arrivals is drawn once from the mix's own `pool_seed`; `--seed`
draws the token ids (and, in an open loop, starts that ring at another
point). A tail of queueing time depends on which long requests meet which
burst, so a free permutation would make the 95th percentile a property of
the seed; a rotation keeps every burst and its neighbours and moves only
where the window cuts the ring. Runs with different seeds then differ as two
runs of one seed do.
"""
import numpy as np

SEED_MOD = 2 ** 31 - 1   # the driver's seeds pass 32 signed bits


def rng_for(seed, *stream):
    return np.random.default_rng([int(seed) % SEED_MOD, *stream])


def lengths(rng, n, spec):
    """`n` log-normal lengths: median, sigma of the log, clipped."""
    raw = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def size_pool(mix, n):
    """`n` (prompt, output) length pairs from the mix's own seed, each
    pair held to `max_total` by shortening the output."""
    rng = rng_for(mix["pool_seed"], 0)
    prompt = lengths(rng, n, mix["prompt_tokens"])
    out = lengths(rng, n, mix["output_tokens"])
    out = np.maximum(np.minimum(out, mix["max_total_tokens"] - prompt),
                     mix["output_tokens"]["min"])
    return prompt, out


def serve_requests(mix, seed, seconds, vocab_size):
    """The requests of one run, in the order they are sent.

    Open loop: round(rate * seconds) requests whose gaps are exponential
    draws scaled so that the last gap ends with the window; each request
    has a `due_s`; `--seed` starts the ring of sizes and gaps at another
    point. Closed loop: one endless generator per client; client i sends
    entries i, i + clients, i + 2 * clients, ... of the ring of `pool`
    sizes, whatever the seed and whichever client is ahead, because with
    some tens of requests in a window tokens per second follow the sizes
    that happen to be in flight (on the chip, PR 24: 32 to 40 tokens/s from
    one ring started at six points, against two runs of one start agreeing
    to four digits in five pairs of six)."""
    def request(ids, p, o, due):
        return {"prompt": ids.integers(0, vocab_size, size=int(p),
                                       dtype=np.int32),
                "max_new": int(o), "due_s": due,
                "timeout_ms": 1e3 * mix["request_timeout_s"]}

    if mix["loop"] == "open":
        ids = rng_for(seed, 2)
        n = max(1, round(mix["rate_per_s"] * seconds))
        prompt, out = size_pool(mix, n)
        gaps = rng_for(mix["pool_seed"], 1).exponential(size=n)
        start = int(rng_for(seed, 1).integers(n))
        order = np.roll(np.arange(n), -start)
        gaps = gaps[order] * (seconds / gaps.sum())
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        return [request(ids, prompt[i], out[i], float(d))
                for i, d in zip(order, due)]
    if mix["loop"] != "closed":
        raise ValueError(f"a serving mix has loop open or closed, "
                         f"not {mix['loop']!r}")
    prompt, out = size_pool(mix, mix["pool"])

    def client(c):
        ids, i = rng_for(seed, 2, c), c
        while True:
            yield request(ids, prompt[i % mix["pool"]],
                          out[i % mix["pool"]], None)
            i += mix["clients"]
    return [client(c) for c in range(mix["clients"])]


def zipf_cdf(vocab_size, exponent, first_id):
    """Cumulative unigram distribution over ids first_id..vocab_size-1."""
    w = 1.0 / np.arange(1, vocab_size - first_id + 1) ** exponent
    return np.cumsum(w / w.sum())


def pretrain_sample(mix, cdf, first_id, mask_id, seed, index):
    """One packed MLM + NSP sample: (input ids, MLM labels, NSP label).
    Tokens are Zipf unigram draws, so there is a distribution to learn;
    `mask_prob` of the positions are replaced by `mask_id` and carry their
    token as the label, every other label is -100 (ignored)."""
    rng = rng_for(seed, 3, index)
    seq = mix["seq_len"]
    tokens = (np.minimum(np.searchsorted(cdf, rng.random(seq)), len(cdf) - 1)
              + first_id).astype(
        np.int32)
    masked = rng.random(seq) < mix["mask_prob"]
    masked[rng.integers(seq)] = True   # never a sample with no label
    labels = np.where(masked, tokens, -100).astype(np.int64)
    ids = np.where(masked, mask_id, tokens).astype(np.int32)
    return ids, labels, np.int64(rng.integers(2))
