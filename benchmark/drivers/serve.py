"""Driver of kind `serve`: a configuration behind `serving.GenerationEngine`,
every request through `submit_stream`, timed from the client's side.

Open loop: one dispatcher sends each request when it is due and one reader
thread per request in flight stamps every token; a request's first token is
timed from when it was DUE. Closed loop: `clients` threads, each sending its
next request when its last one finished. The window is `seconds` long from
the first request and tokens are counted inside it. An open loop's requests
in flight at its end are drained (bounded, outside the count), and what is
then unfinished, refused or errored has failed; a closed loop counts the
requests that reached an outcome, and leaves out those its clients still
have in flight.
"""
import threading
import time

import jax
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.profiler import step_log

from benchmark import trace_reduce, trafficgen

ENGINE = "bench"


class Reader(threading.Thread):
    """One request from its submission to its stream's end: stamps each
    token. A refusal at submission (queue full, no pages) is a failed
    request, not a crash: the reader then holds the error and no stream."""

    def __init__(self, engine, request):
        super().__init__(daemon=True)
        self.request = request
        self.stamps, self.tokens, self.error = [], [], None
        self.sent = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench:submit"):
                self.stream = engine.submit_stream(
                    request["prompt"], max_new_tokens=request["max_new"],
                    timeout_ms=request["timeout_ms"])
        except Exception as e:      # EngineOverloaded, ResourceExhausted
            self.stream, self.error = (), e
        self.start()

    def run(self):
        try:
            for tok in self.stream:
                self.stamps.append(time.perf_counter())
                self.tokens.append(tok)
        except Exception as e:      # the engine's error for this request
            self.error = e

    @property
    def finished(self):
        return (not self.is_alive() and self.error is None
                and len(self.tokens) == self.request["max_new"])


def open_loop(engine, requests, t0, seconds, readers):
    for q in requests:
        wait = t0 + q["due_s"] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        readers.append(Reader(engine, q))
    rest = t0 + seconds - time.perf_counter()
    if rest > 0:
        time.sleep(rest)


def closed_loop(engine, requests, t0, seconds, readers):
    def client(own):
        while time.perf_counter() < t0 + seconds:
            r = Reader(engine, next(own))
            readers.append(r)       # list.append is atomic
            r.join()

    threads = [threading.Thread(target=client, args=(own,), daemon=True)
               for own in requests]
    for t in threads:
        t.start()
    return threads


def pctl(values, q):
    return float(np.percentile(np.asarray(values, float), q))


def build(ctx):
    """The model and a warmed engine: all of it set-up."""
    cfg, say = ctx.config, ctx.say
    paddle.seed(ctx.seed % trafficgen.SEED_MOD)
    mcfg = ctx.resolve(cfg["run"]["config_class"])(**ctx.model_kwargs)
    t = time.perf_counter()
    net = ctx.resolve(cfg["run"]["model_class"])(mcfg)
    net.eval()
    say(f"serve: model built in {time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    eopts = dict(cfg["run"]["engine"])
    eopts["prefill_buckets"] = tuple(eopts["prefill_buckets"])
    engine = serving.GenerationEngine(net, name=ENGINE, **eopts)
    say(f"serve: engine warmed in {time.perf_counter() - t:.1f}s, compile "
        f"ledger {engine.stats()['compiles']}")
    # one request end to end before the clock: threads, queues, first use
    warm_reader = Reader(engine, {"prompt": np.arange(8, dtype=np.int32) + 1,
                                  "max_new": 4, "timeout_ms": None})
    warm_reader.join(120)
    if not warm_reader.finished:
        raise RuntimeError(f"the warm-up request did not finish: "
                           f"{warm_reader.error!r}")
    return net, engine


def window(ctx, engine, mix, seconds, trace):
    """One measured window of `mix`, and the drain after it."""
    say = ctx.say
    requests = trafficgen.serve_requests(mix, ctx.seed, seconds,
                                         ctx.config["vocab_size"])
    readers = []
    tracer = None
    if trace:
        trace_s = min(mix["trace_seconds"], seconds / 2)

        def traced():
            time.sleep(seconds / 2 - trace_s / 2)
            span = trace_reduce.open_window(ctx.trace_dir)
            time.sleep(trace_s)
            trace_reduce.close_window(span)
        tracer = threading.Thread(target=traced, daemon=True)
    warm = dict(engine.stats()["compiles"])
    t0 = time.perf_counter()
    t1 = t0 + seconds
    if tracer:
        tracer.start()
    clients = []
    if mix["loop"] == "open":
        open_loop(engine, requests, t0, seconds, readers)
    else:
        clients = closed_loop(engine, requests, t0, seconds, readers)
        time.sleep(max(0.0, t1 - time.perf_counter()))
    payload = step_log.steps_payload()["engines"][ENGINE]
    # drain what is in flight, bounded and outside the window's count
    deadline = time.perf_counter() + mix["drain_seconds"]
    for th in clients:
        th.join(max(0.0, deadline - time.perf_counter()))
    for r in list(readers):
        r.join(max(0.0, deadline - time.perf_counter()))
    # an open loop's request was due in the window, so one still unfinished
    # has failed; a closed loop always ends with its clients' requests in
    # flight, and those belong to no window: only outcomes are counted
    sent = [r for r in list(readers)
            if mix["loop"] == "open" or not r.is_alive()]
    if tracer:
        tracer.join(60)
    compiled = engine.stats()["compiles"] != warm
    if compiled:
        say(f"serve: COMPILED in the window: {engine.stats()['compiles']} "
            f"vs {warm}")

    done = [r for r in sent if r.finished]
    for r in sent:
        if not r.finished:
            say(f"serve: FAILED request (prompt {len(r.request['prompt'])}, "
                f"new {r.request['max_new']}): {r.error or 'unfinished'!r}")
            break
    in_window = sum(1 for r in sent for s in r.stamps if s <= t1)
    gaps = [b - a for r in sent
            for a, b in zip(r.stamps, r.stamps[1:]) if b <= t1]
    end_to_end = {"serve_tokens_per_s": in_window / seconds}
    if mix["loop"] == "open":
        ttft = [r.stamps[0] - (t0 + r.request["due_s"]) for r in done]
        late = [r.sent - (t0 + r.request["due_s"]) for r in sent]
        end_to_end["ttft_p95_ms"] = pctl(ttft, 95) * 1e3
        end_to_end["gap_p95_ms"] = pctl(gaps, 95) * 1e3
        say(f"serve: ttft ms p50 {pctl(ttft, 50) * 1e3:.1f} p95 "
            f"{end_to_end['ttft_p95_ms']:.1f} over {len(ttft)}; generator "
            f"lateness ms p95 {pctl(late, 95) * 1e3:.2f}")
    # the window's records of the step ring, by their own clock
    recs = [r for r in payload["records"] if t0 <= r["t"] <= t1]
    lost = (payload["recorded_total"] > payload["ring_capacity"]
            and min(r["t"] for r in payload["records"]) > t0)
    if lost or not recs:
        raise RuntimeError("the step ring lost the window's first records: "
                           "raise FLAGS_gen_step_log_size or shorten the run")

    def depth(lo, hi):
        part = [r["queue_depth"] for r in recs
                if t0 + lo * seconds <= r["t"] <= t0 + hi * seconds]
        return sum(part) / max(1, len(part))
    finished_in = sum(1 for r in done if r.stamps[-1] <= t1)
    say(f"serve: gap ms p50 {pctl(gaps, 50) * 1e3:.2f} p95 "
        f"{pctl(gaps, 95) * 1e3:.2f} over {len(gaps)} gaps; {len(sent)} "
        f"requests sent, {finished_in} finished in the window of {seconds}s "
        f"and {len(done)} by the drain's end; {in_window} tokens in the "
        f"window; mean queue depth {depth(0.4, 0.6):.2f} mid-window, "
        f"{depth(0.8, 1.0):.2f} in its last fifth")
    buckets = {k: round(sum(r[k] for r in recs), 1) for k in (
        "attr_admit_ms", "prefill_ms", "attr_promote_ms", "decode_ms",
        "attr_bookkeep_ms", "attr_idle_ms", "attr_wall_ms")}
    say(f"serve: step thread's buckets over the window, ms: {buckets}; "
        f"{len(recs)} iterations")
    return {"attempted": len(sent), "failed": len(sent) - len(done),
            "done": done, "compiled": compiled, "setup_end": t0,
            "end_to_end": end_to_end, "steps": recs,
            "finished_in_window": finished_in,
            "queue_depth": (depth(0.4, 0.6), depth(0.8, 1.0))}


def run(ctx):
    cfg = ctx.config
    net, engine = build(ctx)
    if ctx.sweep:
        return sweep(ctx, engine)
    w = window(ctx, engine, ctx.traffic, ctx.seconds, ctx.trace)
    engine.shutdown(drain=False, timeout_s=30)

    # correct: a seeded sample of finished requests, teacher-forced
    # through the plain reference
    done = w["done"]
    pick = trafficgen.rng_for(ctx.seed, 4).permutation(len(done))[
        :cfg["run"]["check_requests"]]
    ref = ctx.load("reference", ctx.cell["config"])
    seqs = [np.concatenate([done[i].request["prompt"], done[i].tokens])
            for i in pick]
    short = ref.shortfalls(ref.weights(net.state_dict()), seqs,
                           [len(done[i].request["prompt"]) for i in pick],
                           ctx.model_kwargs["num_heads"])
    flat = np.concatenate(short)
    near = bool(flat.max() <= cfg["run"]["near_margin"])
    ctx.say(f"serve: {len(pick)} requests ({flat.size} tokens) against the "
            f"plain reference: {np.mean(flat == 0):.3f} are its argmax, "
            f"largest shortfall {flat.max():.5f} (margin "
            f"{cfg['run']['near_margin']}) -> {'ok' if near else 'WRONG'}")
    return {
        "correct": near and not w["compiled"] and bool(done),
        "attempted": w["attempted"], "failed": w["failed"],
        "setup_end": w["setup_end"], "end_to_end": w["end_to_end"],
        "record": {"steps": w["steps"], "engine": cfg["run"]["engine"]},
    }


def sweep(ctx, engine):
    """`--sweep r1,r2,...`: one window of the open-loop mix at each rate on
    one engine, to find the knee when a cell is defined. A rate counts as
    sustained when the requests finished inside the window are at least 0.95
    of those offered less the ones that could not have finished (due in the
    window's last mean service time), and the queue in the window's last
    fifth is no deeper than mid-window + 1. Prints a table, no result."""
    rows = []
    for rate in ctx.sweep:
        ctx.say(f"sweep: {rate} requests/s for {ctx.seconds}s")
        w = window(ctx, engine, dict(ctx.traffic, rate_per_s=rate),
                   ctx.seconds, False)
        rows.append({"rate_per_s": rate, "attempted": w["attempted"],
                     "failed": w["failed"],
                     "finished_in_window": w["finished_in_window"],
                     "queue_mid": w["queue_depth"][0],
                     "queue_end": w["queue_depth"][1], **w["end_to_end"]})
        ctx.say(f"sweep: {rows[-1]}")
    engine.shutdown(drain=False, timeout_s=30)
    return {"sweep": rows}
