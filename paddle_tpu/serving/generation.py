"""Continuous-batching generation engine over a paged KV cache.

The PR 2/3 engine is one-shot: a request enters a bucket, runs once,
leaves. Autoregressive decode — the dominant production inference
workload — needs **iteration-level scheduling** (Orca) over a
**paged KV cache** (vLLM): requests join the running batch via a
prefill pass, every engine step advances EVERY live sequence by one
token through a single jitted decode program, and sequences leave on
EOS / max-tokens / deadline, freeing their pages the same step.

Shape discipline is what makes this TPU-native: the decode batch is a
FIXED number of slots (`FLAGS_gen_max_slots`) with inactive slots
masked, and prompts pad up to `FLAGS_gen_prefill_buckets`, so XLA
compiles exactly **one decode step** and **one prefill per bucket** —
sequences joining and leaving mid-decode never retrace (the compile
ledger in `stats()` proves it, the same exactness contract as the PR 3
per-(device, bucket) ledgers). K/V lives in `serving.PagedKVCache`
pools; on TPU the Pallas `paged_attention` kernel reads pages in place,
elsewhere a dense gather reference keeps the math bit-anchored to
`GPTModel.generate` (`ops/paged_ops.py`). With
`kv_cache_dtype="int8"` (FLAGS_kv_cache_dtype) the pools store int8
pages + per-(layer, head, page) scale pools — quantize-on-append,
dequantize-on-read, ~4x the concurrent sequences per HBM byte; parity
vs fp32 pages is token-level (different compiled programs). A
`quantize_weights`'d model composes independently: its decode-weight
pytree carries (int8, scale) leaves dequantized in-graph.

**Prefix cache (ISSUE 12, `FLAGS_gen_prefix_cache` /
`prefix_cache=True`)**: full pages of prompt K/V are indexed by a
content-hash block chain (`serving/prefix_cache.py`) over refcounted
pages; a request whose prompt walks a cached chain maps those pages
read-only and prefills ONLY the tail through a per-bucket
`prefill_tail` program (tail queries attend cached pages + their own
in-flight K/V — `ops/paged_ops.paged_prefix_attention`). A full-prompt
match recomputes just its last position, copy-on-write splitting the
page that holds it (int8 mode clones the scale row too) so the shared
original is never written under other readers. Zero-on-free keys on
refcounts — a freed sequence's shared pages survive for future hits —
and refcount-0 cached chains are LRU-evicted BEFORE alloc whenever the
free list alone is short, so `can_admit`/`headroom` count them as
reclaimable. TTFT collapses for shared-system-prompt traffic while
greedy output stays token-identical with the cache off: the cached
pages hold the same K/V the skipped prefill would have produced.

**Speculative decoding (ISSUE 14, `FLAGS_gen_spec_k` / `spec_k=K`)**:
decode is weight-streaming-bound, so ONE fixed-k jitted verify program
replaces the decode step — each live slot's [current token + K
prompt-lookup drafts] block (`serving/spec_decode.py`, the sequence's
own history as the draft model) runs one `gpt_spec_verify` pass over
the paged cache, acceptance (exact greedy agreement) is computed
in-graph, and only consumed positions' K/V commit; rejected draft
lanes scrub to the scratch page, so a step delivers 1..K+1 tokens with
greedy output token-identical to speculation off and zero retraces as
drafts are accepted or rejected. **Chunked prefill
(`FLAGS_gen_prefill_chunk`)**: long prompts admit immediately but
prefill one fixed-size chunk per engine iteration through the
per-bucket tail programs, interleaved with decode steps — a long
prompt stops spiking every live sequence's TPOT; the slot joins decode
when its final chunk lands.

**Streaming (`submit_stream`)**: a per-token `TokenStream` fed from the
step thread — each token is staged during the iteration and delivered
only after `_record_iteration` lands (the same deferred-resolution
barrier as futures, so a consumer never observes a token the step ring
doesn't account for yet), and the final token always precedes the
future's resolution. Stream deadlines split: `ttft_timeout_ms` is HARD
(expiry before the first token cancels with `ExecutionTimeoutError`),
`timeout_ms` is SOFT once tokens flow (expiry mid-stream stops decoding
and resolves with what was delivered — tokens already left the engine
and cannot be retracted).

**One decode step in flight (ISSUE 34)**: the step thread launches decode
step n+1 BEFORE it reads step n. Step n's next-token output is step n+1's
token input as a device array that no one reads in between (the program
also takes host tokens and a per-slot mask), positions, activity and the
page table come from the host's own count, and the PRNG key is folded
inside the program from the base key and the step number. Then the thread
reads step n, delivers, completes, records and admits while the chip runs
step n+1. A request admitted meanwhile joins the same way: its prefill is
followed by the small `gen_first_token` program, which samples the first
token on the device and writes it into that token input at the request's
slot, and the prefill is read only after step n+1 is launched behind it.
What is learned a step late — an EOS, a poison flag, of a decode step or
of a prefill — costs one step: the token step n+1 computed for that slot
is dropped (never staged, never counted; its K/V write fell inside the
request's own pages, zeroed on release as ever). What needs tokens on the
host before the next launch — speculation, a `_pre_step_hook`, an armed
step failpoint — finds no step in flight and every prefill read at once:
the one loop settles first, decided from the engine's own state
(`stats()["lookahead"]`).

Hardening carries over from the one-shot engine, re-expressed at token
granularity: bounded intake (`EngineOverloaded`), worst-case page
admission control (a request is only admitted when the allocator can
cover prompt + max-new, so running sequences are never starved;
exhaustion defers admission and dumps a flight record), per-request
deadlines enforced before EVERY decode step (a mid-decode expiry
cancels just that sequence and frees its pages), poison isolation via
per-slot non-finite-logit flags (a poisoned sequence fails only its own
future; its pages are zeroed before reuse so NaNs cannot leak through
masked attention into the next owner), shutdown-drain, and
`/readyz`-compatible `health()`. TTFT/TPOT spans feed the `ttft_ms` /
`tpot_ms` histograms and `reqspan:` trace instants
(`tools/latency_report.py`).

Single-device by design: one engine owns one chip's pools and step
loop (the PR 3 lane made token-level — collector and lane collapse into
one step thread because the decode batch IS the lane). Data-parallel
scale-out = one engine per chip behind the router tier's `/readyz`.
"""
from __future__ import annotations

import itertools
import queue as _queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import List, Optional

import numpy as np

from ..framework import monitor
from ..framework.errors import (ExecutionTimeoutError, FatalError,
                                InvalidArgumentError,
                                ResourceExhaustedError, UnavailableError)
from ..framework.flags import flag
from ..profiler import (RecordEvent, audit, device_telemetry, exporter,
                        flight_recorder, slo, spans, step_log,
                        timeseries, trace_context)
from . import failpoints
from .decode_family import ProgramContext, family_of, sample_next
from .device_clock import DeviceClock
from .kv_cache import TRASH_PAGE
from .kv_tier import HostTier
from .prefix_cache import PrefixCache
from .spec_decode import NGramProposer

# the intake queue legitimately moves both ways; registering it as an
# "updown" gauge makes the exporter render a Prometheus gauge while the
# cross-process relay keeps summing its stat_add/stat_sub deltas
# (monitor is the single registry of gauge names — ISSUE 11)
monitor.register_gauge("STAT_gen_queue_depth", updown=True)

__all__ = ["CrashManifest", "GenerationConfig", "GenerationEngine",
           "ReplayEntry", "TokenStream"]


def _now_ms() -> float:
    return time.perf_counter() * 1000.0


class GenerationConfig:
    """Continuous-batching knobs; defaults ride the FLAGS_gen_* /
    FLAGS_paged_* registry so deployments tune engines without code
    changes."""

    def __init__(self, max_slots: Optional[int] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 pages_per_seq: Optional[int] = None,
                 prefill_buckets=None,
                 max_new_tokens: Optional[int] = None,
                 max_queue_depth: Optional[int] = None,
                 request_timeout_ms: Optional[float] = None,
                 kv_cache_dtype: Optional[str] = None,
                 prefix_cache: Optional[bool] = None,
                 prefix_cache_max_pages: Optional[int] = None,
                 spec_k: Optional[int] = None,
                 spec_ngram: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 kv_tier: Optional[bool] = None,
                 kv_tier_host_bytes: Optional[int] = None,
                 kv_tier_chunk_pages: Optional[int] = None,
                 tp: Optional[int] = None,
                 top_k: int = 0, seed: int = 0, warmup: bool = True,
                 gc_freeze: bool = False):
        self.max_slots = int(flag("FLAGS_gen_max_slots")
                             if max_slots is None else max_slots)
        if self.max_slots < 1:
            raise InvalidArgumentError("max_slots must be >= 1")
        self.page_size = int(flag("FLAGS_paged_page_size")
                             if page_size is None else page_size)
        self.num_pages = int(flag("FLAGS_paged_num_pages")
                             if num_pages is None else num_pages)
        self.pages_per_seq = int(flag("FLAGS_paged_pages_per_seq")
                                 if pages_per_seq is None else pages_per_seq)
        if prefill_buckets is None:
            raw = str(flag("FLAGS_gen_prefill_buckets"))
            prefill_buckets = [int(x) for x in raw.split(",") if x.strip()]
        buckets = sorted({int(b) for b in prefill_buckets if int(b) >= 1})
        if not buckets:
            raise InvalidArgumentError("prefill_buckets must be non-empty")
        self.prefill_buckets = tuple(buckets)
        self.max_new_tokens = int(flag("FLAGS_gen_max_new_tokens")
                                  if max_new_tokens is None
                                  else max_new_tokens)
        self.max_queue_depth = int(flag("FLAGS_gen_max_queue_depth")
                                   if max_queue_depth is None
                                   else max_queue_depth)
        self.request_timeout_ms = float(
            flag("FLAGS_gen_request_timeout_ms")
            if request_timeout_ms is None else request_timeout_ms)
        self.kv_cache_dtype = str(flag("FLAGS_kv_cache_dtype")
                                  if kv_cache_dtype is None
                                  else kv_cache_dtype)
        if self.kv_cache_dtype not in ("auto", "int8", "float32",
                                       "bfloat16"):
            raise InvalidArgumentError(
                f"kv_cache_dtype must be auto/int8/float32/bfloat16, "
                f"got {self.kv_cache_dtype!r}")
        self.prefix_cache = bool(flag("FLAGS_gen_prefix_cache")
                                 if prefix_cache is None else prefix_cache)
        self.prefix_cache_max_pages = int(
            flag("FLAGS_gen_prefix_cache_max_pages")
            if prefix_cache_max_pages is None else prefix_cache_max_pages)
        if self.prefix_cache_max_pages < 0:
            raise InvalidArgumentError(
                "prefix_cache_max_pages must be >= 0 (0 = unbounded)")
        self.spec_k = int(flag("FLAGS_gen_spec_k")
                          if spec_k is None else spec_k)
        if self.spec_k < 0:
            raise InvalidArgumentError("spec_k must be >= 0 (0 = off)")
        self.spec_ngram = int(flag("FLAGS_gen_spec_ngram")
                              if spec_ngram is None else spec_ngram)
        if self.spec_k and self.spec_ngram < 1:
            raise InvalidArgumentError(
                "spec_ngram must be >= 1 when spec_k > 0")
        self.prefill_chunk = int(flag("FLAGS_gen_prefill_chunk")
                                 if prefill_chunk is None
                                 else prefill_chunk)
        if self.prefill_chunk < 0:
            raise InvalidArgumentError(
                "prefill_chunk must be >= 0 (0 = whole-prompt prefill)")
        # tiered KV cache (ISSUE 18): host-RAM demotion tier under the
        # prefix cache — demoted chains re-upload instead of
        # re-prefilling. The tier is a prefix-cache extension: without
        # the chain index there is nothing to demote or promote.
        self.kv_tier = bool(flag("FLAGS_kv_tier")
                            if kv_tier is None else kv_tier)
        if self.kv_tier and not self.prefix_cache:
            raise InvalidArgumentError(
                "kv_tier requires prefix_cache (the host tier demotes "
                "prefix-cache chains; enable FLAGS_gen_prefix_cache)")
        self.kv_tier_host_bytes = int(
            flag("FLAGS_kv_tier_host_bytes")
            if kv_tier_host_bytes is None else kv_tier_host_bytes)
        if self.kv_tier and self.kv_tier_host_bytes < 1:
            raise InvalidArgumentError(
                "kv_tier_host_bytes must be >= 1 when kv_tier is on")
        self.kv_tier_chunk_pages = int(
            flag("FLAGS_kv_tier_chunk_pages")
            if kv_tier_chunk_pages is None else kv_tier_chunk_pages)
        if self.kv_tier and self.kv_tier_chunk_pages < 1:
            raise InvalidArgumentError(
                "kv_tier_chunk_pages must be >= 1 when kv_tier is on")
        # mesh-slice lane (ISSUE 19): tensor-parallel degree — the
        # engine builds its whole program pack sharded over a 'tp'
        # mesh axis when > 1 (or when an explicit mesh is handed to
        # GenerationEngine, which then wins over the flag/knob)
        self.tp = int(flag("FLAGS_gen_tp") if tp is None else tp)
        if self.tp < 1:
            raise InvalidArgumentError("tp must be >= 1")
        self.top_k = int(top_k)
        self.seed = int(seed)
        self.warmup = bool(warmup)
        # once warmed, collect and `gc.freeze()`: everything alive then
        # (jax, the programs, the weights' pytrees: some 150k container
        # objects) leaves the collector's sight, so a full (generation-2)
        # collection during serving walks the requests' objects and not
        # the process — unfrozen it takes ~100 ms with every thread of the
        # process waiting for the GIL, the step thread included (read on
        # the chip, PERF.md PR 27: two stalls of 90 and 113 ms in a 20 s
        # window, 118 ms a collection on the CPU). Process-wide, so it is
        # the deployment's choice and off by default; `shutdown` unfreezes.
        self.gc_freeze = bool(gc_freeze)


class TokenStream:
    """Per-token delivery handle returned by
    `GenerationEngine.submit_stream`.

    Iterate it to receive generated token ids as the step thread
    decodes them (each delivered AFTER its iteration's step-ring record
    lands — the same deferred-resolution barrier futures honor);
    iteration ends after the final token, and the streamed tokens
    concatenate exactly to `result()`'s generated part. A failed
    request raises the same exception from the iterator and from
    `result()`. `result(timeout)` returns the full sequence (prompt +
    generated, numpy int32) — the final token is always queued before
    the future resolves, so a consumer woken by `result()` can drain
    the remaining tokens without blocking."""

    _END = object()

    def __init__(self, future: Future):
        self._q = _queue.SimpleQueue()
        self._exc: Optional[BaseException] = None
        self._ended = False
        self.future = future
        # fleet trace id (ISSUE 20) — set at admission so a streaming
        # caller can correlate its tokens with the merged fleet trace
        self.trace_id: Optional[str] = None

    def _put(self, item) -> None:     # engine-side (step thread)
        self._q.put(item)

    def __iter__(self):
        return self

    def __next__(self) -> int:
        if self._exc is not None:
            raise self._exc
        if self._ended:
            raise StopIteration
        item = self._q.get()
        if item is TokenStream._END:
            self._ended = True
            raise StopIteration
        if isinstance(item, BaseException):
            self._exc = item
            raise item
        return int(item)

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """The full sequence, exactly what `submit().result()` would
        have returned for the same request."""
        return self.future.result(timeout)


class _GenRequest:
    __slots__ = ("rid", "prompt", "max_new", "eos", "do_sample",
                 "temperature", "future", "deadline_ms", "t_enqueue_ms",
                 "span", "slot", "pt_row", "toks", "next_pos", "ordinal",
                 "defer_logged", "stream", "ttft_deadline_ms",
                 "prefix_tokens", "prefill_pos", "pending_digests",
                 "spec_accepted", "claimed", "retries", "skip_stream",
                 "trace_id", "unread")

    _ids = itertools.count(1)

    def __init__(self, prompt, max_new, eos, do_sample, temperature,
                 future, deadline_ms, t_enqueue_ms, span,
                 stream=None, ttft_deadline_ms=None, trace_id=None):
        self.rid = next(self._ids)
        self.prompt = prompt            # np.int32 [S]
        self.max_new = max_new
        self.eos = eos
        self.do_sample = do_sample
        self.temperature = temperature
        self.future = future
        self.deadline_ms = deadline_ms
        self.t_enqueue_ms = t_enqueue_ms
        self.span = span                # GenSpan or None
        self.slot: Optional[int] = None
        self.pt_row = None              # np.int32 [pages_per_seq]
        self.toks: List[int] = []       # generated tokens (eos included)
        self.next_pos = 0               # cache position the NEXT launch
        #                                 writes (advanced AT the launch:
        #                                 a step in flight has its own)
        self.ordinal = 0                # engine-local submit ordinal
        self.defer_logged = set()       # audit DEFER_* causes noted once
        self.stream = stream            # TokenStream or None
        self.ttft_deadline_ms = ttft_deadline_ms  # HARD (streams)
        self.prefix_tokens = 0          # prompt tokens served from cache
        self.prefill_pos = None         # chunked prefill: next prompt
        #                                 position to prefill (None =
        #                                 prefill complete / not chunked)
        self.pending_digests = None     # prompt digests held across chunks
        self.spec_accepted = 0          # draft tokens accepted (ISSUE 14)
        self.claimed = False            # future claimed running (_admit)
        self.retries = 0                # supervised restarts survived
        self.skip_stream = 0            # stream tokens to suppress on a
        #                                 from-scratch greedy replay
        #                                 (exactly-once across restarts)
        self.trace_id = trace_id        # fleet trace id (ISSUE 20) —
        #                                 survives replay so one id
        #                                 spans every incarnation
        self.unread = False             # its prefill (and first token) is
        #                                 launched and not yet read


class ReplayEntry:
    """One request's restartable state inside a `CrashManifest`
    (ISSUE 15): the immutable submit parameters verbatim, the generated
    prefix so a live slot replays as a prompt+generated continuation,
    the preserved future/stream the caller still holds, and the
    bookkeeping exactly-once replay needs (`delivered` streamed tokens,
    `claimed` future state, the `retries` budget already spent)."""

    __slots__ = ("rid", "ordinal", "prompt", "toks", "max_new", "eos",
                 "do_sample", "temperature", "future", "stream",
                 "deadline_ms", "ttft_deadline_ms", "t_enqueue_ms",
                 "claimed", "retries", "delivered", "queued", "trace_id")

    def __init__(self, req: "_GenRequest", queued: bool):
        self.rid = req.rid
        self.ordinal = req.ordinal
        self.prompt = req.prompt
        self.toks = list(req.toks)
        self.max_new = req.max_new
        self.eos = req.eos
        self.do_sample = req.do_sample
        self.temperature = req.temperature
        self.future = req.future
        self.stream = req.stream
        self.deadline_ms = req.deadline_ms
        self.ttft_deadline_ms = req.ttft_deadline_ms
        self.t_enqueue_ms = req.t_enqueue_ms
        self.claimed = req.claimed
        self.retries = req.retries
        # _die flushes the staged stream queue before the manifest is
        # built, so every generated token was either delivered or —
        # during a from-scratch replay — SUPPRESSED because an earlier
        # incarnation already delivered it (skip_stream counts the
        # suppressions still owed). Total tokens the CALLER has seen =
        # generated here + still-owed suppressions; dropping the
        # residual would re-deliver tokens if THIS replay dies too.
        self.delivered = (len(req.toks) + req.skip_stream
                          if req.stream is not None else 0)
        self.queued = queued
        self.trace_id = req.trace_id    # one trace id per request,
        #                                 across every incarnation


class CrashManifest:
    """Everything `EngineSupervisor` needs to resurrect a dead engine
    (ISSUE 15): the replayable requests in original admission order
    (live slots first, then the still-queued tail), the fatal error,
    the KV-pool postmortem snapshot, the compile ledger at death (the
    zero-new-traces baseline the rebuilt engine is held to), and the
    degraded-mode state that must survive the restart."""

    __slots__ = ("engine", "incarnation", "error", "entries",
                 "degraded_spec_off", "kv", "compiles")

    def __init__(self, engine: str, incarnation: int,
                 error: BaseException, entries: List[ReplayEntry],
                 degraded_spec_off: bool, kv: dict, compiles: dict):
        self.engine = engine
        self.incarnation = incarnation
        self.error = error
        self.entries = entries
        self.degraded_spec_off = degraded_spec_off
        self.kv = kv
        self.compiles = compiles

    def summary(self) -> dict:
        """Flight-dump payload: counts + per-entry state, no futures."""
        return {
            "engine": self.engine, "incarnation": self.incarnation,
            "error": repr(self.error),
            "entries": [{"rid": e.rid, "queued": e.queued,
                         "generated": len(e.toks),
                         "delivered": e.delivered,
                         "stream": e.stream is not None,
                         "retries": e.retries}
                        for e in self.entries],
            "degraded_spec_off": self.degraded_spec_off,
            "kv": self.kv, "compiles": dict(self.compiles)}


class _ProgramPack:
    """The engine's jitted program set + its exactly-once compile
    ledger, shareable across supervised-restart incarnations
    (ISSUE 15). `jax.jit` caches compiled executables on the WRAPPER
    object, so a rebuilt engine that reuses the same wrappers (same
    config, same model → identical signatures) re-warms entirely from
    cache: zero new in-process traces, and because the ledger dict is
    owned here — not by any one engine — the shared count proves it.

    `execs` maps the step program's name (the ledger's own key) to the
    AOT `jax.stages.Compiled` that `_build_programs` compiled with the
    pools' layout asked of the compiler (PR 28); a resurrection adopts
    it, so the rebuilt engine runs the same executable."""

    __slots__ = ("ledger", "execs", "prefill", "tail", "first",
                 "decode", "verify", "zero", "cow", "npool", "W",
                 "tier_gather", "tier_write", "formats", "preferred")

    def __init__(self, ledger, prefill, tail, first, decode, verify, zero,
                 cow, npool, W, execs=None, tier_gather=None,
                 tier_write=None, formats=None, preferred=None):
        self.ledger = ledger
        self.execs = {} if execs is None else execs
        self.prefill = prefill
        self.tail = tail
        self.first = first
        self.decode = decode
        self.verify = verify
        self.zero = zero
        self.cow = cow
        self.npool = npool
        self.W = W
        # tiered KV cache (ISSUE 18): ride the pack like every other
        # wrapper, or a supervised restart would retrace them
        self.tier_gather = tier_gather
        self.tier_write = tier_write
        # the pools' layout the programs were compiled for, and the one
        # the compiler chose when asked (PR 28)
        self.formats = formats
        self.preferred = preferred


def jit_program(fn, name, fmts, counters=False, with_w=True, donates=True,
                slot_state=False):
    """A family's program body `fn` behind the engine's jit boundary: the
    pools donated, and taken and returned as `fmts` says, one
    `jax.experimental.layout.Format` a pool — the layout left to the
    compiler where the step program is asked, the pools' own for every
    program that serves (`GenerationEngine._build_programs`). `name` is the body's key in the
    family's `build` (its signature: serving/decode_family.py);
    `counters`: the decode program also returns the family's
    `step_counters`; `slot_state`: the family keeps state by slot, so its
    prefill is told the slot (one more argument)."""
    import jax
    fmts = tuple(fmts)
    # what the program takes after the pools and returns after them
    # (None: it returns no pool)
    n_in, n_out = {
        "prefill": (3 + bool(slot_state), 1), "prefill_tail": (4, 1),
        "decode": (10, 2 + bool(counters)), "verify": (8, 3),
        "zero_pages": (1, 0), "cow_copy": (2, 0),
        "tier_gather": (1, None), "tier_write": (1 + len(fmts), 0)}[name]
    lead = int(with_w)
    return jax.jit(
        fn,
        donate_argnums=(tuple(range(lead, lead + len(fmts)))
                        if donates else ()),
        in_shardings=(None,) * lead + fmts + (None,) * n_in,
        out_shardings=(None if n_out is None
                       else fmts + (None,) * n_out))


def step_key(base_key, step):
    """The PRNG key of decode step `step` (the count of decode steps
    launched before it): `fold_in(base, step)`, folded INSIDE the decode
    program from two arguments, so no eager fold (and its microsecond
    programs) runs before a launch. Bit-identical to the eager
    `jax.random.fold_in(PRNGKey(seed), step)`."""
    import jax
    return jax.random.fold_in(base_key, step)


def with_step_inputs(decode):
    """A family's decode body (serving/decode_family.py) behind the inputs
    the step thread has when it launches a step AHEAD of the last one's
    read-back: `prev`, the next-token output of the step before, still on
    the device, with the first token of each request admitted since
    written in at its slot (`first_token_program`); `tok` and `fresh`,
    the host's token for the slots whose token `prev` does not hold, and
    their mask; the base key and the step's number, folded here. One
    compiled program as before, the family's body unchanged inside it:

        gen_decode(W, *pools, pt, prev, tok, fresh, pos, active, temps,
                   smask, base_key, step) -> what the family's body returns
    """
    def gen_decode(W, *rest):
        import jax
        import jax.numpy as jnp
        *lead, prev, tok, fresh, pos, active, temps, smask, base_key, \
            step = rest
        with jax.named_scope("step_inputs"):
            tok = jnp.where(fresh, tok, prev)
            key = step_key(base_key, step)
        return decode(W, *lead, tok, pos, active, temps, smask, key)
    return gen_decode


def first_token_program(top_k):
    """The body of the program that follows every prefill on the device: the
    request's first token sampled from the prefill's logits as a decode step
    samples (`decode_family.sample_next`; greedy is the first maximum, as
    `np.argmax` gives it), written into the next decode step's token input
    at the request's slot, so that step can be launched before anyone reads
    the prefill:

        gen_first_token(logits, prev [M], slot, temp, smask, base_key,
                        ordinal) -> (prev' [M], tok, bad)

    `bad` is the prefill's poison flag (a logit that is not finite). A
    sampled request draws from `fold_in(base_key, ordinal)`, its
    engine-local ordinal: two engines of one seed sample the same first
    tokens. The engine gives it a base key of its own, split from the
    decode steps' (`fold_in` of one key by a step's number and by an
    ordinal would otherwise draw the same noise)."""
    def gen_first_token(logits, prev, slot, temp, smask, base_key, ordinal):
        import jax
        import jax.numpy as jnp
        with jax.named_scope("first_token"):
            nxt, bad = sample_next(
                logits.reshape(1, -1), jnp.ones((1,), bool), temp[None],
                smask[None], jax.random.fold_in(base_key, ordinal), top_k)
            return prev.at[slot].set(nxt[0]), nxt[0], bad[0]
    return gen_first_token


class _Prefill:
    """One prefill launched and not yet read, with the first-token program
    behind it: the request, the token and poison flag on the device, the
    prefill's device-clock stamps, its bucket and the prompt's page digests
    for the prefix cache, and the count of decode steps launched before it
    (a larger count when it is read: one was launched behind it)."""

    __slots__ = ("req", "outs", "launch", "bucket", "digests", "steps")

    def __init__(self, req, outs, launch, bucket, digests, steps):
        self.req = req
        self.outs = outs            # (first token, poison flag)
        self.launch = launch
        self.bucket = bucket
        self.digests = digests
        self.steps = steps


class _Flight:
    """One decode step launched and not yet delivered: which request owned
    each slot when it was launched (what is learned a step late — an EOS,
    a poison flag, an expiry — is settled against THIS, not against the
    slots as they are when the step is read), its outputs on the device,
    and once its end has been observed the same on the host with the
    step's own time."""

    __slots__ = ("owners", "outs", "host", "ahead", "decode_ms", "wait_ms",
                 "launch")

    def __init__(self, owners, outs, ahead, launch):
        self.owners = owners        # per slot: the request, or None
        self.outs = outs            # (next tokens, poison flags[, counters])
        self.host = None            # the same as numpy, once observed
        self.ahead = ahead          # launched with the step before unread
        self.launch = launch        # its device-clock stamps, or None
        self.decode_ms = 0.0        # launch (or the end of the program
        #                             before it) to its own observed end
        self.wait_ms = 0.0          # of it, blocked in the read-back


def _pool_view(i):
    """Property over entry `i` of an engine's pool list."""
    return property(lambda self: self._pool_arrays[i],
                    lambda self, v: self._pool_arrays.__setitem__(i, v))


class GenerationEngine:
    """Token-level continuous-batching front-end over a model that has a
    decode family (`serving/decode_family.py`): the head-pool family
    builds every option below, the latent-pool family and the hybrid family
    (pages and a state by slot) prefill, decode and zero-pages — an option
    a family does not build is refused by name at construction. Nothing
    below the family seam knows a model.

    `submit(prompt_ids, ...)` returns a `concurrent.futures.Future`
    resolving to the full token sequence (prompt + generated, numpy
    int32). Greedy by default; `do_sample=True` draws from the
    temperature-scaled distribution using the ENGINE's PRNG stream
    (`config.seed` folded with the step counter — per-request seeds
    don't exist because co-resident sequences share each step's
    program).

    Scheduling contract: admission is FIFO with head-of-line blocking —
    a request is admitted the moment a slot AND its worst-case pages
    (prompt + max_new) are both available, prefills immediately, and
    joins the very next decode step. Deadlines are whole-request and
    checked before every step; an expired sequence is cancelled
    mid-decode with nothing delivered (deadline semantics are
    streaming-unsafe by design — there is no partial result).

    Numerics: decode always runs the one compiled [max_slots] program,
    so a sequence's tokens are independent of WHO shares the batch
    (row-independent math) and bit-stable across repeats on one engine
    config. Comparisons against `GPTModel.generate` cross program/shape
    boundaries and hold at token level (greedy) / float tolerance, per
    the standard XLA per-shape caveat.
    """

    def __init__(self, model, config: Optional[GenerationConfig] = None,
                 name: str = "generation", device=None, mesh=None,
                 metrics_port: Optional[int] = None,
                 incarnation: int = 0, on_death=None, _carryover=None,
                 **overrides):
        if config is None:
            config = GenerationConfig(**overrides)
        elif overrides:
            raise InvalidArgumentError(
                "pass either a GenerationConfig or keyword overrides, "
                "not both")
        import copy
        self._cfg = copy.copy(config)
        self.name = name
        # supervised-restart seam (ISSUE 15, serving/supervisor.py):
        # `incarnation` is this engine generation's ordinal (rides every
        # step-ring record + reqspan so reports distinguish
        # generations); `on_death` — when set — makes _die hand a
        # CrashManifest to the supervisor instead of stranding work,
        # and the supervisor (not this engine) owns the exporter
        # registration; `_carryover` passes the previous incarnation's
        # program pack + step/audit rings + degraded state forward
        self.incarnation = int(incarnation)
        self._on_death = on_death
        carry = _carryover or {}
        # everything that depends on what the model IS comes from its
        # decode family (serving/decode_family.py); a model without one
        # is refused by name
        self._family = family = family_of(model)
        # a family that keeps a fixed-size state per SLOT beside its pages
        # says so: its prefill program is told the request's slot
        self._slot_state = bool(getattr(family, "slot_state", False))
        self._model = model
        pack: Optional[_ProgramPack] = carry.get("pack")
        # a resurrection reuses the pack's exact weight pytree so the
        # rebuilt programs see identical leaves
        self._W = pack.W if pack is not None else family.weights()
        self._max_position = family.max_position
        # mesh-slice lane (ISSUE 19): tp > 1 generalizes the lane from
        # one chip to a mesh slice — every program rebuilds as a
        # shard_map program over the 'tp' axis with projections and KV
        # pools head-sharded, partial sums psum-reduced once per block.
        # An explicit `mesh` wins over FLAGS_gen_tp/config.tp and must
        # carry a 'tp' axis; without one the engine builds its own
        # slice from the first `tp` visible devices.
        if mesh is not None:
            if "tp" not in mesh.shape:
                raise InvalidArgumentError(
                    f"GenerationEngine mesh needs a 'tp' axis (got "
                    f"{tuple(mesh.axis_names)})")
            self._mesh = mesh
            self._tp = int(mesh.shape["tp"])
        else:
            self._tp = int(self._cfg.tp)
            if self._tp > 1:
                from ..parallel.spmd import tp_mesh
                self._mesh = tp_mesh(self._tp)
            else:
                self._mesh = None
        self._cfg.tp = self._tp
        # the family refuses, by name, an option it does not build
        family.check(self._cfg, self._tp)
        if self._tp > 1 and pack is None:
            # one-time placement: head-sharded projection leaves,
            # replicated embeddings/LNs (a resurrection's pack.W is
            # already placed — reuse keeps leaves identical)
            self._W = family.shard_weights(self._W, self._mesh)
        if self._cfg.pages_per_seq <= 0:
            self._cfg.pages_per_seq = -(-self._max_position
                                        // self._cfg.page_size)
        # buckets are bounded by the PER-SEQUENCE page capacity too, not
        # just max_position: a wider bucket would compute page indices
        # past the table width, which the gather CLAMPS onto the
        # sequence's last real page — pad-token K/V would silently
        # overwrite prompt state there
        cap = min(self._max_position,
                  self._cfg.pages_per_seq * self._cfg.page_size)
        self._cfg.prefill_buckets = tuple(sorted(
            {min(int(b), cap) for b in self._cfg.prefill_buckets}))
        self._device = device
        if device is not None and pack is None and self._tp == 1:
            # a one-device lane keeps its own copy of the weights on
            # ITS device (committed there, so every program runs there);
            # the pools below are allocated under the same device
            import jax
            self._W = jax.device_put(self._W, device)
        kv_dtype = (str(family.dtype(self._W))
                    if self._cfg.kv_cache_dtype == "auto"
                    else self._cfg.kv_cache_dtype)
        with self._dev_ctx():
            self._cache = family.make_cache(self._cfg, kv_dtype, self._mesh)
        # int8 page mode: quantize-on-append decode/prefill programs
        # thread the parallel scale pools (donated alongside the pages);
        # everything above this line — admission arithmetic, page
        # tables, zero-on-free, the compile ledger — is dtype-blind
        self._quant_kv = self._cache.quantized
        # the donated device pools, in the order the family's programs
        # take and return them (head pools: K, V[, K scales, V scales];
        # a latent pool: the one; the hybrid family: K, V, then the state
        # and the window, which have no page axis)
        self._pool_arrays = list(self._cache.pools)
        # prefix cache (ISSUE 12): content-hash chain index over the
        # refcounted pages; None keeps the PR 8 ownership semantics
        # exactly (every page refcount 1, nothing cached or shared)
        self._prefix = (PrefixCache(
            self._cache, name,
            max_pages=self._cfg.prefix_cache_max_pages)
            if self._cfg.prefix_cache else None)
        # tiered KV cache (ISSUE 18): bounded host-RAM store the prefix
        # cache demotes cold chains into instead of discarding them —
        # attach_tier (below, once the audit ring exists) wires the
        # demote-gather and audit hooks
        self._tier = (HostTier(self._cfg.kv_tier_host_bytes, name)
                      if (self._cfg.kv_tier and self._prefix is not None)
                      else None)
        # chunked prefill (ISSUE 14): chunks ride the per-bucket tail
        # programs, so a chunk can never be wider than the largest
        # bucket; 0 keeps whole-prompt prefill at admission
        self._cfg.prefill_chunk = min(self._cfg.prefill_chunk,
                                      self._cfg.prefill_buckets[-1])
        # the tail-extension programs serve BOTH prefix-cache hits and
        # prefill chunks — warmed whenever either consumer exists
        self._use_tail = (self._prefix is not None
                          or self._cfg.prefill_chunk > 0)
        # speculative decoding (ISSUE 14): model-free prompt-lookup
        # drafts + ONE fixed-k verify program replacing the decode step
        self._spec_k = self._cfg.spec_k
        self._proposer = (NGramProposer(self._cfg.spec_ngram)
                          if self._spec_k else None)
        self._spec_drafted_total = 0
        self._spec_accepted_total = 0
        self._chunks_total = 0

        self._cv = threading.Condition()
        self._queue: deque = deque()
        self._slots: List[Optional[_GenRequest]] = \
            [None] * self._cfg.max_slots
        self._closed = False
        self._abort = False
        # futures whose resolution is held until this iteration's
        # step-ring record lands (step-thread only; see _resolve_later)
        self._resolve_q: List[tuple] = []
        # streamed tokens / end markers staged the same way — flushed
        # BEFORE the futures, so a stream's final token always precedes
        # its future's resolution (step-thread only)
        self._stream_q: List[tuple] = []
        # (stream_q, resolve_q) pairs whose iteration's record has landed,
        # waiting for the next program's read-back (see _release_staged)
        self._released: List[tuple] = []
        self._warmed = False
        self._steps_total = 0
        self._prefills_total = 0
        self._tokens_total = 0
        self._exhaust_dumped = False   # one flight dump per episode
        self._req_seq = 0              # engine-local submit ordinal
        self._ledger = {}              # "decode[m=M]"/"prefill[b=S]" -> traces
        self._death: Optional[BaseException] = None
        self._pre_step_hook = None     # test seam: runs on the step thread
        self._hist = monitor.histogram(f"{name}_request_ms")
        self._base_key = None          # PRNGKey, built lazily on first use
        # one decode step in flight (ISSUE 34): the step launched and not
        # yet delivered; the device's next tokens of the LAST decode launch
        # with the requests they belong to (the next launch's token input
        # for the slots that still hold the same request); the page table
        # and the per-slot arrays, built once and patched where a slot's
        # request changed (`_rows`: whose row each line holds)
        M, PP = self._cfg.max_slots, self._cfg.pages_per_seq
        self._flight: Optional[_Flight] = None
        self._prev = None              # (device next tokens, owners)
        self._table = np.zeros((M, PP), np.int32)
        self._temps = np.ones((M,), np.float32)
        self._smask = np.zeros((M,), bool)
        self._rows: List[Optional[_GenRequest]] = [None] * M
        self._no_prev = np.zeros((M,), np.int32)
        import jax
        # the base key as the decode program takes it (it folds the step's
        # number in: `step_key`), and the first-token program's, split from
        # it (it folds a request's ordinal in)
        self._key_host = np.asarray(jax.random.PRNGKey(self._cfg.seed))
        self._first_key_host = np.asarray(
            jax.random.split(self._key_host)[1])
        self._step_span = f"generation::step[m={M}]"
        self._ahead_total = 0          # steps launched ahead of a read-back
        self._settled = {}             # steps that settled first, by reason
        #                                (prefills read at once: "prefill:")
        self._dropped_tokens = 0       # computed for a request that had left
        # prefills launched and not yet read, oldest first; how many were
        # read only after a decode step was launched behind them
        self._unread: deque = deque()
        self._prefills_ahead = 0
        # attribution (ISSUE 20 / 34): `_cursor` is how far the step
        # thread's timeline has been charged to a bucket; `_unobserved`
        # counts the timed programs launched whose end has not been
        # observed — while it is over 0 the host's work hides under the
        # chip's and is charged to nothing; `_phase` is the host bucket
        # that exposed time goes to
        self._cursor = time.perf_counter()
        self._unobserved = 0
        self._phase = "attr_bookkeep_ms"
        # the device's own timeline (`device_clock.py`): started with the
        # step thread where the step ring is on, None otherwise
        self._devclock: Optional[DeviceClock] = None
        # degraded modes (ISSUE 15): detector knobs snapshotted at
        # construction (a runtime flag flip must not flip speculation
        # onto an un-warmed program); the spec-off verdict itself rides
        # the crash manifest so a restart stays degraded
        self._poison_degrade_k = int(flag("FLAGS_gen_poison_degrade_k"))
        self._exhaust_clamp_k = int(flag("FLAGS_gen_exhaust_clamp_k"))
        self._degraded_window_s = float(flag("FLAGS_gen_degraded_window_s"))
        self._degraded_spec_off = bool(carry.get("degraded_spec_off"))
        self._poison_times: deque = deque()
        self._exhaust_times: deque = deque()
        self._admit_clamped = False
        # scheduler X-ray (ISSUE 11): decision audit ring (always on —
        # one deque append per decision) + per-iteration step ring
        # (FLAGS_gen_step_log; snapshot at construction so one engine's
        # A/B arm can't half-enable the other's). A resurrection reuses
        # the previous incarnation's rings: the restart's own events
        # land in the SAME postmortem trail as the death that caused it
        self._audit = carry.get("audit") or audit.AuditLog(name)
        if self._tier is not None:
            # demote-on-evict (ISSUE 18): evictions now gather page
            # content off-device into the host store before freeing HBM
            self._prefix.attach_tier(self._tier, self._tier_gather_page,
                                     audit=self._audit)
        self._step_log = carry.get("step_log") or (
            step_log.StepLog(name) if step_log.enabled() else None)
        if carry.get("step_log") is not None:
            # re-register the carried ring: a failed rebuild attempt's
            # error path unregisters it, and the retry must restore it
            step_log.register(self._step_log)
        self._iters = 0
        # last-seen cumulative tier counters — _record_iteration takes
        # deltas so the step ring carries per-iteration demote/promote
        # counts without a second bookkeeping path
        self._tier_counts = (0, 0)
        self._it = self._new_counts()
        # published BEFORE the step thread exists so a router polling a
        # freshly built replica reads a truthful empty-engine snapshot
        self._pressure = self._compute_pressure()

        self._build_programs(pack)
        flight_recorder.touch()
        device_telemetry.touch()
        timeseries.touch()
        if self._on_death is None:
            # supervised engines never register themselves: the
            # SUPERVISOR is the stable /readyz + /stats entity across
            # incarnations (a restarted engine re-registering would
            # evict it from the exporter's name-keyed registry)
            exporter.register_engine(self)
        try:
            if self._cfg.warmup:
                self._warmup()
            self._warmed = True
            if self._cfg.gc_freeze:
                import gc
                gc.collect()
                gc.freeze()
            if self._step_log is not None:
                self._devclock = DeviceClock(name)
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name=f"{name}-genstep")
            self._thread.start()
            self._owns_metrics_server = (metrics_port is not None
                                         and int(metrics_port) == 0)
            self.metrics_server = None
            self.metrics_server = exporter.start_metrics_server(
                metrics_port)
        except Exception:
            exporter.unregister_engine(self)  # identity-guarded no-op
            #                                   for supervised engines
            if self._step_log is not None:
                step_log.unregister(self._step_log)
            if self._devclock is not None:
                self._devclock.stop()
            raise

    # -- jitted programs ---------------------------------------------------

    def _pools(self):
        """The donated device-pool tuple the jitted programs thread, as
        the family's cache laid it out: (k_pages, v_pages) plus the
        parallel scale pools in the int8 page mode, or the one latent
        pool."""
        return tuple(self._pool_arrays)

    def _set_pools(self, pools):
        pools = list(pools)
        assert len(pools) == len(self._pool_arrays)
        self._pool_arrays = pools

    # head-pool views by position (tier staging, tests): K, V and the
    # int8 mode's two scale pools
    _kp, _vp, _ks, _vs = map(_pool_view, range(4))

    def _build_programs(self, pack: Optional[_ProgramPack] = None):
        # which attention the decode program takes — the family's shape
        # rule, so it is known before (and whether or not) anything is
        # traced
        self._decode_attention = self._family.decode_attention(
            self._cfg, self._tp, self._pools())
        # what else a family says of the programs it builds, by name
        # (`describe`, optional): `stats()` shows it
        describe = getattr(self._family, "describe", None)
        self._family_info = (describe(self._cfg, self._pools())
                             if describe else {})
        if pack is not None:
            # resurrection path (ISSUE 15): adopt the previous
            # incarnation's jit wrappers and SHARE its ledger dict —
            # warmup re-runs against the jit caches (identical
            # signatures), so the ledger not moving IS the
            # zero-new-traces proof
            self._ledger = pack.ledger
            self._npool = pack.npool
            self._prefill_jit = pack.prefill
            self._tail_jit = pack.tail
            self._first_jit = pack.first
            self._decode_jit = pack.decode
            self._verify_jit = pack.verify
            self._zero_jit = pack.zero
            self._cow_jit = pack.cow
            self._tier_gather_jit = pack.tier_gather
            self._tier_write_jit = pack.tier_write
            self._execs = pack.execs
            self._pack = pack
            self._pool_formats = pack.formats
            self._preferred = pack.preferred
            self._note_pool_layout()
            return
        import jax
        from jax.experimental.layout import Format, Layout

        # the trace-time closures capture the LEDGER and scalars, never
        # the engine object (ProgramContext). The programs' names are
        # fixed on purpose (gen_prefill, gen_prefill_tail, gen_decode,
        # gen_first_token, gen_verify, gen_zero_pages, gen_cow_copy,
        # gen_tier_gather, gen_tier_write): a profiler trace's `XLA
        # Modules` line reads `jit_gen_decode(...)`, and
        # tools/trace_report.py sums device time per program by them
        NP = self._npool = len(self._pool_arrays)
        self._fns = self._family.build(ProgramContext(
            self._cfg, self._tp, self._mesh, NP, self._quant_kv,
            self._decode_attention, self._W, self._ledger))
        # the family's decode body behind the inputs of a step launched
        # ahead of the last one's read-back (ISSUE 34): still ONE decode
        # program, under the same name
        self._fns["decode"] = with_step_inputs(self._fns["decode"])

        # the step program's AOT executable, by its ledger key
        self._execs = {}

        # THE LAYOUT CONTRACT (PR 28). Every program takes and returns
        # the pools in ONE layout, the one they lie in on the device, so
        # a donated pool aliases straight into its output and no program
        # relays a pool at its boundary — provided that layout is the one
        # the step program (decode; verify where speculation replaces
        # it) reads and writes in place. That is ASKED, not assumed: the
        # step program is compiled with the pools' layout left to the
        # compiler and its choice read back from that compile, the one
        # that serves (`_execs`). Where the choice is how the pools lie —
        # what the head pools' form (ops/paged_ops.HeadPoolForm) and the
        # latent row's width exist to make true — nothing more happens.
        # Where it is not, the pools can NOT be moved to it: an
        # executable that JAX's persistent compile cache hands back
        # returns its results in the default layout whatever it was
        # compiled for (read on the v5e and on the CPU, PR 28), so a
        # layout that is not the pools' own holds only until the next
        # process. The step program is then compiled once more, held to
        # the layout the pools have, and `stats()["pools"]` shows what
        # the compiler `preferred`: a shape to repair, not a fault. No
        # layout is written down here: each is read from an array or
        # from a compile.
        from ..device import layout_name
        jit = self._jit_program
        step = "verify" if self._spec_k else "decode"
        step_name = (f"verify[k={self._spec_k}]" if self._spec_k
                     else f"decode[m={self._cfg.max_slots}]")
        step_args = (self._spec_arrays()[0] if self._spec_k
                     else self._step_arrays())
        fmts = tuple(a.format for a in self._pool_arrays)
        abstract = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                         sharding=a.sharding)
                    for a in self._pool_arrays]
        args = (self._W, *abstract, *step_args)
        with self._dev_ctx():
            # (`args` holds the pools as shapes: an array would bring a
            # layout of its own)
            compiled = jit(step, [Format(Layout.AUTO, f.sharding)
                                  for f in fmts]).lower(*args).compile()
            chosen = tuple(compiled.input_formats[0][1:1 + NP])
            if not (chosen == tuple(compiled.output_formats[:NP]) == fmts):
                # (traced once all the same: the body's trace is reused)
                compiled = jit(step, fmts).lower(*args).compile()
        self._execs[step_name] = compiled
        self._pool_formats = fmts
        self._preferred = [layout_name(f, a.shape, a.dtype)
                           for a, f in zip(self._pool_arrays, chosen)]
        self._note_pool_layout()

        self._prefill_jit = jit("prefill", fmts)
        self._tail_jit = jit("prefill_tail", fmts)
        # the engine's own, the same for every family (no pool, no weights;
        # not in the ledger, which counts the family's programs)
        self._first_jit = jax.jit(first_token_program(self._cfg.top_k))
        # `.lower()` of these two gives the step program's text; the
        # engine itself runs `_execs[step_name]`, compiled above
        self._decode_jit = jit("decode", fmts)
        self._verify_jit = jit("verify", fmts) if self._spec_k else None
        self._zero_jit = jit("zero_pages", fmts, with_w=False)
        self._cow_jit = jit("cow_copy", fmts, with_w=False)
        self._tier_gather_jit = (
            jit("tier_gather", fmts, with_w=False, donates=False)
            if self._tier is not None else None)
        self._tier_write_jit = (
            jit("tier_write", fmts, with_w=False)
            if self._tier is not None else None)
        self._pack = _ProgramPack(
            ledger=self._ledger, prefill=self._prefill_jit,
            tail=self._tail_jit, first=self._first_jit,
            decode=self._decode_jit,
            verify=self._verify_jit, zero=self._zero_jit,
            cow=self._cow_jit, npool=self._npool, W=self._W,
            execs=self._execs,
            tier_gather=self._tier_gather_jit,
            tier_write=self._tier_write_jit, formats=self._pool_formats,
            preferred=self._preferred)

    def _jit_program(self, name, fmts, with_w=True, donates=True):
        """`jit_program` over one of this engine's program bodies; a
        program the family does not build (and whose option it therefore
        refused at construction) stays None."""
        fn = self._fns.get(name)
        if fn is None:
            return None
        return jit_program(fn, name, fmts, bool(self._family.step_counters),
                           with_w, donates, self._slot_state)

    def _note_pool_layout(self):
        """The pools lie as every program was compiled to take them (a
        fresh pool: its default layout): checked, and told to the cache,
        which reports it."""
        from ..device import layout_name
        self._check_pool_layout("built")
        self._cache.note_layout(self._pool_arrays)
        self._compiled_for = [
            layout_name(f, a.shape, a.dtype)
            for a, f in zip(self._pool_arrays, self._pool_formats)]

    def _check_pool_layout(self, when: str):
        """The pools the engine holds lie as its programs were compiled to
        take them — or a program relays a pool at its boundary on every
        call, which is what the contract exists to prevent: refuse."""
        for a, f in zip(self._pool_arrays, self._pool_formats):
            if a.format != f:
                raise FatalError(
                    f"{self.name}: {when}, a pool compiled for as {f} "
                    f"lies as {a.format}")

    def _dev_ctx(self):
        import jax
        import contextlib
        return (jax.default_device(self._device)
                if self._device is not None else contextlib.nullcontext())

    def _decode_call(self, *args):
        """One decode dispatch: the executable `_build_programs` compiled
        where decode is the step program, else (pre-warmed under
        speculation, for DEGRADED_SPEC_OFF) the jit wrapper."""
        with self._dev_ctx():
            return self._execs.get(f"decode[m={self._cfg.max_slots}]",
                                   self._decode_jit)(*args)

    def _verify_call(self, *args):
        """One speculative-verify dispatch: the step program's
        executable."""
        with self._dev_ctx():
            return self._execs[f"verify[k={self._spec_k}]"](*args)

    def _first_token_call(self, logits, prev, slot: int,
                          temperature: float, do_sample: bool, ordinal: int):
        """One dispatch of the first-token program (`first_token_program`);
        returns (prev', token, poison flag), all on the device."""
        with self._dev_ctx():
            return self._first_jit(logits, prev, np.int32(slot),
                                   np.float32(temperature),
                                   np.bool_(do_sample), self._first_key_host,
                                   np.int32(ordinal))

    def _zero_pages(self, pages):
        # chunked to the fixed zero-scatter width: one sequence's free
        # fits a single row, but a prefix-cache eviction sweep can
        # return more pages than pages_per_seq at once
        PP = self._cfg.pages_per_seq
        for i in range(0, max(len(pages), 1), PP):
            row = self._cache.zero_rows(pages[i:i + PP])
            with self._dev_ctx():
                self._set_pools(self._zero_jit(*self._pools(), row))

    def _cow_copy(self, src: int, dst: int):
        """Device-side CoW clone of one page (content + int8 scale row)."""
        with self._dev_ctx():
            self._set_pools(self._cow_jit(*self._pools(), np.int32(src),
                                          np.int32(dst)))

    # -- host tier (ISSUE 18) ----------------------------------------------

    def _tier_gather_page(self, page: int):
        """Demotion gather callback (`PrefixCache.attach_tier`): one
        page's raw blocks off-device as host numpy — (k, v, ks, vs),
        scale rows None outside the int8 mode. None = gather failed
        (the `kv_tier.demote_gather` failpoint): the eviction proceeds
        plain, content discarded — the PR 12 behavior exactly."""
        if failpoints.fire("kv_tier.demote_gather") is not None:
            return None
        with self._dev_ctx():
            out = self._tier_gather_jit(*self._pools(), np.int32(page))
        if self._quant_kv:
            return tuple(np.asarray(o) for o in out)
        return (np.asarray(out[0]), np.asarray(out[1]), None, None)

    def _promote_upload(self, req: _GenRequest, host_digests,
                        matched_hbm: int) -> bool:
        """Re-upload an admission's matched host-tier run into its own
        fresh target pages (`pt_row[matched_hbm:]`), double-buffered:
        chunk i+1's `jax.device_put` staging overlaps chunk i's (async)
        tier_write dispatch, and nothing here syncs the host — the tail
        prefill queues behind the uploads on the device stream, which
        is how the promotion hides behind prefill instead of adding to
        TTFT. Returns True on success, False on abandon.

        Abandon (the `kv_tier.promote_upload` failpoint, checked BEFORE
        each chunk's donating dispatch so no pool is ever
        half-consumed): the target pages written so far are zeroed —
        content AND int8 scale grids, essential because the tail
        prefill's requant write would otherwise merge junk scales into
        a grid that only ever widens — the never-written tail is
        already zero (fresh pages arrive zeroed), and the caller falls
        back to cold-prefilling the whole suffix. The popped host
        entries are gone either way: move semantics, one copy ever."""
        import jax
        C = self._cfg.kv_tier_chunk_pages
        n = len(host_digests)
        targets = [int(p) for p in
                   req.pt_row[matched_hbm:matched_hbm + n]]
        entries, cascaded = self._prefix.consume_promoted(host_digests)
        if cascaded:
            self._audit.audit("KV_TIER_EVICT", rid=req.rid,
                              entries=cascaded)
        if any(e is None for e in entries):
            # defensive: protect() held these across the eviction pass,
            # so a missing entry is a logic fault — abandon cleanly
            # (nothing written yet) rather than upload garbage
            self._tier.note_abandon()
            self._audit.audit("KV_PROMOTE_ABANDON", rid=req.rid,
                              pages=n, written=0)
            return False

        def stage(lo: int):
            hi = min(lo + C, n)
            row = np.full((C,), TRASH_PAGE, np.int32)
            row[:hi - lo] = targets[lo:hi]
            e0 = entries[0]
            blocks = [np.zeros((C,) + e0.k.shape, e0.k.dtype),
                      np.zeros((C,) + e0.v.shape, e0.v.dtype)]
            if self._quant_kv:
                blocks += [np.zeros((C,) + e0.ks.shape, e0.ks.dtype),
                           np.zeros((C,) + e0.vs.shape, e0.vs.dtype)]
            for j in range(lo, hi):
                blocks[0][j - lo] = entries[j].k
                blocks[1][j - lo] = entries[j].v
                if self._quant_kv:
                    blocks[2][j - lo] = entries[j].ks
                    blocks[3][j - lo] = entries[j].vs
            with self._dev_ctx():
                if self._tp == 1:
                    return [jax.device_put(a) for a in [row] + blocks]
                # stage straight onto the slice: each block is a chunk of
                # FULL host pages — split its head axis across
                # the mesh here so the donating tier_write dispatch
                # pays no reshard (the overlap this path exists for)
                from jax.sharding import NamedSharding
                form = self._cache.form

                def ns(a):
                    return NamedSharding(self._mesh,
                                         form.spec(a.ndim, lead=1))
                return [jax.device_put(row)] + [
                    jax.device_put(a, ns(a)) for a in blocks]

        t0 = _now_ms()
        # the uploads' host time is the promote bucket's (ISSUE 20), an
        # abandoned upload's too — where no program is in flight to hide it
        was = self._enter_phase("promote_ms")
        try:
            written = 0
            staged = stage(0)
            while written < n:
                if failpoints.fire("kv_tier.promote_upload") is not None:
                    self._zero_pages(targets[:written])
                    self._tier.note_abandon()
                    self._audit.audit("KV_PROMOTE_ABANDON", rid=req.rid,
                                      pages=n, written=written)
                    return False
                nxt = stage(written + C) if written + C < n else None
                with RecordEvent(f"generation::tier_write[w={C}]"):
                    with self._dev_ctx():
                        self._set_pools(self._tier_write_jit(
                            *self._pools(), *staged))
                written = min(written + C, n)
                staged = nxt
            self._tier.note_promotion(n)
            self._audit.audit("KV_PROMOTE", rid=req.rid, pages=n,
                              tokens=n * self._cfg.page_size,
                              ms=round(_now_ms() - t0, 3))
            return True
        finally:
            self._enter_phase(was)

    def _warmup(self):
        """Compile every prefill bucket + the first-token program + the
        decode step (or, with speculation on, the ONE verify[k] program
        that replaces it) + the zeroing scatter up front: no live request
        pays a compile,
        and the ledger's exactly-once invariant is observable from step
        one. Warmup writes land only in the reserved scratch page."""
        M, PP = self._cfg.max_slots, self._cfg.pages_per_seq
        trash = np.zeros((PP,), np.int32)
        with RecordEvent("generation::warmup"):
            for b in self._cfg.prefill_buckets:
                ids = np.zeros((1, b), np.int32)
                with self._dev_ctx():
                    # lint: allow(use-after-donate): donate_argnums covers only the NP pool args riding in the *splat; trash sits AFTER them (position NP+1) and is never donated — reused read-only across warmup prefills
                    out = self._prefill_jit(self._W, *self._pools(), trash,
                                            ids, np.int32(1),
                                            *self._slot_arg(0))
                self._set_pools(out[:-1])
                lg = out[-1]
                np.asarray(lg)
                if self._use_tail:
                    # one tail-prefill compile per bucket too: prefix
                    # hits AND prefill chunks ride these programs, and
                    # neither may pay a runtime compile — the ledger's
                    # exactly-once invariant covers both prefill shapes
                    # from step one
                    with self._dev_ctx():
                        # lint: allow(use-after-donate): donate covers only the NP pool args in the *splat; trash/ids ride AFTER them (positions NP+1/NP+2), read-only across warmup prefills
                        out = self._tail_jit(self._W, *self._pools(), trash,
                                             ids, np.int32(1), np.int32(0))
                    self._set_pools(out[:-1])
                    np.asarray(out[-1])
            # the first-token program, on both forms of the token input it
            # is given: the host's zeros and a device array (its own output;
            # a decode step's, below)
            prev = self._no_prev
            for _ in range(2):
                prev = self._first_token_call(lg, prev, 0, 1.0, False, 0)[0]
            np.asarray(prev)
            if self._prefix is not None:
                with self._dev_ctx():
                    out = self._cow_jit(*self._pools(),
                                        np.int32(TRASH_PAGE),
                                        np.int32(TRASH_PAGE))
                self._set_pools(out)
            if self._tier is not None:
                with self._dev_ctx():
                    g = self._tier_gather_jit(*self._pools(),
                                              np.int32(TRASH_PAGE))
                blocks = [np.asarray(b) for b in g]
                C = self._cfg.kv_tier_chunk_pages
                row = np.full((C,), TRASH_PAGE, np.int32)
                args = [row] + [np.zeros((C,) + b.shape, b.dtype)
                                for b in blocks]
                with self._dev_ctx():
                    # lint: allow(use-after-donate): donate covers only the NP pool args in the *splat; row/blocks ride AFTER them, read-only
                    self._set_pools(self._tier_write_jit(*self._pools(),
                                                         *args))
            if self._spec_k:
                # speculation replaces the decode program outright: the
                # engine's ledger shows ONE verify[k] trace and no
                # decode entry at all (the acceptance-criteria shape)
                out = self._verify_call(self._W, *self._pools(),
                                        *self._spec_arrays()[0])
                np.asarray(out[-2])
                self._set_pools(out[:-3])
            if (not self._spec_k or self._poison_degrade_k
                    or self._degraded_spec_off):
                # (under speculation: the poison-storm detector (ISSUE 15)
                # may flip this engine to the plain decode program
                # mid-flight — pre-warm it so the DEGRADED_SPEC_OFF flip
                # mints no runtime compile; the ledger then shows BOTH
                # verify[k] and decode[m], each exactly once)
                out = self._decode_call(self._W, *self._pools(),
                                        *self._step_arrays())
                np.asarray(out[self._npool])
                self._set_pools(out[:self._npool])
                np.asarray(self._first_token_call(
                    lg, out[self._npool], 0, 1.0, False, 0)[0])
            self._zero_pages([])
        # every program has run once: each returned the pools as it took
        # them (an executable handed back by a compile cache included)
        self._check_pool_layout("warmed")

    # -- request intake ----------------------------------------------------

    def submit(self, prompt_ids, max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               timeout_ms: Optional[float] = None,
               do_sample: bool = False,
               temperature: float = 1.0,
               trace_id: Optional[str] = None) -> Future:
        """Enqueue one prompt (1-D int token ids); returns a Future of
        the full sequence (prompt + generated tokens, numpy int32; EOS,
        when hit, is included). Raises `EngineOverloaded` at
        max_queue_depth, `InvalidArgumentError`/`ResourceExhaustedError`
        for requests that could never run. `trace_id` is an upstream
        hop's fleet trace id (ISSUE 20) — omitted, the engine mints its
        own when FLAGS_trace_propagation is on."""
        return self._submit(prompt_ids, max_new_tokens, eos_token_id,
                            timeout_ms, do_sample, temperature,
                            stream=None, ttft_timeout_ms=None,
                            trace_id=trace_id).future

    def submit_stream(self, prompt_ids,
                      max_new_tokens: Optional[int] = None,
                      eos_token_id: Optional[int] = None,
                      timeout_ms: Optional[float] = None,
                      ttft_timeout_ms: Optional[float] = None,
                      do_sample: bool = False,
                      temperature: float = 1.0,
                      trace_id: Optional[str] = None) -> TokenStream:
        """Streaming submit: tokens leave the engine as they are
        decoded. Returns a `TokenStream` — iterate it for per-token
        delivery (each token lands after its iteration's step-ring
        record; the final token always precedes the future's
        resolution), `stream.result()` for the full sequence.

        Deadline semantics split for streams (ISSUE 12):
        `ttft_timeout_ms` is HARD — expiry before the first token
        cancels the request with `ExecutionTimeoutError` (a stream that
        cannot start on time is useless). `timeout_ms` is SOFT once
        tokens flow — expiry mid-stream stops decoding, frees the
        pages, and resolves the stream AND future with the tokens
        already delivered (they left the engine; there is nothing to
        retract), counted as a timeout for SLO purposes."""
        if ttft_timeout_ms is not None and float(ttft_timeout_ms) < 0:
            raise InvalidArgumentError("ttft_timeout_ms must be >= 0")
        stream = TokenStream(Future())
        self._submit(prompt_ids, max_new_tokens, eos_token_id,
                     timeout_ms, do_sample, temperature,
                     stream=stream, ttft_timeout_ms=ttft_timeout_ms,
                     trace_id=trace_id)
        return stream

    def _submit(self, prompt_ids, max_new_tokens, eos_token_id,
                timeout_ms, do_sample, temperature, stream,
                ttft_timeout_ms, trace_id=None) -> _GenRequest:
        from . import EngineOverloaded
        with RecordEvent("generation::submit"):
            from ..framework.tensor import Tensor
            if isinstance(prompt_ids, Tensor):
                prompt_ids = prompt_ids.numpy()
            prompt = np.asarray(prompt_ids)
            if prompt.ndim != 1 or prompt.size < 1:
                raise InvalidArgumentError(
                    f"{self.name}: prompt_ids must be a non-empty 1-D "
                    f"token array, got shape {tuple(prompt.shape)}")
            if not np.issubdtype(prompt.dtype, np.integer):
                raise InvalidArgumentError(
                    f"{self.name}: prompt_ids must be integer token ids")
            prompt = prompt.astype(np.int32)
            max_new = int(self._cfg.max_new_tokens
                          if max_new_tokens is None else max_new_tokens)
            if max_new < 1:
                raise InvalidArgumentError("max_new_tokens must be >= 1")
            S = int(prompt.size)
            total = S + max_new
            if S > self._cfg.prefill_buckets[-1]:
                raise InvalidArgumentError(
                    f"{self.name}: prompt length {S} exceeds the largest "
                    f"prefill bucket {self._cfg.prefill_buckets[-1]}")
            if total > self._max_position:
                raise InvalidArgumentError(
                    f"{self.name}: {total} positions exceed "
                    f"max_position_embeddings={self._max_position}")
            if not self._cache.fits(total):
                raise ResourceExhaustedError(
                    f"{self.name}: {total} tokens need "
                    f"{self._cache.pages_needed(total)} pages but the "
                    f"pool holds {self._cache.usable_pages} "
                    f"(pages_per_seq={self._cache.pages_per_seq}); raise "
                    f"FLAGS_paged_num_pages or shrink the request")
            if self._admit_clamped and not self._cache.can_admit(total):
                # degraded admission clamp (ISSUE 15): the allocator
                # has been exhausted repeatedly — a request the pool
                # cannot cover RIGHT NOW would only queue toward a
                # timeout, so shed it fast with a typed error
                monitor.stat_add("STAT_gen_rejected")
                raise ResourceExhaustedError(
                    f"{self.name}: admission clamped after repeated "
                    f"allocator exhaustion "
                    f"(FLAGS_gen_exhaust_clamp_k) and the pool cannot "
                    f"cover {total} tokens now; retry later or shrink "
                    f"the request")
            t = _now_ms()
            tmo = (self._cfg.request_timeout_ms if timeout_ms is None
                   else float(timeout_ms))
            ttft_tmo = (0.0 if ttft_timeout_ms is None
                        else float(ttft_timeout_ms))
            # fleet trace context (ISSUE 20): an upstream hop (the
            # Router) supplies the id — the chain was opened there, so
            # the span emits a flow STEP; a direct submit mints locally
            # (chain root) when propagation is on; off = no id, no cost
            tid, trace_root = None, True
            if trace_id is not None and trace_context.is_trace_id(
                    str(trace_id)):
                tid, trace_root = str(trace_id), False
            elif trace_context.enabled():
                tid = trace_context.new_trace_id()
            reject_depth = None
            with self._cv:
                if self._closed:
                    raise UnavailableError(
                        f"{self.name}: engine is shut down")
                if len(self._queue) >= self._cfg.max_queue_depth:
                    reject_depth = len(self._queue)
                else:
                    req = _GenRequest(
                        prompt, max_new, eos_token_id, bool(do_sample),
                        float(temperature),
                        stream.future if stream is not None else Future(),
                        None if not tmo else t + tmo, t,
                        spans.start_gen(self.name,
                                        incarnation=self.incarnation,
                                        trace_id=tid,
                                        trace_root=trace_root),
                        stream=stream,
                        ttft_deadline_ms=(t + ttft_tmo if ttft_tmo
                                          else None),
                        trace_id=tid)
                    if stream is not None:
                        stream.trace_id = tid
                    self._req_seq += 1
                    req.ordinal = self._req_seq
                    self._queue.append(req)
                    monitor.stat_add("STAT_gen_queue_depth")
                    self._cv.notify_all()
            if reject_depth is not None:
                # audited OUTSIDE the lock: the JSONL sink's disk write
                # must not stall the step thread behind rejecting
                # clients, and rejections spike exactly under overload
                monitor.stat_add("STAT_gen_rejected")
                self._audit.audit("REJECT_QUEUE_FULL",
                                  queue_depth=reject_depth)
                self._audit.flush_sink()
                raise EngineOverloaded(
                    f"{self.name}: queue depth "
                    f"{self._cfg.max_queue_depth} reached; shed load "
                    f"or raise FLAGS_gen_max_queue_depth")
            monitor.stat_add("STAT_gen_requests")
            return req

    def generate(self, prompt_ids, **kw) -> np.ndarray:
        """Synchronous submit: blocks for this prompt's full sequence."""
        return self.submit(prompt_ids, **kw).result()

    def replay_submit(self, entry: ReplayEntry, prompt: np.ndarray,
                      max_new: int, skip_stream: int = 0) -> None:
        """Re-enqueue a crash-manifest entry on THIS (rebuilt) engine
        (ISSUE 15, the supervisor seam). The caller-held future and
        stream are preserved verbatim; `prompt`/`max_new` are the
        supervisor's continuation (prompt + generated-so-far, remaining
        budget) or the original pair for a from-scratch replay, where
        `skip_stream` suppresses re-delivery of already-streamed greedy
        tokens. Deadlines carry over unchanged — a replay never buys a
        request more time. Bypasses the queue-depth bound: the request
        was admitted once already and must not be shed by the very
        restart that interrupted it."""
        prompt = np.asarray(prompt, np.int32)
        with self._cv:
            if self._closed:
                raise UnavailableError(
                    f"{self.name}: engine is shut down")
            # the hard TTFT deadline applies to the FIRST token ever
            # delivered, and an entry that generated anything met it in
            # a previous incarnation — carrying the (likely elapsed)
            # deadline onto the replay would expire a request the
            # caller already saw streaming (the whole-request deadline
            # still carries over unchanged)
            ttft = (entry.ttft_deadline_ms
                    if not entry.toks and not entry.delivered else None)
            req = _GenRequest(
                prompt, int(max_new), entry.eos, entry.do_sample,
                entry.temperature, entry.future, entry.deadline_ms,
                entry.t_enqueue_ms,
                spans.start_gen(self.name,
                                incarnation=self.incarnation,
                                trace_id=entry.trace_id,
                                trace_root=False),
                stream=entry.stream,
                ttft_deadline_ms=ttft,
                trace_id=entry.trace_id)
            req.claimed = entry.claimed
            req.retries = entry.retries + 1
            req.skip_stream = int(skip_stream)
            self._req_seq += 1
            req.ordinal = self._req_seq
            self._queue.append(req)
            monitor.stat_add("STAT_gen_queue_depth")
            self._cv.notify_all()
        monitor.stat_add("STAT_gen_replayed_requests")
        self._audit.audit(
            "REPLAY_ADMIT", rid=req.rid, orig_rid=entry.rid,
            retries=req.retries, generated=len(entry.toks),
            continuation=int(prompt.size) > int(entry.prompt.size),
            skip_stream=int(skip_stream),
            **({"trace": entry.trace_id} if entry.trace_id else {}))

    # -- step loop ---------------------------------------------------------

    def _num_active(self) -> int:
        return sum(1 for r in self._slots if r is not None)

    def _new_counts(self) -> dict:
        """One iteration's counters and attribution buckets, zeroed."""
        return {"admitted": 0, "completed": 0, "expired": 0,
                "poisoned": 0, "aborted": 0, "freed": 0,
                "prefix_tokens": 0, "cow_splits": 0,
                "tokens": 0, "spec_drafted": 0, "spec_accepted": 0,
                "prefill_chunks": 0, "ahead": 0, "prefill_tokens": 0,
                "prefill_ms": 0.0, "decode_ms": 0.0,
                "promote_ms": 0.0,
                "attr_idle_ms": 0.0, "attr_admit_ms": 0.0,
                "attr_bookkeep_ms": 0.0, "decode_wait_ms": 0.0,
                "prefill_wait_ms": 0.0, "admit_wait_ms": 0.0,
                **dict.fromkeys(self._family.step_counters, 0)}

    # -- where the step thread's time goes (ISSUE 20 / 34) -------------------
    #
    # Every stretch of the step thread's timeline is charged to ONE bucket
    # by moving `_cursor` over it, so the six buckets tile the timeline
    # exactly whatever overlaps what. While a timed program (a decode or
    # verify step, a prefill) is launched and its end not yet observed,
    # the chip is running and the host's own work hides under it: that
    # stretch belongs to the program and is charged when its end is
    # observed, from the later of (its launch, the observed end of the
    # program before it) — so a program's time reads its device time or
    # more, short only by the host's lag in SEEING the program before it
    # end (a stalled step thread), which that program's time holds: over
    # consecutive programs nothing is lost. Host buckets (admission, bookkeeping, promotion)
    # get only the time during which NO program was in flight: the time
    # the chip really waited for the host.

    def _charge_host(self):
        """Charge the thread's time since the cursor to the current host
        bucket — unless a program is in flight, whose time it is."""
        if self._unobserved:
            return
        now = time.perf_counter()
        self._it[self._phase] += (now - self._cursor) * 1000.0
        self._cursor = now

    def _enter_phase(self, bucket: str) -> str:
        """Exposed host time goes to `bucket` from here; returns the
        bucket it went to before."""
        self._charge_host()
        was, self._phase = self._phase, bucket
        return was

    def _program_launched(self):
        """A timed program is about to be launched: with nothing in flight
        the host's time up to here was exposed, and the program's starts."""
        self._charge_host()
        self._unobserved += 1

    def _program_ended(self) -> float:
        """A timed program's end has just been observed: its time, in ms —
        from its launch or the observed end of the program before it,
        whichever is later (that is where the cursor stands)."""
        now = time.perf_counter()
        ms = (now - self._cursor) * 1000.0
        self._cursor = now
        self._unobserved -= 1
        return ms

    def _dispatched(self, kind: str, out):
        """A timed program's dispatch has just returned, so it is in the
        device's queue: the device clock's stamps for it, or None with the
        clock off. `out` is an output only the host reads (never a donated
        pool), which the clock's watcher waits on."""
        clock = self._devclock
        return None if clock is None else clock.launched(kind, out,
                                                         self._phase)

    def _idle_wait(self, timeout: Optional[float]):
        """Wait on the engine's condition (held) with nothing in flight:
        the wait is the idle bucket's."""
        self._charge_host()
        with RecordEvent("generation::idle"):
            self._cv.wait(timeout)
        now = time.perf_counter()
        self._it["attr_idle_ms"] += (now - self._cursor) * 1000.0
        self._cursor = now

    def _loop(self):
        self._cursor = time.perf_counter()
        try:
            while True:
                with self._cv:
                    while (not self._queue and self._num_active() == 0
                           and self._flight is None and not self._closed):
                        self._idle_wait(None)
                    if self._closed and self._abort:
                        # the step in flight is dropped, not read: nothing
                        # of it was staged or counted
                        self._drop_unread()
                        self._evict_all(UnavailableError(
                            f"{self.name}: engine shut down"))
                        # flush the aborted/freed counts: the ring's
                        # sums must reconcile even on the abort exit
                        # (self._cv is an RLock-backed Condition, so
                        # re-acquiring inside is fine)
                        self._record_iteration()
                        self._flush_resolutions()
                        return
                    if (self._closed and not self._queue
                            and self._num_active() == 0
                            and self._flight is None):
                        return
                self._enter_phase("attr_admit_ms")
                with RecordEvent("generation::admit"):
                    self._admit()
                    self._expire_active()
                    if self._cfg.prefill_chunk:
                        self._advance_prefills()
                self._enter_phase("attr_bookkeep_ms")
                stepped = self._step()
                with RecordEvent("generation::record"):
                    self._record_iteration()
                    # sink before resolutions: a caller woken by
                    # result() may immediately read the JSONL — its own
                    # event must already be on disk (no lock held here)
                    self._audit.flush_sink()
                    # with a step in flight or sequences decoding, the
                    # next iteration reads or launches a program at once
                    # and a read-back (`_read`, `_observe`) hands these
                    # out while the chip runs; otherwise nothing is certain
                    # to follow, so they go out now
                    self._release_staged()
                    if self._flight is None and not self._decoding():
                        self._flush_released()
                if not stepped:
                    with self._cv:
                        if (self._queue and self._num_active() == 0
                                and not self._abort):
                            # unadmittable head (page exhaustion): bounded
                            # wait so queued deadlines still expire
                            self._idle_wait(0.01)
        except BaseException as e:  # noqa: BLE001 — never hang submitters
            if self._die(e):
                return  # supervised: the death was handed over and
                #         handled — no stderr traceback for a recovery
                #         that worked
            raise
        finally:
            # however the loop ends, nothing is launched after it: the
            # device clock's watcher ends too (`shutdown()` joins it)
            if self._devclock is not None:
                self._devclock.stop()

    def _record_iteration(self):
        """One compact scheduler record per engine iteration (ISSUE 11):
        decision counts taken this pass, queue pressure, page-pool
        occupancy, prefill-vs-decode wall. Pure host bookkeeping — one
        ring append plus two histogram observes, no device syncs beyond
        what the iteration already did. The per-iteration counter dict
        is zeroed whether or not the ring is on, so an A/B flag flip
        can't leak one arm's counts into the other."""
        # (what the host did since the last charge, up to this record, is
        # this iteration's; the record's own cost is the next one's)
        self._charge_host()
        it, self._it = self._it, self._new_counts()
        # pressure snapshot (ISSUE 17): republished every iteration on
        # the step thread — the only thread that mutates the allocator —
        # so `pressure()` readers never need the engine lock. Runs even
        # with the step ring off: the router polls regardless.
        self._pressure = self._compute_pressure()
        if self._step_log is None:
            return
        self._iters += 1
        with self._cv:
            depth = len(self._queue)
            oldest = (self._queue[0].t_enqueue_ms if self._queue
                      else None)
            live = self._num_active()
        # host-tier activity this iteration (ISSUE 18): deltas of the
        # tier's cumulative counters — one bookkeeping path, no second
        # per-iteration dict to zero
        tier_dem = tier_pro = 0
        if self._tier is not None:
            d, p = self._tier.demotions, self._tier.promotions
            ld, lp = self._tier_counts
            tier_dem, tier_pro = d - ld, p - lp
            self._tier_counts = (d, p)
        # goodput attribution (ISSUE 20 / 34): six buckets that reconcile
        # EXACTLY to the iteration's wall, which is their sum: the
        # stretches of the step thread's timeline charged to this
        # iteration (`_charge_host` and its neighbours). With a step in
        # flight an iteration's stretches are not one interval — the time
        # of the step it READ may have begun under the iteration before —
        # but every stretch is charged once, so the records' walls still
        # sum to the thread's elapsed time. Every stored value is rounded
        # first and bookkeeping is the remainder OF THE ROUNDED parts, so
        # `/steps` readers can assert the sum without fp slack from our
        # side. Admission and bookkeeping hold only the host time during
        # which no program was in flight (the chip waited for the host);
        # decode_ms / prefill_ms run from the later of (the program's
        # launch, the observed end of the program before it) to its own
        # observed end.
        a_idle = round(it["attr_idle_ms"], 3)
        a_prefill = round(it["prefill_ms"], 3)
        a_promote = round(it["promote_ms"], 3)
        a_decode = round(it["decode_ms"], 3)
        a_admit = round(it["attr_admit_ms"], 3)
        a_wall = round(it["attr_idle_ms"] + it["prefill_ms"]
                       + it["promote_ms"] + it["decode_ms"]
                       + it["attr_admit_ms"] + it["attr_bookkeep_ms"], 3)
        a_book = (a_wall - a_idle - a_admit - a_prefill - a_promote
                  - a_decode)
        # the device's own timeline (`device_clock.py`): the device time of
        # the programs this iteration READ, and the idle that closed at a
        # launch of this iteration, by the step thread's scope; the parts
        # are rounded first and the idle is their sum
        dev, idle = self._devclock.close()
        idle_by = {k: round(s * 1000.0, 3) for k, s in idle.items()}
        idle_by = {k: ms for k, ms in idle_by.items() if ms > 0}
        rec = step_log.StepRecord(
            it=self._iters, step=self._steps_total,
            t=time.perf_counter(), live=live,
            queue_depth=depth,
            oldest_age_ms=round(_now_ms() - oldest, 3)
            if oldest is not None else 0.0,
            pages_in_use=self._cache.pages_in_use,
            free_pages=self._cache.free_pages,
            admitted=it["admitted"], completed=it["completed"],
            expired=it["expired"], poisoned=it["poisoned"],
            aborted=it["aborted"], freed=it["freed"],
            prefix_tokens=it["prefix_tokens"],
            cow_splits=it["cow_splits"],
            tokens=it["tokens"],
            spec_drafted=it["spec_drafted"],
            spec_accepted=it["spec_accepted"],
            prefill_chunks=it["prefill_chunks"],
            prefill_ms=a_prefill,
            decode_ms=a_decode,
            incarnation=self.incarnation,
            tier_demotions=tier_dem, tier_promotions=tier_pro,
            tp=self._tp,
            attr_admit_ms=a_admit, attr_promote_ms=a_promote,
            attr_bookkeep_ms=a_book, attr_idle_ms=a_idle,
            attr_wall_ms=a_wall,
            # ISSUE 25 sub-splits: the read-back's share of the two
            # device buckets, clipped to its rounded parent
            decode_wait_ms=min(a_decode, round(it["decode_wait_ms"], 3)),
            prefill_wait_ms=min(a_prefill,
                                round(it["prefill_wait_ms"], 3)),
            admit_wait_ms=round(it["admit_wait_ms"], 3),
            # 1 where this iteration's decode step was launched with the
            # step before it still unread (ISSUE 34)
            ahead=it["ahead"],
            prefill_tokens=it["prefill_tokens"],
            decode_dev_ms=round(dev["decode"] * 1000.0, 3),
            prefill_dev_ms=round(dev["prefill"] * 1000.0, 3),
            dev_idle_ms=round(sum(idle_by.values(), 0.0), 3),
            dev_idle_by=idle_by,
            # what the family's decode program counted on the device
            **{name: it[name] for name in self._family.step_counters})
        self._step_log.record(rec)

    def _resolve_later(self, req: Optional[_GenRequest], fut,
                       result=None, exc=None):
        """Hold a future's resolution until after this iteration's
        _record_iteration(): a caller woken by result() must observe a
        step ring / audit tail that already includes its own outcome —
        resolving mid-iteration let a reader hit /steps before the
        record landed and see counts that don't reconcile. `req` rides
        along so _die can dedupe by rid: a request with a staged
        outcome must never ALSO receive the death error."""
        self._resolve_q.append((req, fut, result, exc))

    def _resolve_req_later(self, req: _GenRequest, result=None, exc=None):
        """Request-level resolution: the stream (when present) gets its
        terminal marker — the error, or the end-of-stream sentinel —
        staged BEFORE the future, behind the same barrier."""
        if req.stream is not None:
            self._stream_q.append((req.stream,
                                   exc if exc is not None
                                   else TokenStream._END))
        self._resolve_later(req, req.future, result, exc)

    def _stage_token(self, req: _GenRequest, tok: int):
        """Stage one decoded token for post-barrier stream delivery.
        A from-scratch greedy replay (ISSUE 15) suppresses the first
        `skip_stream` tokens — they were already delivered by the
        previous incarnation, and greedy re-derivation makes them
        byte-identical, so suppression preserves exactly-once."""
        if req.stream is None:
            return
        if req.skip_stream > 0:
            req.skip_stream -= 1
            return
        self._stream_q.append((req.stream, tok))

    def _release_staged(self):
        """This iteration's record has landed: what it staged may go out.
        It goes out at the next `_flush_released` — with sequences
        decoding that is inside the next program's read-back, while the
        chip runs (32 woken stream readers between two launches cost the
        chip 4 ms a step, PERF.md PR 27)."""
        if self._stream_q or self._resolve_q:
            self._released.append((self._stream_q, self._resolve_q))
            self._stream_q, self._resolve_q = [], []

    def _flush_released(self):
        batches, self._released = self._released, []
        for sq, q in batches:
            # streams first: a stream's final token / terminal marker
            # must be readable by the time its future resolves
            # ("streamed tokens arrive before resolved")
            for stream, item in sq:
                stream._put(item)
            for _req, fut, result, exc in q:
                try:
                    if exc is not None:
                        fut.set_exception(exc)
                    else:
                        fut.set_result(result)
                except Exception:  # lint: allow(except-pass): racing caller-side cancel pre-admission — the future is already settled, there is nothing left to deliver
                    pass

    def _flush_resolutions(self):
        self._release_staged()
        self._flush_released()

    def _die(self, e: BaseException):
        # a step in flight and the prefills launched and unread are dropped,
        # not read (the pools they were launched on may be what failed):
        # none of their tokens was staged or counted, so the manifest's
        # `toks` are exactly what was delivered and a replay derives the
        # dropped tokens again — once
        self._drop_unread()
        # two INDEPENDENT try blocks: a ring-record failure on a
        # half-broken engine must not also strand the staged
        # resolutions (they carry real results/errors already decided)
        try:
            # flush whatever the dying iteration already counted, so
            # the dump's step_log_tail reconciles with the audit tail
            self._record_iteration()
        except Exception:  # lint: allow(except-pass): best-effort ring record on a dying engine — the death path must keep going
            pass
        # settled BEFORE the flush: these requests already have an
        # outcome staged this iteration — after the flush delivers it,
        # the death error below must never reach them too (a request
        # observing BOTH a result and the death error was the ISSUE 15
        # resolution race)
        settled = {req.rid
                   for _sq, q in self._released + [([], self._resolve_q)]
                   for req, _f, _r, _e in q if req is not None}
        try:
            self._flush_resolutions()
        except Exception:  # lint: allow(except-pass): best-effort flush on a dying engine — per-future failures are already guarded inside
            pass
        stranded = []
        with self._cv:
            self._closed = True
            self._death = e
            while self._queue:
                req = self._queue.popleft()
                monitor.stat_sub("STAT_gen_queue_depth")
                if req.rid not in settled:
                    stranded.append(req)
            self._cv.notify_all()
        active = [r for r in self._slots
                  if r is not None and r.rid not in settled]
        if self._on_death is not None:
            # supervised (ISSUE 15): hand the queued + live work to the
            # supervisor as a crash manifest instead of stranding it —
            # the supervisor rebuilds the engine and replays
            manifest = CrashManifest(
                engine=self.name, incarnation=self.incarnation,
                error=e,
                entries=([ReplayEntry(r, queued=False)
                          for r in sorted(active,
                                          key=lambda r: r.ordinal)]
                         + [ReplayEntry(r, queued=True)
                            for r in stranded]),
                degraded_spec_off=self._degraded_spec_off,
                kv=self._cache.manifest(), compiles=dict(self._ledger))
            self._audit.flush_sink()
            flight_recorder.dump("gen_engine_death", {
                "engine": self.name, "error": repr(e),
                "supervised": True,
                "manifest": manifest.summary(),
                "inflight_spans": [r.span.to_dict() for r in active
                                   if r.span is not None][:64],
                "step_log_tail": (self._step_log.tail(32)
                                  if self._step_log is not None else []),
                "audit_tail": self._audit.tail(64)})
            try:
                self._on_death(manifest)
                return True
            except Exception as sup_e:  # supervisor itself failed:
                #                         fall through and strand typed
                #                         rather than hang the callers
                e = RuntimeError(
                    f"supervisor failed during restart: {sup_e!r} "
                    f"(original death: {e!r})")
        err = UnavailableError(f"{self.name}: generation engine died: "
                               f"{e!r}")
        for req in active + stranded:
            if req.stream is not None:
                # direct put (no barrier): the step loop is dead, no
                # further _flush_resolutions will run
                req.stream._put(err)
            try:
                req.future.set_exception(err)
            except Exception:  # lint: allow(except-pass): racing caller-side cancel — the future is already settled
                pass
            self._audit.audit("ENGINE_DIED", rid=req.rid,
                              error=repr(e))
            slo.observe_request(self.name, ok=False)
        self._audit.flush_sink()
        flight_recorder.dump("gen_engine_death", {
            "engine": self.name, "error": repr(e),
            "stranded_requests": len(stranded),
            "active_sequences": len(active),
            "inflight_spans": [r.span.to_dict() for r in active
                               if r.span is not None][:64],
            # the scheduler state that LED here: last step-ring records
            # + the decision-audit tail with reason codes (ISSUE 11)
            "step_log_tail": (self._step_log.tail(32)
                              if self._step_log is not None else []),
            "audit_tail": self._audit.tail(64)})
        return False

    # -- admission ---------------------------------------------------------

    def _admit(self):
        """Admit queued requests while a slot AND worst-case pages are
        both free (FIFO, head-of-line blocking — later smaller requests
        never overtake, so admission latency stays predictable)."""
        while True:
            if (self._prefix is not None and self._unread and self._queue
                    and None in self._slots):
                # the next admission's lookup walks the pages that the
                # prefills before it register when they are read
                self._settle_prefills("prefix_cache")
            with self._cv:
                # whole-queue sweep, not just the head: a request queued
                # BEHIND a page-blocked head must still get its deadline
                # error on time (head-of-line blocking blocks admission,
                # never expiry)
                self._expire_queued()
                if not self._queue:
                    return
                req = self._queue[0]
                slot = next((i for i, r in enumerate(self._slots)
                             if r is None), None)
                if slot is None:
                    # once per request per cause: a full batch defers
                    # the head every iteration, and a per-iteration
                    # event would drown the audit ring in repeats
                    if "slots" not in req.defer_logged:
                        req.defer_logged.add("slots")
                        self._audit.audit(
                            "DEFER_SLOTS", rid=req.rid,
                            queue_depth=len(self._queue))
                    return
                S = int(req.prompt.size)
                total = S + req.max_new
                need = self._cache.pages_needed(total)
                # prefix plan (ISSUE 12): the longest cached chain this
                # prompt walks maps read-only; a FULL-prompt match keeps
                # every page but must recompute its last position's
                # logits, so the page holding position S-1 is CoW-split
                # (the one divergent write) — tail length stays >= 1
                # either way, there is always a token to prefill
                digests, hit_pages, host_digests = [], [], []
                if self._prefix is not None:
                    if self._tier is not None:
                        digests, hit_pages, host_digests = \
                            self._prefix.lookup_tiered(req.prompt)
                    else:
                        digests, hit_pages = self._prefix.lookup(
                            req.prompt)
                matched_hbm = len(hit_pages)
                promote_n = len(host_digests)
                matched = matched_hbm + promote_n
                full_match = (matched > 0
                              and matched * self._cfg.page_size == S)
                # a full match whose tail comes up from the host tier
                # needs NO CoW: position S-1's recompute writes into
                # the LAST promoted page, which is this request's own
                # fresh target — private until register() re-indexes it
                cow_needed = full_match and promote_n == 0
                # promotion targets are fresh pages too, so the
                # admission arithmetic counts in-flight promotions
                # naturally: (need - matched) suffix pages + promote_n
                # targets = need - matched_hbm
                fresh_needed = (need - matched_hbm
                                + (1 if cow_needed else 0))
                pinned = bool(matched_hbm)
                if pinned:
                    # hold the matched chain across the eviction pass:
                    # refcount >= 2 takes its pages out of the
                    # evictable set, so the eviction below can never
                    # reclaim the very pages this admission maps
                    self._cache.pin(hit_pages)
                if promote_n:
                    # the SAME eviction pass may demote victims INTO
                    # the tier — shield the matched host run from its
                    # LRU until the promotion consumes it
                    self._prefix.protect(host_digests)
                try:
                    # alloc_exhaust failpoint: force the exhaustion
                    # verdict without draining the pool — the DEFER /
                    # clamp machinery downstream runs unchanged
                    if (fresh_needed > self._cache.reclaimable_pages
                            or failpoints.fire("alloc_exhaust")
                            is not None):
                        monitor.stat_add("STAT_gen_admit_blocked")
                        # every blocked ITERATION counts toward the
                        # clamp detector (head-of-line blocking means
                        # only the head defers — a per-request count
                        # would see one event per episode)
                        self._note_exhaust()
                        if "pages" not in req.defer_logged:
                            req.defer_logged.add("pages")
                            self._audit.audit(
                                "DEFER_PAGES", rid=req.rid,
                                need_pages=fresh_needed,
                                free_pages=self._cache.free_pages,
                                reclaimable=self._cache
                                .reclaimable_pages)
                        if not self._exhaust_dumped:
                            self._exhaust_dumped = True
                            flight_recorder.dump(
                                "gen_allocator_exhausted", {
                                    "engine": self.name, "rid": req.rid,
                                    "need_pages": fresh_needed,
                                    "cache": self._cache.stats(),
                                    "queue_depth": len(self._queue),
                                    "step_log_tail":
                                        (self._step_log.tail(32)
                                         if self._step_log is not None
                                         else []),
                                    "audit_tail": self._audit.tail(64)})
                        return
                    if fresh_needed > self._cache.free_pages:
                        # evictable pages counted as admission capacity
                        # above; reclaim them NOW, before alloc — the
                        # deferred zero-on-free point for cached chains
                        # (the pinned matched chain is never victimized)
                        freed = self._prefix.evict(
                            fresh_needed - self._cache.free_pages,
                            exclude=hit_pages)
                        self._audit.audit(
                            "EVICT_PREFIX_LRU", rid=req.rid,
                            pages=len(freed),
                            free_pages=self._cache.free_pages)
                        if freed:
                            self._zero_pages(freed)
                        if fresh_needed > self._cache.free_pages:
                            # under-delivery (every remaining chain is
                            # live-shared or excluded): defer rather
                            # than let alloc raise into engine death —
                            # pages reclaim through those sequences'
                            # frees
                            monitor.stat_add("STAT_gen_admit_blocked")
                            return
                    self._queue.popleft()
                    monitor.stat_sub("STAT_gen_queue_depth")
                    if not req.claimed:
                        # a REPLAYED request's future is already in the
                        # RUNNING state from its first admission — a
                        # second set_running_or_notify_cancel would
                        # raise InvalidStateError (ISSUE 15)
                        if not req.future.set_running_or_notify_cancel():
                            self._audit.audit("CANCELLED", rid=req.rid)
                            if req.stream is not None:
                                from concurrent.futures import \
                                    CancelledError
                                self._stream_q.append(
                                    (req.stream, CancelledError()))
                            continue
                        req.claimed = True
                    req.slot = slot
                    req.pt_row = self._cache.alloc_shared(
                        req.rid, total, hit_pages)
                finally:
                    if pinned:
                        self._cache.unpin(hit_pages)
                    if promote_n:
                        self._prefix.unprotect()
                cow_src = cow_dst = None
                if cow_needed:
                    cow_src = hit_pages[-1]
                    cow_dst = self._cache.cow_split(req.rid, cow_src)
                    req.pt_row[matched - 1] = cow_dst
                    monitor.stat_add("STAT_cow_splits")
                    self._it["cow_splits"] += 1
                    self._audit.audit("COW_SPLIT", rid=req.rid,
                                      src_page=cow_src, dst_page=cow_dst)
                self._slots[slot] = req
                self._it["admitted"] += 1
                if self._admit_clamped:
                    # the pool covered an admission again: the
                    # exhaustion episode is over, lift the clamp
                    self._admit_clamped = False
                    self._exhaust_times.clear()
            if cow_dst is not None:
                # clone the shared page (content + int8 scale row)
                # before the tail prefill writes position S-1 through
                # the private copy; the shared original is never
                # written under its other readers
                self._cow_copy(cow_src, cow_dst)
            if promote_n:
                # host-tier promotion (ISSUE 18) — outside the lock
                # like the CoW clone: device traffic must not stall
                # submitters. On abandon the match shrinks back to the
                # HBM run and the tail prefill covers the rest cold.
                if not self._promote_upload(req, host_digests,
                                            matched_hbm):
                    matched, full_match = matched_hbm, False
            # the admission accounting lands AFTER the promotion
            # resolved (step-thread-local state — safe off the lock):
            # an abandon must not count host pages it never served
            req.prefix_tokens = ((S - 1) if full_match
                                 else matched * self._cfg.page_size)
            if self._prefix is not None:
                self._prefix.note_admitted(
                    req.prefix_tokens,
                    host_tokens=((matched - matched_hbm)
                                 * self._cfg.page_size))
            self._it["prefix_tokens"] += req.prefix_tokens
            if matched:
                self._audit.audit(
                    "ADMIT_PREFIX_HIT", rid=req.rid, slot=slot,
                    pages=need, shared_pages=matched_hbm,
                    promoted_pages=matched - matched_hbm,
                    prefix_tokens=req.prefix_tokens,
                    queued_ms=round(_now_ms() - req.t_enqueue_ms, 3))
            else:
                self._audit.audit(
                    "ADMIT", rid=req.rid, slot=slot, pages=need,
                    queued_ms=round(_now_ms() - req.t_enqueue_ms, 3))
            t_admitted = time.perf_counter()
            t_queued = req.t_enqueue_ms / 1000.0
            if req.span is not None:
                req.span.slot = slot
                req.span.prefix_tokens = req.prefix_tokens
                req.span.stamp("admitted", t_admitted)
                t_queued = req.span.stamps.get("queued", t_queued)
            self._it["admit_wait_ms"] += (t_admitted - t_queued) * 1000.0
            chunk = self._cfg.prefill_chunk
            if chunk and S - req.prefix_tokens > chunk:
                # chunked prefill (ISSUE 14): the slot is admitted NOW
                # (pages reserved, FIFO order kept) but prefills one
                # chunk per engine iteration through the tail programs,
                # interleaved with decode steps — a long prompt stops
                # spiking every live sequence's TPOT. The slot joins
                # decode only when prefill_pos reaches the prompt end.
                req.prefill_pos = req.prefix_tokens
                req.pending_digests = digests
            else:
                self._do_prefill(req, digests)

    def _expire_queued(self):
        """Fail every expired request and drop every cancelled one from
        the WHOLE queue (position-independent); caller holds the lock.
        While queued nothing has been delivered, so BOTH stream
        deadlines are hard here: the TTFT deadline (first token cannot
        arrive on time) and the whole-request deadline alike."""
        t = _now_ms()
        live = deque()
        for req in self._queue:
            deadlines = [d for d in (req.deadline_ms,
                                     req.ttft_deadline_ms)
                         if d is not None]
            if deadlines and t > min(deadlines):
                monitor.stat_sub("STAT_gen_queue_depth")
                monitor.stat_add("STAT_gen_timeouts")
                self._it["expired"] += 1
                self._audit.audit(
                    "EXPIRE_QUEUED", rid=req.rid,
                    queued_ms=round(t - req.t_enqueue_ms, 3))
                slo.observe_request(self.name, ok=False)
                self._resolve_req_later(req, exc=ExecutionTimeoutError(
                    f"{self.name}: request expired after "
                    f"{t - req.t_enqueue_ms:.1f}ms in queue"))
                continue
            if req.future.cancelled():
                monitor.stat_sub("STAT_gen_queue_depth")
                self._audit.audit("CANCELLED", rid=req.rid)
                if req.stream is not None:
                    from concurrent.futures import CancelledError
                    self._stream_q.append((req.stream, CancelledError()))
                continue
            live.append(req)
        self._queue = live

    def _read_chunk(self, launch, logits):
        """Read a prefill chunk's logits at once, with what was launched
        before it read first, in launch order: the decode step in flight,
        then the prefills unread (settled, as "chunk")."""
        self._settle_prefills("chunk")
        fl = self._flight
        if fl is not None and fl.host is None:
            self._observe(fl)
        return self._read("prefill", launch, logits)

    def _read(self, kind: str, launch, *outs):
        """The blocking read of a timed program's host outputs (`kind`:
        "prefill", or "decode" for the verify step; `launch`: its device
        clock stamps, which the read's return completes) — with
        `_observe`, which reads the decode step in flight, the only places
        the step thread waits for the chip. The blocked time goes to this
        iteration's `<kind>_wait_ms`, the program's own time (`_program_
        ended`) to `<kind>_ms`, of which the wait is a sub-split. Programs
        are read in launch order, so a program launched BEHIND another
        ends after it and that one's end is observed first: each gets its
        own time and no stretch is counted twice."""
        t0 = _now_ms()
        # the program is launched and the chip busy: the tokens and
        # outcomes the LAST iteration staged (its record has landed) wake
        # their readers now, under the device's time, not between two
        # launches where the chip would wait for the wake-ups
        self._flush_released()
        host = [np.asarray(o) for o in outs]
        if launch is not None:
            launch.read = time.perf_counter()
        self._it[f"{kind}_wait_ms"] += _now_ms() - t0
        self._it[f"{kind}_ms"] += self._program_ended()
        return host if len(host) > 1 else host[0]

    def _observe(self, fl: _Flight):
        """Block until the decode step in flight has ended and take its
        outputs to the host; it is delivered by `_settle`. Its own time
        and wait ride on the flight, for the record of the iteration that
        delivers it."""
        t0 = _now_ms()
        self._flush_released()      # as in `_read`: the chip is busy
        with RecordEvent("generation::read"):
            fl.host = [np.asarray(o) for o in fl.outs]
            if fl.launch is not None:
                fl.launch.read = time.perf_counter()
        fl.wait_ms = _now_ms() - t0
        fl.decode_ms = self._program_ended()

    def _drop_unread(self):
        """Forget the step in flight and the prefills launched, unread
        (abort, death): nothing of them was staged or counted."""
        fl, self._flight = self._flight, None
        launches = [p.launch for p in self._unread]
        self._unread.clear()
        if fl is not None and fl.host is None:
            launches.append(fl.launch)
        for launch in launches:
            self._unobserved -= 1
            if launch is not None:
                self._devclock.dropped(launch)

    def _slot_arg(self, slot) -> tuple:
        """The prefill program's last argument for a family that keeps
        state by slot (its `slot_state`); nothing for the others."""
        return (np.int32(slot),) if self._slot_state else ()

    def _bucket_for(self, S: int) -> int:
        for b in self._cfg.prefill_buckets:
            if b >= S:
                return b
        return self._cfg.prefill_buckets[-1]

    def _do_prefill(self, req: _GenRequest, digests=None):
        """Launch the request's prompt through the bucketed prefill program
        (writes its K/V pages) and the first-token program behind it
        (`_first_token`), and mark the slot live — it joins the very next
        decode step, its token input the device's own. A prefix hit
        (req.prefix_tokens > 0) prefills ONLY the tail through the
        per-bucket tail program — the cached pages are read, never
        written. A poisoned request (non-finite logits — the pools came
        back valid) fails ONLY this request and returns its pages
        zeroed, once the prefill is read; an exception from the jitted
        call itself is engine-fatal, because the pools were DONATED into
        it and may already be consumed — touching them again (even to
        zero this request's pages) would dereference deleted buffers
        (same contract as a decode-step exception)."""
        failpoints.maybe_raise("prefill_raise")  # engine-fatal, like a
        #                                          real prefill jit error
        S = int(req.prompt.size)
        pfx = req.prefix_tokens
        tail = S - pfx
        bucket = self._bucket_for(tail)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :tail] = req.prompt[pfx:]
        if pfx:
            span = f"generation::prefill_tail[b={bucket}]"
            prog, last = self._tail_jit, (np.int32(tail), np.int32(pfx))
        else:
            span = f"generation::prefill[b={bucket}]"
            prog, last = self._prefill_jit, (np.int32(S),
                                             *self._slot_arg(req.slot))
        # (with a decode step in flight the prefill is launched behind it:
        # the chip goes from the step straight into the prefill)
        with RecordEvent(span):
            self._program_launched()
            with self._dev_ctx():
                out = prog(self._W, *self._pools(), req.pt_row, ids, *last)
            launch = self._dispatched("prefill", out[-1])
            self._set_pools(out[:-1])
        # real prompt tokens through a prefill program, whatever the family
        self._it["prefill_tokens"] += tail
        self._first_token(req, out[-1], launch, bucket, digests)

    def _first_token(self, req: _GenRequest, logits, launch, bucket: int,
                     digests) -> None:
        """Behind a launched prefill (whole prompt, prefix tail or final
        chunk; `logits` its output, `launch` its device-clock stamps):
        launch the first-token program, which writes the request's first
        token into the next decode step's token input at its slot, and
        leave the prefill unread — it is read once the next decode step
        has been launched behind it (`_step`), or at once where something
        needs its token on the host first (`_settles_first`)."""
        M = self._cfg.max_slots
        prev, owners = self._prev or (self._no_prev, [None] * M)
        prev, tok, bad = self._first_token_call(
            logits, prev, req.slot, req.temperature, req.do_sample,
            req.ordinal)
        owners = list(owners)
        owners[req.slot] = req
        self._prev = (prev, owners)
        # the step that takes this token writes it at the prompt's end
        req.next_pos = int(req.prompt.size)
        req.unread = True
        self._unread.append(_Prefill(req, (tok, bad), launch, bucket,
                                     digests, self._steps_total))
        why = self._settles_first()
        if why is not None:
            self._settle_prefills(why)

    def _settle_prefills(self, why: str) -> None:
        """Read the prefills launched and unread, oldest first, and deliver
        their first tokens (`_finish_prefill`). The decode step in flight
        is read first where it was launched before them; where one was
        launched behind them it stays in flight, and they count as read
        ahead (`prefills_ahead`). The others count under `settled` as
        "prefill:<why>": what did not let them wait for a launch."""
        if not self._unread:
            return
        fl = self._flight
        if (fl is not None and fl.host is None
                and self._steps_total == self._unread[0].steps):
            self._observe(fl)
        while self._unread:
            p = self._unread.popleft()
            with RecordEvent("generation::read_prefill"):
                tok, bad = self._read("prefill", p.launch, *p.outs)
            if self._steps_total > p.steps:
                self._prefills_ahead += 1
                monitor.stat_add("STAT_gen_prefills_ahead")
            else:
                key = f"prefill:{why}"
                self._settled[key] = self._settled.get(key, 0) + 1
            p.req.unread = False
            if bad:
                self._poison_prefill(p.req, p.bucket)
            else:
                self._finish_prefill(p.req, int(tok), p.digests)

    def _inject_poison(self, bad: np.ndarray, owners=None) -> np.ndarray:
        """`decode_poison_nan` failpoint: mark the first live slot's
        logits non-finite host-side — the exact verdict the decode
        program's in-graph isfinite check would have returned, so the
        whole poison-isolation path downstream is exercised unchanged.
        `owners`: the step's own (a slot it ran whose request is still
        there); without them, the slots as they are."""
        bad = np.array(bad, copy=True)
        for i, r in enumerate(self._slots):
            if (r is not None and r.prefill_pos is None
                    and (owners is None or owners[i] is r)):
                bad[i] = True
                break
        return bad

    def _note_poison(self):
        """Poison-storm detector (ISSUE 15): k poison events inside the
        rolling window flip speculation OFF for this engine —
        non-finite logits keep arriving, so stop spending verify-wide
        commits on them and fall back to the (pre-warmed) one-token
        decode program. The verdict survives restarts via the crash
        manifest."""
        if (not self._poison_degrade_k or not self._spec_k
                or self._degraded_spec_off):
            return
        now = time.monotonic()
        self._poison_times.append(now)
        while (self._poison_times
               and now - self._poison_times[0] > self._degraded_window_s):
            self._poison_times.popleft()
        if len(self._poison_times) >= self._poison_degrade_k:
            self._degraded_spec_off = True
            monitor.stat_add("STAT_gen_degraded_spec_off")
            self._audit.audit(
                "DEGRADED_SPEC_OFF",
                poison_events=len(self._poison_times),
                window_s=self._degraded_window_s)

    def _note_exhaust(self):
        """Admission-clamp detector (ISSUE 15): k page-blocked
        admission iterations inside the rolling window clamp admission
        — new submits the pool cannot cover RIGHT NOW fail fast with
        ResourceExhaustedError instead of queueing toward a timeout.
        Cleared by the next successful admission."""
        if not self._exhaust_clamp_k or self._admit_clamped:
            return
        now = time.monotonic()
        self._exhaust_times.append(now)
        while (self._exhaust_times
               and now - self._exhaust_times[0] > self._degraded_window_s):
            self._exhaust_times.popleft()
        if len(self._exhaust_times) >= self._exhaust_clamp_k:
            self._admit_clamped = True
            monitor.stat_add("STAT_gen_admit_clamped")
            self._audit.audit(
                "DEGRADED_ADMIT_CLAMP",
                exhaust_events=len(self._exhaust_times),
                window_s=self._degraded_window_s,
                free_pages=self._cache.free_pages)

    def _poison_decode(self, req: _GenRequest, slot: int):
        """Non-finite decode/verify logits: only THIS sequence fails,
        its pages return zeroed (shared by the plain and speculative
        step paths — one poison diagnostic shape for both)."""
        monitor.stat_add("STAT_gen_poisoned")
        self._it["poisoned"] += 1
        self._note_poison()
        self._audit.audit("POISON_DECODE", rid=req.rid, slot=slot,
                          generated=len(req.toks))
        slo.observe_request(self.name, ok=False)
        flight_recorder.dump("gen_poisoned_sequence", {
            "engine": self.name, "rid": req.rid, "stage": "decode",
            "slot": slot, "generated": len(req.toks),
            "error": "non-finite decode logits",
            "step_log_tail": (self._step_log.tail(32)
                              if self._step_log is not None else []),
            "audit_tail": self._audit.tail(64)})
        self._evict(req, FatalError(
            f"{self.name}: sequence {req.rid} produced "
            f"non-finite logits at step {len(req.toks)}"))

    def _poison_prefill(self, req: _GenRequest, bucket: int):
        """Non-finite prefill logits (whole-prompt, tail or chunk): the
        pools came back valid, so only THIS request fails and its pages
        return zeroed."""
        monitor.stat_add("STAT_gen_poisoned")
        self._it["poisoned"] += 1
        self._note_poison()
        self._audit.audit("POISON_PREFILL", rid=req.rid,
                          bucket=bucket)
        slo.observe_request(self.name, ok=False)
        flight_recorder.dump("gen_poisoned_sequence", {
            "engine": self.name, "rid": req.rid, "stage": "prefill",
            "bucket": bucket, "error": "non-finite prefill logits",
            "step_log_tail": (self._step_log.tail(32)
                              if self._step_log is not None else []),
            "audit_tail": self._audit.tail(64)})
        self._release(req)
        self._resolve_req_later(req, exc=FatalError(
            f"{self.name}: non-finite prefill logits for request "
            f"{req.rid} (poisoned prompt or weights)"))

    def _register_pages(self, req: _GenRequest, digests) -> None:
        """Index full pages in the prefix cache (matched nodes touched,
        fresh pages take a cache reference and outlive this request's
        free). With FLAGS_gen_prefix_cache_max_pages set, registration
        eagerly LRU-evicts OTHER chains back to budget — the freed
        pages are zeroed here, same hygiene as the pre-alloc
        eviction."""
        freed = self._prefix.register(digests, req.pt_row)
        if freed:
            self._zero_pages(freed)
            self._audit.audit("EVICT_PREFIX_BUDGET", rid=req.rid,
                              pages=len(freed),
                              free_pages=self._cache.free_pages)

    def _finish_prefill(self, req: _GenRequest, tok: int, digests) -> None:
        """Shared tail of every prefill flavor (whole-prompt, prefix
        tail, final chunk), once it is read: register cacheable pages,
        deliver the first token the device sampled."""
        self._prefills_total += 1
        monitor.stat_add("STAT_gen_prefills")
        if self._prefix is not None and digests:
            self._register_pages(req, digests)
        req.toks.append(tok)
        self._tokens_total += 1
        monitor.stat_add("STAT_gen_tokens")
        self._it["tokens"] += 1
        self._stage_token(req, tok)
        if req.span is not None:
            req.span.stamp("prefilled")
            req.span.stamp("first_token")
            req.span.stamp("last_token")
        if self._finished(req, tok):
            self._complete(req)

    def _advance_prefills(self):
        """Advance the OLDEST partially-prefilled slot by ONE chunk
        through the per-bucket tail program (FLAGS_gen_prefill_chunk).
        One chunk per engine iteration by design: between chunks the
        loop runs a decode step for every live sequence, which is
        exactly the TPOT protection chunked prefill exists for — the
        long prompt's admission cost is spread across iterations
        instead of stalling the step thread for its whole prefill."""
        req = None
        for r in self._slots:
            if (r is not None and r.prefill_pos is not None
                    and (req is None or r.ordinal < req.ordinal)):
                req = r
        if req is None:
            return
        failpoints.maybe_raise("prefill_raise")
        S = int(req.prompt.size)
        take = min(self._cfg.prefill_chunk, S - req.prefill_pos)
        bucket = self._bucket_for(take)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :take] = req.prompt[req.prefill_pos:req.prefill_pos + take]
        with RecordEvent(f"generation::prefill_chunk[b={bucket}]"):
            self._program_launched()
            with self._dev_ctx():
                out = self._tail_jit(
                    self._W, *self._pools(), req.pt_row, ids,
                    np.int32(take), np.int32(req.prefill_pos))
            launch = self._dispatched("prefill", out[-1])
            self._set_pools(out[:-1])
        self._it["prefill_chunks"] += 1
        self._it["prefill_tokens"] += take
        self._chunks_total += 1
        monitor.stat_add("STAT_gen_prefill_chunks")
        req.prefill_pos += take
        if req.prefill_pos == S:
            # the final chunk: its first token as any prefill's
            req.prefill_pos = None
            digests, req.pending_digests = req.pending_digests, None
            self._first_token(req, out[-1], launch, bucket, digests)
            return
        # a chunk before it is read at once: only its poison flag is wanted
        lg = self._read_chunk(launch, out[-1])
        if not np.all(np.isfinite(lg)):
            req.prefill_pos = None
            req.pending_digests = None
            self._poison_prefill(req, bucket)

    # -- decode step -------------------------------------------------------

    def _step_arrays(self):
        """The decode program's arguments after the pools, for the slots
        as they are (construction, warm-up, `.lower()` in tests)."""
        return self._step_inputs(None)[0]

    def _step_inputs(self, flight: Optional[_Flight]):
        """The arguments of the decode step about to be launched, from the
        host's own count, and who owns each slot it runs. `flight`: the
        step launched before it and still unread, if any.

        Admission reserved each request's pages for prompt + max_new and
        `pt_row` is fixed from then on, `next_pos` advances by one a
        launch, and a request that ends by max_new ends at a step the host
        knows before it launches it (a prefill's unread token counts) — so
        no slot runs a step it does not need, except the one step after an
        EOS or a poison flag the host has not read yet.
        The table and the per-slot arrays are patched where a slot's
        request changed, not rebuilt: an inactive slot's row is zero (its
        write lands in the reserved scratch page)."""
        M = self._cfg.max_slots
        tok = np.zeros((M,), np.int32)
        fresh = np.ones((M,), bool)
        pos = np.zeros((M,), np.int32)
        active = np.zeros((M,), bool)
        owners: List[Optional[_GenRequest]] = [None] * M
        held = self._prev[1] if self._prev is not None else None
        rows = self._rows
        for i, req in enumerate(self._slots):
            if req is not None and (
                    req.prefill_pos is not None     # still chunk-prefilling
                    or (len(req.toks) + req.unread
                        + (flight is not None and flight.owners[i] is req)
                        >= req.max_new)):           # ends at its prefill or
                req = None                          # the step in flight
            if req is not rows[i]:
                rows[i] = req
                if req is None:
                    self._table[i] = 0
                    self._temps[i], self._smask[i] = 1.0, False
                else:
                    self._table[i] = req.pt_row
                    self._temps[i] = req.temperature
                    self._smask[i] = req.do_sample
            if req is None:
                continue
            owners[i] = req
            active[i] = True
            pos[i] = req.next_pos
            if held is not None and held[i] is req:
                fresh[i] = False    # its token is the device's own output
            else:
                tok[i] = req.toks[-1]
        prev = self._prev[0] if self._prev is not None else self._no_prev
        # (copies: the program's arguments must not change under a launch
        # that has not taken them yet)
        return (self._table.copy(), prev, tok, fresh, pos, active,
                self._temps.copy(), self._smask.copy(), self._key_host,
                np.int32(self._steps_total)), owners

    def _spec_arrays(self):
        """Verify-step inputs (ISSUE 14): per-slot [current token + k
        drafts] blocks. Drafts come from the prompt-lookup proposer
        over each sequence's OWN token history, truncated to the
        request's remaining token budget (so every consumed position
        stays inside the pages the admission reserved); sampled slots
        take no drafts. Returns (args, drafted_count)."""
        M, PP = self._cfg.max_slots, self._cfg.pages_per_seq
        K = self._spec_k
        toks_blk = np.zeros((M, K + 1), np.int32)
        dmask = np.zeros((M, K), bool)
        pos = np.zeros((M,), np.int32)
        active = np.zeros((M,), bool)
        temps = np.ones((M,), np.float32)
        smask = np.zeros((M,), bool)
        pt = np.zeros((M, PP), np.int32)
        drafted = 0
        for i, req in enumerate(self._slots):
            if req is None or req.prefill_pos is not None:
                continue
            active[i] = True
            toks_blk[i, 0] = req.toks[-1]
            pos[i] = req.next_pos
            temps[i] = req.temperature
            smask[i] = req.do_sample
            pt[i] = req.pt_row
            if not req.do_sample:
                budget = min(K, req.max_new - len(req.toks) - 1)
                if budget > 0:
                    drafts = self._proposer.propose(
                        np.concatenate([req.prompt,
                                        np.asarray(req.toks, np.int32)]),
                        budget)
                    n = int(drafts.size)
                    if n:
                        toks_blk[i, 1:1 + n] = drafts
                        dmask[i, :n] = True
                        drafted += n
        key = self._step_key()
        return (pt, toks_blk, dmask, pos, active, temps, smask,
                key), drafted

    def _step_key(self):
        import jax
        if self._base_key is None:
            self._base_key = jax.random.PRNGKey(self._cfg.seed)
        return jax.random.fold_in(self._base_key, self._steps_total)

    def _decoding(self) -> bool:
        """Some slot holds a sequence past its prefill."""
        return any(r is not None and r.prefill_pos is None
                   for r in self._slots)

    def _settles_first(self) -> Optional[str]:
        """Why the next decode step may NOT be launched ahead of the last
        one's read-back, nor a prefill be left unread — what needs a step's
        or a prefill's tokens on the host before the next launch — or None.
        Decided from the engine's own state:
        speculation (the proposer reads the token history), a
        `_pre_step_hook` (it may look at, or wait for, what was
        delivered), an armed step failpoint (serving/failpoints.py:
        they act between a read-back and the next launch)."""
        if self._spec_k and not self._degraded_spec_off:
            return "speculation"
        if self._pre_step_hook is not None:
            return "pre_step_hook"
        if failpoints.armed("slow_step_ms", "decode_step_raise",
                            "decode_poison_nan"):
            return "failpoint"
        return None

    def _step(self) -> bool:
        """The decode half of one iteration of the ONE loop, with at most
        one step in flight; returns whether anything ran.

        With step n in flight: launch step n+1 from what the device holds
        (`_launch`), THEN read step n and deliver it (`_settle`), THEN read
        the prefills this iteration launched behind n (`_settle_prefills`),
        all while the chip runs n+1 — unless something needs n's tokens on
        the host first (`_settles_first`), in which case n is read and
        nothing is launched. With none in flight (the degenerate case: the
        first step after an idle engine, every step of an engine that
        settles first): the hook and the failpoints as ever, then a
        speculative step, or a launch — read at once where a reason to
        settle stands, left in flight otherwise — and the prefills read
        after it. No prefill is left unread past this. Every live sequence
        advances one token a step through the single compiled decode
        program (inactive slots are masked into the reserved scratch
        page), or 1 to k+1 through the single compiled verify program.

        Output is the same work as a loop that reads every step before
        the next: greedy streams are token-identical; step k still draws
        its samples from `fold_in(base, k)`, so two engines of one seed
        that see the same arrivals sample identical streams. Against
        that loop a request joins one step number later (it is admitted
        while the next step is already launched), and a step that ran for
        an EOS overshoot alone, with no other slot live, takes a step
        number that loop would not have spent: sampled streams equal its
        streams only where neither happened. A request's first token is
        drawn from `fold_in` of the first-token key by its ordinal, which
        no launch order moves."""
        fl = self._flight
        if fl is not None:
            why = self._settles_first()
            if why is None:
                self._launch(fl)
            self._settle(fl)
            self._settle_prefills(why or "no_decode")
            return True
        if not self._decoding():
            return False
        if self._pre_step_hook is not None:
            self._flush_released()      # a hook may wait for a reader
            self._pre_step_hook(self)
        # fault-injection seams (ISSUE 15, serving/failpoints.py): a
        # slow step first (SLO exercises), then the engine-fatal raise
        # — InjectedFault escapes to _loop exactly like a real decode
        # jit exception (the pools-donated contract)
        ms = failpoints.fire("slow_step_ms")
        if ms:
            self._flush_released()
            time.sleep(ms / 1000.0)
        failpoints.maybe_raise("decode_step_raise")
        why = self._settles_first()
        if why is not None:
            self._settled[why] = self._settled.get(why, 0) + 1
        if why == "speculation":
            self._settle_prefills(why)
            self._spec_step()
            return True
        fl = self._launch(None)
        self._settle_prefills(why or "no_decode")
        if fl is not None and why is not None:
            self._settle(fl)
        return True

    def _launch(self, flight: Optional[_Flight]) -> Optional[_Flight]:
        """Launch one decode step and leave it in flight. `flight`: the
        step before it, still unread — this one is then launched AHEAD,
        its token input the device's own output of that step. Nothing is
        launched (None) where no slot has a step to run: every sequence
        ends at the step in flight.

        An exception out of the launched program is engine-fatal: the
        pools were donated into it (the contract of every program)."""
        with RecordEvent("generation::prepare"):
            args, owners = self._step_inputs(flight)
        if not any(r is not None for r in owners):
            return None
        self._program_launched()
        with RecordEvent(self._step_span):
            out = self._decode_call(self._W, *self._pools(), *args)
            NP = self._npool
            # (*pools, next tokens, poison flags[, the family's counters])
            launch = self._dispatched("decode", out[NP + 1])
        self._set_pools(out[:NP])
        for req in owners:
            if req is not None:
                req.next_pos += 1
        self._prev = (out[NP], owners)
        self._flight = fl = _Flight(owners, out[NP:], flight is not None,
                                    launch)
        self._steps_total += 1
        monitor.stat_add("STAT_gen_steps")
        if fl.ahead:
            self._ahead_total += 1
            monitor.stat_add("STAT_gen_steps_ahead")
        return fl

    def _settle(self, fl: _Flight):
        """Read a launched step (`_observe`, unless a read-back behind it
        already did) and deliver it: tokens staged, sequences completed,
        its counters and its time into this iteration's record. A slot
        whose request has left since the launch — it ended by EOS or was
        poisoned at the step before (learned a step late), expired, was
        evicted — computed a token nobody takes: dropped, never staged,
        never counted."""
        if fl.host is None:
            self._observe(fl)
        if self._flight is fl:
            self._flight = None
        nxt, bad, *counted = fl.host
        if failpoints.fire("decode_poison_nan") is not None:
            bad = self._inject_poison(bad, fl.owners)
        it = self._it
        for name, n in zip(self._family.step_counters,
                           counted[0] if counted else ()):
            it[name] += int(n)
        it["decode_ms"] += fl.decode_ms
        it["decode_wait_ms"] += fl.wait_ms
        it["ahead"] += fl.ahead
        with RecordEvent("generation::deliver"):
            for i, req in enumerate(fl.owners):
                if req is None:
                    continue
                if self._slots[i] is not req:
                    self._dropped_tokens += 1
                    continue
                if bad[i]:
                    # poison isolation: only THIS sequence fails; its pages
                    # are zeroed before reuse so the NaN cannot reach the
                    # next owner's masked attention
                    self._poison_decode(req, i)
                    continue
                tok = int(nxt[i])
                req.toks.append(tok)
                self._tokens_total += 1
                monitor.stat_add("STAT_gen_tokens")
                it["tokens"] += 1
                self._stage_token(req, tok)
                if req.span is not None:
                    req.span.stamp("last_token")
                if self._finished(req, tok):
                    self._complete(req)

    def _spec_step(self):
        """ONE speculative engine step (ISSUE 14): every live sequence
        advances 1 to k+1 tokens through the single compiled verify
        program — the current token plus the longest prefix of its
        prompt-lookup drafts the model greedily agrees with, plus the
        bonus token the verify pass scored at the first disagreement.
        Rejected draft positions were scratch-routed in-graph, so there
        is nothing to undo on the host; acceptance is exact greedy
        agreement, so the token stream is identical to the one the
        plain decode program would have produced, just delivered in
        fewer weight streams."""
        with RecordEvent("generation::prepare"):
            args, drafted = self._spec_arrays()
        # (never with a step in flight or a prefill unread:
        # `_settles_first`; the device's next tokens of an earlier decode
        # launch are stale after this)
        self._prev = None
        self._program_launched()
        with RecordEvent(f"generation::verify[k={self._spec_k}]"):
            out = self._verify_call(self._W, *self._pools(), *args)
            launch = self._dispatched("decode", out[-1])
            n_acc, nxt, bad = self._read("decode", launch, out[-3],
                                         out[-2], out[-1])
        if failpoints.fire("decode_poison_nan") is not None:
            bad = self._inject_poison(bad)
        self._set_pools(out[:-3])
        self._steps_total += 1
        monitor.stat_add("STAT_gen_steps")
        if drafted:
            monitor.stat_add("STAT_spec_drafted", drafted)
            self._it["spec_drafted"] += drafted
            self._spec_drafted_total += drafted
        toks_blk = args[1]
        with RecordEvent("generation::deliver"):
            for i, req in enumerate(self._slots):
                if req is None or req.prefill_pos is not None:
                    continue
                if bad[i]:
                    self._poison_decode(req, i)
                    continue
                acc = int(n_acc[i])
                if acc:
                    monitor.stat_add("STAT_spec_accepted", acc)
                    self._it["spec_accepted"] += acc
                    self._spec_accepted_total += acc
                    req.spec_accepted += acc
                # accepted drafts in order, then the bonus token; EOS (or
                # the max-new budget) inside the block ends the sequence
                # there — later committed positions sit past next_pos,
                # masked from every future attend and zeroed with the free
                for tok in ([int(t) for t in toks_blk[i, 1:1 + acc]]
                            + [int(nxt[i])]):
                    req.toks.append(tok)
                    req.next_pos += 1
                    self._tokens_total += 1
                    monitor.stat_add("STAT_gen_tokens")
                    self._it["tokens"] += 1
                    self._stage_token(req, tok)
                    if self._finished(req, tok):
                        break
                if req.span is not None:
                    req.span.stamp("last_token")
                if self._finished(req, req.toks[-1]):
                    self._complete(req)

    def _finished(self, req: _GenRequest, tok: int) -> bool:
        return ((req.eos is not None and tok == req.eos)
                or len(req.toks) >= req.max_new)

    def _expire_active(self):
        """Per-step deadline enforcement: an expired non-streaming
        sequence cancels mid-decode — pages freed the same step, only
        its future fails. A STREAMING sequence's whole-request deadline
        is soft once tokens flow (ISSUE 12): expiry stops decoding the
        same step but resolves with the tokens already delivered —
        they left the engine and cannot be retracted — still counted as
        a timeout (STAT_gen_timeouts, SLO error)."""
        t = _now_ms()
        for req in list(self._slots):
            if req is None or req.unread:
                # (a prefill unread is read this iteration, its first token
                # with it: checked at the next)
                continue
            deadlines = [req.deadline_ms] if req.deadline_ms else []
            if req.ttft_deadline_ms is not None and not req.toks:
                # a chunk-prefilling stream has been admitted but has
                # no first token yet: its HARD TTFT deadline still
                # applies (pre-chunking, admission implied an immediate
                # prefill so this window could never be observed live)
                deadlines.append(req.ttft_deadline_ms)
            if not deadlines:
                continue
            if t > min(deadlines):
                monitor.stat_add("STAT_gen_timeouts")
                self._it["expired"] += 1
                self._audit.audit(
                    "EXPIRE_DECODE", rid=req.rid, slot=req.slot,
                    generated=len(req.toks),
                    stream=req.stream is not None,
                    age_ms=round(t - req.t_enqueue_ms, 3))
                slo.observe_request(self.name, ok=False)
                if (req.stream is not None and req.toks
                        and req.skip_stream == 0):
                    # soft: pages freed now, stream closed normally,
                    # future resolves with the partial sequence.
                    # skip_stream > 0 (a from-scratch replay still
                    # re-deriving tokens an earlier incarnation
                    # delivered) takes the HARD path below instead —
                    # resolving now would hand back FEWER generated
                    # tokens than the caller already streamed
                    self._release(req)
                    self._resolve_req_later(req, result=np.concatenate(
                        [req.prompt, np.asarray(req.toks, np.int32)]))
                    if req.span is not None:
                        req.span.stamp("resolved")
                        req.span.finish(len(req.toks),
                                        prefix_tokens=req.prefix_tokens,
                                        spec_tokens=req.spec_accepted)
                    continue
                ttft_hit = (req.ttft_deadline_ms is not None
                            and not req.toks
                            and t > req.ttft_deadline_ms)
                self._evict(req, ExecutionTimeoutError(
                    f"{self.name}: request {req.rid} missed its HARD "
                    f"TTFT deadline after {t - req.t_enqueue_ms:.1f}ms "
                    f"admitted but still prefilling (no first token)"
                    if ttft_hit else
                    f"{self.name}: request {req.rid} expired after "
                    f"{t - req.t_enqueue_ms:.1f}ms with "
                    f"{len(req.toks)}/{req.max_new} tokens decoded "
                    f"(whole-request deadlines are hard for "
                    f"non-streaming submits; no partial result is "
                    f"delivered)"))

    # -- completion / eviction ---------------------------------------------

    def _release(self, req: _GenRequest):
        """Return the request's slot + pages (pages zeroed on device)."""
        pages = self._cache.free(req.rid)
        if pages:
            self._zero_pages(pages)
            self._exhaust_dumped = False  # pages freed: new episode
        if req.slot is not None and self._slots[req.slot] is req:
            self._slots[req.slot] = None
            self._it["freed"] += 1
        with self._cv:
            self._cv.notify_all()

    def _complete(self, req: _GenRequest):
        out = np.concatenate([req.prompt,
                              np.asarray(req.toks, np.int32)])
        if self._prefix is not None and req.pt_row is not None:
            # generated-suffix registration (ISSUE 14): index the full
            # pages of prompt + answer BEFORE the release, so a
            # follow-up turn whose prompt is this whole conversation
            # (prompt_n+1 = prompt_n + answer_n, the agent-loop shape)
            # walks the chain end-to-end. Only pages fully covered by
            # WRITTEN positions qualify: the final token's K/V is never
            # written (it was sampled, not stepped), so the chain stops
            # at `written` — registering past it would serve zeros
            # (counted from the tokens delivered, not from `next_pos`:
            # after an EOS learned a step late that has moved once more)
            written = int(req.prompt.size) + len(req.toks) - 1
            self._register_pages(
                req, self._prefix.digests(out)[:written
                                               // self._cfg.page_size])
        self._release(req)
        t_done = _now_ms()
        self._hist.observe(t_done - req.t_enqueue_ms)
        if req.deadline_ms is not None and t_done > req.deadline_ms:
            # finished the same instant it expired: honor the deadline
            # (a timeout, NOT a completion — the two counters partition
            # the finished-naturally outcomes)
            monitor.stat_add("STAT_gen_timeouts")
            self._it["expired"] += 1
            self._audit.audit("EXPIRE_LATE", rid=req.rid,
                              generated=len(req.toks))
            slo.observe_request(self.name, ok=False)
            if req.stream is not None:
                # the stream's whole-request deadline is soft: tokens
                # already left, deliver the (complete) sequence
                self._resolve_req_later(req, result=out)
                if req.span is not None:
                    req.span.stamp("resolved")
                    req.span.finish(len(req.toks),
                                    prefix_tokens=req.prefix_tokens,
                                    spec_tokens=req.spec_accepted)
                return
            self._resolve_later(req, req.future, exc=ExecutionTimeoutError(
                f"{self.name}: request expired after "
                f"{t_done - req.t_enqueue_ms:.1f}ms"))
            return
        # delivery cannot fail: _admit claimed the future via
        # set_running_or_notify_cancel, so a caller-side cancel is no
        # longer possible — count now, resolve after the ring record
        # (the stream's end marker flushes before the future resolves)
        self._resolve_req_later(req, result=out)
        monitor.stat_add("STAT_gen_completions")  # delivered results
        self._it["completed"] += 1
        self._audit.audit(
            "COMPLETE_EOS" if (req.eos is not None
                               and req.toks
                               and req.toks[-1] == req.eos)
            else "COMPLETE_MAX_NEW",
            rid=req.rid, generated=len(req.toks),
            e2e_ms=round(t_done - req.t_enqueue_ms, 3))
        slo.observe_request(self.name, ok=True)
        if req.span is not None:
            req.span.stamp("resolved")
            req.span.finish(len(req.toks),
                            prefix_tokens=req.prefix_tokens,
                            spec_tokens=req.spec_accepted)

    def _evict(self, req: _GenRequest, err: BaseException):
        """Cancel a LIVE sequence mid-decode: free + zero its pages,
        fail only its own future (and stream, when present)."""
        self._release(req)
        monitor.stat_add("STAT_gen_evictions")
        self._resolve_req_later(req, exc=err)

    def _evict_all(self, err: BaseException):
        for req in list(self._slots):
            if req is not None:
                # deliberate operator action (shutdown/abort): audited
                # but NOT an SLO error — a drain must not burn the
                # error budget of the replicas still serving
                self._it["aborted"] += 1
                self._audit.audit("EVICT_SHUTDOWN", rid=req.rid,
                                  generated=len(req.toks))
                self._evict(req, err)

    # -- lifecycle / introspection -----------------------------------------

    def stats(self) -> dict:
        """Engine snapshot: per-slot state, page-pool occupancy + KV
        introspection, the exact compile ledger, token/step totals, and
        the TTFT/TPOT + end-to-end latency histograms."""
        with self._cv:
            depth = len(self._queue)
            slots = [{"slot": i,
                      "rid": r.rid if r is not None else None,
                      "generated": len(r.toks) if r is not None else 0,
                      "prompt_len": int(r.prompt.size)
                      if r is not None else 0}
                     for i, r in enumerate(self._slots)]
            decode_tokens = self._tokens_total - self._prefills_total
            slot_of = {r.rid: i for i, r in enumerate(self._slots)
                       if r is not None}
            ledger = dict(self._ledger)
            steps, prefills, tokens = (self._steps_total,
                                       self._prefills_total,
                                       self._tokens_total)
        pages = self._cache.stats()
        return {
            "slots": slots,
            "queue_depth": depth,
            "pages": pages,
            # what the engine holds (PR 28): per pool its logical shape,
            # the layout the device holds it in, the one the programs
            # were compiled for (the same), the one the compiler
            # `preferred` for the step program when left to choose
            # (another: that program relays the pool inside itself),
            # device and logical bytes
            "pools": [dict(p, compiled_for=c, preferred=w) for p, c, w in
                      zip(pages["pools"], self._compiled_for,
                          self._preferred)],
            "kv": self._kv_introspection(slot_of),
            "compiles": ledger,
            # "kernel" / "pool" / "reference" (head pools), "latent_kernel"
            # / "latent_gather" (a latent pool): the paged attention the
            # decode program was built with (ops/paged_ops.py)
            "decode_attention": self._decode_attention,
            # what else the family names of its programs (the hybrid
            # family: "ssm_decode_path")
            **self._family_info,
            "steps": steps,
            "prefills": prefills,
            "tokens": tokens,
            # mesh-slice lane (ISSUE 19): slice degree + what one chip
            # of it holds (pages stats carry the per-shard bytes too)
            "tp": self._tp,
            # speculative decoding + chunked prefill (ISSUE 14): the
            # acceptance economics (tokens_per_step > 1 is the win) and
            # the chunk count the bench + reports read
            "spec": {
                "enabled": bool(self._spec_k),
                "k": self._spec_k,
                "drafted": self._spec_drafted_total,
                "accepted": self._spec_accepted_total,
                "acceptance_rate": round(
                    self._spec_accepted_total
                    / max(1, self._spec_drafted_total), 4),
                # decode-delivered tokens per decode step — every
                # successful prefill delivers exactly one token, so
                # subtracting prefills leaves the honest speculation
                # signal (> 1.0 only when drafts were accepted)
                "tokens_per_step": round(
                    decode_tokens / max(1, steps), 4),
            },
            "prefill_chunks": self._chunks_total,
            # one decode step in flight (ISSUE 34): decode steps launched
            # while the step before was still unread (`steps` has them
            # all), prefills read only after a decode step was launched
            # behind them (`prefills` has them all), steps that settled
            # first by what needed their tokens on the host
            # ("speculation", "pre_step_hook", "failpoint") and prefills
            # read at once ("prefill:" and that, or "no_decode": no step
            # followed, "chunk": a prefill chunk was read, "prefix_cache":
            # the next admission's lookup), and tokens the chip computed
            # for a request that had left by the time they were read (the
            # step after an EOS or a poison flag learned a step late, an
            # expiry, an eviction): dropped, never staged or counted
            "lookahead": {"ahead": self._ahead_total,
                          "prefills_ahead": self._prefills_ahead,
                          "settled": dict(self._settled),
                          "dropped_tokens": self._dropped_tokens},
            # fault tolerance (ISSUE 15): which engine generation this
            # is, and whether a degraded mode is active
            "incarnation": self.incarnation,
            "degraded": {
                "spec_off": self._degraded_spec_off,
                "admit_clamped": self._admit_clamped,
                "poison_degrade_k": self._poison_degrade_k,
                "exhaust_clamp_k": self._exhaust_clamp_k,
            },
            "step_log": {
                "enabled": self._step_log is not None,
                "recorded": (self._step_log.recorded
                             if self._step_log is not None else 0),
                "audit_events": self._audit.recorded,
            },
            "latency_ms": self._hist.snapshot(),
            "ttft_ms": monitor.histogram("ttft_ms").snapshot(),
            "tpot_ms": monitor.histogram("tpot_ms").snapshot(),
        }

    def _kv_introspection(self, slot_of=None) -> dict:
        """`stats()["kv"]`: pool stats + watermarks, the per-sequence
        page-ownership map (joined to decode slots), and the admission-
        headroom estimate for this engine's representative request
        shapes — one `can_admit` count per (prefill bucket + default
        max-new) total, the per-replica pressure surface the router
        tier compares (ISSUE 11)."""
        out = dict(self._cache.stats())
        owners = self._cache.owners()
        if slot_of is None:
            with self._cv:
                slot_of = {r.rid: i for i, r in enumerate(self._slots)
                           if r is not None}
        out["owners"] = [
            {"rid": rid, "slot": slot_of.get(rid), "pages": pages}
            for rid, pages in sorted(owners.items())]
        # prefix-cache surface (ISSUE 12): hit/eviction counters + the
        # cached/evictable page split the admission arithmetic uses
        out["prefix"] = (self._prefix.stats() if self._prefix is not None
                         else {"enabled": False})
        shapes = {b + self._cfg.max_new_tokens
                  for b in self._cfg.prefill_buckets}
        out["admit_headroom"] = {
            str(tokens): n
            for tokens, n in sorted(
                self._cache.headroom(sorted(shapes)).items())}
        return out

    def _compute_pressure(self) -> dict:
        """Step-thread half of `pressure()`: admission headroom per
        representative request shape (prefill bucket + default max-new,
        the same shapes as stats()["kv"]["admit_headroom"]), pool
        occupancy, and slot availability. Called only from __init__
        (before the step thread exists) and `_record_iteration` (on it),
        and published as one plain-dict attribute store — the atomic
        handoff `pressure()` reads."""
        shapes = sorted({b + self._cfg.max_new_tokens
                         for b in self._cfg.prefill_buckets})
        snap = {
            "headroom": {str(t): n for t, n in sorted(
                self._cache.headroom(shapes).items())},
            "free_pages": self._cache.free_pages,
            "pages_in_use": self._cache.pages_in_use,
            "slots_free": sum(1 for r in self._slots if r is None),
            "live": self._num_active(),
            # mesh-slice lane (ISSUE 19): page counts above are
            # tp-invariant (the page axis is FULL on every shard);
            # kv_shard_bytes is what ONE chip of the slice pays — the
            # per-device HBM reality the router compares (== the whole
            # pool for a single-chip lane)
            "tp": self._tp,
            "kv_shard_bytes": self._cache.shard_hbm_bytes(),
        }
        if self._tier is not None:
            # host-tier surface (ISSUE 18): the router folds the tier
            # hit-rate into placement the same way the headroom fields
            # feed least-pressure — a replica resurrecting prefixes
            # from host RAM is cheaper than one prefilling them cold
            snap["tier"] = {
                "host_bytes": self._tier.host_bytes,
                "entries": len(self._tier),
                "hit_rate": round(
                    self._tier.hits
                    / max(1, self._prefix.hits + self._prefix.misses),
                    4),
            }
        return snap

    def pressure(self) -> dict:
        """Cheap per-replica pressure snapshot for the router tier
        (ISSUE 17): page/slot fields come from the step thread's last
        published `_compute_pressure()` dict (read as one GIL-atomic
        attribute load — NO engine lock taken, so a polling router can
        never contend the step loop), while queue depth and oldest-queue
        age are overlaid live — the queue grows on the submitter side
        between iterations and staleness there is exactly what a
        balancer must see. `len(deque)` and `deque[0]` are GIL-atomic;
        the head may race an admit's popleft, hence the IndexError arm."""
        snap = dict(self._pressure)
        q = self._queue
        snap["queue_depth"] = len(q)
        try:
            snap["oldest_age_ms"] = round(
                _now_ms() - q[0].t_enqueue_ms, 3)
        except IndexError:
            snap["oldest_age_ms"] = 0.0
        snap["queue_limit"] = self._cfg.max_queue_depth
        return snap

    def health(self) -> dict:
        """`/readyz` verdict, same shape as InferenceEngine.health() so
        the router tier drains generation replicas identically."""
        with self._cv:
            depth = len(self._queue)
            draining = self._closed
            live = int(getattr(self, "_thread", None) is not None
                       and self._thread.is_alive() and self._death is None)
            slots_free = sum(1 for r in self._slots if r is None)
        limit = self._cfg.max_queue_depth
        warmed = self._warmed
        if draining:
            reason = "draining"
        elif not warmed:
            reason = "warming up"
        elif not live:
            reason = "step loop dead"
        elif depth >= limit:
            reason = "queue at rejection threshold"
        else:
            # SLO folding (ISSUE 11): with FLAGS_slo_max_burn_rate set,
            # a replica burning its error budget too fast reports
            # not-ready so the router sheds load BEFORE the budget is
            # gone — the pre-emptive drain surface
            reason = slo.shed_verdict(self.name) or "ok"
        return {"ready": reason == "ok", "reason": reason,
                "warmup_complete": warmed, "draining": draining,
                "live_lanes": live, "queue_depth": depth,
                "queue_limit": limit, "slots_free": slots_free,
                "slots": self._cfg.max_slots}

    def shutdown(self, drain: bool = True,
                 timeout_s: Optional[float] = None):
        """Stop intake; by default every queued + live sequence finishes
        before the step loop exits. drain=False fails pending futures
        fast (live sequences are evicted, pages freed)."""
        dropped = []
        with self._cv:
            self._closed = True
            if not drain:
                self._abort = True
                while self._queue:
                    req = self._queue.popleft()
                    monitor.stat_sub("STAT_gen_queue_depth")
                    dropped.append(req)
                    err = UnavailableError(
                        f"{self.name}: engine shut down")
                    if req.stream is not None:
                        req.stream._put(err)  # never admitted: no
                        # barrier to honor, nothing was recorded
                    try:
                        req.future.set_exception(err)
                    except Exception:  # lint: allow(except-pass): racing caller-side cancel — the future is already settled
                        pass
            self._cv.notify_all()
        for req in dropped:
            # audited OUTSIDE the lock (disk sink); queued drops get
            # their own code so the step ring's aborted count still
            # reconciles exactly with the live EVICT_SHUTDOWN events
            self._audit.audit("EVICT_SHUTDOWN_QUEUED", rid=req.rid,
                              queued_ms=round(_now_ms()
                                              - req.t_enqueue_ms, 3))
        t = getattr(self, "_thread", None)
        if t is not None:
            t.join(timeout_s)
        if self._devclock is not None:
            # the loop stopped it on its way out; told again in case the
            # loop outlived the join
            self._devclock.stop()
            self._devclock.join(timeout_s)
        exporter.unregister_engine(self)
        if self._step_log is not None:
            step_log.unregister(self._step_log)
        self._audit.close()
        slo.forget(self.name)
        if self._cfg.gc_freeze:
            import gc
            gc.unfreeze()
        if getattr(self, "_owns_metrics_server", False) \
                and self.metrics_server is not None:
            self.metrics_server.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False
