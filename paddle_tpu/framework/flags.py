"""Global flags registry.

Reference: gflags table in `paddle/fluid/platform/flags.cc` +
`pybind/global_value_getter_setter.cc` (paddle.set_flags/get_flags).
Here flags are a plain validated dict; a few map onto jax.config.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Iterable

__all__ = ["set_flags", "get_flags", "register_flag", "flag"]

_FLAGS: Dict[str, Any] = {}


def register_flag(name: str, default: Any, doc: str = "") -> None:
    env = os.environ.get(name)
    if env is not None:
        if isinstance(default, bool):
            default = env.lower() in ("1", "true", "yes")
        elif isinstance(default, int):
            default = int(env)
        elif isinstance(default, float):
            default = float(env)
        else:
            default = env
    _FLAGS[name] = default


# Subset of the reference's 32 flags that are meaningful on TPU, plus ours.
register_flag("FLAGS_check_nan_inf", False,
              "scan op outputs for nan/inf (reference platform/flags.cc:44)")
register_flag("FLAGS_eager_op_jit", True,
              "compile eager ops through a cached jit rather than op-by-op")
register_flag("FLAGS_allocator_strategy", "xla",
              "kept for parity; XLA owns allocation on TPU")
register_flag("FLAGS_fraction_of_gpu_memory_to_use", 0.92, "parity no-op")
register_flag("FLAGS_cudnn_deterministic", False, "parity: deterministic ops")
register_flag("FLAGS_benchmark", False, "sync after every op for timing")
register_flag("FLAGS_use_flash_attention", True,
              "use the Pallas flash-attention kernel on TPU when applicable")
register_flag("FLAGS_flash_attention_interpret", False,
              "force the Pallas flash kernels in interpreter mode (CPU "
              "test meshes; TPU semantics, interpreter speed)")
register_flag("FLAGS_flash_attention_min_seq", 512,
              "shortest query length dispatched to the Pallas flash kernel; "
              "below this XLA's fused dense attention wins (crossover "
              "measured on a v5e before PR 21)")
register_flag("FLAGS_flash_block_q", 512,
              "preferred q tile for the flash/splash attention kernels "
              "(multiple of 128; an on-chip sweep before PR 21 — v5e, "
              "S=2048, bf16 — picked 512). Kernels fall back to the "
              "largest of 128/256/512/this that divides the sequence")
register_flag("FLAGS_flash_block_kv", 512,
              "preferred kv tile for the flash/splash attention kernels "
              "(multiple of 128; same sweep as FLAGS_flash_block_q)")
register_flag("FLAGS_use_splash_attention", True,
              "use the Pallas segment-aware splash-attention kernel for "
              "scaled_dot_product_attention calls that carry segment_ids "
              "(sequence packing); off routes packed batches through the "
              "dense segment-masked fallback")
register_flag("FLAGS_splash_attention_min_seq", 512,
              "shortest packed-row length dispatched to the splash kernel; "
              "below this the dense segment-masked attention wins (same "
              "crossover assumption as FLAGS_flash_attention_min_seq: "
              "not swept on-chip)")
register_flag("FLAGS_use_paged_attention", True,
              "decode-time cached attention over a paged KV cache: on the "
              "TPU backend dispatch to the repo's Pallas head-pool kernel "
              "(ops/latent_attention_kernel.head_decode_attention: pages "
              "stay in place, one copy a page for all K/V heads); off — or "
              "any non-TPU backend — gathers the page table into a dense "
              "[B,H,T,D] buffer and runs the same masked attention as "
              "GPTModel.generate's fixed cache (the CPU/interpret parity "
              "reference, ops/paged_ops.py)")
register_flag("FLAGS_kv_cache_dtype", "auto",
              "page dtype of serving.PagedKVCache pools: 'auto' stores "
              "pages in the served model's dtype; 'int8' enables the "
              "quantized page mode — int8 pools + per-(layer,head,page) "
              "fp32 scale pools, quantize-on-append / dequantize-on-"
              "read (ops/paged_ops.py), ~4x pages per HBM byte so the "
              "same pool budget admits ~4x the concurrent sequences "
              "(tests/test_quantized_serving.py holds >=1.9x at equal "
              "bytes); "
              "'float32'/'bfloat16' force an unquantized page dtype")
register_flag("FLAGS_paged_page_size", 16,
              "tokens per KV-cache page (serving.PagedKVCache); the TPU "
              "head-pool kernel wants whole sublane tiles (8 float32, "
              "16 bfloat16 rows)")
register_flag("FLAGS_paged_num_pages", 512,
              "total pages in the per-layer K/V pools (page 0 is a "
              "reserved scratch page, so usable pages = this - 1); "
              "pool HBM = 2·layers·heads·pages·page_size·head_dim·dtype")
register_flag("FLAGS_paged_pages_per_seq", 0,
              "page-table width (most pages one sequence may hold); 0 "
              "derives ceil(max_position_embeddings / page_size) from "
              "the served model")
register_flag("FLAGS_gen_max_slots", 8,
              "serving.GenerationEngine: fixed decode-batch slot count — "
              "the ONE compiled decode-step shape; live sequences join "
              "and leave the running batch without recompiling")
register_flag("FLAGS_gen_prefill_buckets", "16,64,256",
              "serving.GenerationEngine: prompt-length buckets a prompt "
              "is right-padded up to, so XLA compiles exactly one "
              "prefill per bucket (clipped to max_position_embeddings)")
register_flag("FLAGS_gen_max_new_tokens", 64,
              "serving.GenerationEngine: default per-request new-token "
              "budget (admission reserves worst-case pages for it)")
register_flag("FLAGS_gen_max_queue_depth", 256,
              "serving.GenerationEngine: pending-request bound; submits "
              "beyond it fail fast with EngineOverloaded")
register_flag("FLAGS_gen_request_timeout_ms", 30000.0,
              "serving.GenerationEngine: default per-request deadline, "
              "enforced while queued AND before every decode step — an "
              "expired sequence is cancelled mid-decode, its pages freed, "
              "only its own future fails (0 disables)")
register_flag("FLAGS_gen_prefix_cache", False,
              "serving.GenerationEngine: content-hash prefix cache over "
              "the paged KV pools (serving/prefix_cache.py) — a request "
              "whose prompt prefix matches a cached block chain maps "
              "those pages read-only (copy-on-write on the one "
              "divergent write) and prefills only the tail; refcount-0 "
              "chains are LRU-evicted before alloc. Opt-in: off keeps "
              "the PR 8 single-owner page semantics exactly")
register_flag("FLAGS_gen_spec_k", 0,
              "serving.GenerationEngine: speculative-decoding draft "
              "tokens per decode step (serving/spec_decode.py prompt-"
              "lookup proposer + ONE fixed-k jitted verify program "
              "scoring k+1 positions over the paged KV cache per "
              "step; the longest greedily-agreeing draft prefix is "
              "accepted plus the bonus token, so a step delivers 1 to "
              "k+1 tokens — greedy output stays token-identical to "
              "speculation off). 0 disables (the plain one-token "
              "decode program)")
register_flag("FLAGS_gen_spec_ngram", 3,
              "serving.GenerationEngine: longest n-gram the prompt-"
              "lookup draft proposer matches against the sequence's "
              "own token history (tried n..1, rightmost match wins); "
              "only read when FLAGS_gen_spec_k > 0")
register_flag("FLAGS_gen_tp", 1,
              "serving.GenerationEngine: tensor-parallel degree of the "
              "lane's mesh slice (ISSUE 19) — every jitted program in "
              "the pack (prefill/tail/decode/verify/cow/zero/tier) is "
              "built as ONE shard_map program over a 'tp' mesh axis "
              "with attention/MLP projection weights and the paged K/V "
              "pools (+ int8 scale grids) head-sharded via "
              "NamedSharding, page tables/lengths/sampling state "
              "replicated, and the row-parallel partial sums psum-"
              "reduced once per block. num_heads and the MLP hidden "
              "width must divide it; 1 = the single-chip lane "
              "(bit-identical to the pre-mesh engine). An explicit "
              "GenerationEngine(mesh=...) overrides the flag")
register_flag("FLAGS_gen_prefill_chunk", 0,
              "serving.GenerationEngine: split prompts longer than "
              "this into fixed-size prefill chunks driven through the "
              "per-bucket tail-extension programs, ONE chunk per "
              "engine iteration interleaved with decode steps — a "
              "long prompt admitting no longer stalls every live "
              "sequence's TPOT for its whole prefill. 0 disables "
              "(whole-prompt bucketed prefill at admission)")
register_flag("FLAGS_gen_prefix_cache_max_pages", 0,
              "serving.GenerationEngine: byte budget for the prefix "
              "cache as a page-count cap — register() eagerly LRU-"
              "evicts cached chains back to this budget (audit code "
              "EVICT_PREFIX_BUDGET) instead of waiting for an "
              "admission to run short of free pages. 0 = unbounded "
              "(evict-on-demand only, the ISSUE 12 behavior)")
register_flag("FLAGS_kv_tier", False,
              "serving.GenerationEngine: host-RAM demotion tier under "
              "the prefix cache (serving/kv_tier.py) — prefix-cache "
              "eviction demotes a cold chain's pages off-device into a "
              "bounded host store (raw int8 bytes + fp32 scale rows, so "
              "the round-trip is exact) instead of discarding them, and "
              "a later lookup that misses HBM but hits the host tier "
              "re-uploads the pages through a double-buffered "
              "device_put pipeline overlapped with the tail prefill. "
              "Requires FLAGS_gen_prefix_cache; off keeps the PR 12 "
              "two-state (HBM or gone) semantics exactly")
register_flag("FLAGS_kv_tier_host_bytes", 256 << 20,
              "serving/kv_tier.py host-store byte budget: demoted page "
              "entries beyond it are LRU-evicted (demote-of-demoted = "
              "final eviction, audit code KV_TIER_EVICT); an entry "
              "that alone exceeds the budget is refused and the "
              "eviction proceeds plain")
register_flag("FLAGS_kv_tier_chunk_pages", 4,
              "pages per upload chunk of the promotion pipeline "
              "(serving/kv_tier.py): the engine device_put-stages chunk "
              "i+1 while chunk i's jitted scatter is in flight — the "
              "double-buffer depth knob, and the fixed width of the ONE "
              "compiled tier_write program")
register_flag("FLAGS_gen_step_log", True,
              "serving.GenerationEngine: record one compact scheduler "
              "record per engine iteration into the bounded per-engine "
              "step ring (profiler/step_log.py; /steps, chrome counter "
              "tracks, engine_step_ms/gen_queue_age_ms histograms); off "
              "removes the per-iteration accounting entirely")
register_flag("FLAGS_gen_step_log_size", 16384,
              "per-engine step-ring capacity in records; the oldest "
              "record is overwritten (same bounding discipline as "
              "FLAGS_trace_ring_size). 16,384: at 13 ms an iteration a "
              "50 s window holds up to 3,900 records, and a reader of "
              "the window wants the drain after it too")
register_flag("FLAGS_gen_audit_log", "",
              "optional JSONL sink for the generation scheduler's "
              "decision audit log (profiler/audit.py): every "
              "admit/defer/evict/expire/poison decision appends one "
              "reason-coded line to this path; '' keeps the bounded "
              "in-memory ring only")
register_flag("FLAGS_failpoints", "",
              "deterministic fault-injection spec (serving/failpoints.py): "
              "';'-separated `site@trigger[:arg]` terms where trigger is "
              "`N` (fire on the Nth hit only) or `every:K` (every Kth "
              "hit) and arg is a site-specific number (slow_step_ms "
              "sleep). Sites: decode_step_raise, prefill_raise, "
              "decode_poison_nan, alloc_exhaust, slow_step_ms, "
              "kv_tier.promote_upload, kv_tier.demote_gather. '' "
              "disables injection entirely (the zero-cost no-op path)")
register_flag("FLAGS_gen_retry_limit", 2,
              "serving.EngineSupervisor: per-request replay budget — a "
              "request may survive at most this many engine restarts "
              "before it fails with a typed UnavailableError "
              "(audit code RETRY_EXHAUSTED)")
register_flag("FLAGS_gen_restart_backoff_ms", 100.0,
              "serving.EngineSupervisor base backoff between consecutive "
              "engine deaths (doubles per consecutive death, capped at "
              "32x; also the serving lane-restart base backoff)")
register_flag("FLAGS_gen_breaker_threshold", 5,
              "serving.EngineSupervisor crash-storm circuit breaker: "
              "this many engine deaths inside "
              "FLAGS_gen_breaker_window_s opens the breaker — the "
              "supervisor stays down, /readyz reports 503 with the "
              "breaker reason, and pending work fails typed "
              "(audit code BREAKER_OPEN)")
register_flag("FLAGS_gen_breaker_window_s", 30.0,
              "rolling window the crash-storm breaker counts engine "
              "deaths over (see FLAGS_gen_breaker_threshold)")
register_flag("FLAGS_gen_poison_degrade_k", 0,
              "serving.GenerationEngine degraded mode: this many poison "
              "events (non-finite logits) inside "
              "FLAGS_gen_degraded_window_s flips speculative decoding "
              "OFF for the engine (audit code DEGRADED_SPEC_OFF; the "
              "plain decode program is pre-warmed so the flip mints no "
              "compile). 0 disables the detector; snapshotted at "
              "engine construction")
register_flag("FLAGS_gen_exhaust_clamp_k", 0,
              "serving.GenerationEngine degraded mode: this many "
              "page-blocked admission iterations inside "
              "FLAGS_gen_degraded_window_s clamps admission — new "
              "submits that cannot be covered by the pool RIGHT NOW "
              "fail fast with ResourceExhaustedError instead of "
              "queueing toward a timeout (audit code "
              "DEGRADED_ADMIT_CLAMP; clears on the next successful "
              "admission). 0 disables; snapshotted at construction")
register_flag("FLAGS_gen_degraded_window_s", 60.0,
              "rolling window both degraded-mode detectors "
              "(FLAGS_gen_poison_degrade_k / "
              "FLAGS_gen_exhaust_clamp_k) count events over")
register_flag("FLAGS_slo_ttft_p99_ms", 0.0,
              "SLO objective: generative time-to-first-token p99 target "
              "in ms — at most 1% of requests in a window may exceed it "
              "(profiler/slo.py burn rates, /slo, Prometheus gauges); "
              "0 disables the objective")
register_flag("FLAGS_slo_tpot_p99_ms", 0.0,
              "SLO objective: generative time-per-output-token p99 "
              "target in ms (same 1% budget semantics); 0 disables")
register_flag("FLAGS_slo_error_rate", 0.0,
              "SLO objective: max fraction of requests that may fail "
              "(timeout/poison/engine death) per rolling window; "
              "0 disables")
register_flag("FLAGS_slo_windows_s", "60,300",
              "comma-separated rolling-window lengths (seconds) the SLO "
              "burn rates are evaluated over — shortest window first "
              "(the fast-burn window readiness shedding keys on)")
register_flag("FLAGS_slo_max_burn_rate", 0.0,
              "fold SLO burn into /readyz: an engine reports not-ready "
              "while any objective's fast-window burn rate is >= this "
              "value, so the router sheds load BEFORE the error budget "
              "is gone (0 never sheds; 1.0 = shedding exactly at "
              "budget-burn speed)")
register_flag("FLAGS_router_replicas", 2,
              "default replica count for serving.Router when neither "
              "num_replicas nor prebuilt replicas are passed — each "
              "replica is an EngineSupervisor-wrapped GenerationEngine "
              "(serving/router.py)")
register_flag("FLAGS_router_affinity", True,
              "prefix-affinity placement (serving/router.py): steer a "
              "request to the replica whose sketch holds the longest "
              "blake2b chain over the prompt's leading full pages; "
              "False = pure round-robin over undrained replicas")
register_flag("FLAGS_router_sketch_digests", 8192,
              "per-replica LRU sketch capacity, in chain digests, the "
              "router's affinity placement matches against — bounds "
              "router memory at 16 bytes/digest per replica; oldest "
              "digests age out first (serving/router.py)")
register_flag("FLAGS_router_pressure_ttl_ms", 50.0,
              "max age of the router's cached per-replica pressure + "
              "health snapshot before a placement refreshes it — the "
              "poll cadence bound on GenerationEngine.pressure(); 0 "
              "refreshes every placement (serving/router.py)")
register_flag("FLAGS_train_step_donate", True,
              "donate the (params, buffers, opt_state) carry into the jitted "
              "train step so XLA updates parameters in place instead of "
              "allocating a second copy of the model state every step; "
              "disable for A/B numerics checks (hapi/model.py)")
register_flag("FLAGS_train_tail_bucketing", True,
              "Model.fit/evaluate/predict with drop_last=False: pad the "
              "partial tail batch up to the loader's batch size (rows "
              "replicated from the last real sample) with a row mask "
              "folded into the loss mean, so the tail reuses the "
              "full-batch executable instead of compiling one extra XLA "
              "program per tail shape. Requires a row-independent forward "
              "(the serving engine's contract; BatchNorm-style cross-row "
              "stats will see the padded rows) and a loss that is a "
              "mean/sum over rows (hapi/model.py falls back to the "
              "unpadded step otherwise)")
register_flag("FLAGS_serving_max_batch_size", 64,
              "serving.InferenceEngine: most request rows coalesced into "
              "one device batch (also the largest default shape bucket)")
register_flag("FLAGS_serving_max_batch_delay_ms", 2.0,
              "serving.InferenceEngine: how long the micro-batcher holds "
              "the first request of a batch open for co-riders before "
              "dispatching a partial batch")
register_flag("FLAGS_serving_batch_buckets", "1,4,16,64",
              "serving.InferenceEngine: comma-separated batch-size buckets "
              "a device batch is padded up to, so XLA compiles exactly one "
              "executable per bucket instead of one per observed batch size")
register_flag("FLAGS_serving_max_queue_depth", 256,
              "serving.InferenceEngine: pending-request bound; submits "
              "beyond it fail fast with EngineOverloaded (backpressure) "
              "instead of growing an unbounded queue")
register_flag("FLAGS_serving_max_inflight", 2,
              "serving.InferenceEngine: device batches a dispatch lane may "
              "have in flight (dispatched but not yet completed). 2 keeps "
              "the device fed while batch N computes (JAX async dispatch); "
              "1 disables pipelining (dispatch blocks until completion)")
register_flag("FLAGS_serving_devices", "",
              "serving.InferenceEngine default device set: '' = every "
              "local device for artifact-path/Config models (one dispatch "
              "lane + Predictor replica per chip), 'all', or a "
              "comma-separated list of local device INDICES ('0,2'); an "
              "integer lane COUNT is only meaningful as the devices= "
              "argument, not through this string flag")
register_flag("FLAGS_serving_request_timeout_ms", 30000.0,
              "serving.InferenceEngine: default per-request deadline, "
              "enforced while queued AND again at completion — a request "
              "that expired while its batch was on-device fails with "
              "ExecutionTimeoutError, never a late result (0 disables)")
register_flag("FLAGS_serving_lane_restarts", 0,
              "serving.InferenceEngine: how many CONSECUTIVE times a "
              "dead dispatch lane is rebuilt in place (fresh threads, "
              "same replica/device) with exponential backoff "
              "(FLAGS_gen_restart_backoff_ms base) before it stays "
              "permanently out of rotation; deaths separated by more "
              "than FLAGS_gen_breaker_window_s reset the budget and "
              "the backoff. 0 keeps the legacy behavior: lane death "
              "permanently shrinks capacity")
register_flag("FLAGS_trace_ring_size", 16384,
              "profiler.tracer: per-thread trace event ring capacity; the "
              "ring overwrites its oldest events instead of growing, so "
              "trace memory stays bounded under serving soak runs")
register_flag("FLAGS_flight_recorder", True,
              "always-on bounded crash context: RecordEvent scopes keep "
              "recording into the per-thread rings even with the profiler "
              "stopped, and the hardened failure paths (serving lane "
              "death, poisoned-batch retry, poisoned donated carry, "
              "DataLoader worker crash) dump a postmortem JSON artifact "
              "(profiler/flight_recorder.py)")
register_flag("FLAGS_flight_recorder_events", 512,
              "how many trailing trace events a flight-recorder dump "
              "includes (the tail of the merged per-thread rings)")
register_flag("FLAGS_flight_recorder_dir", "",
              "directory for flight-recorder dump files; '' = "
              "<tempdir>/paddle_tpu_flightrec")
register_flag("FLAGS_flight_recorder_interval_s", 2.0,
              "period of the flight recorder's background counter "
              "sampler (the periodic monitor snapshots that give a dump "
              "its recent-counters timeline); 0 disables the sampler")
register_flag("FLAGS_flight_recorder_max_dumps", 16,
              "most dump files kept per process; the oldest is pruned "
              "so a crash-looping failure path cannot fill the disk")
register_flag("FLAGS_serving_spans", True,
              "per-request latency attribution: submit() assigns a span "
              "that stamps every pipeline phase (queued/claimed/padded/"
              "dispatched/device_done/sliced/resolved), feeding the "
              "serving_queue_ms/pad_ms/device_ms/resolve_ms histograms, "
              "chrome-trace flow events linking submit to its lane's "
              "dispatch/complete scopes, and the engine.stats() phase "
              "breakdown; off removes the per-request accounting from "
              "the hot path (profiler/spans.py)")
register_flag("FLAGS_device_telemetry_interval_s", 5.0,
              "period of the lazy device-telemetry sampler "
              "(profiler/device_telemetry.py): per-device live HBM "
              "bytes, cumulative compile-ms ledger, estimated train-step "
              "FLOPs/MFU gauges — started by engines, Model.fit and the "
              "MetricsServer; 0 disables telemetry (the sampler idles "
              "and the per-compile cost-analysis retrace is skipped, so "
              "untelemetered training pays nothing; explicit sample() "
              "calls still refresh memory/compile gauges). Runtime "
              "set_flags toggling works in both directions")
register_flag("FLAGS_device_peak_flops", 0.0,
              "per-device peak FLOP/s used for the MFU gauge; 0 = look "
              "up the device kind in the built-in table (TPU v2-v5p "
              "bf16 peaks) — unknown kinds (CPU test hosts) simply "
              "don't export MFU")
register_flag("FLAGS_metrics_port", 0,
              "profiler.exporter.MetricsServer port: serve /metrics "
              "(Prometheus text), /stats (JSON incl. engine lanes) and "
              "/trace (chrome trace) on 127.0.0.1; 0 = off; engines "
              "also accept InferenceEngine(metrics_port=)")
register_flag("FLAGS_trace_propagation", True,
              "fleet-wide trace-context propagation "
              "(profiler/trace_context.py): the Router (or the engine, "
              "for direct submits) mints one 16-hex trace id per "
              "request; it rides placement audits (trace=), supervisor "
              "delegation and replay, per-incarnation GenSpans "
              "(',tid=' reqspan field) and streams, and is emitted as "
              "cross-process-stable 'fleet_request' chrome flow events "
              "that tools/fleet_trace.py links across N replicas' "
              "/trace exports; off = no ids minted, zero per-request "
              "cost")
register_flag("FLAGS_metrics_history_interval_s", 5.0,
              "period of the lazy time-series sampler "
              "(profiler/timeseries.py): every registered monitor "
              "counter (as a rate/s) and gauge (as a level) plus "
              "per-engine pressure() ticks recorded into bounded "
              "per-name rings, served as /history JSON and chrome 'C' "
              "counter tracks; 0 disables sampling (the thread idles; "
              "runtime set_flags toggling works in both directions)")
register_flag("FLAGS_metrics_history_samples", 512,
              "max samples kept per series by the time-series sampler; "
              "bounds /history memory no matter how long the process "
              "runs (ring semantics: oldest samples drop first)")


def set_flags(flags: Dict[str, Any]) -> None:
    from .errors import NotFoundError
    for k, v in flags.items():
        if k not in _FLAGS:
            raise NotFoundError(f"Unknown flag {k!r}")
        _FLAGS[k] = v


def get_flags(names: Iterable[str] | str) -> Dict[str, Any]:
    if isinstance(names, str):
        names = [names]
    return {n: _FLAGS[n] for n in names}


def flag(name: str) -> Any:
    return _FLAGS[name]
