"""Device telemetry: live HBM, compile-time ledger, FLOPs/MFU gauges.

The PR 5 surface measured the HOST (thread scopes, request counters);
the device itself stayed invisible — an operator could not answer "how
full is HBM", "how much wall time has gone to XLA compiles on chip 3",
or "what MFU is the train step achieving" without attaching a profiler.
This module closes that with a lazy periodic sampler (same lifecycle as
the flight recorder's counter sampler: `touch()`d by long-running
subsystems — engines, `Model.fit`, the `MetricsServer` — so a process
that never serves or trains never pays for the thread):

- **live HBM** — `jax` per-device `memory_stats()` →
  `STAT_device<id>_hbm_bytes_in_use` / `_hbm_bytes_limit` gauges; a
  graceful no-op on backends that return nothing (CPU test hosts).
- **compile-seconds ledger** — the serving lanes' exact per-replica
  compile counters already detect WHEN a (device, bucket) pair
  compiles; `note_compile()` adds the measured dispatch wall of that
  call to a cumulative per-(device, bucket) ledger, exported as
  `STAT_compile_ms_<key>` counters plus the full ledger in
  `snapshot()` → `/stats`.
- **FLOPs / MFU** — `hapi.Model` / the sharded pjit step call
  `note_train_step_lowering()` once per newly-compiled step; an XLA
  HLO cost analysis on the *lowered* module (no second backend
  compile) yields per-step FLOPs (`STAT_train_step_flops`). The
  sampler turns the `STAT_train_steps` delta per wall interval into
  achieved FLOP/s and divides by the device-kind peak (table below, or
  `FLAGS_device_peak_flops`) × participating devices →
  `STAT_train_mfu_bp` (basis points, i.e. 100·percent). Unknown device
  kinds simply don't export MFU.

All values live in the ordinary monitor registry, so they render as
Prometheus gauges in `/metrics` AND as "C" counter tracks in the chrome
trace via the existing `sample_counters()` path — no new export plumbing.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

from ..framework import monitor
from ..framework.flags import flag

__all__ = ["touch", "active", "sample", "note_compile",
           "note_train_step_lowering", "snapshot", "peak_flops"]

# bf16 peak FLOP/s per chip by device kind substring (public TPU specs);
# checked in order, first hit wins
_PEAK_TABLE = (
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

_lock = threading.Lock()
_sampler = [None]             # lazy daemon thread, one per process
_compile_ledger = {}          # (device_key, bucket) -> cumulative seconds
_flops_per_step = [0.0]       # from the last cost-analyzed train step
_train_devices = [1]          # devices participating in that step
_mfu_prev = [None]            # (t, STAT_train_steps) at the last window
# shortest steps/sec measurement window: every sample() caller (the
# periodic thread AND each /metrics scrape) shares one anchor under
# _lock, and the anchor only advances once a window this long has
# elapsed — a scrape landing 40ms after a sampler tick must not measure
# 1 step over 40ms and report a 5x MFU spike
_MIN_MFU_WINDOW_S = 0.5


def active() -> bool:
    """True while telemetry is wanted AND enabled: some subsystem has
    touch()ed the sampler and the interval flag is currently positive.
    The cost-analysis hooks check this, so flipping the flag to 0 at
    runtime stops both the sampling and the per-compile step retrace —
    and flipping it back on revives them (the sampler thread re-reads
    the flag every tick)."""
    return (_sampler[0] is not None
            and float(flag("FLAGS_device_telemetry_interval_s")) > 0)


def touch() -> None:
    """Start the sampler thread (idempotent, lazy; same contract as
    flight_recorder.touch). The thread starts even while the interval
    flag is 0 — it idles cheaply and honors a later runtime
    set_flags(interval>0), instead of being permanently unenableable
    because the flag happened to be 0 at touch() time."""
    with _lock:
        if _sampler[0] is None:
            t = threading.Thread(target=_sampler_loop, daemon=True,
                                 name="paddle_tpu-device-telemetry")
            _sampler[0] = t
            t.start()


def _sampler_loop():
    while True:
        iv = float(flag("FLAGS_device_telemetry_interval_s"))
        time.sleep(max(iv, 0.5) if iv > 0 else 5.0)
        if iv > 0:
            try:
                sample()
            except Exception:
                pass


def peak_flops(device) -> float:
    """Peak FLOP/s for one device: the flag override when set, else the
    device-kind table; 0.0 = unknown (no MFU gauge)."""
    override = float(flag("FLAGS_device_peak_flops"))
    if override > 0:
        return override
    kind = str(getattr(device, "device_kind", "")).lower()
    for sub, peak in _PEAK_TABLE:
        if sub in kind:
            return peak
    return 0.0


def sample() -> dict:
    """Take one telemetry sample, set the gauges, and return it (also
    called at `/metrics` scrape time so dashboards never read a stale
    interval-old value)."""
    out = {"devices": {}, "mfu_bp": None, "flops_per_step":
           int(_flops_per_step[0])}
    try:
        import jax
        devices = jax.local_devices()
    except Exception:
        devices = []
    peak_total = 0.0
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # backend without memory introspection
            stats = None
        if stats:
            in_use = int(stats.get("bytes_in_use", 0))
            monitor.stat_set(f"STAT_device{d.id}_hbm_bytes_in_use", in_use)
            dev = {"hbm_bytes_in_use": in_use}
            limit = stats.get("bytes_limit") or stats.get(
                "bytes_reservable_limit")
            if limit:
                monitor.stat_set(f"STAT_device{d.id}_hbm_bytes_limit",
                                 int(limit))
                dev["hbm_bytes_limit"] = int(limit)
            out["devices"][str(d.id)] = dev
        peak_total += peak_flops(d)
    # MFU: achieved train FLOP/s over the measurement window vs peak of
    # the devices the step actually runs on. One anchor shared by every
    # caller, advanced under the lock and only after a minimum window —
    # concurrent scrapes can neither double-attribute a step delta nor
    # measure over an arbitrarily tiny interval.
    steps = monitor.stat_get("STAT_train_steps")
    now = time.perf_counter()
    flops = _flops_per_step[0]
    if flops > 0:
        monitor.stat_set("STAT_train_step_flops", int(flops))
    window = None
    with _lock:
        prev = _mfu_prev[0]
        if prev is None:
            _mfu_prev[0] = (now, steps)
        elif now - prev[0] >= _MIN_MFU_WINDOW_S:
            _mfu_prev[0] = (now, steps)
            window = (now - prev[0], steps - prev[1])
    if flops > 0 and window is not None:
        n_dev = max(1, int(_train_devices[0]))
        per_dev = peak_total / max(len(devices), 1) if devices else 0.0
        peak = per_dev * n_dev
        if peak > 0:
            dt, dsteps = window
            # dsteps == 0 decays the gauge to 0: an idle trainer reads
            # as idle, not as its last busy window forever
            mfu = (flops * max(0, dsteps) / dt) / peak
            out["mfu_bp"] = int(round(mfu * 10000))
            monitor.stat_set("STAT_train_mfu_bp", out["mfu_bp"])
    return out


def note_compile(device_key, bucket, seconds: float) -> None:
    """Add one observed XLA compile's wall seconds to the cumulative
    (device, bucket) ledger. Called by serving lanes when their exact
    per-replica compile counters detect a trace — the measured dispatch
    wall of that call is compile-dominated."""
    key = (str(device_key), bucket)
    with _lock:
        _compile_ledger[key] = _compile_ledger.get(key, 0.0) + seconds
    monitor.stat_add(f"STAT_compile_ms_{device_key}",
                     int(round(seconds * 1000)))


def note_train_step_lowering(jitted, args, n_devices: int = 1) -> None:
    """Estimate per-step FLOPs for a freshly-compiled train step via HLO
    cost analysis on the lowered (NOT re-compiled) module. No-op unless
    the sampler is active — tracing the step a second time is cheap but
    not free, and a process that never asked for telemetry shouldn't
    pay it. Never raises (telemetry must not break training)."""
    if not active():
        return
    try:
        ca = jitted.lower(*args).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0)) if ca else 0.0
        if flops > 0:
            _flops_per_step[0] = flops
            _train_devices[0] = max(1, int(n_devices))
            monitor.stat_set("STAT_train_step_flops", int(flops))
    except Exception:
        pass


def snapshot() -> dict:
    """The `/stats` section: compile ledger per (device, bucket), FLOPs
    and device count of the last analyzed step, sampler state."""
    with _lock:
        ledger = {f"{dev}/b{bkt}": round(s, 6)
                  for (dev, bkt), s in sorted(_compile_ledger.items())}
    return {"compile_seconds": ledger,
            "flops_per_step": int(_flops_per_step[0]),
            "train_devices": int(_train_devices[0]),
            "sampler_active": active(),
            "interval_s": float(flag("FLAGS_device_telemetry_interval_s"))}
