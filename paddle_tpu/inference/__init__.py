"""Inference stack (reference `paddle/fluid/inference/`:
AnalysisPredictor:82, AnalysisConfig, zero-copy tensors, pass pipeline).

TPU-native: the serving artifact is the StableHLO export written by
`jit.save` (.pdmodel) + weights (.pdiparams). "Analysis passes" (fusion,
memory optimize) are XLA's job at artifact-compile time; the predictor
deserializes once, compiles once per shape, and runs zero-copy on device
buffers. API mirrors `paddle.inference`: Config / create_predictor /
get_input_handle / run / get_output_handle.
"""
from __future__ import annotations

import contextlib
import os
import weakref
from typing import Dict, List, Optional

import numpy as np

__all__ = ["Config", "create_predictor", "Predictor", "PredictorTensor",
           "AnalysisConfig", "Analyzer", "Argument",
           "compile_subgraph_engine", "format_input_sig", "check_fed_input",
           "as_device", "resolve_devices"]

from .analysis import Analyzer, Argument, compile_subgraph_engine  # noqa: E402

# STAT_quant_weight_hbm_bytes gauges device-resident quantized-weight
# bytes across LIVE predictor replicas: each replica gauge_add()s its
# integer tensors on load and subtracts them when it is collected
# (weakref.finalize — Predictor has no explicit close; CPython refcount
# collection makes this prompt in practice), so the gauge tracks actual
# residency instead of growing monotonically across engine restarts.
def _note_quant_bytes(delta: int) -> None:
    from ..framework import monitor
    monitor.stat_gauge_add("STAT_quant_weight_hbm_bytes", delta)


def _same_buffer(a, b) -> bool:
    """Do two jax Arrays share one device buffer? (device_put onto the
    buffer's current device aliases instead of copying — distinct Array
    objects, same memory.)"""
    if a is b:
        return True
    try:
        return a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()
    except Exception:  # backends without buffer introspection
        return False


class Config:
    """reference `api/paddle_analysis_config.h`."""

    def __init__(self, prog_file: Optional[str] = None,
                 params_file: Optional[str] = None):
        self._use_accel = True
        self._threads = 1
        self._enable_profile = False
        self._memory_pool_mb = 0
        self.set_model(prog_file, params_file)

    def set_model(self, prog_file, params_file=None):
        """Update only the model/params paths. (Historically this re-ran
        __init__, silently resetting user-set options like `_threads`,
        `_enable_profile` and `_memory_pool_mb` — reference
        AnalysisConfig::SetModel only touches the paths.)"""
        if prog_file and prog_file.endswith(".pdmodel"):
            prog_file = prog_file[:-len(".pdmodel")]
        self.model_path = prog_file
        self.params_file = params_file

    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._use_accel = True
        self._memory_pool_mb = memory_pool_init_size_mb

    def enable_use_tpu(self, device_id=0):
        self._use_accel = True

    def disable_gpu(self):
        self._use_accel = False

    def use_gpu(self):
        return self._use_accel

    def set_cpu_math_library_num_threads(self, n):
        self._threads = n

    def enable_profile(self):
        self._enable_profile = True

    def switch_ir_optim(self, flag=True):
        pass  # XLA always optimizes

    def enable_memory_optim(self):
        pass

    def enable_tensorrt_engine(self, *a, **k):
        import warnings
        warnings.warn("TensorRT does not exist on TPU; the XLA-compiled "
                      "artifact is already the fused engine")

    def summary(self):
        return f"Config(model={self.model_path}, accel={self._use_accel})"


AnalysisConfig = Config


class PredictorTensor:
    """Zero-copy handle (reference zero-copy PaddleTensor)."""

    def __init__(self, name):
        self.name = name
        self._value = None

    def reshape(self, shape):
        pass

    def copy_from_cpu(self, arr: np.ndarray):
        import jax.numpy as jnp
        self._value = jnp.asarray(arr)

    def copy_to_cpu(self) -> np.ndarray:
        return np.asarray(self._value)

    def shape(self):
        return list(self._value.shape) if self._value is not None else []


def format_input_sig(name, dims, dtype):
    """'name: dtype[b,8]' rendering of one saved-signature entry (symbolic
    dims print as 'b')."""
    if dims is None:
        return str(name)
    ds = ",".join("b" if d is None else str(d) for d in dims)
    return f"{name}: {np.dtype(dtype).name if dtype is not None else '?'}[{ds}]"


def check_fed_input(arr, name, dims, dtype, *, skip_batch_dim=False,
                    ctx="Predictor.run", expect=""):
    """Shared rank/dim/dtype check for one fed array — used by both
    Predictor.run and serving.InferenceEngine.submit so validation and
    error wording never drift apart. Returns the array (same-kind-cast to
    the saved dtype when needed) or raises a ValueError naming the
    expected signature."""
    note = f"; model signature is [{expect}]" if expect else ""
    if dims is not None:
        if arr.ndim != len(dims):
            raise ValueError(
                f"{ctx}: input {name!r} expects rank {len(dims)} "
                f"({format_input_sig(name, dims, dtype)}) but got rank "
                f"{arr.ndim} with shape {tuple(arr.shape)}{note}")
        for axis, (want, got) in enumerate(zip(dims, arr.shape)):
            if skip_batch_dim and axis == 0:
                continue
            if want is not None and int(want) != int(got):
                raise ValueError(
                    f"{ctx}: input {name!r} dim {axis} must be {want} but "
                    f"got {got} (shape {tuple(arr.shape)}; expected "
                    f"{format_input_sig(name, dims, dtype)}){note}")
    if dtype is not None and np.dtype(arr.dtype) != np.dtype(dtype):
        if not np.can_cast(arr.dtype, dtype, casting="same_kind"):
            raise ValueError(
                f"{ctx}: input {name!r} expects dtype "
                f"{np.dtype(dtype).name} but got {np.dtype(arr.dtype).name}"
                f" (not safely castable){note}")
        arr = np.asarray(arr, dtype=dtype)
    return arr


def as_device(dev):
    """Canonicalize one device spec: an int is an index into
    `jax.local_devices()`; a jax Device passes through."""
    if isinstance(dev, (int, np.integer)):
        import jax
        local = jax.local_devices()
        if not 0 <= int(dev) < len(local):
            raise ValueError(f"device index {dev} out of range; host has "
                             f"{len(local)} local device(s)")
        return local[int(dev)]
    return dev


def resolve_devices(devices):
    """Expand a device-set spec into a list of jax Devices. Accepts
    'all' (every local device), an int count (first N local devices), a
    comma-separated index string ('0,2'), or a sequence of indices /
    Devices. The serving engine builds one Predictor replica (and one
    dispatch lane) per entry."""
    import jax
    local = jax.local_devices()
    if isinstance(devices, str):
        if devices.strip().lower() == "all":
            return list(local)
        devices = [int(x) for x in devices.split(",") if x.strip()]
    elif isinstance(devices, (int, np.integer)):
        n = int(devices)
        if not 1 <= n <= len(local):
            raise ValueError(f"asked for {n} serving device(s) but host "
                             f"has {len(local)}")
        return list(local[:n])
    out = [as_device(d) for d in devices]
    if not out:
        raise ValueError("empty device list")
    return out


class Predictor:
    def __init__(self, config: Config, device=None):
        import jax
        from .. import jit
        self._config = config
        self._device = as_device(device) if device is not None else None
        self._legacy = None
        if config.model_path is None:
            raise ValueError("Config has no model path")
        try:
            self._translated = jit.load(config.model_path)
            self._quant = self._translated._quant
            self._qargs = self._load_quant_args()
            nin = len(self._translated._exported.in_avals) \
                - len(self._qargs)
            self._input_names = [f"input_{i}" for i in range(nin)]
        except Exception as stablehlo_err:
            # not our StableHLO artifact — try the reference ProgramDesc
            # format (.pdmodel + combined .pdiparams; pd_import.py)
            from .pd_import import load_legacy_inference_model
            model_file = config.model_path + ".pdmodel"
            if not os.path.exists(model_file):
                raise
            params_file = config.params_file
            if params_file is None:
                cand = config.model_path + ".pdiparams"
                params_file = cand if os.path.exists(cand) else None
            try:
                self._legacy = load_legacy_inference_model(model_file,
                                                           params_file)
            except Exception as legacy_err:
                raise RuntimeError(
                    f"{model_file} is neither a loadable StableHLO "
                    f"artifact ({stablehlo_err!r}) nor a parseable "
                    f"reference ProgramDesc ({legacy_err!r})"
                ) from legacy_err
            self._translated = None
            self._quant = None
            self._qargs = []
            self._input_names = list(self._legacy.feed_names)
        self._inputs: Dict[str, PredictorTensor] = {}
        self._outputs: List[PredictorTensor] = []
        for n in self._input_names:
            self._inputs[n] = PredictorTensor(n)
        self._jit_call = None
        self._sig = None
        self._sig_str = ""
        import threading
        self._jit_lock = threading.Lock()
        # exact per-predictor XLA compile count (bumped at jit trace time;
        # Python side effects run once per trace = once per new signature)
        self.compile_count = 0

    @property
    def device(self):
        """The jax Device this predictor is pinned to (None = backend
        default). Pinning happens at dispatch via `jax.default_device`,
        so fed host arrays land — and the executable compiles — there."""
        return self._device

    # -- quantized artifacts ----------------------------------------------

    def _load_quant_args(self):
        """Device-resident integer weights for a quantized artifact: the
        .pdmeta manifest names the int8/packed-int4 tensors + scales the
        export expects as leading runtime arguments. They are uploaded
        ONCE per replica (to this predictor's device) and stay in
        integer form in HBM — the dequant is inside the compiled call,
        fused into the matmul, so no fp32 copy of any quantized weight
        ever materializes host- or device-side."""
        if not self._quant:
            return []
        import jax
        from ..framework import monitor
        qargs = [jax.device_put(v, self._device)
                 for v in self._translated._qargs]
        monitor.stat_add("STAT_quant_weights_loaded",
                         len(self._quant["entries"]))
        # gauge only buffers this replica's device_put actually CREATED:
        # a put onto the buffer's current device aliases it (same
        # underlying buffer, no new HBM), so a same-device replica adds
        # 0 and a cross-device replica adds its full copy — the base
        # materialization itself is accounted once by TranslatedLayer
        total = sum(int(a.nbytes) for a, v in
                    zip(qargs, self._translated._qargs)
                    if not _same_buffer(a, v))
        if total:
            _note_quant_bytes(total)
            # LIVE residency: subtract when this replica is collected
            # (its device buffers go with it)
            weakref.finalize(self, _note_quant_bytes, -total)
        return qargs

    def quant_info(self) -> Optional[dict]:
        """None for fp artifacts; else {bits histogram, device-resident
        integer bytes, tensor count} — surfaced by engine.stats()."""
        if not self._quant:
            return None
        bits = {}
        for e in self._quant["entries"]:
            bits[str(e["bits"])] = bits.get(str(e["bits"]), 0) + 1
        return {"weight_tensors": len(self._quant["entries"]),
                "bits": bits,
                "resident_bytes": sum(int(a.nbytes)
                                      for a in self._qargs)}

    def clone_for_device(self, device) -> "Predictor":
        """Replica on another device sharing the already-deserialized
        artifact (no disk re-load) but with its OWN cached jit wrapper,
        trace counter, and I/O handles. Serving lanes need one replica
        per device precisely because a `jax.jit` executable is per-device
        state: a fresh wrapper per replica keeps `compile_count` an exact
        per-(device, bucket) compile ledger."""
        import copy as _copy
        import threading
        p = _copy.copy(self)
        p._device = as_device(device) if device is not None else None
        p._inputs = {n: PredictorTensor(n) for n in self._input_names}
        p._outputs = []
        p._jit_call = None
        p._jit_lock = threading.Lock()
        p.compile_count = 0
        # integer weights are per-device state: each replica uploads its
        # own copy to its chip (same int8/int4 bytes, new residence)
        p._qargs = p._load_quant_args()
        return p

    def get_input_names(self):
        return list(self._input_names)

    def get_input_handle(self, name):
        return self._inputs[name]

    # -- saved signature ---------------------------------------------------

    def input_signature(self):
        """[(name, dims, dtype)] from the saved artifact; symbolic dims
        (shape-polymorphic exports) are None. Legacy ProgramDesc artifacts
        carry no aval info → dims/dtype are None. Immutable → built once
        (run() revalidates every request against it)."""
        if self._sig is not None:
            return self._sig
        if self._translated is None:
            sig = [(n, None, None) for n in self._input_names]
        else:
            sig = []
            # a quantized artifact's leading avals are its integer
            # weights + scales (fed by the predictor, not the caller)
            user_avals = self._translated._exported.in_avals[
                len(self._qargs):]
            for n, aval in zip(self._input_names, user_avals):
                dims = tuple(d if isinstance(d, int) else None
                             for d in aval.shape)
                sig.append((n, dims, np.dtype(aval.dtype)))
        self._sig = sig
        self._sig_str = ", ".join(format_input_sig(*s) for s in sig)
        return sig

    def _validate_feed(self, arrays):
        """Check fed arrays against the saved signature; raise a ValueError
        naming the expected inputs instead of failing deep inside JAX."""
        sig = self.input_signature()
        expect = self._sig_str
        if len(arrays) != len(sig):
            raise ValueError(
                f"Predictor.run: model expects {len(sig)} input(s) "
                f"[{expect}] but {len(arrays)} were fed")
        out = []
        for a, (name, dims, dtype) in zip(arrays, sig):
            if a is None:
                raise ValueError(
                    f"Predictor.run: input {name!r} was never fed "
                    f"(expected [{expect}]; use get_input_handle"
                    f"({name!r}).copy_from_cpu(...) or pass inputs=)")
            arr = np.asarray(a) if not hasattr(a, "dtype") else a
            out.append(check_fed_input(arr, name, dims, dtype,
                                       ctx="Predictor.run", expect=expect))
        return out

    # -- compiled zero-copy path ------------------------------------------

    def _get_jit_call(self):
        """One jax.jit wrapper around the deserialized executable, cached
        on the predictor: repeat runs (and every serving-engine dispatch)
        reuse the compiled-per-shape executable zero-copy instead of
        re-dispatching `exported.call` eagerly. The trace-time counter
        bump makes STAT_predictor_compiles an exact compile count (Python
        side effects run once per trace = once per new input signature)."""
        if self._jit_call is None:
            with self._jit_lock:  # concurrent first runs must not build
                if self._jit_call is not None:  # two wrappers (= two traces
                    return self._jit_call       # per shape, breaking the
                import jax                      # exact-compile-count contract)
                from ..framework import monitor
                exported = self._translated._exported

                def _call(*args):
                    monitor.stat_add("STAT_predictor_compiles")
                    self.compile_count += 1
                    return exported.call(*args)
                self._jit_call = jax.jit(_call)
        return self._jit_call

    def run_device(self, arrays):
        """Run on already-validated arrays; returns device-resident output
        leaves (no host round-trip, and no host sync — under JAX async
        dispatch the leaves are futures the caller blocks on). The serving
        engine's lane-dispatch hot path."""
        import jax
        ctx = (jax.default_device(self._device) if self._device is not None
               else contextlib.nullcontext())
        with ctx:
            if self._legacy is not None:
                out = self._legacy.run(dict(zip(self._input_names, arrays)))
            else:
                # quantized artifacts: the device-resident integer
                # weights ride every dispatch as leading jit ARGUMENTS —
                # being runtime inputs (not baked constants) is what
                # stops XLA from dequant-folding them to fp32 in HBM
                out = self._get_jit_call()(*self._qargs, *arrays)
        return jax.tree_util.tree_leaves(out)

    def run(self, inputs: Optional[List[np.ndarray]] = None):
        import jax
        if inputs is not None:
            # validate BEFORE touching the handles: a rejected call must
            # not leave half-fed state behind
            args = self._validate_feed([np.asarray(a) for a in inputs])
            # upload under the pin so the host array lands directly on
            # this predictor's device instead of hopping via the default
            ctx = (jax.default_device(self._device)
                   if self._device is not None else contextlib.nullcontext())
            with ctx:
                for n, a in zip(self._input_names, args):
                    self._inputs[n].copy_from_cpu(a)
            # compute from the device-resident handle values so the upload
            # copy_from_cpu just did is the only host→device transfer
            args = [self._inputs[n]._value for n in self._input_names]
        else:
            args = self._validate_feed(
                [self._inputs[n]._value for n in self._input_names])
        leaves = self.run_device(args)
        self._outputs = []
        for i, leaf in enumerate(leaves):
            t = PredictorTensor(f"output_{i}")
            t._value = leaf
            self._outputs.append(t)
        if inputs is not None:
            return [np.asarray(o._value) for o in self._outputs]
        return True

    def get_output_names(self):
        return [t.name for t in self._outputs] or ["output_0"]

    def get_output_handle(self, name):
        idx = int(name.rsplit("_", 1)[1])
        return self._outputs[idx]


def create_predictor(config: Config, device=None) -> Predictor:
    """Build a Predictor; `device` (jax Device or local index) pins its
    compilation and execution to one chip — `serving.InferenceEngine`
    passes a different device per dispatch lane."""
    return Predictor(config, device=device)
