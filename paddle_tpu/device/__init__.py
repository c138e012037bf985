"""paddle.device namespace (reference `python/paddle/device.py`).

Also owns the one decision about JAX's persistent compilation cache,
made once here at import by a config update that initialises no backend
— so the trainer, both serving engines and every script are covered
alike:

- `JAX_COMPILATION_CACHE_DIR` set: the cache is placed from outside. JAX
  reads the variable itself and nothing in this repo sets a directory.
- unset: the cache goes to `COMPILE_CACHE_DIR`, one fixed git-ignored
  directory inside the checkout. The path is part of every cache key, so
  it never moves: no `~`, no tempfile, no pid, no time.

Every compile is cached, whatever its size or compile time (JAX's
defaults skip programs that compile in under a second, which makes the
set of entries depend on how fast the machine happened to be). Re-verified
on jax 0.9.0 on the CPU backend too: a donated dp-sharded train step and
the donated `Model.train_batch` step give identical losses from a cold
and a warm cache, so the cache is no longer refused there.

This cache is the system's one warm start: a process that finds its
programs there compiles nothing anew.
"""
import os as _os

import jax as _jax

from ..framework.place import (CPUPlace, CUDAPlace, TPUPlace, device_count,
                               get_device, is_compiled_with_cuda,
                               is_compiled_with_tpu, set_device)

__all__ = ["set_device", "get_device", "CPUPlace", "CUDAPlace", "TPUPlace",
           "device_count", "is_compiled_with_cuda", "is_compiled_with_tpu",
           "cuda", "COMPILE_CACHE_DIR", "compilation_cache_dir"]

# <checkout>/.jax_cache — derived from this package's location only
COMPILE_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__)))), ".jax_cache")


def _place_compilation_cache():
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


_place_compilation_cache()


def compilation_cache_dir():
    """Directory of JAX's persistent compile cache in this process."""
    return _jax.config.jax_compilation_cache_dir


def layout_name(fmt, shape, dtype) -> str:
    """How a `jax.experimental.layout.Format` lays `shape` out on its
    device: `default` where it is the backend's own default for that
    shape, else the compiler's `major_to_minor` and tiling, as reported.
    Read from the format; a tiling is never written down here."""
    from jax.experimental.layout import Layout
    layout, sharding = fmt.layout, fmt.sharding
    if layout is None:
        return "default"
    dev = min(sharding.device_set, key=lambda d: d.id)
    default = Layout.from_pjrt_layout(dev.client.get_default_layout(
        _jax.numpy.dtype(dtype), sharding.shard_shape(tuple(shape)), dev))
    if layout == default:
        return "default"
    tiles = "".join(f"T{tuple(t)}" for t in layout.tiling or ())
    return f"{layout.major_to_minor}:{tiles}".replace(" ", "")


def array_layout(arr):
    """(`layout_name`, device bytes over all its shards) of an array as
    the device holds it: a padded tiling counts in the bytes."""
    return (layout_name(arr.format, arr.shape, arr.dtype),
            sum(int(s.data.on_device_size_in_bytes())
                for s in arr.addressable_shards))


class cuda:
    """Parity shim: paddle.device.cuda.* maps to the accelerator."""

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def synchronize(device=None):
        import jax
        # XLA dataflow orders everything; an explicit fence:
        jax.effects_barrier() if hasattr(jax, "effects_barrier") else None

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def max_memory_allocated(device=None):
        import jax
        try:
            stats = jax.local_devices()[0].memory_stats()
            return stats.get("peak_bytes_in_use", 0)
        except Exception:
            return 0

    @staticmethod
    def memory_allocated(device=None):
        import jax
        try:
            stats = jax.local_devices()[0].memory_stats()
            return stats.get("bytes_in_use", 0)
        except Exception:
            return 0
