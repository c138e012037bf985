"""Deferred device-resident scalars (async-dispatch friendly loss handles).

JAX dispatch is asynchronous: a jitted train step returns immediately with
a device-resident future, and the Python thread only blocks when something
forces the value to the host (`float`, `np.asarray`, ...). The reference
hot loop called `float(loss)` every batch, turning every step into a
device->host round-trip barrier. `DeferredScalar` keeps the handle on
device so the fit loop can run ahead of the accelerator and only pay one
sync per `log_freq` steps (same overlap trick as jax.block_until_ready
placement in Bradbury et al.'s async dispatch model).

Every materialization bumps `STAT_train_host_syncs` so tests can assert the
sync budget of a training loop.
"""
from __future__ import annotations

import numpy as np

from .monitor import STAT_ADD

__all__ = ["DeferredScalar", "materialize_many"]


def materialize_many(values):
    """Host floats for a mixed sequence of DeferredScalar / array / number
    values using ONE device->host transfer for all lazy entries (stacked on
    device), instead of one round-trip per handle. Counts a single
    STAT_train_host_syncs. Entries that can't coerce to float (strings,
    None, ...) come back as None. Used by Model.evaluate and
    callbacks.VisualDL."""
    values = list(values)
    lazy = [i for i, v in enumerate(values)
            if isinstance(v, DeferredScalar) and v._host is None]
    out = [v._host if isinstance(v, DeferredScalar) else v for v in values]
    if lazy:
        import jax.numpy as jnp
        stacked = np.asarray(jnp.stack(
            [jnp.asarray(values[i]._dev, "float32") for i in lazy]))
        STAT_ADD("STAT_train_host_syncs")
        for i, f in zip(lazy, stacked):
            values[i]._host = out[i] = float(f)
            values[i]._dev = None
    res = []
    for v in out:
        if v is None or isinstance(v, float):
            res.append(v)
        else:
            try:
                res.append(float(v))
            except (TypeError, ValueError):
                res.append(None)
    return res


class DeferredScalar:
    """A lazy scalar: holds the device array until a host value is forced.

    `float()` / `item()` / `numpy()` / `__array__` block and cache the host
    value (counted in STAT_train_host_syncs once per handle); `.value`
    returns the raw device array without syncing so callers can batch many
    handles into a single transfer (e.g. `jnp.stack` in Model.evaluate).
    """

    __slots__ = ("_dev", "_host")

    def __init__(self, value):
        self._dev = value
        self._host = None

    @property
    def value(self):
        """Device array if not yet materialized, else the cached float."""
        return self._dev if self._host is None else self._host

    def _materialize(self) -> float:
        if self._host is None:
            STAT_ADD("STAT_train_host_syncs")
            self._host = float(np.asarray(self._dev))
            self._dev = None  # release the device handle
        return self._host

    # -- host coercions (each forces at most one sync; cached after) --------
    def __float__(self):
        return self._materialize()

    def __int__(self):
        return int(self._materialize())

    def __bool__(self):
        # float contract: a 0.0 loss must stay falsy (sync point)
        return bool(self._materialize())

    def item(self):
        return self._materialize()

    def numpy(self):
        return np.asarray(self._materialize(), dtype="float32")

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._materialize(), dtype=dtype)

    def __format__(self, spec):
        return format(self._materialize(), spec)

    def __repr__(self):
        if self._host is not None:
            return f"DeferredScalar({self._host!r})"
        return "DeferredScalar(<device>)"

    # arithmetic/comparison degrade to host floats (sync point)
    def __add__(self, other):
        return self._materialize() + other

    def __radd__(self, other):
        return other + self._materialize()

    def __sub__(self, other):
        return self._materialize() - other

    def __rsub__(self, other):
        return other - self._materialize()

    def __mul__(self, other):
        return self._materialize() * other

    def __rmul__(self, other):
        return other * self._materialize()

    def __truediv__(self, other):
        return self._materialize() / other

    def __rtruediv__(self, other):
        return other / self._materialize()

    def __pow__(self, other):
        return self._materialize() ** other

    def __rpow__(self, other):
        return other ** self._materialize()

    def __neg__(self):
        return -self._materialize()

    def __abs__(self):
        return abs(self._materialize())

    @staticmethod
    def _coerce(other):
        """float(other), or None for non-numeric operands so comparisons
        can return NotImplemented (e.g. `loss == None` in a callback must
        be False, not a TypeError)."""
        try:
            return float(other)
        except (TypeError, ValueError):
            return None

    def __eq__(self, other):
        f = self._coerce(other)
        return NotImplemented if f is None else self._materialize() == f

    def __lt__(self, other):
        f = self._coerce(other)
        return NotImplemented if f is None else self._materialize() < f

    def __le__(self, other):
        f = self._coerce(other)
        return NotImplemented if f is None else self._materialize() <= f

    def __gt__(self, other):
        f = self._coerce(other)
        return NotImplemented if f is None else self._materialize() > f

    def __ge__(self, other):
        f = self._coerce(other)
        return NotImplemented if f is None else self._materialize() >= f

    def __hash__(self):
        return hash(self._materialize())
