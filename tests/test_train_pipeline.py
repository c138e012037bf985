"""Training hot-loop contract (ISSUE 1): donated carry train step, async
DeviceFeeder input staging, deferred host sync, and compile-count
regression guards.

These tests pin the perf-critical *semantics* that CPU CI can check:
numerics are unchanged by donation, batches arrive in order with the
double buffer engaged, the fit loop's host-sync budget is one sync per
`log_freq` interval, and each input-shape key compiles exactly once.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.framework.deferred import DeferredScalar
from paddle_tpu.framework.monitor import stat_get, stat_reset
from paddle_tpu.hapi.callbacks import Callback
from paddle_tpu.io import DataLoader, DeviceFeeder, TensorDataset


def _toy(n=128, dim=8, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.randn(classes, dim).astype("float32") * 3
    y = rng.randint(0, classes, n)
    x = (centers[y] + rng.randn(n, dim)).astype("float32")
    return x, y.astype("int64")


def _toy_model(dim=8, classes=3, lr=0.01):
    net = nn.Sequential(nn.Linear(dim, 16), nn.ReLU(),
                        nn.Linear(16, classes))
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.Adam(lr, parameters=net.parameters()),
                  nn.CrossEntropyLoss())
    # these tests pin the SINGLE-process hot loop; an earlier test in the
    # suite may have left fleet/mesh globals initialized, which would
    # reroute train_batch through the sharded step
    model._dist_ctx = None
    return model, net


@pytest.fixture
def donate_flag():
    """Restore FLAGS_train_step_donate after a test flips it."""
    prev = paddle.get_flags(["FLAGS_train_step_donate"])
    yield
    paddle.set_flags(prev)


# ---------------------------------------------------------------------------
# donation numerics
# ---------------------------------------------------------------------------

def _loss_trajectory(donate, steps=8, bs=8):
    paddle.set_flags({"FLAGS_train_step_donate": donate})
    paddle.seed(0)
    x, y = _toy()
    model, _ = _toy_model()
    losses = []
    for i in range(steps):
        lv, _ = model.train_batch([x[i * bs:(i + 1) * bs]],
                                  [y[i * bs:(i + 1) * bs]])
        losses.append(float(lv[0]))
    return losses


def test_donated_step_losses_bit_identical(donate_flag):
    """ISSUE acceptance: donation must not change numerics — the donated
    carry path produces the exact same loss trajectory as the pre-change
    (non-donated) path, bitwise, on the tier-1 toy model."""
    donated = _loss_trajectory(True)
    plain = _loss_trajectory(False)
    assert donated == plain
    assert all(np.isfinite(donated))


def test_donate_flag_flip_recompiles(donate_flag):
    """The donate setting is part of the jit-cache key: flipping the flag
    mid-run on a live Model must not silently reuse the donated step."""
    paddle.set_flags({"FLAGS_train_step_donate": True})
    paddle.seed(0)
    x, y = _toy(16)
    model, _ = _toy_model()
    stat_reset("STAT_train_step_compiles")
    model.train_batch([x], [y])
    assert stat_get("STAT_train_step_compiles") == 1
    paddle.set_flags({"FLAGS_train_step_donate": False})
    model.train_batch([x], [y])  # same shapes, different donation -> new key
    assert stat_get("STAT_train_step_compiles") == 2


def test_carry_written_back_after_fit():
    """Tensor._value write-back happens on epoch boundaries: after fit the
    network's Tensors hold fresh trained values and no carry is live."""
    paddle.seed(0)
    x, y = _toy(64)
    model, net = _toy_model()
    w0 = net[0].weight.numpy().copy()
    model.fit(TensorDataset([x, y]), batch_size=16, epochs=1, verbose=0)
    assert model._train_carry is None
    w1 = net[0].weight.numpy()
    assert np.isfinite(w1).all()
    assert not np.allclose(w0, w1)  # training actually moved the weights


def test_standalone_train_batch_writes_back():
    """Custom-loop contract: outside fit, every train_batch call flushes
    the carry, so direct Layer reads (net(x), state_dict) stay fresh."""
    paddle.seed(0)
    x, y = _toy(32)
    model, net = _toy_model()
    w0 = net[0].weight.numpy().copy()
    for i in range(3):
        model.train_batch([x[i * 8:(i + 1) * 8]], [y[i * 8:(i + 1) * 8]])
    assert model._train_carry is None  # flushed per call
    assert not np.allclose(net[0].weight.numpy(), w0)
    out = net(paddle.to_tensor(x[:4]))  # forward off the live Tensors
    assert np.isfinite(out.numpy()).all()


# ---------------------------------------------------------------------------
# DeviceFeeder
# ---------------------------------------------------------------------------

def test_device_feeder_order_and_overlap():
    """Batches come out in order with leaves committed as Tensors, and the
    background stage actually runs ahead (overlap counter > 0)."""
    batches = [np.full((4, 3), i, dtype="float32") for i in range(12)]
    stat_reset("STAT_device_feeder_batches")
    stat_reset("STAT_device_feeder_overlap")
    out = []
    for b in DeviceFeeder(batches):
        time.sleep(0.01)  # emulate a compute-bound consumer
        out.append(b)
    assert len(out) == 12
    for i, b in enumerate(out):
        np.testing.assert_array_equal(b.numpy(), batches[i])
    assert stat_get("STAT_device_feeder_batches") == 12
    # with a slow consumer the producer stays ahead: queue depth observed
    # > 0 on at least one hand-out proves the transfer overlapped compute
    assert stat_get("STAT_device_feeder_overlap") > 0


def test_device_feeder_wraps_dataloader_and_len():
    x, y = _toy(32)
    dl = DataLoader(TensorDataset([x, y]), batch_size=8)
    feed = DeviceFeeder(dl)
    assert len(feed) == len(dl) == 4
    seen = [b for b in feed]
    assert len(seen) == 4
    np.testing.assert_allclose(seen[0][0].numpy(), x[:8])
    # re-iterable: a second epoch replays from the start
    assert len(list(feed)) == 4


def test_device_feeder_propagates_source_errors():
    def gen():
        yield np.zeros((2, 2), dtype="float32")
        raise RuntimeError("source blew up")

    it = iter(DeviceFeeder(gen()))
    next(it)
    with pytest.raises(RuntimeError, match="source blew up"):
        next(it)


def test_device_feeder_rejects_bad_depth():
    with pytest.raises(ValueError):
        DeviceFeeder([], depth=0)


# ---------------------------------------------------------------------------
# deferred host sync
# ---------------------------------------------------------------------------

class _LossCapture(Callback):
    """Records the per-batch logged loss; optionally forces an immediate
    host sync (the pre-change per-step behavior)."""

    def __init__(self, eager):
        super().__init__()
        self.eager = eager
        self.raw = []

    def on_train_batch_end(self, step, logs=None):
        v = (logs or {}).get("loss")
        self.raw.append(float(v) if self.eager else v)

    def values(self):
        return [float(v) for v in self.raw]


def _fit_losses(eager, log_freq=4):
    paddle.seed(0)
    x, y = _toy(96)
    model, _ = _toy_model()
    cap = _LossCapture(eager)
    model.fit(TensorDataset([x, y]), batch_size=8, epochs=1,
              log_freq=log_freq, verbose=0, shuffle=False, callbacks=[cap])
    return cap.values()


def test_deferred_sync_matches_per_step_sync():
    """Materializing every step vs. only on the log cadence yields the
    same logged loss sequence — deferral changes when the host blocks,
    never what it reads."""
    assert _fit_losses(eager=True) == _fit_losses(eager=False)


def test_fit_sync_budget_one_per_log_freq():
    """ISSUE acceptance: Model.fit blocks on the host at most once per
    `log_freq` steps (plus the epoch-boundary flush), counted by the
    STAT_train_host_syncs monitor stat."""
    paddle.seed(0)
    x, y = _toy(128)
    model, _ = _toy_model()
    n_steps, log_freq = 16, 4
    stat_reset("STAT_train_host_syncs")
    model.fit(TensorDataset([x, y]), batch_size=8, epochs=1,
              log_freq=log_freq, verbose=0, shuffle=False)
    syncs = stat_get("STAT_train_host_syncs")
    assert 0 < syncs <= n_steps // log_freq + 1, syncs


def test_fit_zero_epochs_is_clean_noop():
    """epochs=0 must not crash on the trailing on_end (logs is bound
    before the epoch loop) and must leave the model untouched."""
    paddle.seed(0)
    x, y = _toy(16)
    model, net = _toy_model()
    w0 = net[0].weight.numpy().copy()
    model.fit(TensorDataset([x, y]), batch_size=8, epochs=0, verbose=0)
    np.testing.assert_array_equal(net[0].weight.numpy(), w0)
    assert model._train_carry is None


def test_train_batch_returns_deferred_scalar():
    paddle.seed(0)
    x, y = _toy(8)
    model, _ = _toy_model()
    lv, _ = model.train_batch([x], [y])
    assert isinstance(lv[0], DeferredScalar)
    stat_reset("STAT_train_host_syncs")
    assert (lv[0] == None) is False  # noqa: E711 — no sync, no TypeError
    assert stat_get("STAT_train_host_syncs") == 0
    f1 = float(lv[0])
    f2 = lv[0].item()  # cached: one handle costs at most one sync
    assert f1 == f2
    assert stat_get("STAT_train_host_syncs") == 1


# ---------------------------------------------------------------------------
# compile-count regression
# ---------------------------------------------------------------------------

def test_one_compile_per_input_shape_key():
    """`train_batch` compiles exactly once per input-shape/dtype key; a
    new batch geometry adds exactly one more compile."""
    paddle.seed(0)
    x, y = _toy(64)
    model, _ = _toy_model()
    stat_reset("STAT_train_step_compiles")
    for i in range(4):
        model.train_batch([x[i * 8:(i + 1) * 8]], [y[i * 8:(i + 1) * 8]])
    assert stat_get("STAT_train_step_compiles") == 1
    model.train_batch([x[:4]], [y[:4]])  # new batch size -> one new key
    assert stat_get("STAT_train_step_compiles") == 2
    model.train_batch([x[4:8]], [y[4:8]])  # seen key -> no recompile
    assert stat_get("STAT_train_step_compiles") == 2
    steps = stat_get("STAT_train_steps")
    assert steps >= 6  # every call above dispatched a step
