"""Legacy (fluid-era) API surface: reduce_*/elementwise_* aliases,
fill_constant, tensor arrays, LoDTensor shim, inplace ops, default dtype.

Reference: `python/paddle/fluid/layers/tensor.py`, `layers/nn.py`,
`python/paddle/tensor/__init__.py` (top-level re-exports).
"""
import numpy as np
import pytest

import paddle_tpu as paddle


def test_reduce_and_elementwise_aliases():
    x = paddle.to_tensor(np.arange(12, dtype=np.float32).reshape(3, 4))
    assert paddle.reduce_sum(x).item() == 66
    assert paddle.reduce_mean(x, dim=0).shape == [4]
    assert paddle.reduce_max(x, dim=1, keep_dim=True).shape == [3, 1]
    np.testing.assert_allclose(
        paddle.elementwise_add(x, x).numpy(), x.numpy() * 2)
    np.testing.assert_allclose(
        paddle.elementwise_pow(x, paddle.to_tensor(2.0)).numpy(),
        x.numpy() ** 2)
    np.testing.assert_allclose(
        paddle.elementwise_floordiv(
            paddle.to_tensor(np.array([7, 8])),
            paddle.to_tensor(np.array([2, 3]))).numpy(), [3, 2])
    # fluid-style mid-rank axis broadcast
    a = paddle.to_tensor(np.ones((2, 3, 4), np.float32))
    b = paddle.to_tensor(np.ones((3,), np.float32))
    assert paddle.elementwise_add(a, b, axis=1).shape == [2, 3, 4]


def test_fill_constant_and_misc():
    t = paddle.fill_constant([2, 3], "float32", 1.5)
    assert t.numpy().sum() == 9.0
    assert paddle.add_n([t, t]).numpy().sum() == 18.0
    assert paddle.broadcast_shape([2, 1, 3], [4, 3]) == [2, 4, 3]
    x = paddle.to_tensor(np.arange(12, dtype=np.float32).reshape(3, 4))
    assert list(paddle.shape(x).numpy()) == [3, 4]
    assert paddle.rank(x).item() == 2
    assert not paddle.has_nan(x).item()
    assert not paddle.has_inf(x).item()
    assert paddle.has_nan(paddle.to_tensor(np.array([np.nan]))).item()
    np.testing.assert_allclose(
        paddle.crop_tensor(x, shape=[2, 2], offsets=[1, 1]).numpy(),
        [[5, 6], [9, 10]])
    np.testing.assert_allclose(
        paddle.reverse(x, axis=0).numpy(), x.numpy()[::-1])
    sn = paddle.scatter_nd(paddle.to_tensor(np.array([[0], [2]])),
                           paddle.to_tensor(np.ones((2, 4), np.float32)),
                           [3, 4])
    assert sn.numpy().sum() == 8


def test_tensor_array():
    x = paddle.to_tensor(np.ones((3, 4), np.float32))
    arr = paddle.create_array()
    paddle.tensor.array_write(x, 0, arr) if hasattr(paddle, 'tensor') else None
    arr = paddle.create_array()
    from paddle_tpu.ops.legacy import array_length, array_read, array_write
    array_write(x, 0, arr)
    array_write(x * 2, 1, arr)
    assert array_length(arr).item() == 2
    np.testing.assert_allclose(array_read(arr, 1).numpy(), 2 * x.numpy())
    out, sizes = paddle.tensor_array_to_tensor(arr, axis=0)
    assert out.shape == [6, 4]


def test_lod_tensor_shim():
    lt = paddle.LoDTensor(np.zeros((3, 2), np.float32), lod=[[0, 1, 3]])
    assert lt.recursive_sequence_lengths() == [[1, 2]]
    lt.set_lod([[0, 3]])
    assert lt.lod() == [[0, 3]]


def test_inplace_ops():
    z = paddle.to_tensor(np.ones((2, 3), np.float32))
    r = paddle.reshape_(z, [3, 2])
    assert r is z and z.shape == [3, 2]
    y = paddle.to_tensor(np.array([0.5], np.float32))
    paddle.tanh_(y)
    np.testing.assert_allclose(y.numpy(), np.tanh(0.5), rtol=1e-5)
    w = paddle.to_tensor(np.ones((4,), np.float32))
    w.zero_()
    assert w.numpy().sum() == 0
    w.fill_(7.0)
    assert w.numpy().sum() == 28


def test_default_dtype():
    paddle.set_default_dtype("bfloat16")
    try:
        assert paddle.get_default_dtype() == "bfloat16"
        t = paddle.ones([2, 2])
        assert t.dtype == paddle.bfloat16
    finally:
        paddle.set_default_dtype("float32")
    with pytest.raises(TypeError):
        paddle.set_default_dtype("int32")


def test_rng_state_roundtrip():
    paddle.seed(7)
    st = paddle.get_cuda_rng_state()
    a = paddle.rand([4]).numpy()
    paddle.set_cuda_rng_state(st)
    b = paddle.rand([4]).numpy()
    np.testing.assert_allclose(a, b)


def test_places_and_misc_shims():
    assert repr(paddle.CUDAPinnedPlace()) == "CUDAPinnedPlace"
    with pytest.raises(RuntimeError, match="no tpu/gpu device"):
        paddle.XPUPlace(0).device()  # an accelerator place is never the CPU
    assert paddle.get_cudnn_version() is None
    assert not paddle.is_compiled_with_xpu()
    assert paddle.VarBase is paddle.Tensor
    paddle.monkey_patch_math_varbase()
    paddle.monkey_patch_variable()
    assert paddle.in_dygraph_mode()
    p = paddle.create_parameter([3, 2], "float32")
    assert p.shape == [3, 2]
    g = paddle.create_global_var([2], 1.0, "float32", persistable=True)
    assert g.persistable


def test_print_op_prints_traced_values_at_run_time(capsys):
    """Under jit the Print op prints VALUES through a host callback, once
    per execution, and first_n counts those prints."""
    import jax

    @jax.jit
    def f(v):
        return paddle.Print(paddle.Tensor(v) * 2, message="traced",
                            first_n=2)._value

    for i in range(3):
        f(np.full((2,), float(i), "float32")).block_until_ready()
    jax.effects_barrier()
    out = capsys.readouterr().out.strip().splitlines()
    assert out == ["traced shape=(2,) dtype=float32 value=[0. 0.]",
                   "traced shape=(2,) dtype=float32 value=[2. 2.]"]
