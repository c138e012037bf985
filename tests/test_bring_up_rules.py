"""The bring-up rules (ISSUE 21), as far as a CPU host can hold them:

- the device is never substituted: asking for an absent accelerator raises;
- kernel dispatch is a stated shape rule: no Pallas interpret mode without
  the flag, and the paged-attention predicate decides kernel vs reference;
- the compile cache is placed from outside, or at one fixed in-checkout path;
- one process per chip: the launchers refuse on a TPU host;
- `chip_smoke.py` refuses to run without a TPU.

What only the chip can show (that the admitted shapes COMPILE, that the main
path runs there) is `chip_smoke.py` and the `chip`-marked tests.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.flags import get_flags, set_flags
from paddle_tpu.framework.monitor import stat_get
from paddle_tpu.ops import paged_ops
from paddle_tpu.ops import pallas_ops as po

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_argv, env_extra=None, unset=()):
    env = {k: v for k, v in os.environ.items() if k not in unset}
    env.update(PYTHONPATH=REPO, JAX_PLATFORMS="cpu", **(env_extra or {}))
    argv = ([sys.executable, "-c", code_or_argv]
            if isinstance(code_or_argv, str) else code_or_argv)
    return subprocess.run(argv, env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=120)


# -- the device is never substituted ----------------------------------------

def test_absent_accelerator_raises():
    assert jax.default_backend() == "cpu"
    for place in (paddle.TPUPlace(), paddle.TPUPlace(3), paddle.CUDAPlace(0),
                  paddle.NPUPlace(0)):
        with pytest.raises(RuntimeError, match="no .* device on this host"):
            place.device()
    before = paddle.get_device()
    for name in ("tpu", "tpu:0", "gpu:1", "xpu"):
        with pytest.raises(RuntimeError, match="device on this host"):
            paddle.set_device(name)
    assert paddle.get_device() == before == "cpu"  # nothing was selected
    with pytest.raises(RuntimeError):
        paddle.to_tensor([1.0]).to("tpu")
    # the CPU says it is the CPU, and an out-of-range id is not clamped
    assert paddle.set_device("cpu").device().platform == "cpu"
    assert not paddle.is_compiled_with_tpu()


# -- kernel dispatch ---------------------------------------------------------

@pytest.fixture
def _no_interpret():
    old = get_flags(["FLAGS_flash_attention_interpret",
                     "FLAGS_flash_attention_min_seq"])
    set_flags({"FLAGS_flash_attention_interpret": False,
               "FLAGS_flash_attention_min_seq": 128})
    yield
    set_flags(old)


def test_no_interpret_mode_without_the_flag(_no_interpret):
    q = jnp.ones((1, 2, 128, 32), jnp.float32)
    bias, seed = jnp.zeros((1, 128), jnp.float32), jnp.zeros((), jnp.int32)
    # the raw kernel entries refuse off-TPU instead of interpreting
    with pytest.raises(RuntimeError, match="compile for TPU only"):
        po._interpret()
    with pytest.raises(RuntimeError, match="compile for TPU only"):
        po.flash_attention_raw(q, q, q, bias, seed, True, 1.0, 0.0)
    from paddle_tpu.ops import splash_ops
    seg = jnp.zeros((1, 128), jnp.int32)
    with pytest.raises(RuntimeError, match="compile for TPU only"):
        splash_ops.splash_attention_raw(q, q, q, seg, seg, seed, True, 1.0,
                                        0.0)
    # the public path takes the dense route: right answer, no kernel traced
    f0 = stat_get("STAT_flash_attention_fwd")
    s0 = stat_get("STAT_splash_attention_fwd")
    import paddle_tpu.nn.functional as F
    t = paddle.to_tensor(np.asarray(q))
    out = F.scaled_dot_product_attention(t, t, t, is_causal=True)
    F.scaled_dot_product_attention(t, t, t, is_causal=True,
                                   segment_ids=np.zeros((1, 128), "int32"))
    np.testing.assert_allclose(out.numpy(), np.ones_like(q), rtol=1e-6)
    assert stat_get("STAT_flash_attention_fwd") == f0
    assert stat_get("STAT_splash_attention_fwd") == s0
    # with the flag, and only then, the interpreter
    set_flags({"FLAGS_flash_attention_interpret": True})
    assert po._interpret() is True


def test_paged_attention_shape_rule():
    ok = paged_ops.paged_kernel_supported
    bf16 = jnp.bfloat16
    table = (8, 64)
    assert ok((8, 16, 128), (16, 512, 16, 128), table)
    assert ok((8, 16, 256), (16, 512, 32, 256), table)
    assert ok((8, 16, 128), (4, 512, 16, 128), table)     # grouped query
    assert ok((96, 20, 128), (4, 3456, 16, 128), (96, 96), bf16)  # falcon
    # GPT-2 small / the 768-wide serving model: head dim 64 -> reference
    assert not ok((8, 12, 64), (12, 512, 16, 64), table)
    # a page is whole sublane tiles of its dtype: 8 rows float32, 16 bf16
    assert ok((8, 16, 128), (16, 512, 8, 128), table)
    assert not ok((8, 16, 128), (16, 512, 8, 128), table, bf16)
    assert not ok((8, 16, 128), (16, 512, 12, 128), table)  # page size 12
    assert not ok((8, 16, 128), (3, 512, 16, 128), table)   # 16 % 3 heads
    assert not ok((8, 16, 128), (16, 512, 16, 128), table, jnp.int8)
    # the round is derived (1 MiB of a page of K plus V, a power of two)
    # and must divide the table: 32 KB pages give 32, not a divisor of 48
    assert ok((96, 20, 128), (4, 3456, 16, 128), (96, 32), bf16)
    assert not ok((96, 20, 128), (4, 3456, 16, 128), (96, 48), bf16)
    # every slot's queries and results in VMEM: 96 slots of 8 ungrouped
    # heads of 512 float32 would take 50 MB
    assert not ok((96, 8, 512), (8, 512, 16, 512), (96, 8))
    # off-TPU even an admitted shape takes the reference — counted, by rule
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.standard_normal((2, 4, 128)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((4, 9, 16, 128)), jnp.float32)
    tb = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    pos = jnp.asarray([17, 40], jnp.int32)
    assert ok(q.shape, kp.shape, tb.shape)
    k0, r0 = (stat_get("STAT_paged_attn_kernel"),
              stat_get("STAT_paged_attn_reference"))
    out = paged_ops.paged_attention(q, kp, kp, tb, pos, 0.1)
    assert out.shape == q.shape
    assert stat_get("STAT_paged_attn_kernel") == k0
    assert stat_get("STAT_paged_attn_reference") == r0 + 1


def test_paged_rule_matches_what_lowers_for_tpu(monkeypatch):
    """The rule against the repo's head-pool kernel, as far as a CPU host
    can see: exporting for `platforms=["tpu"]` runs the Pallas->Mosaic
    lowering (Mosaic's own compile, VMEM included, needs the chip). The
    admitted bfloat16 and float32 shapes, and a group of 5 (falcon's 20
    query heads over 4 K/V heads), lower through `paged_attention`'s kernel
    branch to ONE custom call named `head_decode_attention`, with whole
    pools and the layer as a scalar; head dim 64 is not admitted in that
    split form and lowers to no custom call. In the form the pools give
    64-wide heads, fused rows, it lowers to ONE custom call named
    `row_decode_attention`."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def args(H, Hkv, D, P, dtype):
        return (jax.ShapeDtypeStruct((8, H, D), dtype),
                jax.ShapeDtypeStruct((2, Hkv, 72, P, D), dtype),
                jax.ShapeDtypeStruct((2, Hkv, 72, P, D), dtype),
                jax.ShapeDtypeStruct((8, 8), jnp.int32),
                jax.ShapeDtypeStruct((8,), jnp.int32))

    def lowered(*shapes):
        return jax.export.export(
            jax.jit(lambda *a: paged_ops.paged_attention(*a, 0.1, layer=1)),
            platforms=["tpu"])(*shapes).mlir_module()

    k0 = stat_get("STAT_paged_attn_kernel")
    for H, Hkv, P, dtype in ((8, 8, 16, jnp.bfloat16), (8, 8, 8, jnp.float32),
                             (20, 4, 16, jnp.bfloat16)):
        a = args(H, Hkv, 128, P, dtype)
        assert paged_ops.paged_kernel_supported(
            a[0].shape, a[1].shape[1:], a[3].shape, dtype)
        text = lowered(*a)
        assert text.count("tpu_custom_call") == 1
        assert "head_decode_attention" in text
    assert stat_get("STAT_paged_attn_kernel") == k0 + 3
    a = args(8, 8, 64, 16, jnp.float32)
    assert not paged_ops.paged_kernel_supported(
        a[0].shape, a[1].shape[1:], a[3].shape, jnp.float32)
    assert "tpu_custom_call" not in lowered(*a)
    assert stat_get("STAT_paged_attn_kernel") == k0 + 3
    fused = jax.ShapeDtypeStruct((2, 72, 16, 512), jnp.float32)  # 8 x 64
    assert paged_ops.paged_row_kernel_supported(
        a[0].shape, (8, 72, 16, 64), a[3].shape, jnp.float32)
    text = lowered(a[0], fused, fused, a[3], a[4])
    assert text.count("tpu_custom_call") == 1
    assert "row_decode_attention" in text
    assert stat_get("STAT_paged_attn_kernel") == k0 + 4


def test_flash_gates_bound_the_vmem_resident_sequence():
    from paddle_tpu.ops.splash_ops import splash_supported
    for gate in (po.flash_supported, splash_supported):
        big = (1, 2, 8192, 128)       # one operand: 4 MiB in f32, 2 in bf16
        assert gate(big, min_seq=512, itemsize=2)
        assert not gate(big, min_seq=512, itemsize=4)
        assert not gate(big, min_seq=512)          # dtype unknown: assume 4
        assert gate((1, 2, 4096, 128), min_seq=512)
        assert gate((4, 12, 2048, 64), min_seq=512)
        # a narrow head is charged a full 128-lane row
        assert not gate((1, 2, 8192, 64), min_seq=512, itemsize=4)
        # wider than anything shown to compile at the bound
        assert not gate((1, 2, 1024, 256), min_seq=512, itemsize=2)


def test_flash_and_splash_lower_for_tpu(monkeypatch):
    """Mosaic lowering of the repo's own kernels (forward and the two
    backward kernels), seen from a CPU host by exporting for
    `platforms=["tpu"]`. Splash's per-cell (1, 1) SMEM bounds blocks did
    not lower on jax 0.9.0 and no CPU test could tell: interpret mode
    never checks block shapes."""
    from paddle_tpu.ops import splash_ops as so
    monkeypatch.setattr(po, "_interpret", lambda: False)
    monkeypatch.setattr(so, "_interpret", lambda: False)
    B, H, S, D = 2, 3, 512, 64
    bias, seed = jnp.zeros((B, S), jnp.float32), jnp.zeros((), jnp.int32)
    seg = jnp.asarray(np.repeat(np.arange(4), S // 4)[None].repeat(B, 0),
                      jnp.int32)

    def flash(q, k, v):
        return po.flash_attention_raw(q, k, v, bias, seed, True, 0.1, 0.0)

    def splash(q, k, v):
        return so.splash_attention_raw(q, k, v, seg, seg, seed, True, 0.1,
                                       0.0)

    x = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16)
    for fn in (flash, splash):
        def grads(q, k, v, fn=fn):
            return jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                            argnums=(0, 1, 2))(q, k, v)
        for f, kernels in ((fn, 1), (grads, 3)):
            exp = jax.export.export(jax.jit(f), platforms=["tpu"])(x, x, x)
            assert exp.mlir_module().count("tpu_custom_call") >= kernels


# -- compile cache placed from outside ---------------------------------------

_CACHE_PROBE = (
    "import jax; before = jax.config.jax_compilation_cache_dir\n"
    "import paddle_tpu\n"
    "from jax._src import xla_bridge\n"
    "assert not xla_bridge._backends, 'import initialised a backend'\n"
    "print(before, jax.config.jax_compilation_cache_dir,\n"
    "      paddle_tpu.device.compilation_cache_dir())\n")


def test_compile_cache_dir_set_from_outside_is_untouched(tmp_path):
    r = _run(_CACHE_PROBE, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(tmp_path)] * 3


def test_compile_cache_dir_defaults_to_one_path_in_the_checkout():
    r = _run(_CACHE_PROBE, unset=("JAX_COMPILATION_CACHE_DIR",))
    assert r.returncode == 0, r.stderr[-2000:]
    fixed = os.path.join(REPO, ".jax_cache")
    assert r.stdout.split() == ["None", fixed, fixed]
    assert paddle.device.COMPILE_CACHE_DIR == fixed
    ignored = subprocess.run(["git", "check-ignore", "-q", ".jax_cache/x"],
                             cwd=REPO)
    if os.path.isdir(os.path.join(REPO, ".git")):
        assert ignored.returncode == 0, ".jax_cache/ must be git-ignored"
    # nothing else in the program sets a cache directory
    hits = subprocess.run(
        ["grep", "-rln", "jax_compilation_cache_dir", "paddle_tpu",
         "chip_smoke.py"], cwd=REPO, capture_output=True,
        text=True).stdout.split()
    assert hits == ["paddle_tpu/device/__init__.py"]


# -- one process per chip -----------------------------------------------------

def test_launchers_refuse_processes_per_chip(monkeypatch):
    from jax._src import hardware_utils

    from paddle_tpu.distributed import refuse_processes_per_chip, spawn
    monkeypatch.setattr(hardware_utils,
                        "num_available_tpu_chips_and_device_id",
                        lambda: (4, hardware_utils.TpuVersion.v5e))
    refuse_processes_per_chip(1, "x", env={})              # one process: fine
    refuse_processes_per_chip(4, "x", env={"JAX_PLATFORMS": "cpu"})
    with pytest.raises(RuntimeError, match="4 TPU chip"):
        refuse_processes_per_chip(2, "fleet.launch", env={})
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="ONE process"):
        spawn(print, nprocs=2)


# -- the smoke refuses to run without a TPU ----------------------------------

def test_chip_smoke_exits_nonzero_without_a_tpu():
    r = _run([sys.executable, os.path.join(REPO, "chip_smoke.py")])
    assert r.returncode == 2
    assert r.stdout == ""                       # no result line, nothing
    assert "no TPU" in r.stderr and "platform=cpu" in r.stderr
