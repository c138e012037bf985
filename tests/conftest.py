"""Test env: the CPU backend with 8 virtual devices, so every
sharding/collective test runs the same code path `dryrun_multichip` uses.
Plain environment variables, set before jax is first imported.

`PADDLE_TPU_TEST_ON_CHIP=1` leaves the real TPU backend in place and is the
only way the `chip`-marked tests run (they are skipped otherwise). One call
on the chip runs them all:

    PADDLE_TPU_TEST_ON_CHIP=1 python -m pytest tests -m chip

The rest of the suite assumes the 8-virtual-device CPU mesh and will error
on a 1-chip host, hence `-m chip`.
"""
import os

import pytest

_ON_CHIP = os.environ.get("PADDLE_TPU_TEST_ON_CHIP") == "1"

# On a warm compile cache XLA:CPU logs two ~2 KB ERROR lines per loaded
# executable ("cpu_aot_loader ... prefer-no-scatter is not supported") and
# then loads and runs it; they would bury every captured-stderr report.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

if not _ON_CHIP:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8")


def pytest_collection_modifyitems(config, items):
    if _ON_CHIP:
        return
    skip = pytest.mark.skip(
        reason="chip test: needs PADDLE_TPU_TEST_ON_CHIP=1 on a TPU host")
    for item in items:
        if "chip" in item.keywords:
            item.add_marker(skip)
