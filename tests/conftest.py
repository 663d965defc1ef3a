"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (SURVEY.md §7: test sharding on
host-platform devices; the driver separately dry-runs the multi-chip path).
Env vars must be set before jax initialises.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import numpy as np
import pytest

import jax

# tests run on the 8-device CPU mesh whatever accelerator the host has
jax.config.update("jax_platforms", "cpu")

# numeric-parity tests compare against float64-ish numpy references
jax.config.update("jax_default_matmul_precision", "highest")

# persistent XLA compilation cache: the suite is compile-dominated (every
# jit in every test), and the HLO-keyed disk cache makes repeat runs reuse
# executables across processes and sessions
from paddle_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()


@pytest.fixture(autouse=True)
def _seed_rngs():
    import paddle_tpu

    paddle_tpu.seed(2024)
    np.random.seed(2024)
    yield
    # drop any tape left by a test that didn't call backward
    from paddle_tpu.autograd import tape

    tape.reset_tape()
    tape.set_grad_enabled(True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / e2e tests (several seconds each)")
