"""Test configuration: simulate an 8-device TPU-like mesh on CPU.

This is the multi-device simulation story SURVEY.md §4 calls for: all
DP/TP/PP/CP tests run on XLA's virtual host devices
(``--xla_force_host_platform_device_count=8``) with no hardware.
Must set env vars BEFORE jax initializes its backends.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import pytest  # noqa: E402

# a pytest plugin may have imported jax before this file set the
# environment; backends initialize lazily, so pin the platform through the
# live config as well.
jax.config.update("jax_platforms", "cpu")


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]
