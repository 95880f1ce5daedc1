"""Test bootstrap: every test runs on the CPU backend with 8 virtual devices.

- ``jax.config.update("jax_platforms", "cpu")`` pins the platform in the
  test process itself, so the suite runs on the CPU whatever the caller's
  environment says: on a machine with a TPU attached, an unset
  ``JAX_PLATFORMS`` would otherwise hand the tests the chip (one process
  at a time may hold it, and xdist starts several). It works because
  backend creation is lazy: no backend exists yet when this file runs.
- ``xla_force_host_platform_device_count`` is read from XLA_FLAGS when the
  CPU client is created, which is also lazy — setting it here works. The
  eight virtual devices are how meshes (dp/tp/sp/pp) are tested without
  chips.
- On the CPU the Pallas decode kernel is routed off (``auto`` → "0") or
  run under the interpreter by the tests that ask for it. What only a TPU
  can show lives elsewhere: ``tests/test_tpu_compile.py`` asks the chip's
  compiler without a chip, and ``chip_smoke.py`` runs on one.

Mirrors the reference's clusterless testing stance (SURVEY.md §4: the
reference tests distributed topology without a cluster via a file-backed
fake); multi-chip sharding is tested without TPUs via virtual host devices.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_platform():
    devs = jax.devices()
    assert devs[0].platform == "cpu", f"tests must run on CPU, got {devs[0]}"
    yield


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs
