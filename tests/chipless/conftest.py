"""Fixtures of the chipless compiles: the described chip, its shardings,
the kernel route a TPU backend resolves to, and a module's compiled
programs."""

import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from omnia_tpu.ops import attention as attn
from omnia_tpu.parallel import make_mesh

from .cells import CellPrograms


@pytest.fixture(scope="session")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    # Every worker that holds one of these files loads the TPU library;
    # without this the second to do so aborts on /tmp/libtpu_lockfile.
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        if "lockfile" in str(e):
            pytest.fail("another process holds the TPU library's lock and "
                        "ALLOW_MULTIPLE_LIBTPU_LOAD is "
                        f"{os.environ['ALLOW_MULTIPLE_LIBTPU_LOAD']!r}: {e}")
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="session")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="session")
def tp4_mesh(topo):
    return make_mesh(dp=1, tp=4, devices=topo.devices)


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def kernel_route_on(monkeypatch):
    """Steer ops/attention.py onto the route a TPU backend resolves to
    (``auto`` reads ``jax.default_backend()``, which is the CPU here)."""
    monkeypatch.setenv("OMNIA_PALLAS_DECODE", "1")
    attn._pallas_decode_mode.cache_clear()
    yield
    attn._pallas_decode_mode.cache_clear()


@pytest.fixture(scope="module")
def cell_programs(one_chip):
    """The benchmark cells' programs compiled for the described chip, each
    once a module (``cells.CellPrograms``)."""
    return CellPrograms(one_chip)
