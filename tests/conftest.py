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
  can show lives elsewhere: ``tests/chipless/`` asks the chip's
  compiler without a chip, and ``chip_smoke.py`` runs on one.
- One persistent compile cache a run. An engine a test makes new jitted
  closures, so XLA would compile the same few dozen programs once an
  engine; the cache hands every later engine of the run the first one's
  executable. Unless the caller names ``JAX_COMPILATION_CACHE_DIR``, this
  file makes one fresh directory before ``import jax`` and removes it
  when the session ends, in the process that made it. The xdist
  controller imports this file before it starts its workers, so they
  inherit the variable and share the directory; a serial run makes its
  own. With the variable set the engine's ``enable_compilation_cache()``
  takes its ``from_env`` branch: nothing in the program knows about the
  tests. The directory never outlives the run or leaves the machine, so
  no entry is read back by a CPU with other features. A test that names
  a directory of its own (``tests/test_coldstart.py``) still gets it.
- The longest files first. ``--dist loadfile`` hands files to workers in
  the order they were collected, and a four-minute file that the alphabet
  puts last (``test_xing4.py``) would end the run alone on one worker
  while five stand idle. ``_LONGEST_FIRST`` names what holds more than
  about 150 test-seconds under six workers; the order of everything
  else, and of the cases inside a file, is as collected.

Mirrors the reference's clusterless testing stance (SURVEY.md §4: the
reference tests distributed topology without a cluster via a file-backed
fake); multi-chip sharding is tested without TPUs via virtual host devices.
"""

import atexit
import os
import shutil
import tempfile

if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    _run_cache = tempfile.mkdtemp(prefix="omnia-tests-jax-cache-")
    atexit.register(shutil.rmtree, _run_cache, ignore_errors=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _run_cache
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


#: Directories and files of ``tests/`` that are collected ahead of the rest,
#: longest first (ROADMAP.md, Design 11 says when a file joins).
_LONGEST_FIRST = (
    "chipless", "test_kimi_linear.py", "test_xing4.py", "test_prefill_attention.py",
    "test_kexaone.py", "test_spec_decode.py", "test_distributed.py", "test_engine.py",
)


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(_LONGEST_FIRST)}
    items.sort(key=lambda item: min(rank.get(item.path.name, len(rank)),
                                    rank.get(item.path.parent.name, len(rank))))


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
