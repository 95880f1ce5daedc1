"""Persistent XLA compilation cache.

The engine AOT-compiles every serving shape before readiness (the TTFT
discipline — no compile on the request path), which makes *cold start* pay
the full compile bill. The reference's serving stack has no compile step at
all (it relays HTTPS SSE), so its pods are warm in seconds; a TPU pod that
recompiles ~100 s of XLA programs on every start would make the platform's
scale-to-zero autoscaling (reference internal/controller/autoscaling.go:204)
useless. Persisting compiled executables across process starts turns every
restart after the first into a cache hit: warmup becomes deserialize +
load, not compile.

Where the cache lives is decided outside the program: JAX's own
``JAX_COMPILATION_CACHE_DIR`` when set (this module then sets no directory
at all), else the fixed ``<checkout>/.jax_cache``. The path is part of the
cache key, so it never depends on a temporary directory, a pid or a time;
a directory that cannot be written is an error, not a reason to move.

One call, idempotent, safe before or after backend init. Used by the
engine itself, so every serving path benefits.
"""

from __future__ import annotations

import os

_enabled_dir: str | None = None


def default_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` beside
    the package (the repo root in a checkout, the install prefix in a
    pod image)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(pkg_root, ".jax_cache")


def _require_writable(path: str) -> None:
    """Create `path` and write a file there. os.access lies under
    containers' overlayfs/read-only mounts, so the probe really writes."""
    probe = os.path.join(path, ".write_probe")
    try:
        os.makedirs(path, exist_ok=True)
        with open(probe, "w"):
            pass
        os.remove(probe)
    except OSError as e:
        raise RuntimeError(
            f"compile cache dir {path} is not writable ({e}); point "
            "JAX_COMPILATION_CACHE_DIR at a persistent volume"
        ) from e


def enabled_dir() -> str | None:
    """The directory the persistent compile cache was enabled with, or
    None while disabled. Jax-free to call (module state only) — the
    warmup manifest and the metrics mirror read it."""
    return _enabled_dir


def enable_compilation_cache(cache_dir: str | None = None) -> str | None:
    """Turn JAX's persistent compilation cache on at `cache_dir` (default:
    :func:`default_cache_dir`) and drop the entry-size/compile-time floors
    so *every* serving program is cached — the defaults skip fast
    compiles, and warmup is many of them. Returns the directory, or None
    on a CPU backend that nobody placed a cache for. Raises when the
    directory cannot be written."""
    global _enabled_dir
    if _enabled_dir is not None:
        return _enabled_dir
    import jax

    from_env = cache_dir is None and "JAX_COMPILATION_CACHE_DIR" in os.environ
    if cache_dir is None and not from_env and jax.default_backend() == "cpu":
        # A CPU backend gets no *default* directory: XLA:CPU cache entries
        # are pinned to the machine's CPU features, and reading them back
        # where feature detection differs risks SIGILL, so nothing is
        # written where another machine may find it. Name a directory to
        # cache on the CPU: the tests do (tests/conftest.py, a fresh one
        # a run, since every engine a test recompiles the same programs).
        return None
    cache_dir = cache_dir or default_cache_dir()
    _require_writable(cache_dir)
    if not from_env:
        # With the standard variable set JAX already reads it; setting a
        # directory here would only be a second source of truth.
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _enabled_dir = cache_dir
    return cache_dir
