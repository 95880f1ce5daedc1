"""Engine-loop phase spans, written into the profiler's own trace.

The flight recorder (engine/flight.py) times the engine thread with
``time.monotonic()``, a clock the device trace does not share, so an idle
gap on the device cannot be laid against it. ``phase(name)`` opens a
``jax.profiler.TraceAnnotation`` instead: the span lands in the same
``.xplane.pb`` as the device's ops, on the profiler's clock, and
``benchmark/harness/spans.py`` attributes every device idle gap to the
phase whose self time covers it.

With no profiler session a call costs the TraceMe-enabled check and
returns the shared ``_OFF``: no clock read, no dict, no lock, no flight
event, no allocation. Attributes are therefore set inside an ``if sp:``
at the call site, so that their values are only computed when a session
is on::

    with phase(CLAIM) as sp:
        ...
        if sp:
            sp.set_metadata(request_id=rid)

All engine-thread phases nest under ``STEP`` (a phase's self time is its
duration less its children); ``SUBMIT`` runs on the caller's thread.
``STEP`` carries ``mono_ns``
(``time.monotonic_ns()`` at entry): the offset between the flight
recorder's clock and the profiler's, which ``flight.to_chrome_trace``
takes to lay a flight dump on the profiler's time axis.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

STEP = "omnia.engine.step"
HOUSEKEEPING = "omnia.engine.housekeeping"
FLUSH_PIPELINE = "omnia.engine.flush_pipeline"
CLAIM = "omnia.engine.claim"
PLACE = "omnia.engine.place"
PREFILL_DISPATCH = "omnia.engine.prefill_dispatch"
DECODE_DISPATCH = "omnia.engine.decode_dispatch"
CHUNK_SYNC = "omnia.engine.chunk_sync"
EMIT = "omnia.engine.emit"
IDLE_SLEEP = "omnia.engine.idle_sleep"
SUBMIT = "omnia.engine.submit"


class _Off:
    """The span of a process nobody is profiling: falsy, does nothing."""

    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *_exc) -> bool:
        return False


_OFF = _Off()
enabled = TraceAnnotation.is_enabled


def as_ms(parts) -> dict:
    """``{"flush_s": 0.012}`` → ``{"flush_ms": 12.0}``: the flight
    recorder's stage seconds (engine/flight.py ``note_claim`` /
    ``note_placement``) as span attributes; ``{}`` with no recorder."""
    return {k[:-2] + "_ms": v * 1e3 for k, v in (parts or {}).items()}


def phase(name: str):
    """A ``TraceAnnotation`` named ``name`` while a profiler session is
    on, else the shared no-op. Use as a context manager."""
    return TraceAnnotation(name) if enabled() else _OFF
