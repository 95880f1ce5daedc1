"""Counts programs asked of JAX's persistent compilation cache and how many
it answered (copied from chip_smoke.py::CompileCounter). A program traced
for the first time in a process always asks the cache, hit or miss, so the
count of requests inside the measured window must be 0: there, even a hit
is a shape that warm-up did not cover."""

from __future__ import annotations


class CompileCounter:
    def __init__(self, jax) -> None:
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple:
        return self.requests, self.hits
