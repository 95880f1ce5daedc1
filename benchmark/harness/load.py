"""Drives a schedule against the engine at the Provider boundary:
`InferenceEngine.submit()` -> `RequestHandle.events()`, the call
`runtime/conversation.py` makes. One generator thread (open loop) or one
thread per client (closed loop), and one consumer per request in flight,
as the runtime's gRPC handlers are. All times are this process's
`time.monotonic()`, taken here and never read from the program.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation

from omnia_tpu.engine.types import FinishReason, SamplingParams


@dataclasses.dataclass
class Record:
    index: int
    phase: str
    prompt_tokens: int
    max_tokens: int
    due: Optional[float] = None      # open loop: when it was due (absolute)
    sent: Optional[float] = None
    first: Optional[float] = None    # first token event seen by the consumer
    last: Optional[float] = None     # last token event
    done: Optional[float] = None     # final event
    tokens: int = 0
    tokens_in_window: int = 0        # token events that arrived inside the window
    finish: Optional[str] = None
    error: Optional[str] = None
    request_id: Optional[str] = None

    @property
    def ok(self) -> bool:
        return (self.finish == FinishReason.LENGTH.value
                and self.tokens == self.max_tokens)


def prompt_ids(seed: int, index: int, n: int, vocab: int) -> list:
    """Seeded token ids in [0, vocab): the program receives only these."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, index])
    return rng.integers(0, vocab, size=n, dtype=np.int64).tolist()


def _consume(rec: Record, handle, timeout_s: float, window=(0.0, 0.0)) -> None:
    try:
        for ev in handle.events(timeout=timeout_s):
            now = time.monotonic()
            if ev.token_id is not None:
                if rec.first is None:
                    rec.first = now
                rec.last = now
                rec.tokens += 1
                if window[0] <= now < window[1]:
                    rec.tokens_in_window += 1
            if ev.is_final:
                rec.finish = ev.finish_reason.value
                rec.error = ev.error
                rec.done = now
    except queue.Empty:
        rec.error = f"no event for {timeout_s:.0f} s"
        handle.cancel()


def _submit(engine, rec: Record, ids: list):
    params = SamplingParams(temperature=0.0, max_tokens=rec.max_tokens,
                            stop_token_ids=())
    with TraceAnnotation("bench.submit"):
        rec.sent = time.monotonic()
        handle = engine.submit(ids, params)
    rec.request_id = handle.request_id
    return handle


class Heartbeat:
    """A thread that only sleeps `period_s` at a time and notes how late it
    woke. A beat that is seconds late means this whole process did not run
    (the interpreter lock was held, or the machine stood still): PR 24 saw
    the generator 7-15 s late in 3 of 24 runs, and a run's tails are then
    the stall's, not the server's. With `dump_after_s`, a C watchdog that
    needs no interpreter lock prints every thread's stack to stderr when a
    beat is that late, which names the thread that held the lock."""

    def __init__(self, period_s: float = 0.05, dump_after_s: float = 0.0) -> None:
        self.period, self.dump_after = period_s, dump_after_s
        self.worst = (0.0, 0.0)  # (seconds late, when)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-heartbeat")

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        import faulthandler

        last = time.monotonic()
        while not self._stop.wait(self.period):
            now = time.monotonic()
            late = now - last - self.period
            if late > self.worst[0]:
                self.worst = (late, now)
            last = now
            if self.dump_after:
                faulthandler.dump_traceback_later(self.dump_after, exit=False)
        if self.dump_after:
            faulthandler.cancel_dump_traceback_later()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()


def prime(engine, ecfg, vocab: int, seed: int, compiles, max_passes: int = 4) -> list:
    """Set-up, after `engine.warmup()`: one request per prefill bucket, all
    at once, each run to its end, and again until a pass asks the compiler
    for nothing. `warmup()` restores fresh per-slot arrays, and under a
    mesh the first programs that run on the request path see them with
    other shardings than warm-up did and are compiled anew (found in the
    four-device rehearsal, PR 24); this pass takes those compiles before
    the clock starts. Returns the programs asked of the compiler in each
    pass; the last entry is 0 when the request path is warm."""
    out = 4 * ecfg.decode_chunk  # queue empties, then full chunks run
    asked = []
    for _ in range(max_passes):
        before = compiles.snapshot()[0]
        recs, threads = [], []
        for i, b in enumerate(ecfg.usable_buckets()):
            n = min(b, ecfg.max_seq - 2 - out)
            rec = Record(-1 - i, "prime", n, out)
            handle = _submit(engine, rec, prompt_ids(seed, 1 << 30 | i, n, vocab))
            t = threading.Thread(target=_consume, args=(rec, handle, 600.0))
            t.start()
            recs.append(rec)
            threads.append(t)
        for t in threads:
            t.join()
        bad = [r for r in recs if not r.ok]
        if bad:
            raise RuntimeError(f"priming request failed: {bad[0]}")
        asked.append(compiles.snapshot()[0] - before)
        if asked[-1] == 0:
            break
    return asked


class OpenLoop:
    """Sends each request when it is due, whatever the server does."""

    def __init__(self, engine, sched: dict, seed: int, vocab: int,
                 event_timeout_s: float) -> None:
        self.engine, self.timeout = engine, event_timeout_s
        self.window = tuple(sched["window"])
        self.records = [
            Record(i, r["phase"], r["prompt_tokens"], r["max_tokens"], due=r["due_s"])
            for i, r in enumerate(sched["requests"])
        ]
        self._ids = [prompt_ids(seed, r.index, r.prompt_tokens, vocab)
                     for r in self.records]
        self._stop = threading.Event()
        self._threads: list = []
        self._handles: list = []
        self._gen = threading.Thread(target=self._generate, name="bench-generator")

    def start(self, t0: float) -> None:
        self.t0 = t0
        for r in self.records:
            r.due += t0
        self._gen.start()

    def _generate(self) -> None:
        for rec, ids in zip(self.records, self._ids):
            delay = rec.due - time.monotonic()
            if delay > 0 and self._stop.wait(delay):
                return
            if self._stop.is_set():
                return
            handle = _submit(self.engine, rec, ids)
            t = threading.Thread(target=_consume, args=(rec, handle, self.timeout),
                                 name=f"bench-consumer-{rec.index}")
            t.start()
            self._threads.append(t)
            self._handles.append(handle)

    def measured(self) -> list:
        return [r for r in self.records if r.phase == "window"]

    def wait(self, deadline: float) -> None:
        """Until every window request has finished, or the deadline."""
        while time.monotonic() < deadline:
            if all(r.done is not None or r.error for r in self.measured()):
                break
            time.sleep(0.05)

    def stop(self) -> None:
        self._stop.set()
        self._gen.join()
        for h in self._handles:
            h.cancel()
        for t in self._threads:
            t.join()


class ClosedLoop:
    """`clients` callers; each sends its next request when the last one
    completed. What completes inside the window counts."""

    def __init__(self, engine, sched: dict, seed: int, vocab: int,
                 event_timeout_s: float) -> None:
        self.engine, self.timeout = engine, event_timeout_s
        self.window = tuple(sched["window"])
        self.seed, self.vocab = seed, vocab
        self._lists = sched["clients"]
        self._per_client: list = [[] for _ in self._lists]
        self._stop = threading.Event()
        self._live: dict = {}
        self._threads = [
            threading.Thread(target=self._client, args=(c,), name=f"bench-client-{c}")
            for c in range(len(self._lists))
        ]

    def start(self, t0: float) -> None:
        self.t0 = t0
        for t in self._threads:
            t.start()

    def _client(self, c: int) -> None:
        n = len(self._lists)
        for k, r in enumerate(self._lists[c]):
            if self._stop.is_set():
                return
            index = k * n + c
            rec = Record(index, "loop", r["prompt_tokens"], r["max_tokens"])
            ids = prompt_ids(self.seed, index, rec.prompt_tokens, self.vocab)
            handle = _submit(self.engine, rec, ids)
            self._live[c] = handle
            self._per_client[c].append(rec)
            _consume(rec, handle, self.timeout,
                     (self.t0 + self.window[0], self.t0 + self.window[1]))
        if not self._stop.is_set():
            now = time.monotonic()
            self._per_client[c].append(Record(
                -1, "loop", 0, 0, sent=now, done=now,
                error=f"client {c} ran out of requests"))

    @property
    def records(self) -> list:
        return [r for recs in self._per_client for r in recs]

    def measured(self) -> list:
        """Requests that ended (finished or failed) inside the window."""
        w0, w1 = self.t0 + self.window[0], self.t0 + self.window[1]
        out = []
        for r in self.records:
            end = r.done if r.done is not None else (r.last or r.sent)
            if (r.done is not None or r.error) and w0 <= end < w1:
                out.append(r)
        return out

    def wait(self, deadline: float) -> None:
        end = min(deadline, self.t0 + self.window[1])
        while time.monotonic() < end:
            time.sleep(min(0.05, max(end - time.monotonic(), 0)))

    def stop(self) -> None:
        self._stop.set()
        for h in list(self._live.values()):
            h.cancel()
        for t in self._threads:
            t.join()


DRIVERS = {"open": OpenLoop, "closed": ClosedLoop}
