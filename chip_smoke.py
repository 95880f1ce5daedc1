#!/usr/bin/env python3
"""Chip smoke: the ``type: tpu`` Provider answering requests on a TPU.

One process, the entry points a deployment uses (the shape of
``omnia_tpu.cli.runtime_main``): a compiled pack + a provider spec list →
``ProviderRegistry`` → ``build_engine`` → ``RuntimeServer.serve`` on
127.0.0.1:0, the WebSocket facade in front, real requests over the wire.
llama3-1b at its published widths, all layers, bf16, seeded random weights,
the byte tokenizer.

    python chip_smoke.py                 # one chip (what the driver runs)
    python chip_smoke.py --chips 4       # only the tp=4 path + its comparison
    python chip_smoke.py --rehearse-cpu  # tiny size on the CPU, says so

Everything printed before the last line is smoke output, not a benchmark.
The last line of stdout is one JSON object; ``ok`` is true only if every
phase passed on a TPU. No accelerator and no ``--rehearse-cpu`` → exit 1.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import logging
import os
import re
import sys
import threading
import time
import traceback

PACK = {
    "name": "smoke-agent",
    "version": "1.0.0",
    "prompts": {"system": "You are the smoke test agent. Answer briefly."},
    "sampling": {"temperature": 0.0, "max_tokens": 48},
}
LONG_PROMPT = (
    "Summarise, in one sentence each, why a serving engine keeps a fixed "
    "batch of decode slots, why prefill runs in bucketed lengths, and why "
    "the key/value cache is donated through every compiled step."
)
# Two bf16 evaluations of the same decode step that differ in reduction
# order (kernel vs einsum, tp=1 vs tp=4), as shares of the logit range
# max|b|. Measured on the chip (PERF.md, PR 22): either route sits
# 0.024 of the range (max over 16 x 128256 logits; mean 0.0037) from an
# f32 "highest" evaluation of the same bf16 weights, while the attention
# op alone is within 2 bf16 ulps of f32 on both routes. Two bf16 runs may
# each be that far from the truth on opposite sides, hence twice it.
BF16_LOGITS_RTOL = 5e-2        # max abs difference / range
BF16_LOGITS_MEAN_RTOL = 1e-2   # mean abs difference / range


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class Smoke:
    def __init__(self) -> None:
        self.failures: list[str] = []

    @contextlib.contextmanager
    def phase(self, name: str):
        say(f"--- {name}")
        t0 = time.monotonic()
        try:
            yield
        except Exception as e:  # a failed phase fails the run, not the report
            traceback.print_exc()
            self.fail(f"{name}: {type(e).__name__}: {e}")
        say(f"--- {name}: {time.monotonic() - t0:.1f}s")

    def fail(self, why: str) -> None:
        say(f"FAIL {why}")
        self.failures.append(why)

    def check(self, cond: bool, why: str) -> bool:
        if not cond:
            self.fail(why)
        return bool(cond)


class CompileCounter:
    """Counts compile requests that consulted the persistent cache and
    how many of them it answered (jax.monitoring events)."""

    def __init__(self, jax) -> None:
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def line(self) -> str:
        return (f"{self.requests} programs asked of the cache: "
                f"{self.requests - self.hits} compiled, {self.hits} from cache")


class EngineTap:
    """Observes, in-process, what the engine answered to the requests
    that came over the wire: token ids, finish reason, prompt length."""

    def __init__(self) -> None:
        from omnia_tpu.engine import types as etypes

        self.by_request: dict[str, dict] = {}
        self.by_session: dict[str, list[str]] = {}
        tap, orig_push = self, etypes.RequestHandle._push

        def push(handle, ev):
            rec = tap.by_request.setdefault(
                handle.request_id, {"tokens": [], "final": None}
            )
            if ev.token_id is not None:
                rec["tokens"].append(int(ev.token_id))
            if ev.is_final:
                rec["final"] = ev
            orig_push(handle, ev)

        etypes.RequestHandle._push = push

    def watch(self, engine) -> None:
        orig_submit = engine.submit

        def submit(prompt_tokens, *a, session_id=None, **kw):
            handle = orig_submit(prompt_tokens, *a, session_id=session_id, **kw)
            self.by_session.setdefault(session_id, []).append(handle.request_id)
            return handle

        engine.submit = submit

    def turn(self, session_id: str, index: int) -> dict:
        return self.by_request[self.by_session[session_id][index]]


# ---------------------------------------------------------------------------
# The runtime, the way cli.runtime_main builds it
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def served(pack: dict, provider: dict):
    """(runtime, facade port, engine) for one provider spec; both servers
    are shut down on exit."""
    from omnia_tpu.facade.server import FacadeServer
    from omnia_tpu.runtime.packs import load_pack
    from omnia_tpu.runtime.providers import ProviderRegistry, ProviderSpec
    from omnia_tpu.runtime.server import RuntimeServer

    registry = ProviderRegistry()
    registry.register(ProviderSpec(**provider))
    runtime = RuntimeServer(
        pack=load_pack(pack), providers=registry,
        provider_name=provider["name"],
    )
    facade = None
    try:
        rport = runtime.serve("127.0.0.1:0")  # builds + warms the engine
        facade = FacadeServer(
            runtime_target=f"127.0.0.1:{rport}", agent_name=pack["name"],
            messages_per_minute=600,
        )
        yield runtime, facade.serve(), registry.engine(provider["name"])
    finally:
        if facade is not None:
            facade.shutdown()
        runtime.shutdown()


class WireSession:
    """One WebSocket connection = one session; turns are sequential."""

    def __init__(self, port: int) -> None:
        from websockets.sync.client import connect

        self.ws = connect(f"ws://localhost:{port}/ws")
        hello = json.loads(self.ws.recv(timeout=30))
        if hello["type"] != "connected":
            raise RuntimeError(f"facade did not accept the connection: {hello}")
        self.session_id = hello["session_id"]
        self.turns: list[dict] = []

    def ask(self, content: str, timeout: float = 300.0) -> dict:
        self.ws.send(json.dumps({"type": "message", "content": content}))
        deadline, text = time.monotonic() + timeout, ""
        while True:
            msg = json.loads(self.ws.recv(timeout=deadline - time.monotonic()))
            if msg["type"] == "chunk":
                text += msg["text"]
            elif msg["type"] in ("done", "error"):
                msg["text"] = text
                self.turns.append(msg)
                return msg

    def close(self) -> None:
        self.ws.close()


def ask_concurrently(sessions: list[WireSession], prompts: list[str]) -> None:
    threads = [
        threading.Thread(target=s.ask, args=(q,))
        for s, q in zip(sessions, prompts)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)


def report_turn(smoke: Smoke, tap: EngineTap, label: str, sess: WireSession,
                index: int) -> list[int]:
    """Print one turn's outcome and hold it to: a finish reason on the
    wire and from the engine, tokens generated."""
    wire = sess.turns[index]
    if not smoke.check(wire["type"] == "done", f"{label}: wire said {wire}"):
        return []
    rec = tap.turn(sess.session_id, index)
    final, tokens = rec["final"], rec["tokens"]
    say(f"request {label}: prompt_tokens={final.num_prompt_tokens} "
        f"generated={final.num_generated_tokens} "
        f"engine_finish={final.finish_reason.value} "
        f"wire_finish={wire['finish_reason']} "
        f"wire_completion_tokens={wire['usage'].get('completion_tokens')}")
    smoke.check(bool(wire["finish_reason"]), f"{label}: no wire finish reason")
    smoke.check(final.num_generated_tokens > 0, f"{label}: no tokens generated")
    smoke.check(
        final.num_generated_tokens == len(tokens)
        == wire["usage"].get("completion_tokens"),
        f"{label}: token counts disagree (engine {final.num_generated_tokens}, "
        f"streamed {len(tokens)}, wire {wire['usage']})",
    )
    return tokens


def decode_program_text(engine) -> str:
    """Compiled text of the engine's largest decode-chunk program, from
    the operands the scheduler dispatches it with."""
    fn = engine._decode_fns[max(engine._decode_fns)]
    return fn.lower(
        engine.params, engine._ck, engine._cv, engine._tokens,
        engine._positions, engine._active, engine._budget, engine._stop_ids,
        engine._key_data, engine._temp, engine._top_p, engine._top_k,
    ).compile().as_text()


def collective_lines(text: str) -> dict[str, list[str]]:
    """Compiled-HLO lines of each collective, keyed by its op name (also
    what tests/chipless/ reads a described-chip compile with)."""
    out: dict[str, list[str]] = {}
    for line in text.splitlines():
        m = re.search(
            r"= \(?\w+\[[\d,]*\]\S* (all-reduce|all-gather|all-to-all|"
            r"collective-permute)(?:-start)?\(", line)
        if m:
            out.setdefault(m.group(1), []).append(line.strip())
    return out


def result_dims(line: str) -> list[int]:
    """Dimensions of the array an HLO instruction line produces."""
    m = re.search(r"= \(?\w+\[([\d,]*)\]", line)
    return [int(d) for d in m.group(1).split(",") if d] if m else []


def kv_rows_moved(text: str, cache_len: int, head_dim: int) -> list[str]:
    """All-gathers / all-to-alls whose result carries cache rows
    ([.., S, Hkv, D]). The sampler's collectives over vocab-sharded
    logits are expected; these are not."""
    found = collective_lines(text)
    return [
        ln[:200] for op in ("all-gather", "all-to-all")
        for ln in found.get(op, [])
        if cache_len in result_dims(ln) and head_dim in result_dims(ln)
    ]


def logits_step(cfg, mesh):
    """A fresh jitted one-token decode step → logits [B, V] f32. Fresh
    per call so each trace reads the kernel route in force."""
    import jax

    from omnia_tpu.models import llama

    def step(params, ck, cv, toks, pos):
        logits, ck, cv = llama.forward(
            params, cfg, toks[:, None], pos[:, None], ck, cv, pos, mesh=mesh
        )
        return logits[:, 0], ck, cv

    return jax.jit(step)


def logits_agree(smoke: Smoke, what: str, a, b) -> float:
    """Print and check |a - b| against the bf16 tolerances; → range."""
    import numpy as np

    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    diff, scale = np.abs(a - b), float(np.abs(b).max())
    err, mean = float(diff.max()), float(diff.mean())
    agree = int((a.argmax(-1) == b.argmax(-1)).sum())
    say(f"{what}: max_abs_err={err:.6g} mean_abs_err={mean:.6g} "
        f"max_abs_logit={scale:.6g} max/range={err / scale:.3g} "
        f"(tolerance {BF16_LOGITS_RTOL}) mean/range={mean / scale:.3g} "
        f"(tolerance {BF16_LOGITS_MEAN_RTOL}) argmax_agree={agree}/{len(a)}")
    smoke.check(bool(np.isfinite(a).all()), f"{what}: logits not finite")
    smoke.check(
        err <= BF16_LOGITS_RTOL * scale
        and mean <= BF16_LOGITS_MEAN_RTOL * scale,
        f"{what}: differ by {err / scale:.3g} (max) / {mean / scale:.3g} "
        "(mean) of the logit range",
    )
    return scale


# ---------------------------------------------------------------------------
# One chip: serve llama3-1b, check the kernel against the einsum path
# ---------------------------------------------------------------------------


def kernel_vs_xla(smoke: Smoke, engine, cfg, seed: int, routes) -> None:
    """One decode step at the engine's own widths and parameters, over a
    cache of seeded random rows, through the Pallas route and the XLA
    route. Only the chip can make this comparison of the real kernel."""
    import jax
    import jax.numpy as jnp

    from omnia_tpu.ops import attention as attn

    B, S = engine.cfg.num_slots, engine.cfg.max_seq
    shape = (cfg.num_layers, B, S, cfg.num_kv_heads, cfg.head_dim)
    kk, kv, kt = jax.random.split(jax.random.key(seed + 1), 3)
    dtype = engine._dtype
    ck = jax.random.normal(kk, shape, dtype)
    cv = jax.random.normal(kv, shape, dtype)
    toks = jax.random.randint(kt, (B,), 0, cfg.vocab_size, jnp.int32)
    # Positions spread from a near-empty slot to a full one, so blocks
    # past the position (skipped DMA) and the last block both occur.
    pos = jnp.linspace(3, S - 2, B).astype(jnp.int32)
    prev = os.environ.get("OMNIA_PALLAS_DECODE")
    out = {}
    try:
        for route in routes:
            os.environ["OMNIA_PALLAS_DECODE"] = route
            attn._pallas_decode_mode.cache_clear()
            out[route], _, _ = logits_step(cfg, engine._mesh)(
                engine.params, ck, cv, toks, pos
            )
    finally:
        if prev is None:
            os.environ.pop("OMNIA_PALLAS_DECODE", None)
        else:
            os.environ["OMNIA_PALLAS_DECODE"] = prev
        attn._pallas_decode_mode.cache_clear()
    kernel, xla = (jax.device_get(out[r]) for r in routes)
    logits_agree(
        smoke,
        f"kernel-vs-XLA decode logits (route {routes[0]} vs {routes[1]}, "
        f"{cfg.name} {jnp.dtype(dtype).name}, {B} slots x {S})",
        kernel, xla,
    )


def one_chip(smoke: Smoke, args, jax, counter: CompileCounter, on_tpu: bool):
    from omnia_tpu.models import get_config
    from omnia_tpu.ops.attention import pallas_decode_mode
    from omnia_tpu.utils import compile_cache

    if on_tpu:
        model, options = "llama3-1b", {
            "num_slots": 16, "max_seq": 1024, "prefill_buckets": [64, 256],
            "dtype": "bfloat16",
        }
    else:
        model, options = "test-tiny-gqa8", {
            "num_slots": 4, "max_seq": 128, "prefill_buckets": [16, 64],
            "dtype": "float32",
        }
    provider = {"name": "main", "type": "tpu", "model": model,
                "options": {**options, "seed": args.seed}}
    pack = PACK if on_tpu else {
        **PACK, "prompts": {"system": "Smoke."},
        "sampling": {"temperature": 0.0, "max_tokens": 8},
    }
    long_prompt = LONG_PROMPT if on_tpu else LONG_PROMPT[:24]
    tap = EngineTap()
    engine = None

    with smoke.phase(f"serve {model} through RuntimeServer + facade"):
        t0 = time.monotonic()
        with served(pack, provider) as (runtime, fport, engine):
            ready_s = time.monotonic() - t0
            tap.watch(engine)
            phases = engine._coldstart.snapshot()["phases_s"]
            say(f"model={model} dtype={options['dtype']} layers="
                f"{engine.model_cfg.num_layers} slots={options['num_slots']} "
                f"max_seq={options['max_seq']} seed={args.seed}")
            say(f"compile cache dir: {compile_cache.enabled_dir()} "
                f"(JAX_COMPILATION_CACHE_DIR "
                f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'not set'})")
            say(f"warmup: {counter.line()}")
            say(f"warmup seconds: build+warmup={ready_s:.1f} "
                f"warmup_compile={phases.get('warmup_compile', 0.0):.1f} "
                f"warmup_tasks={engine.metrics['warmup_programs_total']} "
                f"(smoke observation, not a benchmark)")
            say("warmup stage seconds: " + " ".join(
                f"{k.partition('.')[2]}={v:.2f}" for k, v in phases.items()
                if k.startswith("programs.")))
            if on_tpu:
                smoke.check(compile_cache.enabled_dir() is not None,
                            "persistent compile cache is not enabled")
            route = pallas_decode_mode()
            say(f"decode kernel route: {route}")
            smoke.check(route == ("1" if on_tpu else "interpret"),
                        f"unexpected kernel route {route!r}")

            warm_requests = counter.requests
            first = WireSession(fport)
            first.ask("hello")                       # turn 1, session A
            trio = [WireSession(fport) for _ in range(3)]
            ask_concurrently(
                trio, [long_prompt, "tell me a story", "tell me a story"]
            )
            again = WireSession(fport)
            again.ask("hello")                       # same prompt, new session
            reuse_before = engine.metrics["prefix_reuse_tokens"]
            first.ask("and one more thing")          # turn 2, session A
            reused = engine.metrics["prefix_reuse_tokens"] - reuse_before

            t_a1 = report_turn(smoke, tap, "A1 'hello'", first, 0)
            report_turn(smoke, tap, "B long prompt (concurrent)", trio[0], 0)
            t_c = report_turn(smoke, tap, "C story (concurrent)", trio[1], 0)
            t_d = report_turn(smoke, tap, "D story (concurrent)", trio[2], 0)
            t_e = report_turn(smoke, tap, "E 'hello' again", again, 0)
            report_turn(smoke, tap, "A2 second turn", first, 1)
            long_n = tap.turn(trio[0].session_id, 0)["final"].num_prompt_tokens
            smoke.check(long_n > min(options["prefill_buckets"]),
                        f"long prompt ({long_n} tokens) fits the smallest bucket")
            smoke.check(t_a1 == t_e and len(t_a1) > 0,
                        "same prompt asked twice (sequential) gave other tokens")
            smoke.check(t_c == t_d and len(t_c) > 0,
                        "same prompt asked twice (concurrent) gave other tokens")
            say(f"greedy repeat: sequential identical={t_a1 == t_e}, "
                f"concurrent identical={t_c == t_d}")
            say(f"second turn reused {reused} cached prompt tokens "
                f"(cross-turn KV reuse)")
            smoke.check(reused > 0, "second turn reused no session KV")
            for s in (first, again, *trio):
                s.close()
            on_path = counter.requests - warm_requests
            say(f"programs compiled on the request path: {on_path}")
            smoke.check(on_path == 0,
                        f"{on_path} programs compiled while serving: warmup "
                        "missed a shape the request path dispatches")

    if engine is None:
        return
    with smoke.phase("decode executable holds the Mosaic kernel"):
        has_call = "tpu_custom_call" in decode_program_text(engine)
        say(f"decode program contains tpu_custom_call: {has_call}")
        if on_tpu:
            smoke.check(has_call, "decode executable has no Mosaic call")
    with smoke.phase("kernel vs XLA decode logits on the device"):
        kernel_vs_xla(smoke, engine, get_config(model), args.seed,
                      ("1", "0") if on_tpu else ("interpret", "0"))
    stats = jax.devices()[0].memory_stats()
    say("peak_bytes_in_use: "
        + (str(stats["peak_bytes_in_use"]) if stats else "not reported here"))


# ---------------------------------------------------------------------------
# Four chips: llama3-8b tp=4 served; llama3-1b tp=1 against tp=4
# ---------------------------------------------------------------------------

GREEDY_PROMPT, GREEDY_STEPS = 8, 24


def greedy_stream(engine, cfg, prompts):
    """Teacher-force `prompts` [B, P] one token a step, then decode
    GREEDY_STEPS tokens greedily, through the engine's own parameters,
    cache and mesh. → (first-step logits, per-greedy-step logits, tokens)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    step = logits_step(cfg, engine._mesh)
    ck, cv = engine._ck, engine._cv
    B, P = prompts.shape
    toks, first, logits_at, chosen = jnp.asarray(prompts[:, 0]), None, [], []
    for t in range(P + GREEDY_STEPS - 1):
        logits, ck, cv = step(
            engine.params, ck, cv, toks, jnp.full((B,), t, jnp.int32)
        )
        if first is None:
            first = np.asarray(jax.device_get(logits))
        if t + 1 < P:
            toks = jnp.asarray(prompts[:, t + 1])
        else:
            host = np.asarray(jax.device_get(logits))
            logits_at.append(host)
            chosen.append(host.argmax(-1))
            toks = jnp.asarray(chosen[-1], jnp.int32)
    return first, logits_at, np.stack(chosen, 1)


def four_chips(smoke: Smoke, args, jax, counter: CompileCounter, on_tpu: bool):
    import numpy as np

    from omnia_tpu.engine import EngineConfig, InferenceEngine
    from omnia_tpu.models import get_config
    from omnia_tpu.utils import compile_cache

    if on_tpu:
        big, small, dtype = "llama3-8b", "llama3-1b", "bfloat16"
        shape = {"num_slots": 16, "max_seq": 1024, "prefill_buckets": [64]}
        pack = {**PACK, "sampling": {"temperature": 0.0, "max_tokens": 32}}
    else:
        big = small = "test-tiny-gqa8"
        dtype = "float32"
        shape = {"num_slots": 4, "max_seq": 128, "prefill_buckets": [16]}
        pack = {**PACK, "prompts": {"system": "Smoke."},
                "sampling": {"temperature": 0.0, "max_tokens": 8}}
    provider = {"name": "main", "type": "tpu", "model": big,
                "options": {**shape, "dtype": dtype, "tp": 4,
                            "seed": args.seed}}
    tap = EngineTap()
    with smoke.phase(f"serve {big} tp=4 through RuntimeServer + facade"):
        t0 = time.monotonic()
        with served(pack, provider) as (runtime, fport, engine):
            tap.watch(engine)
            say(f"model={big} dtype={dtype} tp=4 mesh={dict(engine._mesh.shape)} "
                f"slots={shape['num_slots']} max_seq={shape['max_seq']}")
            say(f"compile cache dir: {compile_cache.enabled_dir()}")
            say(f"warmup: {counter.line()}")
            say(f"warmup seconds: build+warmup={time.monotonic() - t0:.1f} "
                f"(smoke observation, not a benchmark)")
            used = [
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.devices()[:4]
            ]
            say(f"bytes in use per device after warmup: {used}")
            if on_tpu:
                smoke.check(
                    max(used) <= 1.15 * min(used),
                    f"per-device bytes uneven (more than 15 % apart): {used}",
                )
                smoke.check(max(used) < 0.5 * sum(used),
                            f"one device holds most of the bytes: {used}")
            a, b = WireSession(fport), WireSession(fport)
            ask_concurrently([a, b], ["hello", LONG_PROMPT[:40]])
            a.ask("and one more thing")
            report_turn(smoke, tap, "A1 'hello' (concurrent)", a, 0)
            report_turn(smoke, tap, "B (concurrent)", b, 0)
            report_turn(smoke, tap, "A2 second turn", a, 1)
            a.close()
            b.close()

            text = decode_program_text(engine)
            found = collective_lines(text)
            say("tp=4 decode program: tpu_custom_call="
                f"{'tpu_custom_call' in text} collectives="
                f"{ {k: len(v) for k, v in found.items()} }")
            if on_tpu:
                smoke.check("tpu_custom_call" in text,
                            "tp=4 decode executable has no Mosaic call")
            smoke.check(len(found.get("all-reduce", [])) >= 2,
                        "tp=4 decode has no all-reduce after the projections")
            moved = kv_rows_moved(
                text, shape["max_seq"], engine.model_cfg.head_dim
            )
            smoke.check(not moved, f"a collective moves KV-cache rows: {moved}")
        del engine, runtime
    gc.collect()

    with smoke.phase(f"{small}: tp=1 on one device against tp=4 on four"):
        cfg = get_config(small)
        ecfg = dict(**shape, dtype=dtype, max_sessions=0)
        ecfg["prefill_buckets"] = tuple(ecfg["prefill_buckets"])
        prompts = np.random.default_rng(args.seed).integers(
            0, cfg.vocab_size, (shape["num_slots"], GREEDY_PROMPT)
        ).astype(np.int32)
        runs = {}
        for tp in (1, 4):
            engine = InferenceEngine(
                cfg, EngineConfig(tp=tp, **ecfg), seed=args.seed
            )
            runs[tp] = greedy_stream(engine, cfg, prompts)
            del engine
            gc.collect()
        (first1, steps1, toks1), (first4, _steps4, toks4) = runs[1], runs[4]
        scale = logits_agree(
            smoke, "first-decode-step logits tp=4 vs tp=1", first4, first1
        )
        # Greedy tokens for N = GREEDY_STEPS steps after a GREEDY_PROMPT-token
        # prompt: long enough that rows written by the sharded path are read
        # back through the kernel many times, short enough for a smoke. With
        # random weights the logits are nearly flat (std 0.9, top-2 gaps
        # inside bf16 noise), so most argmaxes sit on near-ties: a slot may
        # diverge only where tp=1's own margin between the two tokens is
        # inside the logits tolerance, and only its steps up to there count.
        first_split, margins = [], []
        for slot in range(toks1.shape[0]):
            diff = np.nonzero(toks1[slot] != toks4[slot])[0]
            if diff.size == 0:
                first_split.append(GREEDY_STEPS)
                continue
            t = int(diff[0])
            ref = steps1[t][slot]
            first_split.append(t)
            margins.append(float(ref[toks1[slot, t]] - ref[toks4[slot, t]]))
            smoke.check(
                margins[-1] <= BF16_LOGITS_RTOL * scale,
                f"slot {slot} step {t}: tp=4 chose another token with "
                f"tp=1 margin {margins[-1]:.4g}",
            )
        agreed = sum(first_split)
        say(f"greedy tokens tp=4 vs tp=1 over N={GREEDY_STEPS} steps x "
            f"{toks1.shape[0]} slots: steps agreed before a slot's first "
            f"split per slot={first_split} ({agreed} of "
            f"{GREEDY_STEPS * toks1.shape[0]}), slots identical throughout="
            f"{first_split.count(GREEDY_STEPS)}, largest tp=1 margin at a "
            f"split={max(margins, default=0.0):.4g} (allowed "
            f"{BF16_LOGITS_RTOL * scale:.4g})")
        smoke.check(agreed > 0, "tp=4 and tp=1 greedy streams share no step")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny model on the CPU backend (never reports ok)")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["OMNIA_PALLAS_DECODE"] = "interpret"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}"
        ).strip()
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(message)s")
    smoke = Smoke()
    device = {"platform": None, "kind": None, "count": 0}
    try:
        import jax

        import omnia_tpu  # noqa: F401 — a lone chip_smoke.py must fail here

        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        say(f"jax {jax.__version__}, device {device['kind']} "
            f"({device['platform']}) x {device['count']}")
        on_tpu = device["platform"] == "tpu"
        if not on_tpu and not args.rehearse_cpu:
            smoke.fail(f"no TPU: JAX reports platform {device['platform']!r}")
        elif device["count"] < args.chips:
            smoke.fail(f"--chips {args.chips} but JAX reports {device['count']}")
        else:
            if args.rehearse_cpu:
                say(f"REHEARSAL on platform {device['platform']!r} at tiny "
                    "size: proves control flow only")
            counter = CompileCounter(jax)
            (four_chips if args.chips == 4 else one_chip)(
                smoke, args, jax, counter, on_tpu
            )
            if args.rehearse_cpu:
                smoke.fail("rehearsal on the CPU is not a chip run")
    except Exception as e:
        traceback.print_exc()
        smoke.fail(f"{type(e).__name__}: {e}")
    ok = not smoke.failures
    for why in smoke.failures:
        say(f"failed: {why}")
    sys.stdout.flush()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    code = main()
    # Daemon threads of the servers must not keep the chip's process alive.
    sys.stdout.flush()
    os._exit(code)
