"""What several per-layer readers share: which XLA modules are the decode
and prefill steps, how many decode steps a trace holds, and how much
context was live while it was taken."""

from __future__ import annotations

from harness.manifest import decode_kernel, decode_kernel_layers, load_layer_metric
from harness.metrics import late_ms
from harness.stats import percentile

DECODE_MODULE = "jit_decode_chunk"
PREFILL_MODULES = ("jit_prefill_insert",)


def variant_of(base: str) -> tuple:
    """(LAYER, UNIT, BETTER, SOURCE, read) of the per-layer metric `base`,
    for a file that reports the same quantity under another name because its
    cells judge another end-to-end metric: it states its own MOVES."""
    mod = load_layer_metric(base)
    return mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.read


def p95_ms(values_s) -> float | None:
    values = [v * 1e3 for v in values_s]
    return percentile(values, 95) if values else None


def queue_wait_p95_ms(ctx) -> float | None:
    """95th percentile of LatencyBreakdown.queue_s (submit -> claim) over
    the measured requests, from the flight recorder of the traced run."""
    waits = [ctx["flight"][r.request_id]["queue_s"] for r in ctx["records"]
             if r.request_id in ctx["flight"]]
    return p95_ms(waits)


def late_p95_ms(ctx) -> float | None:
    """How late the generator sent: sent - due, 95th percentile over the
    measured requests. A starved generator must not read as a fast server."""
    late = late_ms(ctx["records"])
    return percentile(late, 95) if late else None


def prefill_ms_per_ktok(ctx) -> float | None:
    """Device time of the prefill modules per 1000 prompt tokens prefilled
    while the trace ran (`prefill_tokens` delta; prefill is synchronous on
    the engine thread, so the counter is exact to one prompt at either end)."""
    tr = ctx["trace"]
    tokens = ctx["traced"]["counters"]["prefill_tokens"] if tr else 0
    if not tokens:
        return None
    seconds = sum(tr["modules"][m]["seconds"] for m in PREFILL_MODULES
                  if m in tr["modules"])
    return seconds / tokens * 1e6 if seconds else None


def ttft_percentile(q: float):
    """Time to first token from the traced run (profiler and flight
    recorder on), where the cell does not judge that percentile end to end."""
    def read(ctx):
        ttft = [(r.first - r.due) * 1e3 for r in ctx["records"]
                if r.first is not None and r.due is not None]
        return percentile(ttft, q) if ttft else None
    return read


def idle_share(ctx) -> float | None:
    tr = ctx["trace"]
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def kernel_in_decode(ctx):
    """(calls, seconds) inside the decode module of the kernel the
    configuration names as the one that counts its decode steps
    (`manifest.decode_kernel`), or None where the trace shows none."""
    tr = ctx["trace"]
    if not tr:
        return None
    kernel = decode_kernel(ctx["model"])
    calls = seconds = 0.0
    for name, (n, s) in tr["ops_in_module"].get(DECODE_MODULE, {}).items():
        if name.split(".")[0] == kernel:
            calls, seconds = calls + n, seconds + s
    return (calls, seconds) if calls else None


def decode_steps_in_trace(ctx) -> float | None:
    """Decode steps the traced decode modules ran: the kernel runs once a
    step in each layer that calls it, so its calls over the number of those
    layers (`manifest.decode_kernel_layers`: every layer, unless the
    configuration says which). (The module's name does not say its chunk
    size, and the engine's `decode_steps` counter runs ahead of the device
    by the chunks in flight.)"""
    kernel = kernel_in_decode(ctx)
    if kernel is None:
        return None
    return kernel[0] / decode_kernel_layers(ctx["model"])


def decode_step_s(ctx) -> float | None:
    steps = decode_steps_in_trace(ctx)
    if not steps:
        return None
    return ctx["trace"]["modules"][DECODE_MODULE]["seconds"] / steps


def live_context_tokens(ctx) -> float:
    """Time-mean, over the traced span, of the context tokens of the
    requests then decoding: prompt + half of what each generated."""
    ta, tb = ctx["traced"]["t"]
    total = 0.0
    for r in ctx["all_records"]:
        if r.first is None:
            continue
        end = r.done if r.done is not None else tb
        overlap = min(end, tb) - max(r.first, ta)
        if overlap > 0:
            total += overlap * (r.prompt_tokens + r.tokens / 2)
    return total / (tb - ta)
