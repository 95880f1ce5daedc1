"""The first token's life, stage by stage, for the requests around the
percentile a cell judges.

Since PR 37 `LatencyBreakdown` (omnia_tpu/engine/flight.py) tiles submit ->
first token on the engine's side: `slot_wait_s + loop_wait_s + flush_s`
(= `queue_s`) `+ place_s + prefill_s = ttft_s`. The boundary's own clock
adds a stage on each side: `late` (due -> sent, the generator's) before, and
`deliver` = (first - sent) - `ttft_s` after: from the handle's first-token
stamp to the consumer's thread running, with `submit()`'s own call in it.
So for every request

    late + slot_wait + loop_wait + flush + place + prefill + deliver = first - due

and the means over any set of requests add up the same way. A cell's band
is that set: the measured requests whose `first - due` lies between the
40th and the 60th percentile where the median is judged (`ttft50`), at or
above the 90th where the tail is (`ttft95`). The stages of a band say what
that percentile is made of; a percentile of each stage alone (as
`engine.queue_wait_p95_ms` is) is one of another population.

A run without the recorder, or of a program whose breakdowns lack the
stages (the parent of PR 37), has no band, and every reader returns None.
"""

from __future__ import annotations

from harness.stats import percentile

BANDS = {"ttft50": (40.0, 60.0), "ttft95": (90.0, 100.0)}
ENGINE_STAGES = ("slot_wait", "loop_wait", "flush", "place", "prefill")
STAGES = ("late",) + ENGINE_STAGES + ("deliver",)


def _rows(ctx) -> list:
    """[(first - due, {stage: seconds})] of the measured requests that have
    a due time, a first token and a breakdown with the stages."""
    out = []
    for r in ctx["records"]:
        bd = ctx["flight"].get(r.request_id)
        if (r.due is None or r.first is None or r.sent is None or not bd
                or "flush_s" not in bd or not bd.get("ttft_s")):
            continue
        stages = {name: bd[name + "_s"] for name in ENGINE_STAGES}
        stages["late"] = r.sent - r.due
        stages["deliver"] = (r.first - r.sent) - bd["ttft_s"]
        out.append((r.first - r.due, stages))
    return out


def band_means_ms(ctx, band: str):
    """{stage: mean ms over the band's requests} with `total` (their mean
    first - due) and `requests`; None where nothing tiles."""
    rows = _rows(ctx)
    if not rows:
        return None
    lo_q, hi_q = BANDS[band]
    totals = [t for t, _ in rows]
    lo, hi = percentile(totals, lo_q), percentile(totals, hi_q)
    mine = [(t, st) for t, st in rows if lo <= t <= hi]
    means = {name: sum(st[name] for _, st in mine) / len(mine) * 1e3 for name in STAGES}
    means["total"] = sum(t for t, _ in mine) / len(mine) * 1e3
    means["requests"] = len(mine)
    return means


def stage_ms(stage: str, band: str):
    """The reader of one stage of one band."""
    def read(ctx):
        means = band_means_ms(ctx, band)
        return means[stage] if means else None
    return read


def table(ctx) -> str:
    """Both bands side by side, for PERF.md and the run's log."""
    lines = []
    for band in BANDS:
        means = band_means_ms(ctx, band)
        if means is None:
            continue
        parts = sum(means[s] for s in STAGES)
        lines.append(f"first token, band {band} ({means['requests']} requests): "
                     + ", ".join(f"{s} {means[s]:.3f}" for s in STAGES)
                     + f"; sum {parts:.3f} of first - due {means['total']:.3f} ms")
    return "\n".join(lines)
