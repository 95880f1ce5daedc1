"""End-to-end metrics, from the records the load driver took on this
process's own clock. One function per name in BENCHMARK.json `end_to_end`;
each returns None where the sample is empty, and the line leaves it out."""

from __future__ import annotations

from harness.stats import mean_gap_s, percentile


def _ttft_ms(records) -> list:
    return [(r.first - r.due) * 1e3 for r in records
            if r.first is not None and r.due is not None]


def _gaps_ms(records) -> list:
    out = []
    for r in records:
        if r.ok and r.first is not None:
            g = mean_gap_s(r.first, r.last, r.tokens)
            if g is not None:
                out.append(g * 1e3)
    return out


def _pct(values, q):
    return percentile(values, q) if values else None


def ttft_p50_ms(ctx):
    return _pct(_ttft_ms(ctx["records"]), 50)


def gap_p95_ms(ctx):
    return _pct(_gaps_ms(ctx["records"]), 95)


def out_tokens_per_s_chip(ctx):
    """Token events that reached their consumers inside the window, of every
    request: the rate over all the work and all the time of the window.
    (Counting whole requests that completed in it moved in steps of one
    request, 0.5 % here, and read 0 or 1 % of spread by chance: PR 24.)"""
    tokens = sum(r.tokens_in_window for r in ctx["all_records"])
    return tokens / ctx["seconds"] / ctx["chips"] if tokens else None


def setup_s(ctx):
    return ctx["setup"]["setup_s"]


END_TO_END = {f.__name__: f for f in
              (ttft_p50_ms, gap_p95_ms, out_tokens_per_s_chip, setup_s)}


def describe_lengths(records) -> dict:
    prompts = [r.prompt_tokens for r in records]
    outs = [r.max_tokens for r in records]
    if not records:
        return {"requests": 0}
    return {
        "requests": len(records),
        "prompt_tokens": {"sum": sum(prompts), "min": min(prompts),
                          "p50": percentile(prompts, 50), "max": max(prompts)},
        "output_tokens": {"sum": sum(outs), "min": min(outs),
                          "p50": percentile(outs, 50), "max": max(outs)},
    }


def late_ms(records) -> list:
    return [(r.sent - r.due) * 1e3 for r in records
            if r.sent is not None and r.due is not None]


def lateness_histogram(records) -> dict:
    late = late_ms(records)
    edges = (1, 2, 5, 10, 50, 100)
    hist = {f"<={e}ms": sum(1 for x in late if x <= e) for e in edges}
    hist["n"] = len(late)
    hist["max_ms"] = max(late) if late else None
    return hist


def backlog(records) -> dict:
    """Whether the queue grew through the window: the median time to first
    token of the requests due in its first and in its second half."""
    ordered = sorted((r for r in records if r.due is not None), key=lambda r: r.due)
    half = len(ordered) // 2
    return {"ttft_p50_ms_first_half": _pct(_ttft_ms(ordered[:half]), 50),
            "ttft_p50_ms_second_half": _pct(_ttft_ms(ordered[half:]), 50)}
