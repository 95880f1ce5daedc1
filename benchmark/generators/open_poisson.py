"""Open loop, Poisson arrivals, independent requests.

A pure function of (traffic, seed, seconds): the whole schedule exists
before the first request, so a slow server is offered the same trace late
and the driver records the lateness (the coordinated-omission guard of
`omnia_tpu/evals/trafficsim/arrivals.py`, from which the idea is copied).

Unlike a sampled Poisson trace, the window holds a FIXED cycle: round(rate
x seconds) requests whose gaps are the exponential distribution's
stratified quantiles, scaled to fill the window exactly, and whose lengths
are the stratified quantiles of the traffic file's distributions, each in
an even order of its own (`harness/dists.py`). The seed picks where in the
cycle the window starts; the ramp is the stretch of the cycle before that
point and the tail the stretch after, so the load is one continuous
pattern. Every seed offers the same work at the same mean rate.

traffic keys: rate_rps (from the cell), prompt_tokens, output_tokens,
ramp_s, tail_s. Phases: "ramp" (unmeasured, fills the slots), "window"
(measured, `seconds` long), "tail" (unmeasured load kept up while the
window's last requests finish).
"""

from __future__ import annotations

from harness.dists import stratified


def schedule(traffic: dict, seed: int, seconds: float) -> dict:
    n = int(round(traffic["rate_rps"] * seconds))
    if n <= 0:
        raise ValueError("rate x seconds gives no request")
    gaps = stratified({"dist": "exponential"}, n, base=5, integer=False)
    scale = seconds / sum(gaps)
    cycle = list(zip(
        [g * scale for g in gaps],
        stratified(traffic["prompt_tokens"], n, base=2),
        stratified(traffic["output_tokens"], n, base=3),
    ))
    ramp, tail = float(traffic["ramp_s"]), float(traffic["tail_s"])
    start = seed % n

    def at(k: int):
        return cycle[(start + k) % n]

    # Walk back from the window's start until the ramp is filled.
    before, t, k = [], ramp, -1
    while t - at(k)[0] >= 0:
        t -= at(k)[0]
        before.append((t, k))
        k -= 1
    reqs = []
    for t, k in reversed(before):
        reqs.append({"due_s": t, "prompt_tokens": at(k)[1], "max_tokens": at(k)[2],
                     "phase": "ramp"})
    t, k = ramp, 0
    while t < ramp + seconds + tail:
        phase = "window" if k < n else "tail"
        reqs.append({"due_s": t, "prompt_tokens": at(k)[1], "max_tokens": at(k)[2],
                     "phase": phase})
        t += at(k)[0]
        k += 1
    return {"loop": "open", "window": [ramp, ramp + seconds], "requests": reqs}
