"""Closed loop: `clients` callers, each sends its next request when the
last one completed.

A pure function of (traffic, seed, seconds). Each client gets its whole
list of requests up front. A round is one request per client; every round
holds the same set of prompt and output lengths (stratified quantiles in
an even order, `harness/dists.py`), rotated by the round and the seed, so
every seed offers the same work. `first_output_spread` shortens the first
round's outputs to 1/n..n/n of their length so the clients do not finish
in lockstep for the rest of the run.

traffic keys: clients, prompt_tokens, output_tokens, first_output_spread,
ramp_s. The measured window is [ramp_s, ramp_s + seconds) on the run's
clock; what completes inside it counts.
"""

from __future__ import annotations

from harness.dists import stratified

# No client finishes requests faster than this one after another, so the
# lists never run out inside a run (128 tokens take a second at the chip's
# roofline). A client that does run out is reported as a failed request.
_MIN_REQUEST_S = 0.25
# Coprime with any client count that is a power of two: each round meets
# the clients in another rotation of the same cycle.
_ROUND_STRIDE = 37


def schedule(traffic: dict, seed: int, seconds: float) -> dict:
    clients = int(traffic["clients"])
    ramp = float(traffic["ramp_s"])
    rounds = int((ramp + 3 * seconds) / _MIN_REQUEST_S) + 2
    prompts = stratified(traffic["prompt_tokens"], clients, base=2)
    outputs = stratified(traffic["output_tokens"], clients, base=3)
    spread = int(traffic.get("first_output_spread", 0))
    per_client = [[] for _ in range(clients)]
    for r in range(rounds):
        for c in range(clients):
            i = (c + r * _ROUND_STRIDE + seed) % clients
            o = outputs[i]
            if r == 0 and spread > 1:
                o = max(1, o * (c % spread + 1) // spread)
            per_client[c].append({"prompt_tokens": prompts[i], "max_tokens": o})
    return {"loop": "closed", "window": [ramp, ramp + seconds], "clients": per_client}
