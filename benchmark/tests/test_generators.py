import json
import os

import pytest

from harness.manifest import BENCH_DIR, load_generator

BIG_SEED = 2**31 + 12345  # more than 32 signed bits hold


def traffic(name, **extra):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return {**json.load(f), **extra}


@pytest.mark.parametrize("name,extra", [
    ("chat-steady", {"rate_rps": 5.0}),
    ("longprompt-steady", {"rate_rps": 2.5}),
    ("eval-batch", {}),
])
def test_same_seed_same_schedule_other_seed_other_order(name, extra):
    t = traffic(name, **extra)
    gen = load_generator(t["generator"])
    a, b, c = gen(t, BIG_SEED, 20), gen(t, BIG_SEED, 20), gen(t, 7, 20)
    assert a == b
    assert a != c


def _window(sched):
    return [r for r in sched["requests"] if r["phase"] == "window"]


def test_open_poisson_offers_every_seed_the_same_work():
    t = traffic("chat-steady", rate_rps=5.0)
    gen = load_generator("open_poisson")
    a, b = _window(gen(t, 1, 20)), _window(gen(t, BIG_SEED, 20))
    assert len(a) == len(b) == 100  # round(rate x seconds)
    # The seed only rotates the cycle: b is a rotation of a.
    pa = [(r["prompt_tokens"], r["max_tokens"]) for r in a]
    pb = [(r["prompt_tokens"], r["max_tokens"]) for r in b]
    k = (BIG_SEED - 1) % 100
    assert pb == pa[k:] + pa[:k]
    for key in ("prompt_tokens", "max_tokens"):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
    lo, hi = t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]
    assert all(lo <= r["prompt_tokens"] <= hi for r in a)
    # Arrivals fill the window exactly and stay inside it.
    s = gen(t, 1, 20)
    w0, w1 = s["window"]
    assert w0 == t["ramp_s"] and w1 == w0 + 20
    assert all(w0 <= r["due_s"] < w1 for r in a)
    dues = [r["due_s"] for r in s["requests"]]
    assert dues == sorted(dues)
    phases = [r["phase"] for r in s["requests"]]
    assert phases == sorted(phases, key=["ramp", "window", "tail"].index)
    assert s["requests"][0]["due_s"] >= 0 and phases.count("ramp") >= 20
    # The median prompt is the traffic file's median.
    med = sorted(r["prompt_tokens"] for r in a)[50]
    assert abs(med - t["prompt_tokens"]["median"]) <= 8


def test_closed_loop_rounds_hold_the_same_set_and_spread_the_first():
    t = traffic("eval-batch")
    gen = load_generator("closed_loop")
    s = gen(t, 3, 20)
    clients = s["clients"]
    assert len(clients) == t["clients"]
    round1 = sorted(c[1]["prompt_tokens"] for c in clients)
    round2 = sorted(c[2]["prompt_tokens"] for c in clients)
    assert round1 == round2
    assert all(c[1]["max_tokens"] == t["output_tokens"]["value"] for c in clients)
    first = [c[0]["max_tokens"] for c in clients]
    assert min(first) < max(first) == t["output_tokens"]["value"]
    # Never runs out: even at four requests a second the lists outlast the run.
    assert len(clients[0]) * 0.25 > t["ramp_s"] + 20


def test_even_order_spreads_neighbours():
    from harness.dists import even_order, stratified

    order = even_order(64, 2)
    assert sorted(order) == list(range(64))
    # Neighbours in the sequence are far apart in rank, on average.
    steps = [abs(a - b) for a, b in zip(order, order[1:])]
    assert sum(steps) / len(steps) > 64 / 4
    vals = stratified({"dist": "uniform", "min": 0, "max": 640}, 64, base=2)
    assert sorted(vals) == [5 + 10 * i for i in range(64)]
    assert vals != sorted(vals)
