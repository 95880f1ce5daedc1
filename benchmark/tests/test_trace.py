"""The reduction from a trace to busy/idle, modules, ops and gaps: on a
hand-made trace whose answers are known, and on a small trace recorded on
the chip (0.25 s of `mistral-7b.chat-steady`, PR 24, TPU v5 lite) kept
beside this file. Since PR 27 a gap is named by the engine phase that owns
it: on the hand-made trace with an engine thread, and on PR 25's recorded
sample of `mistral-7b.eval-batch`, which holds `omnia.engine.*` spans."""
import gzip
import json
import os

import pytest

from harness import spans as sp
from harness import trace as tr
from harness.layer_common import decode_steps_in_trace, kernel_in_decode

MS = 1e6  # ns


def hand_made():
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_decode_chunk(11)", 0 * MS, 40 * MS],
            ["jit_scatter(5)", 45 * MS, 0.002 * MS],
            ["jit_prefill_insert(7)", 50 * MS, 20 * MS],
            ["jit_decode_chunk(11)", 80 * MS, 20 * MS],
        ]},
        {"name": "XLA Ops", "events": [
            ["while.1", 0 * MS, 40 * MS],             # container: not counted
            ["decode_gqa_attention.3", 0 * MS, 5 * MS],
            ["fusion.1", 5 * MS, 15 * MS],
            ["decode_gqa_attention.3", 20 * MS, 5 * MS],
            ["all-reduce.2", 25 * MS, 5 * MS],
            ["fusion.1", 30 * MS, 10 * MS],
            ["fusion.9", 50 * MS, 20 * MS],
            ["decode_gqa_attention.3", 80 * MS, 5 * MS],
            ["fusion.1", 85 * MS, 15 * MS],
        ]},
    ]}
    host = {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["bench.submit", 41 * MS, 2 * MS]]}]}
    return {"planes": [dev, host]}


def test_short_name():
    assert tr.short_name("%fusion.5 = bf16[32,8]{1,0} fusion(bf16[8] %p)") == "fusion.5"
    assert tr.short_name("while.3") == "while.3"


def test_reduce_hand_made_trace():
    r = tr.reduce(hand_made())
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.100)
    # busy: [0,40] + [50,70] + [80,100]; the while container adds nothing.
    assert r["busy_s"] == pytest.approx(0.080)
    assert r["modules"]["jit_decode_chunk"]["count"] == 2
    assert r["modules"]["jit_decode_chunk"]["seconds"] == pytest.approx(0.060)
    assert r["modules"]["jit_prefill_insert"]["seconds"] == pytest.approx(0.020)
    assert "while.1" not in r["ops"]
    assert r["ops"]["fusion.1"] == pytest.approx(0.040)
    assert r["ops_in_module"]["jit_decode_chunk"]["decode_gqa_attention.3"] == [
        3, pytest.approx(0.015)]
    assert r["collective_s_in_module"]["jit_decode_chunk"] == pytest.approx(0.005)
    # Two gaps of 10 ms: the first overlaps this benchmark's own submit and
    # ends at the prefill (the scatter of 2 us is bookkeeping, not a step).
    assert r["idle_gaps"] == {
        "bench.submit; before jit_prefill_insert": pytest.approx(0.010),
        "engine loop, not attributed; before jit_decode_chunk": pytest.approx(0.010),
    }
    assert r["longest_gap_s"] == pytest.approx(0.010)
    b = tr.breakdown(r)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.040)]
    assert len(b["idle_gaps"]) == 2
    # Steps come from the kernel: 3 calls over 1 layer.
    ctx = {"trace": r, "model": {"num_hidden_layers": 1}}
    assert kernel_in_decode(ctx) == (3, pytest.approx(0.015))
    assert decode_steps_in_trace(ctx) == 3


def test_a_gap_is_named_by_the_engine_phase_that_owns_most_of_it():
    """The same device lines with an engine thread beside them: the first
    gap [40,50] lies under `emit` [41,47] (6 ms of self time) inside a step
    [0,48] whose own time is [40,41] and [47,48]; the second [70,80] under
    `place` [60,79] less its `prefill_dispatch` child [60,72]."""
    raw = hand_made()
    e = "omnia.engine."
    raw["planes"][1]["lines"].append({"name": "engine", "events": [
        [e + "step", 0 * MS, 48 * MS],
        [e + "chunk_sync", 1 * MS, 39 * MS],
        [e + "emit", 41 * MS, 6 * MS],
        [e + "step", 49 * MS, 50 * MS],
        [e + "place", 60 * MS, 19 * MS],
        [e + "prefill_dispatch", 60 * MS, 12 * MS],
    ]})
    r = tr.reduce(raw)
    assert r["idle_gaps"] == {
        "omnia.engine.emit; before jit_prefill_insert": pytest.approx(0.010),
        "omnia.engine.place; before jit_decode_chunk": pytest.approx(0.010),
    }
    # A thread without a step span is not an engine thread: its spans name a
    # gap only where no phase does, as `bench.submit` did above.
    assert r["busy_s"] == pytest.approx(0.080)


def test_recorded_sample_with_engine_spans_names_its_gaps():
    """PR 25's sample is in `harness/spans.py`'s scheme, a fourth element
    on every event; `trace.py`'s has three."""
    path = os.path.join(os.path.dirname(__file__), "spans_sample.json.gz")
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    by_spans = sp.reduce(raw)
    for plane in raw["planes"]:
        for line in plane["lines"]:
            line["events"] = [ev[:3] for ev in line["events"]]
    r = tr.reduce(raw)
    # One attribution (`tr.phase_shares`): the seconds named here are the
    # seconds `spans.reduce` split among the phases, and the phase that owns
    # most idle time there names a gap here.
    assert sum(r["idle_gaps"].values()) == pytest.approx(by_spans["idle_s"])
    biggest = max(by_spans["idle_by_phase"].items(), key=lambda kv: kv[1])[0]
    assert any(label.startswith(biggest + ";") for label in r["idle_gaps"])
    owners = {}
    for label, seconds in r["idle_gaps"].items():
        owner = label.split("; before ")[0]
        owners[owner] = owners.get(owner, 0.0) + seconds
    named = sum(s for o, s in owners.items() if o.startswith("omnia.engine."))
    assert named / sum(owners.values()) > 0.75, owners
    # 0.15 s, two steps; the steps that straddle its edges were cut away.
    assert {"omnia.engine.chunk_sync", "omnia.engine.decode_dispatch"} <= set(owners)
    top = tr.breakdown(r)["idle_gaps"][0][0]
    assert top.startswith("omnia.engine."), top


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce({"planes": [hand_made()["planes"][1]]})


def test_recorded_trace_from_the_chip():
    path = os.path.join(os.path.dirname(__file__), "trace_sample.json.gz")
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    r = tr.reduce(raw)
    assert r["devices"] == 1
    assert 0.1 < r["window_s"] <= 0.2501
    assert 0 < r["busy_s"] <= r["window_s"]
    decode = r["modules"]["jit_decode_chunk"]
    assert decode["count"] >= 1 and decode["seconds"] <= r["busy_s"]
    # The kernel is told apart by name, once a layer a step (14 layers).
    ctx = {"trace": r, "model": {"num_hidden_layers": 14}}
    calls, seconds = kernel_in_decode(ctx)
    assert calls % 14 == 0 and 0 < seconds < decode["seconds"]
    assert decode_steps_in_trace(ctx) == calls / 14
    # Containers are left out; the per-layer cache copies are what it shows.
    assert not any(n.startswith("while") for n in r["ops"])
    top = [n for n, _s in tr.breakdown(r)["device_ops"]]
    assert any("dynamic-slice" in n or "dynamic-update-slice" in n for n in top)
