"""The reduction from a trace to busy/idle, modules, ops and gaps: on a
hand-made trace whose answers are known, and on a small trace recorded on
the chip (0.25 s of `mistral-7b.chat-steady`, PR 24, TPU v5 lite) kept
beside this file."""
import gzip
import json
import os

import pytest

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
