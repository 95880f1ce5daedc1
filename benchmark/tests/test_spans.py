"""Who owns an idle gap and which scope owns a device second: on a
hand-made trace whose answers are known, and on a small trace recorded on
the chip (`spans_sample.json.gz`: 0.15 s of `mistral-7b.eval-batch`, PR 25,
TPU v5 lite, in `harness/spans.py`'s scheme) kept beside this file."""
import gzip
import json
import os

import pytest

from harness import spans
from harness import trace as tr
from harness.manifest import load_layer_metric

MS = 1e6  # ns
E = "omnia.engine."
NEW_READERS = [
    "engine.idle_attributed_share", "engine.idle_ms_per_step.chunk_sync",
    "engine.idle_ms_per_step.emit", "engine.idle_ms_per_step.place",
    "engine.idle_ms_per_step.dispatch", "engine.single_step_share",
    "engine.live_slots_mean", "step.kv_update_share",
]


def hand_made():
    """Two one-step decode calls and a prefill on one device; the engine
    thread's spans around them. Device busy [0,40] [50,70] [80,100]: two
    gaps of 10 ms."""
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_decode_chunk(11)", 0 * MS, 40 * MS, None],
            ["jit_prefill_insert(7)", 50 * MS, 20 * MS, None],
            ["jit_decode_chunk(11)", 80 * MS, 20 * MS, None],
        ]},
        {"name": "XLA Ops", "events": [
            ["while.1", 0 * MS, 40 * MS, "layers.scan_io"],  # container: not counted
            ["dynamic-slice_bitcast_fusion.4", 0 * MS, 16 * MS, "layers.scan_io"],
            ["pad_add_fusion.5", 16 * MS, 4 * MS, "kv.update"],
            ["decode_gqa_attention.3", 20 * MS, 5 * MS, "attn.decode"],
            ["fusion.184", 25 * MS, 10 * MS, "mlp"],
            ["copy.190", 35 * MS, 5 * MS, "xla.copy"],
            ["fusion.171", 50 * MS, 20 * MS, "attn.prefill"],
            ["dynamic-slice_bitcast_fusion.4", 80 * MS, 10 * MS, "layers.scan_io"],
            ["fusion.184", 90 * MS, 10 * MS, "mlp"],
        ]},
    ]}
    engine = {"name": "python3", "events": [
        [E + "step", 0 * MS, 47 * MS, {"mono_ns": 5_000_000_000, "queued": 1, "inflight": 0}],
        [E + "decode_dispatch", 0 * MS, 1 * MS, {"chunk": 1, "active": 32, "single": 1}],
        [E + "chunk_sync", 1 * MS, 41 * MS, {"chunk": 1, "drained": 0}],  # 2 ms past the device
        [E + "emit", 42 * MS, 4 * MS, {"tokens": 32, "finished": 1}],
        # [46,47] is the step's own time, [47,48] nobody's.
        [E + "step", 48 * MS, 51 * MS, {"mono_ns": 5_048_000_000, "queued": 1, "inflight": 0}],
        [E + "claim", 48 * MS, 1 * MS, {"request_id": "req-7"}],
        [E + "place", 49 * MS, 29 * MS, {"request_id": "req-7", "slot": 3}],
        [E + "prefill_dispatch", 49.5 * MS, 1 * MS, {"request_id": "req-7", "take": 500}],
        [E + "decode_dispatch", 78 * MS, 3 * MS, {"chunk": 1, "active": 32, "single": 1}],
        [E + "chunk_sync", 81 * MS, 18 * MS, {"chunk": 1, "drained": 0}],
    ]}
    caller = {"name": "python3", "events": [
        [E + "submit", 43 * MS, 1 * MS, {"request_id": "req-8", "n_prompt": 300}]]}
    return {"planes": [dev, {"name": "/host:CPU", "lines": [engine, caller]}]}


def test_scope_of():
    path = "jit(decode_chunk)/while/body/closed_call/layers/while/body/closed_call/"
    assert spans.scope_of(path + "kv.update/vmap(vmap())/scatter") == "kv.update"
    assert spans.scope_of(path + "mlp/moe.experts/moe.route/dot_general") == "moe.route"
    assert spans.scope_of(
        path + "attn.decode/jit(decode_gqa_attention)/decode_gqa_attention/pallas_call"
    ) == "attn.decode"
    # What the scan emits itself sits directly under `layers`.
    assert spans.scope_of(
        "jit(decode_chunk)/while/body/closed_call/layers/while/body/dynamic_update_slice"
    ) == spans.SCAN_IO
    assert spans.scope_of("jit(decode_chunk)/while/body/closed_call/sample/top_k") == "sample"
    assert spans.scope_of("jit(decode_chunk)/broadcast_in_dim") == spans.UNSCOPED
    # As the profiler writes it: `<op_name>:<op type, often empty>`.
    assert spans.scope_of("jit(f)/layers/while/body/closed_call/mlp/dot_general:") == "mlp"


def _pb(field: int, value) -> bytes:
    """One protobuf field: an int as a varint, bytes/str length-delimited."""
    def varint(n: int) -> bytes:
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    data = value.encode() if isinstance(value, str) else value
    return varint(field << 3 | 2) + varint(len(data)) + data


def test_op_names_reads_the_event_metadata_table(tmp_path):
    """A hand-encoded XSpace (xplane.proto's field numbers): a device plane
    whose ops keep their op name in the `tf_op` stat of their metadata, as
    a string or as a reference to a stat name, and a host plane."""
    def stat_meta(key, name):
        return _pb(5, _pb(1, key) + _pb(2, _pb(1, key) + _pb(2, name)))

    def event_meta(key, name, *stats):
        body = _pb(1, key) + _pb(2, name) + b"".join(_pb(5, st) for st in stats)
        return _pb(4, _pb(1, key) + _pb(2, body))

    fusion = "%fusion.5 = bf16[32,8]{1,0} fusion(bf16[8] %p), kind=kLoop"
    line = _pb(3, _pb(2, "XLA Ops") + _pb(4, _pb(1, 1) + _pb(2, 1000) + _pb(3, 500)))
    device = (
        _pb(1, 7) + _pb(2, "/device:TPU:0") + line
        + stat_meta(9, "tf_op") + stat_meta(10, "flops")
        + stat_meta(11, "jit(decode_chunk)/sample/top_k:")
        + event_meta(1, fusion, _pb(1, 10) + _pb(3, 12345),
                     _pb(1, 9) + _pb(5, "jit(decode_chunk)/layers/while/body/squeeze:"))
        + event_meta(2, "%sort.3 = f32[8] sort(f32[8] %x)", _pb(1, 9) + _pb(7, 11))
        + event_meta(3, "%copy.190 = bf16[8] copy(bf16[8] %y)", _pb(1, 10) + _pb(3, 1))
    )
    host = _pb(2, "/host:CPU") + event_meta(1, "omnia.engine.step")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb(1, device) + _pb(1, host))
    assert spans.op_names(str(path)) == {"/device:TPU:0": {
        fusion: "jit(decode_chunk)/layers/while/body/squeeze:",
        "%sort.3 = f32[8] sort(f32[8] %x)": "jit(decode_chunk)/sample/top_k:",
    }}


def test_innermost_segments_tile_a_thread_without_overlap():
    events = hand_made()["planes"][1]["lines"][0]["events"]
    segs = tr.innermost_segments(events)
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))
    total = {}
    for s, e, name in segs:
        total[name] = total.get(name, 0.0) + (e - s) / MS
    assert total[E + "step"] == pytest.approx(1.0)       # [46,47]; the second has none
    assert total[E + "place"] == pytest.approx(28.0)     # 29 less the program call
    assert total[E + "chunk_sync"] == pytest.approx(59.0)
    assert sum(total.values()) == pytest.approx(47 + 51)


def test_reduce_hand_made_trace():
    r = spans.reduce(hand_made())
    assert r["devices"] == 1 and r["has_engine_spans"]
    assert r["decode_steps"] == 2
    assert r["idle_s"] == pytest.approx(0.020)
    # Gap [40,50]: sync tail 2, emit 4, step's own 1, nobody 1, claim 1,
    # place 0.5 before its program call and that call's first 0.5.
    # Gap [70,80]: place 8 (the first token's readback and the scatters),
    # then the decode dispatch 2.
    assert {k: round(v * 1e3, 6) for k, v in r["idle_by_phase"].items()} == {
        E + "chunk_sync": 2.0, E + "emit": 4.0, E + "step": 1.0,
        spans.UNATTRIBUTED: 1.0, E + "claim": 1.0, E + "place": 8.5,
        E + "prefill_dispatch": 0.5, E + "decode_dispatch": 2.0,
    }
    assert sum(r["idle_by_phase"].values()) == pytest.approx(r["idle_s"])
    # The caller's submit is a phase of the table and owns no gap.
    assert r["phases"][E + "submit"]["count"] == 1
    assert E + "submit" not in r["idle_by_phase"]
    assert r["phases"][E + "place"]["self_s"] == pytest.approx(0.028)
    assert r["scopes"]["jit_decode_chunk"] == {
        "layers.scan_io": pytest.approx(0.026), "kv.update": pytest.approx(0.004),
        "attn.decode": pytest.approx(0.005), "mlp": pytest.approx(0.020),
        "xla.copy": pytest.approx(0.005),
    }
    assert r["scopes"]["jit_prefill_insert"] == {"attn.prefill": pytest.approx(0.020)}
    assert spans.clock_offset_s(r) == pytest.approx(-5.0)
    assert "omnia.engine.place" in spans.tables(r)


def ctx_of(reduced, **counters):
    return {"spans": reduced, "counters_window": counters,
            "traced": {"dir": "/nowhere", "counters": {}}}


def test_readers_on_the_hand_made_trace():
    ctx = ctx_of(spans.reduce(hand_made()), decode_steps=200, decode_dispatches=200,
                 decode_dispatches_single=190, decode_slot_steps=6000)
    got = {name: load_layer_metric(name).read(ctx) for name in NEW_READERS}
    assert got == {
        "engine.idle_attributed_share": pytest.approx(95.0),
        "engine.idle_ms_per_step.chunk_sync": pytest.approx(1.0),
        "engine.idle_ms_per_step.emit": pytest.approx(2.0),
        "engine.idle_ms_per_step.place": pytest.approx(4.25),
        "engine.idle_ms_per_step.dispatch": pytest.approx(1.25),
        "engine.single_step_share": pytest.approx(95.0),
        "engine.live_slots_mean": pytest.approx(30.0),
        "step.kv_update_share": pytest.approx(50.0),  # (26 + 4) of 60 ms
    }


def test_readers_return_nothing_on_a_program_without_spans_scopes_or_counters():
    """The parent of PR 25: no `omnia.*` span, every op unscoped, none of
    the new counters. Also an untraced run."""
    raw = hand_made()
    raw["planes"][1]["lines"] = []
    for e in raw["planes"][0]["lines"][1]["events"]:
        e[3] = spans.UNSCOPED
    bare = ctx_of(spans.reduce(raw), decode_steps=200, tokens_generated=6000)
    untraced = {"traced": None, "counters_window": {"decode_steps": 200}}
    for name in NEW_READERS:
        assert load_layer_metric(name).read(bare) is None, name
        assert load_layer_metric(name).read(dict(untraced)) is None, name


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(os.path.dirname(__file__), "spans_sample.json.gz")
    with gzip.open(path, "rt") as f:
        return spans.reduce(json.load(f))


def test_recorded_sample(recorded):
    r = recorded
    assert r["has_engine_spans"] and r["decode_steps"] > 0
    assert sum(r["idle_by_phase"].values()) == pytest.approx(r["idle_s"])
    per = r["scopes"]["jit_decode_chunk"]
    assert {"layers.scan_io", "attn.decode", "mlp", "sample"} <= set(per)
    ctx = ctx_of(r, decode_steps=100, decode_dispatches=100,
                 decode_dispatches_single=100, decode_slot_steps=3200)
    got = {name: load_layer_metric(name).read(ctx) for name in NEW_READERS}
    assert all(isinstance(v, float) for v in got.values()), got
    # Spans that straddle the sample's edges were cut away with it, so a
    # fifth of its idle time has no owner; the whole trace read 99.6 %.
    assert got["engine.idle_attributed_share"] == pytest.approx(79.1, abs=0.1)
    assert got["step.kv_update_share"] == pytest.approx(53.6, abs=0.1)
    # The one-step program's two whole-cache copies carry no op name.
    assert per["xla.copy"] / sum(per.values()) == pytest.approx(0.23, abs=0.005)
    assert per.get("unscoped", 0.0) / sum(per.values()) < 0.001
    per_step = sum(got[f"engine.idle_ms_per_step.{k}"]
                   for k in ("chunk_sync", "emit", "place", "dispatch"))
    assert per_step <= r["idle_s"] / r["decode_steps"] * 1e3 + 1e-9
