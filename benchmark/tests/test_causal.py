"""Which device program a read-back waited for (`harness/causal.py`): on a
hand-made trace whose answers are known, on traces that must not join, and
on a small trace recorded on the chip (`causal_sample.json.gz`: the first
second of device time of `mistral-7b.chat-steady`'s traced window, PR 37,
TPU v5 lite, seed 3700300007; `harness/spans.py::load`'s scheme with the
device's op lines left out, cut by `trace.sample`) kept beside this file."""
import copy
import gzip
import json
import os

import pytest

from harness import causal
from harness.manifest import load_layer_metric

MS = 1e6  # ns
E = "omnia.engine."
READERS = {
    "engine.readback_lag_ms": "gap_p95_ms",
    "engine.readback_lag_ms.batch": "out_tokens_per_s_chip",
    "engine.readback_lag_ms.ttft50": "ttft_p50_ms",
    "first_token.read_lag_ms.ttft50": "ttft_p50_ms",
    "first_token.read_lag_ms.ttft95": "gap_p95_ms",
}


def hand_made():
    """A chunk of 8 dispatched before the trace began and read in it, one
    dispatched and read in it, a placement with the one step behind its
    prefill, a chunk read late by the host, and a chunk whose module lies
    past the trace's end."""
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": [
        ["jit_decode_chunk(9)", 0 * MS, 8 * MS, None],       # seq 4: before the trace
        ["jit_decode_chunk(9)", 10 * MS, 8 * MS, None],      # seq 5
        ["jit_activate_slot(3)", 19.9 * MS, 0.01 * MS, None],
        ["jit_prefill_insert(7)", 20 * MS, 30 * MS, None],   # req-7
        ["jit_decode_chunk(11)", 50 * MS, 2 * MS, None],     # seq 6: one step
        ["jit_decode_chunk(9)", 60 * MS, 8 * MS, None],      # seq 7
    ]}]}
    stages = {"place_ms": 0.8, "prefill_ms": 31.0, "read_blocked_ms": 30.1}
    engine = {"name": "python3", "events": [
        [E + "step", 1 * MS, 8.4 * MS, {"mono_ns": 5_000_000_000}],
        [E + "chunk_sync", 1 * MS, 7.5 * MS, {"chunk": 8, "seq": 4}],
        [E + "emit", 8.5 * MS, 0.5 * MS, {"tokens": 64, "finished": 0, "seq": 4}],
        [E + "step", 9.4 * MS, 62 * MS, {"mono_ns": 5_008_400_000}],
        [E + "decode_dispatch", 9.5 * MS, 0.5 * MS, {"chunk": 8, "seq": 5}],
        [E + "chunk_sync", 10.5 * MS, 7.9 * MS, {"chunk": 8, "seq": 5}],  # ends 18.4
        [E + "emit", 18.4 * MS, 0.6 * MS, {"tokens": 64, "finished": 0, "seq": 5}],
        [E + "claim", 19 * MS, 0.2 * MS, {"request_id": "req-7", "slot_wait_ms": 0.0,
                                          "loop_wait_ms": 12.0, "flush_ms": 3.0}],
        [E + "place", 19.2 * MS, 0.7 * MS, {"request_id": "req-7", "deferred": 1}],
        [E + "prefill_dispatch", 19.5 * MS, 0.3 * MS,
         {"request_id": "req-7", "take": 500, "bucket": 512, "seq": 0, "last": 1}],
        [E + "decode_dispatch", 20 * MS, 0.5 * MS, {"chunk": 1, "seq": 6, "single": 1}],
        [E + "chunk_sync", 20.6 * MS, 30.1 * MS, {"chunk": 0, "request_id": "req-7"}],
        [E + "emit", 50.7 * MS, 0.3 * MS, {"tokens": 1, "finished": 0,
                                           "request_id": "req-7", **stages}],
        # The host came late: the step's tokens lay ready for 3 ms.
        [E + "chunk_sync", 55 * MS, 0.3 * MS, {"chunk": 1, "seq": 6}],
        [E + "emit", 55.3 * MS, 0.5 * MS, {"tokens": 9, "finished": 0, "seq": 6}],
        [E + "decode_dispatch", 59 * MS, 0.5 * MS, {"chunk": 8, "seq": 7}],
        [E + "decode_dispatch", 66 * MS, 0.5 * MS, {"chunk": 8, "seq": 8}],
        [E + "chunk_sync", 67 * MS, 3 * MS, {"chunk": 8, "seq": 7}],     # ends 70
        [E + "emit", 70 * MS, 0.5 * MS, {"tokens": 72, "finished": 0, "seq": 7}],
    ]}
    caller = {"name": "python3", "events": [
        [E + "submit", 4 * MS, 0.1 * MS, {"request_id": "req-7", "n_prompt": 500}]]}
    return {"planes": [dev, {"name": "/host:CPU", "lines": [engine, caller]}]}


def test_hand_made_join():
    j, why = causal.join(hand_made())
    assert why is None
    rows = {r["seq"]: r for r in j["chunks"]}
    assert list(rows) == [5, 6, 7, 8]
    assert rows[5]["enqueue_to_start_ms"] == pytest.approx(0.5)
    assert rows[5]["device_ms"] == pytest.approx(8.0)
    assert rows[5]["readback_lag_ms"] == pytest.approx(0.4)   # 18.4 - 18
    # Dispatched behind the prefill: it waited the prefill out on the queue.
    assert rows[6]["enqueue_to_start_ms"] == pytest.approx(30.0)
    # Read by a host that came late: only the read itself is lag.
    assert rows[6]["readback_lag_ms"] == pytest.approx(0.3)
    assert rows[7]["readback_lag_ms"] == pytest.approx(2.0)   # 70 - 68
    assert rows[8]["device_ms"] is None and rows[8]["readback_lag_ms"] is None
    (p,) = j["placements"]
    assert (p["request_id"], p["pieces"], p["take"]) == ("req-7", 1, 500)
    assert p["enqueue_to_start_ms"] == pytest.approx(0.5)
    assert p["device_ms"] == pytest.approx(30.0)
    assert p["readback_lag_ms"] == pytest.approx(0.7) == p["read_lag_ms"]
    assert (p["loop_wait_ms"], p["flush_ms"], p["prefill_ms"]) == (12.0, 3.0, 31.0)
    assert j["unjoined"] == {
        "decode_shift": 1,  # seq 4's module: dispatched before the trace
        "prefill_shift": 0,
        "modules_past_the_last_dispatch_span": 0,
        "syncs_of_dispatches_before_the_trace": 1,
        "dispatches_whose_module_is_past_the_trace": 1,
        "dispatches_not_read_in_the_trace": 0,
        "first_tokens_with_a_piece_outside_the_trace": 0,
    }
    ctx = {"causal": j}
    assert causal.readback_lag_ms(ctx) == pytest.approx((0.4 + 0.3 + 2.0) / 3)
    assert causal.read_lag_ms(ctx) == pytest.approx(0.7)
    text = causal.tables(j)
    assert "1 placements and 4 decode dispatches" in text and "seq 7" in text


def test_a_first_token_read_late_has_a_read_lag_beyond_its_readback_lag():
    raw = hand_made()
    sync = next(e for e in raw["planes"][1]["lines"][0]["events"]
                if e[3].get("chunk") == 0)
    sync[1], sync[2] = 52 * MS, 0.4 * MS  # asked 2 ms after the prefill ended
    (p,) = causal.join(raw)[0]["placements"]
    assert p["readback_lag_ms"] == pytest.approx(0.4)
    assert p["read_lag_ms"] == pytest.approx(2.4)


def test_a_chunked_extend_joins_each_piece_and_reads_the_last():
    raw = hand_made()
    mods = raw["planes"][0]["lines"][0]["events"]
    mods[3:4] = [["jit_extend_nosample(5)", 20 * MS, 14 * MS, None],
                 ["jit_extend(6)", 34 * MS, 16 * MS, None]]
    events = raw["planes"][1]["lines"][0]["events"]
    i = next(k for k, e in enumerate(events) if e[0] == E + "prefill_dispatch")
    events[i:i + 1] = [
        [E + "prefill_dispatch", 19.5 * MS, 0.1 * MS,
         {"request_id": "req-7", "take": 256, "bucket": 256, "seq": 0, "last": 0}],
        [E + "prefill_dispatch", 19.7 * MS, 0.1 * MS,
         {"request_id": "req-7", "take": 244, "bucket": 256, "seq": 1, "last": 1}]]
    (p,) = causal.join(raw)[0]["placements"]
    assert (p["pieces"], p["take"]) == (2, 500)
    assert p["device_ms"] == pytest.approx(30.0)
    assert p["read_lag_ms"] == pytest.approx(0.7)


def steady(n=12):
    """`n` chunks of 8, each dispatched, run (8 ms), read 0.4 ms after its
    module ended and emitted before the next is dispatched: 10 ms apart."""
    mods, events = [], [[E + "step", 0.0, 10 * n * MS, {"mono_ns": 1}]]
    for i in range(n):
        at = 10 * i * MS
        mods.append(["jit_decode_chunk(9)", at + 0.6 * MS, 8 * MS, None])
        events += [
            [E + "decode_dispatch", at, 0.5 * MS, {"chunk": 8, "seq": 100 + i}],
            [E + "chunk_sync", at + 0.5 * MS, 8.5 * MS, {"chunk": 8, "seq": 100 + i}],
            [E + "emit", at + 9 * MS, 0.5 * MS, {"tokens": 64, "finished": 0, "seq": 100 + i}],
        ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": mods}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": events}]}]}


def _modules(raw):
    return raw["planes"][0]["lines"][0]["events"]


def test_a_steady_pipeline_joins_chunk_for_chunk():
    j, why = causal.join(steady())
    assert why is None and len(j["chunks"]) == 12 and not j["placements"]
    assert all(r["readback_lag_ms"] == pytest.approx(0.4) for r in j["chunks"])
    assert all(r["enqueue_to_start_ms"] == pytest.approx(0.6) for r in j["chunks"])
    assert not any(j["unjoined"].values())


def _shifted():
    """Every module 5 ms early: each starts before its own dispatch span,
    and the next one ends after that dispatch was read."""
    raw = steady()
    for e in _modules(raw):
        e[1] -= 5 * MS
    return raw


def _all_early():
    """Twelve chunks whose modules all ran before the first dispatch span."""
    raw = steady()
    for k, e in enumerate(_modules(raw)):
        e[1] = (8 * k - 200) * MS
    return raw


def _read_early(by_ms):
    """The seventh chunk's read-back ends `by_ms` before its module does."""
    raw = steady()
    sync = next(e for e in raw["planes"][1]["lines"][0]["events"]
                if e[0] == E + "chunk_sync" and e[3]["seq"] == 106)
    sync[2] = (8.6 - by_ms - 0.5) * MS
    return raw


def _unspanned():
    """A decode program that no dispatch span accounts for, in mid-trace."""
    raw = steady()
    _modules(raw).insert(6, ["jit_decode_chunk(9)", 58.8 * MS, 1 * MS, None])
    return raw


def _edges():
    """The device's trace began after the host's (seq 5's module is not in
    it, nor seq 4's) and ended after it (two chunks more)."""
    raw = hand_made()
    mods = _modules(raw)
    del mods[:2]
    mods += [["jit_decode_chunk(9)", 70 * MS, 8 * MS, None],   # seq 8
             ["jit_decode_chunk(9)", 78 * MS, 8 * MS, None]]   # its dispatch span was not kept
    return raw


def _without_seq():
    raw = hand_made()
    for e in raw["planes"][1]["lines"][0]["events"]:
        e[3].pop("seq", None)
        e[3].pop("last", None)
    return raw


@pytest.mark.parametrize("raw,why", [
    (_all_early(), "decode: no 8 or fewer module events or dispatch spans dropped"),
    (_shifted(), "decode: no 8 or fewer"),
    (_read_early(6.0), "end before its read-back"),
    (_unspanned(), "something without a span ran the program"),
    (_without_seq(), "no .decode_dispatch span carries a seq"),
    ({"planes": [hand_made()["planes"][1]]}, "no /device:TPU:N plane"),
], ids=["modules-before-their-dispatch", "modules-a-chunk-early", "read-before-the-module-ended",
        "a-program-nobody-dispatched", "the-parents-spans", "no-device-plane"])
def test_what_does_not_line_up_is_no_join(raw, why):
    j, said = causal.join(raw)
    assert j is None and why in said


def test_the_device_trace_may_begin_and_end_after_the_hosts():
    j, why = causal.join(_edges())
    assert why is None
    rows = {r["seq"]: r for r in j["chunks"]}
    assert rows[5]["device_ms"] is None          # ran before the device's trace began
    assert rows[6]["readback_lag_ms"] == pytest.approx(0.3)
    assert rows[7]["readback_lag_ms"] == pytest.approx(2.0)
    assert rows[8]["enqueue_to_start_ms"] == pytest.approx(4.0)
    assert (j["unjoined"]["decode_shift"], j["unjoined"]["modules_past_the_last_dispatch_span"],
            j["unjoined"]["dispatches_whose_module_is_past_the_trace"]) == (-1, 1, 1)


def test_a_read_that_ends_within_the_skew_of_its_module_still_joins():
    j, why = causal.join(_read_early(0.5))
    assert why is None
    assert {r["seq"]: r for r in j["chunks"]}[106]["readback_lag_ms"] == pytest.approx(-0.5)


@pytest.mark.parametrize("late_ms,offset_ms,lag_ms", [
    (2.0, -1.75, 0.15),   # the device's clock 2 ms late: tokens "read" 1.6 ms before their module ended
    (-2.0, 1.5, 0.9),     # 2 ms early: modules "start" 1.4 ms before their dispatch span
], ids=["device-clock-late", "device-clock-early"])
def test_clocks_that_disagree_are_moved_by_the_least_that_lines_the_series_up(
        late_ms, offset_ms, lag_ms):
    raw = steady()
    for e in _modules(raw):
        e[1] += late_ms * MS
    j, why = causal.join(raw)
    assert why is None and len(j["chunks"]) == 12
    assert j["clock"]["offset_ms"] == offset_ms
    assert all(r["readback_lag_ms"] == pytest.approx(lag_ms) for r in j["chunks"])
    # What the moved clock would still allow: every lag and every enqueue ->
    # start are known to within this window, their sum (1.0 ms here) exactly.
    lo, hi = j["clock"]["window_ms"]
    assert (lo, hi) == (pytest.approx(lag_ms - 1.0), pytest.approx(lag_ms))
    assert "device clock moved by %g ms" % offset_ms in causal.tables(j)


def test_clocks_as_recorded_are_left_alone_where_the_series_line_up():
    j, _why = causal.join(steady())
    assert j["clock"]["offset_ms"] == 0
    assert j["clock"]["window_ms"] == [pytest.approx(-0.6), pytest.approx(0.4)]


def test_the_recorded_trace_joins_under_another_sessions_clocks(recorded_trace):
    """Two traces of one cell in one process read lags 1.3 ms apart (PR 37);
    one trace of twelve did not line up at all until the clock was moved."""
    want, _why = causal.join(recorded_trace)
    raw = copy.deepcopy(recorded_trace)
    for plane in raw["planes"]:
        if plane["name"].startswith("/device:TPU"):
            for line in plane["lines"]:
                for e in line["events"]:
                    e[1] += 3.6 * MS   # the lags of the sample are 2.2 to 2.7 ms
    got, why = causal.join(raw)
    assert why is None and got["clock"]["offset_ms"] < 0
    assert got["unjoined"] == want["unjoined"]
    # The spread of the lags is the trace's own, whatever the clocks' offset.
    for key in ("chunks", "placements"):
        a = [r["readback_lag_ms"] for r in want[key] if r["readback_lag_ms"] is not None]
        b = [r["readback_lag_ms"] for r in got[key] if r["readback_lag_ms"] is not None]
        assert len(a) == len(b) and min(b) >= 0
        assert max(a) - min(a) == pytest.approx(max(b) - min(b), abs=1e-6)


@pytest.fixture(scope="module")
def recorded_trace():
    path = os.path.join(os.path.dirname(__file__), "causal_sample.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_the_recorded_trace_joins(recorded_trace):
    j, why = causal.join(recorded_trace)
    assert why is None
    read = [r for r in j["chunks"] if r["readback_lag_ms"] is not None]
    assert len(read) >= 10 and j["placements"]
    # Every module starts after its dispatch span starts; no token is read
    # before its module ended (to within the clocks' skew).
    assert all(r["enqueue_to_start_ms"] >= 0 for r in j["chunks"] if r["device_ms"] is not None)
    assert all(p["enqueue_to_start_ms"] >= 0 for p in j["placements"])
    assert min(r["readback_lag_ms"] for r in read + j["placements"]) > -0.05
    assert all(p["read_lag_ms"] >= p["readback_lag_ms"] - 1e-9 for p in j["placements"])
    # The edges of the cut: at most the pipeline's depth + 1 at each.
    assert all(abs(v) <= 3 for v in j["unjoined"].values()), j["unjoined"]
    seqs = [r["seq"] for r in j["chunks"]]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    # A chunk of 8 steps of 9.7 ms, a single step a ninth of it.
    for r in j["chunks"]:
        if r["device_ms"] is not None:
            assert 8 < r["device_ms"] / max(r["chunk"], 1) < 14
    ctx = {"causal": j}
    assert 0 <= causal.readback_lag_ms(ctx) < 5
    assert 0 <= causal.read_lag_ms(ctx) < 50
    # The recorder was on: the trace alone holds each placement's stages.
    for p in j["placements"]:
        assert {"slot_wait_ms", "loop_wait_ms", "flush_ms", "place_ms", "prefill_ms",
                "read_blocked_ms"} <= set(p)
        assert p["prefill_ms"] >= p["device_ms"] - 0.5


@pytest.mark.parametrize("name", sorted(READERS))
def test_causal_reader_files(name, recorded_trace):
    mod = load_layer_metric(name)
    assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
        "engine scheduler", "ms", "lower", "device_trace", READERS[name])
    # An untraced run, and a trace of the parent's spans: nothing, no raise.
    assert mod.read({"traced": None}) is None
    assert mod.read({"causal": causal.join(_without_seq())[0]}) is None
    value = mod.read({"causal": causal.join(recorded_trace)[0]})
    assert isinstance(value, float) and value >= 0
