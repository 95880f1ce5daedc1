"""The first token's life by stage for the band of requests around the
percentile a cell judges (`harness/first_token.py`): on hand-made records
whose answers are known, on breakdowns without the stages (the parent of
PR 37), and on a recorded `ctx["flight"]` with its measured records
(`first_token_sample.json.gz`: the traced run of `mistral-7b.longprompt-steady`,
PR 37, TPU v5 lite, seed 3700100007) kept beside this file."""
import gzip
import json
import os

import pytest

from harness import first_token
from harness.load import Record
from harness.manifest import load_layer_metric

STAGES = ("slot_wait", "loop_wait", "flush", "place", "prefill", "deliver")


def _request(i, total_ms, **stages_ms):
    """A request due at i s whose stages are given in ms; `prefill` takes
    what is left of `total_ms`."""
    st = {"late": 1.0, "slot_wait": 0.0, "loop_wait": 0.0, "flush": 0.0, "place": 4.0,
          "deliver": 0.5, **stages_ms}
    st["prefill"] = total_ms - sum(st.values())
    due = float(i)
    sent = due + st["late"] / 1e3
    ttft_s = sum(st[k] for k in first_token.ENGINE_STAGES) / 1e3
    rec = Record(i, "window", 1500, 32, due=due, sent=sent,
                 first=sent + ttft_s + st["deliver"] / 1e3, request_id=f"req-{i}")
    bd = {name + "_s": st[name] / 1e3 for name in first_token.ENGINE_STAGES}
    bd.update(queue_s=bd["slot_wait_s"] + bd["loop_wait_s"] + bd["flush_s"], ttft_s=ttft_s)
    return rec, bd


def hand_made():
    """101 requests, first - due from 100 to 200 ms: the i-th waits i ms
    for the loop; the upper tenth also waits 20 ms of flush."""
    records, flight = [], {}
    for i in range(101):
        rec, bd = _request(i, 100.0 + i, loop_wait=float(i) - (20.0 if i >= 90 else 0.0),
                           flush=20.0 if i >= 90 else 0.0)
        records.append(rec)
        flight[rec.request_id] = bd
    return {"records": records, "flight": flight}


def test_the_bands_stages_add_up_to_the_bands_mean_first_token():
    ctx = hand_made()
    mid = first_token.band_means_ms(ctx, "ttft50")
    assert mid["requests"] == 21                       # i = 40 .. 60
    assert mid["total"] == pytest.approx(150.0)
    assert mid["loop_wait"] == pytest.approx(50.0) and mid["flush"] == 0.0
    assert mid["prefill"] == pytest.approx(100.0 - 1.0 - 4.0 - 0.5)
    tail = first_token.band_means_ms(ctx, "ttft95")
    assert tail["requests"] == 11                      # i = 90 .. 100
    assert tail["total"] == pytest.approx(195.0)
    assert tail["flush"] == pytest.approx(20.0) and tail["loop_wait"] == pytest.approx(75.0)
    for means in (mid, tail):
        assert sum(means[s] for s in first_token.STAGES) == pytest.approx(means["total"])
        assert means["late"] == pytest.approx(1.0) and means["deliver"] == pytest.approx(0.5)
    text = first_token.table(ctx)
    assert "band ttft50 (21 requests)" in text and "band ttft95 (11 requests)" in text


@pytest.mark.parametrize("spoil", ["parent", "closed-loop", "no-recorder", "no-first-token"])
def test_nothing_to_tile_reads_as_none(spoil):
    ctx = hand_made()
    if spoil == "parent":  # LatencyBreakdown before PR 37: no stage fields
        ctx["flight"] = {k: {"queue_s": v["queue_s"], "placement_s": 0.1, "prefill_s": 0.006,
                             "ttft_s": v["ttft_s"]} for k, v in ctx["flight"].items()}
    elif spoil == "closed-loop":
        for r in ctx["records"]:
            r.due = None
    elif spoil == "no-recorder":
        ctx["flight"] = {}
    else:
        for r in ctx["records"]:
            r.first = None
    assert first_token.band_means_ms(ctx, "ttft50") is None
    assert first_token.table(ctx) == ""
    for stage in STAGES:
        for band in first_token.BANDS:
            assert load_layer_metric(f"first_token.{stage}_ms.{band}").read(ctx) is None


@pytest.mark.parametrize("band,moves", [("ttft50", "ttft_p50_ms"), ("ttft95", "gap_p95_ms")])
@pytest.mark.parametrize("stage", STAGES)
def test_first_token_reader_files(stage, band, moves):
    mod = load_layer_metric(f"first_token.{stage}_ms.{band}")
    layer = "provider boundary" if stage == "deliver" else "engine scheduler"
    assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
        layer, "ms", "lower", "program_span", moves)
    ctx = hand_made()
    assert mod.read(ctx) == pytest.approx(first_token.band_means_ms(ctx, band)[stage])


@pytest.fixture(scope="module")
def recorded_flight():
    path = os.path.join(os.path.dirname(__file__), "first_token_sample.json.gz")
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return {"records": [Record(**r) for r in raw["records"]], "flight": raw["flight"]}


def test_every_recorded_request_tiles(recorded_flight):
    """What the chip run's breakdowns say: the queue's parts sum to
    `queue_s`, `queue_s + place_s + prefill_s = ttft_s` to a microsecond
    (each is rounded to one), and the consumer saw the token after the
    handle stamped it."""
    rows = first_token._rows(recorded_flight)
    assert len(rows) == len(recorded_flight["records"]) > 100
    for r in recorded_flight["records"]:
        bd = recorded_flight["flight"][r.request_id]
        assert bd["slot_wait_s"] + bd["loop_wait_s"] + bd["flush_s"] == pytest.approx(
            bd["queue_s"], abs=2e-6)
        assert bd["queue_s"] + bd["place_s"] + bd["prefill_s"] == pytest.approx(
            bd["ttft_s"], abs=2e-6)
        assert bd["placement_s"] == pytest.approx(bd["place_s"] + bd["prefill_s"], abs=2e-6)
        assert 0 <= bd["read_blocked_s"] <= bd["prefill_s"]
    assert all(st["deliver"] >= 0 and st["late"] >= 0 for _t, st in rows)


@pytest.mark.parametrize("band", sorted(first_token.BANDS))
def test_the_recorded_bands_add_up(recorded_flight, band):
    means = first_token.band_means_ms(recorded_flight, band)
    parts = sum(means[s] for s in first_token.STAGES)
    assert parts == pytest.approx(means["total"], rel=1e-4)
    # Long prompts: the device's prefill is the largest stage of the median.
    assert means["prefill"] > 50 and means["place"] < 20
    assert means["requests"] >= 10
