"""Every cell's readers run on a context made from the recorded traces and
hand-made records: each returns a number or None, never raises, and the
shares stay under 100 %. The readers of engine phases and named scopes
(all cells since PR 27) read PR 25's recorded sample, which has them."""
import gzip
import json
import os

import pytest

from harness import roofline, spans, trace as tr
from harness.load import Record
from harness.layer_common import kernel_in_decode
from harness.manifest import Cell, benchmark_json, decode_kernel


@pytest.fixture(scope="module")
def ctx():
    path = os.path.join(os.path.dirname(__file__), "trace_sample.json.gz")
    with gzip.open(path, "rt") as f:
        reduced = tr.reduce(json.load(f))
    with gzip.open(os.path.join(os.path.dirname(__file__), "spans_sample.json.gz"), "rt") as f:
        spans_reduced = spans.reduce(json.load(f))
    records = [
        Record(i, "window", 400 + 10 * i, 64, due=10.0 + i, sent=10.001 + i,
               first=10.3 + i, last=12.8 + i, done=12.8 + i, tokens=64,
               finish="length", request_id=f"req-{i}")
        for i in range(20)
    ]
    return {
        "seconds": 20.0, "records": records, "all_records": records, "chips": 1,
        "peaks": roofline.peaks("TPU v5 lite"), "engine": {"num_slots": 32},
        "counters_window": {"decode_steps": 500, "tokens_generated": 15000,
                            "prefill_steps": 100, "prefill_tokens": 40000,
                            "decode_dispatches": 100, "decode_dispatches_single": 50,
                            "decode_slot_steps": 12000},
        "setup": {"setup_s": 30.0, "phases": {"warmup_compile": 5.0, "warmup_restore": 0.5}},
        "flight": {f"req-{i}": {"queue_s": 0.05 * i} for i in range(20)},
        "trace": reduced, "spans": spans_reduced,  # as `spans.reduced(ctx)` leaves it
        "traced": {"t": (14.0, 14.25), "counters": {"decode_steps": 4, "prefill_tokens": 500}},
    }


def _base(metric: str) -> str:
    for tag in ("batch", "ttft50"):
        if metric.endswith("_roofline") and metric.startswith(tag + "."):
            return metric[len(tag) + 1:]
        if metric.endswith("." + tag) and not metric.startswith(
                ("device.idle_share", "generator.", "engine.queue_wait", "step.prefill")):
            return metric[:-len(tag) - 1]
    return metric


@pytest.mark.parametrize("name", [w["name"] for w in benchmark_json()["workloads"]])
def test_cell_readers(name, ctx):
    cell = Cell(name)
    ctx = {**ctx, "model": cell.model, "chips": cell.chips}
    # A quantity reads alike under each of its names (`.batch`, `.ttft50`,
    # `batch.`, `ttft50.`: the end-to-end metric its cell judges).
    values = {_base(metric): mod.read(ctx) for metric, mod in cell.layer_metrics}
    assert all(v is None or isinstance(v, (int, float)) for v in values.values()), values
    assert values["programs.warmup_s"] == pytest.approx(5.5)
    assert values["engine.single_step_share"] == pytest.approx(10.0)
    assert values["engine.live_slots_mean"] == pytest.approx(24.0)
    assert 0 < values["engine.idle_attributed_share"] <= 100
    assert 0 < values["step.kv_update_share"] < 100
    for phase in ("chunk_sync", "emit", "place", "dispatch"):
        assert values[f"engine.idle_ms_per_step.{phase}"] >= 0
    # The recorded trace is a one-chip, 14-layer run of the GQA decode kernel:
    # a configuration with another kernel finds no steps in it.
    held = kernel_in_decode(ctx) is not None
    if cell.chips == 1 and held:
        assert values["step.decode_ms"] == pytest.approx(49.3, rel=0.02)
        assert 0 < values["decode_step_roofline"] < 100
        assert 0 < values[f"{decode_kernel(cell.model)}_roofline"] < 100
    elif not held:
        assert values["step.decode_ms"] is None and values["decode_step_roofline"] is None
    for k, v in values.items():
        if k.startswith("device.idle_share"):
            assert 0 <= v < 100
    if "engine.batch_occupancy" in values:
        assert values["engine.batch_occupancy"] == pytest.approx(100 * 14900 / 16000)
