"""The manifest against the contract's naming rules, and the cells against
their own files."""
import json
import os
import re

import pytest

from harness.manifest import BENCH_DIR, ROOT, Cell, benchmark_json, load_layer_metric

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return benchmark_json()


def test_keys_names_units_and_lengths(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in bench["configs"]:
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_per_layer_metric_moves_a_metric_its_cells_report(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        assert m["moves"] in e2e, m
        # Without a list, a metric is due in every cell that reports what it moves.
        for cell in m.get("workloads", e2e[m["moves"]]):
            assert cell in e2e[m["moves"]], (m["name"], cell)
        mod = load_layer_metric(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE, mod.BETTER) == (
            m["layer"], m["unit"], m["moves"], m["source"], m["better"])


def test_every_cell_loads_and_reports_enough(bench):
    for w in bench["workloads"]:
        cell = Cell(w["name"])  # raises where the files disagree
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.layer_metrics
        gen = os.path.join(BENCH_DIR, "generators", cell.traffic["generator"] + ".py")
        assert os.path.exists(gen)
        if cell.traffic["generator"] == "open_poisson":
            assert cell.traffic["rate_rps"] > 0
        # Every prompt fits a bucket and leaves room for its output.
        p, o = cell.traffic["prompt_tokens"], cell.traffic["output_tokens"]
        longest = p.get("max", p.get("value"))
        out = o.get("max", o.get("value"))
        assert longest <= max(cell.engine["prefill_buckets"])
        assert longest + out <= cell.engine["max_seq"] - 2


def test_a_cell_that_lists_a_metric_must_report_what_it_moves(bench, monkeypatch):
    import harness.manifest as mf

    broken = json.loads(json.dumps(bench))
    for m in broken["end_to_end"]:
        if m["name"] == "ttft_p50_ms":  # every metric that moves it lists its cells
            m["workloads"] = ["mistral-7b.eval-batch"]
    monkeypatch.setattr(mf, "benchmark_json", lambda: broken)
    with pytest.raises(ValueError, match="does not report ttft_p50_ms"):
        Cell("mistral-7b.longprompt-steady")


def test_a_metric_without_a_list_is_due_where_what_it_moves_is_reported(bench):
    """`gap_p95_ms` is judged in chat-steady alone since the check of PR 28;
    the same quantities stand in the other cells under names that move what
    those cells judge, and read through the same function."""
    due = {c: {n for n, _ in Cell(c).layer_metrics} for c in
           ("mistral-7b.chat-steady", "mistral-7b.eval-batch", "mistral-7b.longprompt-steady")}
    assert "step.decode_ms" in due["mistral-7b.chat-steady"]
    assert {"step.decode_ms.batch", "request.gap_p95_ms.batch",
            "batch.decode_step_roofline"} <= due["mistral-7b.eval-batch"]
    assert {"step.decode_ms.ttft50", "request.gap_p95_ms.ttft50",
            "ttft50.decode_gqa_attention_roofline"} <= due["mistral-7b.longprompt-steady"]
    assert not due["mistral-7b.eval-batch"] & due["mistral-7b.chat-steady"] - {"programs.warmup_s"}
    base, batch = load_layer_metric("step.decode_ms"), load_layer_metric("step.decode_ms.batch")
    assert (batch.LAYER, batch.UNIT, batch.SOURCE) == (base.LAYER, base.UNIT, base.SOURCE)
    assert batch.MOVES == "out_tokens_per_s_chip" and base.MOVES == "gap_p95_ms"


def test_unknown_device_kind_raises():
    from harness import roofline

    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
