"""`mellum2-12b-a2p5b` (sparse stacks only in the pair family: every expert
held, a softmax router's greedy top-8, no shared expert and no dense layer;
1,024-row rings beside whole-context layers; a rotary table a kind of
attention layer) through the manifest, the weights, the check and the readers
at the rehearsal's widths on the CPU, and what the configuration file, the
traffic file and the cell promise about themselves."""
import dataclasses
import gzip
import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

import harness.manifest as mf
from harness import correct, roofline, spans, trace as tr
from harness.dists import stratified
from harness.layer_common import DECODE_MODULE
from harness.load import Record
from harness.manifest import Cell, load_layer_metric, reference_sizes
from harness.weights import seeded_params

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "mellum2-12b-a2p5b.code-mixed"
SIBLING = "k-exaone-236b-a23b.longdoc-batch"
NEW_READERS = ("step.rope_share.batch", "placement.fresh_token_share.batch")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 4294967311
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
YARN = (16, 8192, 32, 1, 1.2772588722239782)


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL)


def test_the_cell_is_the_one_the_issue_names(cell):
    assert cell.chips == 1 and cell.reference == "mellum2_ref"
    assert cell.model_module == "omnia_tpu.models.llama"   # the default: the pair family
    assert mf.decode_kernel(cell.model) == "decode_gqa_attention"
    assert mf.decode_kernel_layers(cell.model) == 2         # the two full layers call it
    assert cell.engine == {
        "num_slots": 48, "max_seq": 5888, "prefill_buckets": [256, 512, 1024],
        "dtype": "bfloat16", "tp": 1, "decode_chunk": 8, "decode_pipeline": 2, "max_sessions": 0}
    assert cell.traffic == {
        "generator": "closed_loop", "why": cell.traffic["why"], "clients": 72,
        "prompt_tokens": {"dist": "lognormal", "median": 1536, "sigma": 0.9,
                          "min": 128, "max": 5120},
        "output_tokens": {"dist": "lognormal", "median": 192, "sigma": 0.5,
                          "min": 48, "max": 512},
        "first_output_spread": 16, "ramp_s": 20,
        # read by run.py alone: warm-up compiles the extend programs as well
        "sessions": True, "sessions_why": cell.traffic["sessions_why"]}
    assert (cell.traffic["prompt_tokens"]["max"] + cell.traffic["output_tokens"]["max"]
            <= cell.engine["max_seq"] - 2)
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s_chip", "setup_s"}
    listed = [name for name, _ in cell.layer_metrics]
    # every reader of the sibling's cell, the fresh prefill's two, and the two new ones
    assert set(listed) == ({name for name, _ in Cell(SIBLING).layer_metrics}
                           | {"step.prefill_ms_per_ktok.batch", "step.prefill_device_share.batch"}
                           | set(NEW_READERS))
    assert len(listed) == 29 and listed[-2:] == list(NEW_READERS)
    added = {"stack.sparse_window", "stack.sparse_full", "attn.window", "attn.full",
             "attn.qk_norm", "attn.rope", "rope.tables"}
    assert spans.scopes_of(cell.model) == (
        spans.SCOPES | added, spans.SCANS | {s for s in added if s.startswith("stack.")})
    entry = next(w for w in mf.benchmark_json()["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and (entry["config"], entry["traffic"]) == (
        "mellum2-12b-a2p5b", "code-mixed")


def test_the_traffic_is_the_mix_the_issue_computed(cell):
    """Of a round's 72 stratified prompts 23 fit the largest bucket (one fresh
    prefill each, the ring left partly or exactly filled) with 9.8 % of the
    round's prompt tokens; 49 go in pieces; 7 sit at the cap."""
    prompts = stratified(cell.traffic["prompt_tokens"], cell.traffic["clients"], base=2)
    outputs = stratified(cell.traffic["output_tokens"], cell.traffic["clients"], base=3)
    largest = max(cell.engine["prefill_buckets"])
    short = [n for n in prompts if n <= largest]
    assert (len(prompts), len(short), prompts.count(5120)) == (72, 23, 7)
    assert round(100 * sum(short) / sum(prompts), 1) == 9.8
    assert round(sum(prompts) / 72) == 2005 and round(sum(outputs) / 72) == 215
    assert min(prompts) >= 128 and max(n // 1024 for n in prompts) == 5   # wraps of the ring
    assert "9.8 %" in cell.traffic["why"] and "23" in cell.traffic["why"]


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_new_readers_declarations_equal_its_entry(metric):
    entry = next(m for m in mf.benchmark_json()["per_layer"] if m["name"] == metric)
    mod = load_layer_metric(metric)
    assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["better"], entry["source"], entry["moves"])
    assert entry["workloads"][0] == CELL and mod.MOVES == "out_tokens_per_s_chip"
    assert all(name in {w["name"] for w in mf.benchmark_json()["workloads"]}
               and metric in Cell(name).spec["per_layer"] for name in entry["workloads"])


def test_the_model_config_as_published_and_at_rehearsal(cell):
    mc = cell.model_config()
    assert dataclasses.asdict(mc) == {
        **dataclasses.asdict(type(mc)()), "name": "mellum2-12b-a2p5b", "vocab_size": 98304,
        "hidden_size": 2304, "num_layers": 8, "num_heads": 32, "num_kv_heads": 4,
        "head_dim": 128, "ffn_hidden_size": 7168, "rope_theta": 5e5, "rms_norm_eps": 1e-06,
        "tie_embeddings": False, "num_experts": 64, "num_experts_per_tok": 8,
        "max_seq_len": 131072, "moe_ffn_hidden_size": 896, "router_scoring": "softmax",
        "router_topk_method": "greedy", "layer_types": tuple(PERIOD * 7),
        "sliding_window": 1024, "rope_full_yarn": YARN, "qk_norm": True}
    assert not mc.is_latent and not mc.router_bias and mc.experts_held == 64
    assert (mc.num_dense_layers, mc.num_shared_experts, mc.rope_on_full_layers) == (0, 0, True)
    assert mc.attention_kinds == ("window", "window", "window", "full") * 2
    from omnia_tpu.models import llama, model_module

    assert model_module(mc) is llama
    assert llama.stack_kinds(mc) == ("sparse_window", "sparse_full")
    assert llama.layer_order(mc) == ((0, 0), (0, 1), (0, 2), (1, 0), (0, 3), (0, 4), (0, 5), (1, 1))
    assert llama.ring_rows(mc) == 1024
    assert [c.shape for c in jax.eval_shape(lambda: llama.init_kv_cache(mc, 48, 5888))] == [
        (2, 48, 5888, 4, 128)] * 2 + [(6, 48, 1024, 4, 128)] * 2
    tiny = cell.model_config(rehearse=True)
    assert (tiny.hidden_size, tiny.num_layers, tiny.sliding_window, tiny.num_experts,
            tiny.experts_held, tiny.num_experts_per_tok) == (64, 4, 8, 8, 8, 2)
    assert tiny.attention_kinds == ("window", "window", "window", "full") and tiny.qk_norm
    assert tiny.rope_full_yarn == (16, 64, 32, 1, YARN[4])


def test_the_flat_copies_equal_what_the_reference_reads(cell):
    """`assumed` copies `rope_parameters` to the flat keys that ModelConfig is
    built from (`rope_theta`, both groups'; `rope_full_yarn`, the full
    layers' group as one tuple), and the file counts the layers of each
    attention kind under keys of its own; the reference reads the source's
    groups and lists. One model, one set of numbers, at both sizes."""
    m, assumed = cell.model, cell.model["assumed"]
    for key, copy in ((None, m["assumed"]), ("rehearsal", m["rehearsal"])):
        groups = (m if key is None else m[key])["rope_parameters"]
        full, window = groups["full_attention"], groups["sliding_attention"]
        assert assumed["rope_theta"] == full["rope_theta"] == window["rope_theta"] == 500000
        assert (full["rope_type"], window["rope_type"]) == ("yarn", "default")
        assert copy["rope_full_yarn"] == [
            full["factor"], full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"], full["attention_factor"]]
        assert sorted(window) == ["rope_theta", "rope_type"] and len(full) == 7
    assert YARN[4] == pytest.approx(0.1 * jnp.log(16.0) + 1, rel=1e-6)
    run = m["num_hidden_layers"]
    assert m["mlp_layer_types"] == ["sparse"] * 28 and m["layer_types"] == PERIOD * 7
    assert m["layer_types"][:run].count("full_attention") == m["num_full_attention_layers"] == 2
    assert m["layer_types"][:run].count("sliding_attention") == (
        m["num_window_attention_layers"]) == 6
    for rehearse in (False, True):
        mc = cell.model_config(rehearse)
        sizes = reference_sizes(mc, cell.config_as_run(rehearse))
        ref = mf.load_reference(cell.reference)
        from omnia_tpu.models import llama

        assert ref.layer_order(sizes) == llama.layer_order(mc)
        kinds = tuple("sparse_window" if a == "sliding_attention" else "sparse_full"
                      for a in ref.stack_kinds(sizes))
        assert kinds == llama.stack_kinds(mc)
        assert sizes["config"]["assumed"]["qk_norm"] is mc.qk_norm is True
        assert sizes["config"]["sliding_window"] == mc.sliding_window
        assert sizes["config"]["num_experts"] == mc.num_experts == mc.experts_held
        assert (assumed["scoring_func"], assumed["topk_method"]) == (
            mc.router_scoring, mc.router_topk_method)


def test_the_file_keeps_every_published_number_but_the_depth(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == cell.model["source"])
    entry = next(c for c in mf.benchmark_json()["configs"] if c["name"] == "mellum2-12b-a2p5b")
    assert entry["source"] == cell.model["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == list(cell.model["reduced"]) == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert cell.model["reduced"][key]["source"] == value == 28
            assert cell.model["reduced"][key]["here"] == cell.model[key] == 8
        else:
            assert cell.model[key] == value, key
    deployment = cell.model["deployment"]
    assert sum(deployment["layers_a_stage"]) == 28 and deployment["layers_a_stage"][0] == 8
    assert deployment["pipeline_stages"] == deployment["chips"] == 4
    assumed = cell.model["assumed"]
    for key in ("qk_norm", "rotary_pairs", "yarn_convention", "norm_placement", "router",
                "rope_theta", "rope_full_yarn"):
        assert key in assumed and len(assumed[key + "_why"]) > 40, key
    assert "not built" in assumed["multi_token_prediction"]
    for said in ("four-stage pipeline", "8, 8, 8 and 4", "holds the head", "3.5 times"):
        assert said in cell.model["stands_for"], said


@pytest.mark.parametrize("rehearse", [True, False])
def test_the_byte_counts_equal_the_parameter_trees_and_the_issues_arithmetic(cell, rehearse):
    """Everything but the embedding table (gathered, not streamed), summed
    over the stacks `models/llama.py::init_params` makes: every expert is
    held and every expert is counted."""
    from omnia_tpu.models import llama

    mc, m = cell.model_config(rehearse), cell.config_as_run(rehearse)
    tree = jax.eval_shape(lambda: llama.init_params(mc, jax.random.key(0), jnp.bfloat16))
    streamed = {k: v for k, v in tree.items() if k != "embed"}
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(streamed))
    counts = mf.load_decode_bytes(m)
    assert counts.decode_weight_bytes(m) == nbytes
    assert counts.expert_bytes(m) == 3 * mc.hidden_size * mc.moe_ffn_hidden_size * 2
    full = mc.attention_kinds.count("full")
    assert counts.kv_bytes_per_token(m) == full * 2 * mc.num_kv_heads * mc.head_dim * 2
    assert counts.window_row_bytes(m) == (mc.num_layers - full) * counts.full_row_bytes(m)
    assert mc.num_params() == sum(x.size for x in jax.tree.leaves(tree))
    if rehearse:
        return
    d = cell.model["deployment"]["published_parameters"]
    assert d["attention_a_layer"] == 2 * 2304 * 4096 + 2 * 2304 * 512 == 21_233_664
    assert d["routed_expert"] == 3 * 2304 * 896 and d["router_a_layer"] == 2304 * 64
    assert d["experts_a_layer"] == 64 * d["routed_expert"] == 396_361_728
    assert d["layer"] == d["attention_a_layer"] + d["router_a_layer"] + d["experts_a_layer"]
    assert d["embedding_and_head"] == 2 * 98304 * 2304
    assert d["model"] == 28 * d["layer"] + d["embedding_and_head"]
    assert round(d["layer"] / 1e6, 1) == 417.7 and round(d["model"] / 1e9, 2) == 12.15
    held = 2 * (8 * d["layer"] + d["embedding_and_head"])
    assert round(held / 1e9, 2) == 7.59
    # the tree is that, the norms and the QK-norm's gains more
    assert 0 < nbytes + tree["embed"].size * 2 - held < 1e6
    active = d["attention_a_layer"] + d["router_a_layer"] + 8 * d["routed_expert"]
    assert round((28 * active + d["embedding_and_head"]) / 1e9, 2) == 2.44
    assert counts.kv_bytes_per_token(m) == 2 * 2048 and counts.window_row_bytes(m) == 6 * 2048
    assert counts.decode_attention_row(m) == {"flops": 32 * 4 * 128, "bytes": 2048}
    # the cache at the cell's 48 slots x 5888 rows: two whole-context layers, six rings
    cache = jax.eval_shape(lambda: llama.init_kv_cache(mc, 48, 5888))
    sizes = [c.size * 2 for c in cache]
    assert round(sum(sizes[:2]) / 1e9, 2) == 1.16 and round(sum(sizes[2:]) / 1e9, 2) == 0.60
    assert round((held + sum(sizes)) / 1e9, 2) == 9.35        # 58 % of the chip's 16 GB
    assert 0.25 < (held + sum(sizes)) / 16e9


def _engine(cell):
    mc = cell.model_config(rehearse=True)
    params = seeded_params(mc, cell.engine_config(), None, SEED, jnp.bfloat16,
                           model_module=cell.model_module)
    sizes = reference_sizes(mc, cell.config_as_run(rehearse=True))
    return types.SimpleNamespace(params=params, _mesh=None), mc, sizes


def test_the_check_passes_at_rehearsal_and_two_faults_fail_it(cell):
    """Seeded weights and `correct.check` through `omnia_tpu.models.llama` and
    `mellum2_ref`: every layer of the two stacks alone, the first two (both
    sparse window layers) together through a cache of four arrays. With the
    served side's window one row wider than the file's, or the full layers'
    attention factor left out, the check fails (at the rehearsal's window of
    8 its 136 tokens cross the window 128 times)."""
    engine, mc, sizes = _engine(cell)
    assert isinstance(engine.params["layers"], list) and len(engine.params["layers"]) == 2
    check = lambda cfg: correct.check(engine, cfg, sizes, SEED,  # noqa: E731
                                      reference=cell.reference, model_module=cell.model_module)
    sound = check(mc)
    assert sound["ok"] is True, sound
    assert sound["decided_positions"] >= correct.PREFILL + correct.DECODE + correct.MIN_DECIDED
    assert sound["layers_noise_ratio_max"] < 1.5 and sound["layers_decode_max_over_range"] < 1e-2
    assert sound["pair_decode_median_worst_over_range"] < correct.PAIR_TOL / 5
    for wrong_cfg in (dataclasses.replace(mc, sliding_window=mc.sliding_window + 1),
                      dataclasses.replace(mc, rope_full_yarn=(*mc.rope_full_yarn[:4], 1.0))):
        wrong = check(wrong_cfg)
        assert wrong["ok"] is False, wrong
        assert wrong["layers_noise_ratio_max"] > wrong["limits"]["layers_noise_ratio_max"]


def test_an_engine_says_it_serves_the_model_by_llama(cell):
    from omnia_tpu.engine.engine import InferenceEngine
    from omnia_tpu.engine.types import EngineConfig

    ecfg = EngineConfig(num_slots=2, max_seq=256, prefill_buckets=(64,), max_sessions=0)
    engine = InferenceEngine(cell.model_config(rehearse=True), ecfg)
    assert mf.served_by(engine) == cell.model_module == "omnia_tpu.models.llama"
    assert [c.shape for c in engine._cache] == [(1, 2, 256, 2, 16)] * 2 + [(3, 2, 8, 2, 16)] * 2
    assert {"moe_assignments_held", "moe_experts_hit", "decode_window_rows",
            "extend_tokens"} <= set(engine.metrics)


@pytest.fixture(scope="module")
def traced(cell):
    """The recorded one-chip trace (its 56 calls of `decode_gqa_attention`
    are 28 steps of this model, whose two full layers call it), with a window
    kernel's calls put beside what it holds, the counters a run of this cell
    would have, and a scope table in place of the trace directory's."""
    with gzip.open(os.path.join(HERE, "trace_sample.json.gz"), "rt") as f:
        reduced = tr.reduce(json.load(f))
    ops = reduced["ops_in_module"][DECODE_MODULE]
    calls = sum(n for name, (n, _s) in ops.items() if name.split(".")[0] == "decode_gqa_attention")
    assert calls == 56
    ops["decode_window_attention.3"] = (6 * 28, 6 * 28 * 150e-6)   # 150 us a layer a step
    records = [
        Record(i, "mixed", 1500 + 10 * i, 200, due=10.0 + i, sent=10.001 + i,
               first=10.3 + i, last=12.8 + i, done=12.8 + i, tokens=200,
               finish="length", request_id=f"req-{i}")
        for i in range(20)
    ]
    scopes = {DECODE_MODULE: {"mlp": 0.07, "attn.window": 0.01, "attn.full": 0.01,
                              "attn.rope": 0.004, "rope.tables": 0.001, "lm_head": 0.005}}
    return {"records": records, "all_records": records, "chips": 1, "model": cell.model,
            "peaks": roofline.peaks("TPU v5 lite"), "trace": reduced, "spans": {"scopes": scopes},
            "counters_window": {"prefill_tokens": 500_000, "extend_tokens": 451_000,
                                "decode_steps": 4000},
            "traced": {"t": (14.0, 14.25),
                       "counters": {"decode_steps": 100, "prefill_tokens": 40_000,
                                    "decode_window_rows": 100 * 48 * 1024}}}


def test_the_new_readers_read_the_cell(traced):
    read = lambda metric: load_layer_metric(metric).read(traced)  # noqa: E731
    assert read("step.rope_share.batch") == pytest.approx(100 * 0.005 / 0.1)
    assert read("placement.fresh_token_share.batch") == pytest.approx(9.8)
    # the accepted readers this cell lists read it through `mellum2_bytes`: 48 live
    # rings of 1,024 rows, K and V, six window layers, over 819 GB/s, against the
    # 6 x 150 us a step the kernel took
    floor = 48 * 1024 * 6 * 2048 / traced["peaks"]["hbm_bytes_per_s"]
    assert load_layer_metric("batch.decode_window_attention_roofline").read(traced) == (
        pytest.approx(100 * floor / (6 * 150e-6)))
    assert 0 < load_layer_metric("batch.decode_window_attention_roofline").read(traced) < 105
    assert load_layer_metric("batch.decode_gqa_attention_roofline").read(traced) > 0
    assert roofline.kv_bytes_per_token(traced["model"]) == 4096


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes_or_the_counter(traced):
    """Laid over a parent that has neither the scopes nor the counter, or on
    a run that was not traced, the readers return None and raise nothing."""
    bare = {**traced, "spans": {"scopes": {DECODE_MODULE: {"mlp": 0.1}}}}
    rope = load_layer_metric("step.rope_share.batch")
    assert rope.read(bare) is None
    assert rope.read({**traced, "spans": None, "trace": None, "traced": None}) is None
    fresh = load_layer_metric("placement.fresh_token_share.batch")
    assert fresh.read({**traced, "counters_window": {"prefill_tokens": 9}}) is None
    assert fresh.read({**traced, "counters_window": {"prefill_tokens": 0, "extend_tokens": 0}}) \
        is None
    assert fresh.read({**traced, "counters_window": None}) is None
    # a window in which every prompt was placed whole reads 100, not nothing
    assert fresh.read({**traced, "counters_window": {"prefill_tokens": 9, "extend_tokens": 0}}) \
        == 100.0
