"""`k-exaone-236b-a23b` (a period of window and full attention layers in the
pair family, rings beside one whole-context layer, NoPE full layers, QK-norm,
the dropless expert share behind a leading dense layer) through the manifest,
the weights, the check and the readers at the rehearsal's widths on the CPU,
and what the configuration file promises about itself."""
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
from harness.layer_common import DECODE_MODULE
from harness.load import Record
from harness.manifest import Cell, load_layer_metric, reference_sizes
from harness.weights import seeded_params

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "k-exaone-236b-a23b.longdoc-batch"
SIBLING = "mistral-7b.eval-batch"
NEW_READERS = ("step.extend_ms_per_ktok.batch", "step.extend_device_share.batch",
               "step.window_attn_share.batch", "step.full_attn_share.batch",
               "extend.window_attn_share.batch", "extend.full_attn_share.batch",
               "batch.decode_window_attention_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 4294967311
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL)


def test_the_cell_is_the_one_the_issue_names(cell):
    assert cell.chips == 1 and cell.reference == "kexaone_ref"
    assert cell.model_module == "omnia_tpu.models.llama"   # the default: the pair family
    assert mf.decode_kernel(cell.model) == "decode_gqa_attention"
    assert mf.decode_kernel_layers(cell.model) == 1         # the full layer alone calls it
    assert cell.engine == {
        "num_slots": 32, "max_seq": 8960, "prefill_buckets": [256, 512, 1024],
        "dtype": "bfloat16", "tp": 1, "decode_chunk": 8, "decode_pipeline": 2, "max_sessions": 0}
    assert cell.traffic == {
        "generator": "closed_loop", "why": cell.traffic["why"], "clients": 48,
        "prompt_tokens": {"dist": "uniform", "min": 4096, "max": 8192},
        "output_tokens": {"dist": "fixed", "value": 512},
        "first_output_spread": 16, "ramp_s": 10,
        # read by run.py alone: warm-up compiles the extend programs as well
        "sessions": True, "sessions_why": cell.traffic["sessions_why"]}
    # every prompt is longer than the largest bucket: all are placed in pieces
    assert cell.traffic["prompt_tokens"]["min"] > max(cell.engine["prefill_buckets"])
    assert (cell.traffic["prompt_tokens"]["max"] + cell.traffic["output_tokens"]["value"]
            <= cell.engine["max_seq"] - 2)
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s_chip", "setup_s"}
    listed = [name for name, _ in cell.layer_metrics]
    assert listed == ([name for name, _ in Cell(SIBLING).layer_metrics]
                      + ["batch.moe_experts_roofline", "step.moe_share.batch"]
                      + list(NEW_READERS))
    assert len(listed) == 25
    added = {"stack.dense_window", "stack.sparse_window", "stack.sparse_full",
             "attn.window", "attn.full", "attn.qk_norm"}
    assert spans.scopes_of(cell.model) == (
        spans.SCOPES | added, spans.SCANS | {s for s in added if s.startswith("stack.")})


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_new_readers_declarations_equal_its_entry(metric):
    entry = next(m for m in mf.benchmark_json()["per_layer"] if m["name"] == metric)
    mod = load_layer_metric(metric)
    assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["better"], entry["source"], entry["moves"])
    assert entry["workloads"] == [CELL] and mod.MOVES == "out_tokens_per_s_chip"


def test_the_model_config_as_published_and_at_rehearsal(cell):
    mc = cell.model_config()
    assert dataclasses.asdict(mc) == {
        **dataclasses.asdict(type(mc)()), "name": "k-exaone-236b-a23b", "vocab_size": 19200,
        "hidden_size": 6144, "num_layers": 5, "num_heads": 64, "num_kv_heads": 8,
        "head_dim": 128, "ffn_hidden_size": 18432, "rope_theta": 1e6, "rms_norm_eps": 1e-05,
        "tie_embeddings": False, "num_experts": 128, "num_experts_per_tok": 8,
        "max_seq_len": 262144, "moe_ffn_hidden_size": 2048, "num_shared_experts": 1,
        "num_experts_held": 16, "expert_rank": 0, "routed_scaling_factor": 2.5,
        "router_scoring": "sigmoid", "router_topk_method": "noaux_tc", "num_dense_layers": 1,
        "layer_types": tuple(PERIOD * 12), "sliding_window": 128,
        "rope_on_full_layers": False, "qk_norm": True}
    assert not mc.is_latent and mc.router_bias and mc.experts_held == 16
    assert mc.attention_kinds == ("window", "window", "window", "full", "window")
    from omnia_tpu.models import llama, model_module

    assert model_module(mc) is llama
    assert llama.stack_kinds(mc) == ("dense_window", "sparse_window", "sparse_full")
    assert llama.layer_order(mc) == ((0, 0), (1, 0), (1, 1), (2, 0), (1, 2))
    assert llama.ring_rows(mc) == 128
    assert [c.shape for c in jax.eval_shape(lambda: llama.init_kv_cache(mc, 32, 8960))] == [
        (1, 32, 8960, 8, 128)] * 2 + [(4, 32, 128, 8, 128)] * 2
    tiny = cell.model_config(rehearse=True)
    assert (tiny.hidden_size, tiny.num_layers, tiny.num_dense_layers, tiny.sliding_window,
            tiny.num_experts, tiny.num_experts_held, tiny.num_experts_per_tok) == (
                64, 3, 1, 8, 8, 4, 2)
    assert tiny.attention_kinds == ("window", "window", "full") and tiny.qk_norm


def test_the_flat_copies_equal_what_the_reference_reads(cell):
    """`assumed` copies `rope_parameters.rope_theta` to a flat key for
    ModelConfig, the program reads the FFN kinds as `first_k_dense_replace`
    and counts the layers of each attention kind under keys of its own; the
    reference reads the source's groups and lists. One model, one set of
    numbers."""
    m, assumed = cell.model, cell.model["assumed"]
    assert assumed["rope_theta"] == m["rope_parameters"]["rope_theta"] == 1000000
    assert m["rope_parameters"]["rope_type"] == "default"
    run = m["num_hidden_layers"]
    assert m["mlp_layer_types"][:run] == (["dense"] * m["first_k_dense_replace"]
                                          + ["sparse"] * (run - m["first_k_dense_replace"]))
    assert m["layer_types"][:run].count("full_attention") == m["num_full_attention_layers"] == 1
    assert m["layer_types"][:run].count("sliding_attention") == (
        m["num_window_attention_layers"]) == 4
    assert [w for w in m["sliding_windows"] if w] == [m["sliding_window"]] * 36
    assert [bool(w) for w in m["sliding_windows"]] == [
        t == "sliding_attention" for t in m["layer_types"]]
    assert m["expected_experts_hit"] == round(16 * (1 - (1 - 8 / 128) ** 32), 2) == 13.97
    for rehearse in (False, True):
        mc = cell.model_config(rehearse)
        sizes = reference_sizes(mc, cell.config_as_run(rehearse))
        ref = mf.load_reference(cell.reference)
        from omnia_tpu.models import llama

        assert ref.layer_order(sizes) == llama.layer_order(mc)
        kinds = tuple(f"{ffn}_{'window' if a == 'sliding_attention' else 'full'}"
                      for ffn, a in ref.stack_kinds(sizes))
        assert kinds == llama.stack_kinds(mc)
        assert ref._key(sizes["config"], "qk_norm") is mc.qk_norm is True
        assert ref._key(sizes["config"], "rope_on_full_layers") is mc.rope_on_full_layers is False
        assert sizes["config"]["sliding_window"] == mc.sliding_window
        assert sizes["config"]["num_experts"] == mc.experts_held


def test_the_file_keeps_every_published_number_but_the_reduced(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == cell.model["source"])
    entry = next(c for c in mf.benchmark_json()["configs"] if c["name"] == "k-exaone-236b-a23b")
    assert entry["source"] == cell.model["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(cell.model["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert cell.model["reduced"][key]["source"] == value
            assert cell.model["reduced"][key]["here"] == cell.model[key] != value
        else:
            assert cell.model[key] == value, key
    # the first five of the stage's six
    assert cell.model["reduced"]["num_hidden_layers"]["here"] == 5 == (
        cell.model["deployment"]["layers_a_stage"] - 1)
    assert cell.model["num_experts_source"] == row["config"]["num_experts"] == 128
    assert cell.model["num_experts"] * cell.model["chips_sharing_a_layer"] == 128
    assert cell.model["vocab_size"] * cell.model["chips_sharing_a_layer"] == 153600
    deployment = cell.model["deployment"]
    assert deployment["pipeline_stages"] * deployment["layers_a_stage"] == 48
    assert deployment["pipeline_stages"] * deployment["chips_a_stage"] == deployment["chips"] == 64
    assumed = cell.model["assumed"]
    for key in ("qk_norm", "rope_on_full_layers", "norm_placement", "topk_method"):
        assert key in assumed and len(assumed[key + "_why"]) > 40, key
    assert "not built" in assumed["multi_token_prediction"]
    assert cell.model["num_nextn_predict_layers"] == 1
    assert "4 : 1" in cell.model["stands_for"] and "3 : 1" in cell.model["stands_for"]
    assert "64-chip" in cell.model["stands_for"]


@pytest.mark.parametrize("rehearse", [True, False])
def test_the_byte_counts_equal_the_parameter_trees_and_the_issues_arithmetic(cell, rehearse):
    """Everything but the embedding table (gathered, not streamed), summed
    over the stacks `models/llama.py::init_params` makes, with the held
    experts counted as the file's expected hit and not all of them."""
    from omnia_tpu.models import llama

    mc, m = cell.model_config(rehearse), cell.config_as_run(rehearse)
    tree = jax.eval_shape(lambda: llama.init_params(mc, jax.random.key(0), jnp.bfloat16))
    streamed = {k: v for k, v in tree.items() if k != "embed"}
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(streamed))
    counts = mf.load_decode_bytes(m)
    sparse = mc.num_layers - mc.num_dense_layers
    not_hit = (mc.experts_held - m["expected_experts_hit"]) * counts.expert_bytes(m)
    assert counts.decode_weight_bytes(m) == int(nbytes - sparse * not_hit)
    assert counts.expert_bytes(m) == 3 * mc.hidden_size * mc.moe_ffn_hidden_size * 2
    full = mc.attention_kinds.count("full")
    assert counts.kv_bytes_per_token(m) == full * 2 * mc.num_kv_heads * mc.head_dim * 2
    assert counts.window_row_bytes(m) == (mc.num_layers - full) * counts.full_row_bytes(m)
    assert mc.num_params() == sum(x.size for x in jax.tree.leaves(tree))
    if rehearse:
        return
    d = cell.model["deployment"]["published_parameters"]
    assert d["attention_a_layer"] == 2 * 6144 * 8192 + 2 * 6144 * 1024 == 113_246_208
    assert d["routed_expert"] == d["shared_expert_a_layer"] == 3 * 6144 * 2048
    assert d["dense_ffn_a_layer"] == 3 * 6144 * 18432 and d["router_a_layer"] == 6144 * 128
    assert d["embedding_and_head"] == 2 * 153600 * 6144
    sparse_layer = (d["attention_a_layer"] + 16 * d["routed_expert"]
                    + d["shared_expert_a_layer"] + d["router_a_layer"])
    dense_layer = d["attention_a_layer"] + d["dense_ffn_a_layer"]
    assert round(sparse_layer / 1e6, 1) == 755.8 and round(dense_layer / 1e6, 1) == 453.0
    held = 2 * (dense_layer + 4 * sparse_layer + d["embedding_and_head"] // 8)
    assert round(held / 1e9, 2) == 7.42
    # the tree is that, the norms, the QK-norm's gains and the float32 bias more
    assert 0 < nbytes + tree["embed"].size * 2 - held < 1e6
    assert counts.kv_bytes_per_token(m) == 4096 and counts.window_row_bytes(m) == 4 * 4096
    assert counts.decode_attention_row(m) == {"flops": 64 * 4 * 128, "bytes": 4096}
    # the cache at the cell's 32 slots x 8960 rows, against five whole-context layers
    cache = jax.eval_shape(lambda: llama.init_kv_cache(mc, 32, 8960))
    assert round(sum(c.size * 2 for c in cache) / 1e9, 2) == 1.24
    assert round(5 * 32 * 8960 * 4096 / 1e9, 2) == 5.87


def _engine(cell):
    mc = cell.model_config(rehearse=True)
    params = seeded_params(mc, cell.engine_config(), None, SEED, jnp.bfloat16,
                           model_module=cell.model_module)
    sizes = reference_sizes(mc, cell.config_as_run(rehearse=True))
    return types.SimpleNamespace(params=params, _mesh=None), mc, sizes


def test_the_check_passes_at_rehearsal_and_a_wider_window_fails_it(cell):
    """Seeded weights and `correct.check` through `omnia_tpu.models.llama` and
    `kexaone_ref`: every layer of the three stacks alone, the dense and the
    first sparse layer together through a cache of four arrays. With the
    served side's window one row wider than the file's the check fails (at
    the rehearsal's window of 8 its 136 tokens cross the window 128 times)."""
    engine, mc, sizes = _engine(cell)
    assert isinstance(engine.params["layers"], list) and len(engine.params["layers"]) == 3
    check = lambda cfg: correct.check(engine, cfg, sizes, SEED,  # noqa: E731
                                      reference=cell.reference, model_module=cell.model_module)
    sound = check(mc)
    assert sound["ok"] is True, sound
    assert sound["decided_positions"] >= correct.PREFILL + correct.DECODE + correct.MIN_DECIDED
    assert sound["layers_noise_ratio_max"] < 1.5 and sound["layers_decode_max_over_range"] < 1e-2
    assert sound["pair_decode_median_worst_over_range"] < correct.PAIR_TOL / 5
    wrong = check(dataclasses.replace(mc, sliding_window=mc.sliding_window + 1))
    assert wrong["ok"] is False, wrong
    assert wrong["layers_noise_ratio_max"] > wrong["limits"]["layers_noise_ratio_max"]


def test_an_engine_says_it_serves_the_model_by_llama(cell):
    from omnia_tpu.engine.engine import InferenceEngine
    from omnia_tpu.engine.types import EngineConfig

    ecfg = EngineConfig(num_slots=2, max_seq=256, prefill_buckets=(64,), max_sessions=0)
    engine = InferenceEngine(cell.model_config(rehearse=True), ecfg)
    assert mf.served_by(engine) == cell.model_module == "omnia_tpu.models.llama"
    assert [c.shape for c in engine._cache] == [(1, 2, 256, 2, 16)] * 2 + [(2, 2, 8, 2, 16)] * 2
    assert {"moe_assignments_held", "moe_experts_hit", "decode_window_rows"} <= set(
        engine.metrics)


@pytest.fixture(scope="module")
def traced(cell):
    """The recorded one-chip trace (its 56 calls of `decode_gqa_attention`
    are 56 steps of this model, whose one full layer calls it), with a window
    kernel's calls and the extend modules put beside what it holds, the
    counters a traced run of this cell would have, and a scope table in place
    of the trace directory's."""
    with gzip.open(os.path.join(HERE, "trace_sample.json.gz"), "rt") as f:
        reduced = tr.reduce(json.load(f))
    ops = reduced["ops_in_module"][DECODE_MODULE]
    calls = sum(n for name, (n, _s) in ops.items() if name.split(".")[0] == "decode_gqa_attention")
    assert calls == 56
    ops["decode_window_attention.3"] = (4 * calls, 4 * calls * 20e-6)   # 20 us a layer a step
    reduced["modules"]["jit_extend_nosample"] = {"calls": 50, "seconds": 1.5}
    reduced["modules"]["jit_extend"] = {"calls": 10, "seconds": 0.3}
    records = [
        Record(i, "window", 6000 + 10 * i, 512, due=10.0 + i, sent=10.001 + i,
               first=10.3 + i, last=12.8 + i, done=12.8 + i, tokens=512,
               finish="length", request_id=f"req-{i}")
        for i in range(20)
    ]
    scopes = {DECODE_MODULE: {"mlp": 0.06, "attn.window": 0.002, "attn.full": 0.01,
                              "attn.decode": 0.001, "lm_head": 0.027},
              "jit_extend_nosample": {"mlp": 1.0, "attn.window": 0.03, "attn.full": 0.32,
                                      "attn.qkv": 0.15},
              "jit_extend": {"mlp": 0.2, "attn.window": 0.006, "attn.full": 0.064,
                             "attn.qkv": 0.03}}
    return {"records": records, "all_records": records, "chips": 1, "model": cell.model,
            "peaks": roofline.peaks("TPU v5 lite"), "trace": reduced, "spans": {"scopes": scopes},
            "traced": {"t": (14.0, 14.25),
                       "counters": {"decode_steps": 100, "prefill_tokens": 40_000,
                                    "decode_window_rows": 100 * 32 * 128}}}


def test_the_new_readers_read_the_cell(traced):
    read = lambda metric: load_layer_metric(metric).read(traced)  # noqa: E731
    busy = traced["trace"]["busy_s"]
    assert read("step.extend_ms_per_ktok.batch") == pytest.approx(1.8 / 40_000 * 1e6)
    assert read("step.extend_device_share.batch") == pytest.approx(100 * 1.8 / busy)
    assert read("step.window_attn_share.batch") == pytest.approx(100 * 0.002 / 0.1)
    assert read("step.full_attn_share.batch") == pytest.approx(100 * 0.01 / 0.1)
    assert read("extend.window_attn_share.batch") == pytest.approx(100 * 0.036 / 1.8)
    assert read("extend.full_attn_share.batch") == pytest.approx(100 * 0.384 / 1.8)
    # 32 live rings of 128 rows, K and V, four window layers, over 819 GB/s,
    # against the 4 x 20 us a step the kernel took
    floor = 32 * 128 * 4 * 4096 / traced["peaks"]["hbm_bytes_per_s"]
    assert read("batch.decode_window_attention_roofline") == pytest.approx(
        100 * floor / (4 * 20e-6))
    assert 0 < read("batch.decode_window_attention_roofline") < 105
    # the accepted readers this cell lists read it too: the full layer's kernel
    # over the full layer's rows alone
    assert load_layer_metric("batch.decode_gqa_attention_roofline").read(traced) > 0
    assert roofline.kv_bytes_per_token(traced["model"]) == 4096


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes(traced):
    """Laid over a parent that has not the scopes, the counter or the
    kernel, or on a run that was not traced, the readers return None and
    raise nothing."""
    bare = {**traced, "spans": {"scopes": {DECODE_MODULE: {"mlp": 0.1},
                                           "jit_extend": {"mlp": 0.4}}}}
    for metric in ("step.window_attn_share.batch", "step.full_attn_share.batch",
                   "extend.window_attn_share.batch", "extend.full_attn_share.batch"):
        assert load_layer_metric(metric).read(bare) is None
        assert load_layer_metric(metric).read({**bare, "spans": None, "traced": None}) is None
    no_counter = {**traced, "traced": {**traced["traced"],
                                       "counters": {"decode_steps": 100, "prefill_tokens": 9}}}
    assert load_layer_metric("batch.decode_window_attention_roofline").read(no_counter) is None
    ops = {k: v for k, v in traced["trace"]["ops_in_module"][DECODE_MODULE].items()
           if not k.startswith("decode_window_attention")}
    no_kernel = {**traced, "trace": {**traced["trace"], "ops_in_module": {DECODE_MODULE: ops}}}
    assert load_layer_metric("batch.decode_window_attention_roofline").read(no_kernel) is None
    no_modules = {**traced, "trace": {**traced["trace"], "modules": {
        k: v for k, v in traced["trace"]["modules"].items() if not k.startswith("jit_extend")}}}
    untraced = {**traced, "trace": None}
    for metric in NEW_READERS:
        assert load_layer_metric(metric).read({**untraced, "spans": None}) is None
    for metric in ("step.extend_ms_per_ktok.batch", "step.extend_device_share.batch"):
        assert load_layer_metric(metric).read(no_modules) is None
