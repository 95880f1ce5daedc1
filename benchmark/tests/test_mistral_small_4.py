"""`mistral-small-4` (latent attention, a share of the routed experts, a
shared expert) through the manifest, the weights, the check and the readers
at the rehearsal's widths on the CPU, and what the configuration file
promises about itself."""
import dataclasses
import gzip
import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

import harness.manifest as mf
from harness import correct, roofline, trace as tr
from harness.layer_common import DECODE_MODULE, decode_steps_in_trace
from harness.load import Record
from harness.manifest import Cell, load_layer_metric, reference_sizes
from harness.weights import seeded_params

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "mistral-small-4.reason-batch"
NEW_READERS = ("batch.decode_mla_attention_roofline", "batch.moe_experts_roofline",
               "step.moe_share.batch", "step.latent_attn_share.batch")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL)


def test_the_cell_is_the_one_the_issue_names(cell):
    assert cell.chips == 1 and cell.reference == "mla_moe_ref"
    assert cell.model_module == "omnia_tpu.models.mla"
    assert mf.decode_kernel(cell.model) == "decode_mla_attention"
    assert cell.engine == {
        "num_slots": 96, "max_seq": 3072, "prefill_buckets": list(range(640, 1537, 128)),
        "dtype": "bfloat16", "tp": 1, "decode_chunk": 8, "decode_pipeline": 2, "max_sessions": 0}
    assert cell.traffic == {
        "generator": "closed_loop", "why": cell.traffic["why"], "clients": 128,
        "prompt_tokens": {"dist": "uniform", "min": 512, "max": 1536},
        "output_tokens": {"dist": "fixed", "value": 1024},
        "first_output_spread": 32, "ramp_s": 10}
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s_chip", "setup_s"}
    listed = [name for name, _ in cell.layer_metrics]
    assert set(NEW_READERS) <= set(listed) and len(listed) == 19
    assert "batch.decode_gqa_attention_roofline" not in listed


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
        **dataclasses.asdict(type(mc)()), "name": "mistral-small-4", "vocab_size": 32768,
        "hidden_size": 4096, "num_layers": 5, "num_heads": 32, "num_kv_heads": 32,
        "head_dim": 128, "ffn_hidden_size": 12288, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-06, "tie_embeddings": False, "num_experts": 128,
        "num_experts_per_tok": 4, "max_seq_len": 1048576, "kv_rank": 256, "q_rank": 1024,
        "qk_nope_head_dim": 64, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "rope_yarn": (128, 8192, 32, 1, 1, 1), "rope_interleave": True,
        "q_scaling_beta": 0.1, "moe_ffn_hidden_size": 2048, "num_shared_experts": 1,
        "num_experts_held": 32, "expert_rank": 0, "routed_scaling_factor": 1}
    tiny = cell.model_config(rehearse=True)
    assert (tiny.hidden_size, tiny.num_layers, tiny.kv_rank, tiny.num_experts,
            tiny.num_experts_held, tiny.num_experts_per_tok) == (64, 2, 32, 16, 4, 4)
    assert tiny.rope_yarn == mc.rope_yarn and tiny.is_latent and mc.is_latent


def test_the_flat_copies_equal_rope_parameters(cell):
    """`assumed` copies rope_parameters to flat keys for ModelConfig; the
    reference reads the group itself. One model, so one set of numbers."""
    rp, assumed = cell.model["rope_parameters"], cell.model["assumed"]
    assert assumed["rope_theta"] == rp["rope_theta"]
    assert assumed["rope_yarn"] == [rp[k] for k in (
        "factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale",
        "mscale_all_dim")]
    assert assumed["llama_4_scaling_beta"] == rp["llama_4_scaling_beta"]


def test_the_file_keeps_every_published_number_but_the_reduced(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == cell.model["source"])
    entry = next(c for c in mf.benchmark_json()["configs"] if c["name"] == "mistral-small-4")
    assert sorted(entry["reduced"]) == sorted(cell.model["reduced"])
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert cell.model["reduced"][key]["source"] == value
            assert cell.model["reduced"][key]["here"] == cell.model[key] != value
        else:
            assert cell.model[key] == value, key
    assert cell.model["n_routed_experts_source"] == row["config"]["n_routed_experts"]
    assert cell.model["chips_sharing_a_layer"] * cell.model["n_routed_experts"] == 128


@pytest.mark.parametrize("rehearse", [True, False])
def test_decode_weight_bytes_equal_the_parameter_trees(cell, rehearse):
    """Everything but the embedding table (gathered, not streamed), summed
    over the tree `models/mla.py::init_params` makes: from shapes alone at
    the published widths, 8.86 GB of the chip's 9.13."""
    from omnia_tpu.models import mla

    mc, m = cell.model_config(rehearse), cell.config_as_run(rehearse)
    tree = jax.eval_shape(lambda: mla.init_params(mc, jax.random.key(0), jnp.bfloat16))
    streamed = {k: v for k, v in tree.items() if k != "embed"}
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(streamed))
    counts = mf.load_decode_bytes(m)
    assert counts.decode_weight_bytes(m) == nbytes
    assert counts.kv_bytes_per_token(m) == mc.num_layers * (mc.kv_rank + mc.qk_rope_head_dim) * 2
    assert counts.expert_bytes(m) == 3 * mc.hidden_size * mc.moe_ffn_hidden_size * 2
    if not rehearse:
        assert nbytes == 5 * 1_718_110_720 + 268_443_648 and counts.kv_bytes_per_token(m) == 5 * 640
        assert counts.decode_attention_row(m) == {"flops": 32 * (320 + 256) * 2, "bytes": 640}


def _check_with(cell, mc, sizes, engine, served_params):
    """`correct.check` with the served side's parameters replaced: the
    reference keeps the engine's."""
    real = correct._served_logits
    wrong = types.SimpleNamespace(params=served_params, _mesh=None)
    try:
        correct._served_logits = lambda engine, *a, **k: real(wrong, *a, **k)
        return correct.check(engine, mc, sizes, 4294967311, reference=cell.reference,
                             model_module=cell.model_module)
    finally:
        correct._served_logits = real


def test_the_check_passes_at_rehearsal_and_dropped_experts_show(cell):
    """Seeded weights and `correct.check` through `omnia_tpu.models.mla` and
    `mla_moe_ref`. Controls on the served side: one of the four held experts
    dropped (its output projection zeroed) lifts the noise ratio by a third
    and more, and the whole share dropped passes its limit. (At these widths
    an expert adds little to the stream; at the published ones it dwarfs it:
    PERF.md section 6 has the chip's reading.)"""
    mc = cell.model_config(rehearse=True)
    sizes = reference_sizes(mc, cell.config_as_run(rehearse=True))
    params = seeded_params(mc, cell.engine_config(), None, 4294967311, jnp.bfloat16,
                           model_module=cell.model_module)
    engine = types.SimpleNamespace(params=params, _mesh=None)
    sound = correct.check(engine, mc, sizes, 4294967311, reference=cell.reference,
                          model_module=cell.model_module)
    assert sound["ok"] is True, sound
    assert sound["decided_positions"] >= correct.MIN_DECIDED
    assert sound["layers_noise_ratio_max"] < 1.4 and sound["layers_decode_max_over_range"] < 1e-2

    def without(experts):
        wd = params["layers"]["mlp"]["wd"]
        mlp = {**params["layers"]["mlp"], "wd": wd.at[:, experts].set(0)}
        return {**params, "layers": {**params["layers"], "mlp": mlp}}

    one = _check_with(cell, mc, sizes, engine, without(0))
    assert one["layers_noise_ratio_max"] > 1.33 * sound["layers_noise_ratio_max"], one
    none = _check_with(cell, mc, sizes, engine, without(slice(None)))
    assert none["ok"] is False and none["layers_noise_ratio_max"] > correct.NOISE_FACTOR, none


FEATURES = {"kv_quant": {"kv_quant": "int8"}, "kv_pages": {"kv_pages": 64, "kv_page_tokens": 64},
            "max_sessions": {"max_sessions": 4}, "prefix_cache_slots": {"prefix_cache_slots": 2},
            "spec_decode": {"spec_decode": 4}, "prefill_chunk_tokens": {"prefill_chunk_tokens": 64},
            "tp": {"tp": 2}}


@pytest.mark.parametrize("feature", list(FEATURES))
def test_the_latent_family_refuses_what_is_not_ported_by_name(cell, feature):
    from omnia_tpu.engine.engine import InferenceEngine
    from omnia_tpu.engine.types import EngineConfig

    ecfg = EngineConfig(**{"num_slots": 2, "max_seq": 256, "prefill_buckets": (64,),
                           "max_sessions": 0, **FEATURES[feature]})
    with pytest.raises(NotImplementedError, match=rf"EngineConfig\.{feature}=.*not ported to the "
                                                  r"latent-attention family"):
        InferenceEngine(cell.model_config(rehearse=True), ecfg)


def test_an_engine_says_it_serves_the_family_by_mla(cell):
    from omnia_tpu.engine.engine import InferenceEngine
    from omnia_tpu.engine.types import EngineConfig

    ecfg = EngineConfig(num_slots=2, max_seq=256, prefill_buckets=(64,), max_sessions=0)
    engine = InferenceEngine(cell.model_config(rehearse=True), ecfg)
    assert mf.served_by(engine) == cell.model_module == "omnia_tpu.models.mla"
    assert len(engine._cache) == 1 and engine._cache[0].shape == (2, 2, 256, 128)
    assert {"moe_assignments_held", "moe_experts_hit"} <= set(engine.metrics)


@pytest.fixture(scope="module")
def traced(cell):
    """The recorded one-chip trace with its decode kernel under the latent
    kernel's name (56 calls over the model's 5 layers), the counters a traced run of this cell would
    have, and a scope table in place of the trace directory's."""
    with gzip.open(os.path.join(HERE, "trace_sample.json.gz"), "rt") as f:
        reduced = tr.reduce(json.load(f))
    ops = reduced["ops_in_module"][DECODE_MODULE]
    reduced["ops_in_module"][DECODE_MODULE] = {
        k.replace("decode_gqa_attention", "decode_mla_attention"): v for k, v in ops.items()}
    records = [
        Record(i, "window", 400 + 10 * i, 64, due=10.0 + i, sent=10.001 + i,
               first=10.3 + i, last=12.8 + i, done=12.8 + i, tokens=64,
               finish="length", request_id=f"req-{i}")
        for i in range(20)
    ]
    steps = 56.0 / 5
    scopes = {DECODE_MODULE: {"moe.experts": 0.004 * steps, "mlp": 0.001 * steps,
                              "moe.route": 0.0005 * steps, "attn.qkv": 0.001 * steps,
                              "attn.decode": 0.002 * steps, "attn.out": 0.0005 * steps,
                              "kv.update": 0.0005 * steps, "lm_head": 0.0005 * steps}}
    return {"records": records, "all_records": records, "chips": 1, "model": cell.model,
            "peaks": roofline.peaks("TPU v5 lite"), "trace": reduced, "spans": {"scopes": scopes},
            "traced": {"t": (14.0, 14.25),
                       "counters": {"decode_steps": 100, "moe_experts_hit": 100 * 5 * 30.5}}}


def test_the_readers_read_the_latent_cell(traced):
    model, hbm = traced["model"], traced["peaks"]["hbm_bytes_per_s"]
    steps = 56.0 / 5
    assert decode_steps_in_trace(traced) == pytest.approx(steps)
    read = lambda metric: load_layer_metric(metric).read(traced)  # noqa: E731
    step_ms = read("step.decode_ms.batch")
    counts = mf.load_decode_bytes(model)
    floor = (counts.decode_weight_bytes(model) + 914.0 * 3200) / hbm
    assert read("batch.decode_step_roofline") == pytest.approx(100 * floor / (step_ms / 1e3), rel=1e-9)
    kernel_s = 0.010241656999999998 / steps
    assert read("batch.decode_mla_attention_roofline") == pytest.approx(
        100 * 914.0 * 3200 / hbm / kernel_s, rel=1e-9)
    # 30.5 experts hit a layer a step, 50.3 MB each, against 4 ms a step of moe.experts
    assert read("batch.moe_experts_roofline") == pytest.approx(
        100 * 5 * 30.5 * 50_331_648 / hbm / 0.004, rel=1e-9)
    assert read("step.moe_share.batch") == pytest.approx(100 * 5.5 / 10.0)
    assert read("step.latent_attn_share.batch") == pytest.approx(100 * 3.5 / 10.0)


def test_the_new_readers_find_nothing_in_a_program_without_the_counters(traced):
    """Laid over a parent that has neither the counters nor the scopes, the
    readers return None and raise nothing."""
    bare = {**traced, "spans": {"scopes": {}},
            "traced": {"t": (14.0, 14.25), "counters": {"decode_steps": 100}}}
    for metric in ("batch.moe_experts_roofline", "step.moe_share.batch",
                   "step.latent_attn_share.batch"):
        assert load_layer_metric(metric).read(bare) is None
    assert load_layer_metric("batch.moe_experts_roofline").read({**bare, "trace": None}) is None
