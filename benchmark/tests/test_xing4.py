"""`xing4-29b-a4b` (two stacks of unlike layers in the latent family, a
residual of four copies mixed by Sinkhorn-projected hyper-connections, a
sigmoid router with a selection bias over all 64 experts held) through the
manifest, the weights, the check and the readers at the rehearsal's widths
on the CPU, and what the configuration file promises about itself."""
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
from harness.layer_common import DECODE_MODULE, PREFILL_MODULES
from harness.load import Record
from harness.manifest import Cell, load_layer_metric, reference_sizes
from harness.weights import seeded_params

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "xing4-29b-a4b.judge-batch"
SIBLING = "mistral-small-4.reason-batch"
NEW_READERS = ("step.prefill_ms_per_ktok.batch", "step.prefill_device_share.batch",
               "step.hc_mix_share.batch", "prefill.hc_mix_share.batch")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 4294967311


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL)


def test_the_cell_is_the_one_the_issue_names(cell):
    assert cell.chips == 1 and cell.reference == "xing4_ref"
    assert cell.model_module == "omnia_tpu.models.mla"
    assert mf.decode_kernel(cell.model) == "decode_mla_attention"
    assert mf.decode_kernel_layers(cell.model) == 5      # every layer of both stacks calls it
    assert cell.engine == {
        "num_slots": 48, "max_seq": 2304, "prefill_buckets": list(range(1152, 2049, 128)),
        "dtype": "bfloat16", "tp": 1, "decode_chunk": 8, "decode_pipeline": 2, "max_sessions": 0}
    assert cell.traffic == {
        "generator": "closed_loop", "why": cell.traffic["why"], "clients": 64,
        "prompt_tokens": {"dist": "uniform", "min": 1024, "max": 2048},
        "output_tokens": {"dist": "fixed", "value": 96},
        "first_output_spread": 16, "ramp_s": 10}
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s_chip", "setup_s"}
    listed = [name for name, _ in cell.layer_metrics]
    assert listed == [name for name, _ in Cell(SIBLING).layer_metrics] + list(NEW_READERS)
    assert len(listed) == 23
    assert spans.scopes_of(cell.model) == (
        spans.SCOPES | {"hc.mix", "hc.maps", "hc.sinkhorn", "stack.dense", "stack.sparse"},
        spans.SCANS | {"stack.dense", "stack.sparse"})


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
        **dataclasses.asdict(type(mc)()), "name": "xing4-29b-a4b", "vocab_size": 131072,
        "hidden_size": 3584, "num_layers": 5, "num_heads": 32, "num_kv_heads": 32,
        "head_dim": 192, "ffn_hidden_size": 9216, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-06, "tie_embeddings": False, "num_experts": 64,
        "num_experts_per_tok": 4, "max_seq_len": 262144, "kv_rank": 512, "q_rank": 768,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "rope_yarn": (64, 4096, 32, 1, 1, 1), "rope_interleave": True,
        "moe_ffn_hidden_size": 1024, "num_shared_experts": 1, "routed_scaling_factor": 2,
        "router_scoring": "sigmoid", "router_topk_method": "noaux_tc", "num_dense_layers": 1,
        "residual_copies": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
        "hc_res_clamp_min": -30, "hc_res_clamp_max": 30}
    assert mc.is_latent and mc.router_bias and mc.experts_held == 64
    tiny = cell.model_config(rehearse=True)
    assert (tiny.hidden_size, tiny.num_layers, tiny.num_dense_layers, tiny.residual_copies,
            tiny.kv_rank, tiny.num_experts, tiny.num_experts_per_tok) == (64, 3, 1, 4, 32, 8, 4)
    assert tiny.rope_yarn == mc.rope_yarn and tiny.router_scoring == "sigmoid"


def test_the_flat_copies_equal_the_sources_nested_keys(cell):
    """`assumed` copies rope_scaling to a flat key for ModelConfig; the
    reference reads the group itself. One model, so one set of numbers."""
    rs, assumed = cell.model["rope_scaling"], cell.model["assumed"]
    assert assumed["rope_yarn"] == [rs[k] for k in (
        "factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale",
        "mscale_all_dim")]
    assert assumed["head_dim"] == cell.model["qk_nope_head_dim"] + cell.model["qk_rope_head_dim"]
    assert assumed["rope_interleave"] is True and "rope_interleave" not in (
        set(cell.model) - {"assumed"})
    sizes = reference_sizes(cell.model_config(), cell.config_as_run())
    ref = mf.load_reference(cell.reference)
    assert ref.layer_order(sizes) == ((0, 0), (1, 0), (1, 1), (1, 2), (1, 3))
    seen = ref._attention_sizes(sizes)["config"]
    assert seen["rope_parameters"] == {**rs, "rope_theta": cell.model["rope_theta"]}
    assert seen["rope_interleave"] is True


def test_the_file_keeps_every_published_number_but_the_reduced(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == cell.model["source"])
    entry = next(c for c in mf.benchmark_json()["configs"] if c["name"] == "xing4-29b-a4b")
    assert sorted(entry["reduced"]) == sorted(cell.model["reduced"]) == [
        "first_k_dense_replace", "num_hidden_layers"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert cell.model["reduced"][key]["source"] == value
            assert cell.model["reduced"][key]["here"] == cell.model[key] != value
        else:
            assert cell.model[key] == value, key
    deployment = cell.model["deployment"]
    assert deployment["pipeline_stages"] * deployment["layers_a_stage"] == 40
    assert deployment["ep_size"] == cell.model["ep_size"] == 1
    assert "not built" in cell.model["assumed"]["multi_token_prediction"]
    assert cell.model["num_nextn_predict_layers"] == 1


@pytest.mark.parametrize("rehearse", [True, False])
def test_the_byte_counts_equal_the_parameter_trees_and_the_issues_arithmetic(cell, rehearse):
    """Everything but the embedding table (gathered, not streamed), summed
    over the two stacks `models/mla.py::init_params` makes."""
    from omnia_tpu.models import mla

    mc, m = cell.model_config(rehearse), cell.config_as_run(rehearse)
    tree = jax.eval_shape(lambda: mla.init_params(mc, jax.random.key(0), jnp.bfloat16))
    streamed = {k: v for k, v in tree.items() if k != "embed"}
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(streamed))
    counts = mf.load_decode_bytes(m)
    assert counts.decode_weight_bytes(m) == nbytes
    assert counts.kv_bytes_per_token(m) == mc.num_layers * (mc.kv_rank + mc.qk_rope_head_dim) * 2
    assert counts.expert_bytes(m) == 3 * mc.hidden_size * mc.moe_ffn_hidden_size * 2
    if rehearse:
        return
    d = cell.model["deployment"]["published_parameters"]
    assert d["attention_a_layer"] == (3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192
                                      + 4096 * 3584) == 28_409_856
    assert d["routed_expert"] == d["shared_expert_a_layer"] == 3 * 3584 * 1024
    assert d["hyper_connection_maps_a_layer"] == 2 * 14336 * (4 + 4 + 16) == 688_128
    assert d["dense_ffn_a_layer"] == 3 * 3584 * 9216 and d["router_a_layer"] == 3584 * 64
    assert d["embedding_and_head"] == 2 * 131072 * 3584
    sparse = (d["attention_a_layer"] + 64 * d["routed_expert"] + d["shared_expert_a_layer"]
              + d["router_a_layer"] + d["hyper_connection_maps_a_layer"])
    dense = d["attention_a_layer"] + d["dense_ffn_a_layer"] + d["hyper_connection_maps_a_layer"]
    assert round(2 * sparse / 1e9, 2) == 1.49 and round(2 * dense / 1e9, 2) == 0.26
    held = 2 * (dense + 4 * sparse + d["embedding_and_head"])
    assert round(held / 1e9, 1) == 8.1
    # the tree is that, the norms and the float32 bias and alpha of the maps more
    assert 0 < nbytes + tree["embed"].size * 2 - held < 1e6
    assert counts.kv_bytes_per_token(m) == 5 * 1152
    assert counts.decode_attention_row(m) == {"flops": 32 * (576 + 512) * 2, "bytes": 1152}


def _engine(cell):
    mc = cell.model_config(rehearse=True)
    params = seeded_params(mc, cell.engine_config(), None, SEED, jnp.bfloat16,
                           model_module=cell.model_module)
    sizes = reference_sizes(mc, cell.config_as_run(rehearse=True))
    return types.SimpleNamespace(params=params, _mesh=None), mc, sizes


def test_the_check_passes_at_rehearsal_and_a_fault_in_the_mix_shows(cell, monkeypatch):
    """Seeded weights and `correct.check` through `omnia_tpu.models.mla` and
    `xing4_ref`: every layer of both stacks alone on a stream of 4 x 64, the
    dense and the first sparse layer together through a cache of two. With
    Sinkhorn left out of the served side the check fails by the layers'
    largest distance."""
    from omnia_tpu.ops import hyper_connections as hc

    engine, mc, sizes = _engine(cell)
    assert isinstance(engine.params["layers"], list) and len(engine.params["layers"]) == 2
    check = lambda: correct.check(engine, mc, sizes, SEED, reference=cell.reference,  # noqa: E731
                                  model_module=cell.model_module)
    sound = check()
    assert sound["ok"] is True, sound
    # every position of the dense layer is decided: 136, and some of the sparse layers'
    assert sound["decided_positions"] >= correct.PREFILL + correct.DECODE + correct.MIN_DECIDED
    assert sound["layers_noise_ratio_max"] < 1.4 and sound["layers_decode_max_over_range"] < 1e-2
    assert sound["pair_decode_median_worst_over_range"] < correct.PAIR_TOL / 5
    monkeypatch.setattr(hc, "sinkhorn", lambda m, iters, eps: m)
    wrong = check()
    assert wrong["ok"] is False, wrong
    assert wrong["layers_prefill_max_over_range"] > wrong["limits"]["layers_max_over_range"]


def test_an_engine_says_it_serves_the_family_by_mla(cell):
    from omnia_tpu.engine.engine import InferenceEngine
    from omnia_tpu.engine.types import EngineConfig

    ecfg = EngineConfig(num_slots=2, max_seq=256, prefill_buckets=(64,), max_sessions=0)
    engine = InferenceEngine(cell.model_config(rehearse=True), ecfg)
    assert mf.served_by(engine) == cell.model_module == "omnia_tpu.models.mla"
    assert len(engine._cache) == 1 and engine._cache[0].shape == (3, 2, 256, 128)
    assert {"moe_assignments_held", "moe_experts_hit"} <= set(engine.metrics)


@pytest.fixture(scope="module")
def traced(cell):
    """The recorded one-chip trace with its decode kernel under the latent
    kernel's name (56 calls over the model's 5 layers), the counters a traced
    run of this cell would have, and a scope table in place of the trace
    directory's: 40 ms of a 400 ms prefill under the mix's three scopes."""
    with gzip.open(os.path.join(HERE, "trace_sample.json.gz"), "rt") as f:
        reduced = tr.reduce(json.load(f))
    ops = reduced["ops_in_module"][DECODE_MODULE]
    reduced["ops_in_module"][DECODE_MODULE] = {
        k.replace("decode_gqa_attention", "decode_mla_attention"): v for k, v in ops.items()}
    records = [
        Record(i, "window", 1400 + 10 * i, 96, due=10.0 + i, sent=10.001 + i,
               first=10.3 + i, last=12.8 + i, done=12.8 + i, tokens=96,
               finish="length", request_id=f"req-{i}")
        for i in range(20)
    ]
    scopes = {DECODE_MODULE: {"mlp": 0.06, "attn.decode": 0.02, "hc.mix": 0.004,
                              "hc.maps": 0.003, "hc.sinkhorn": 0.001, "lm_head": 0.012},
              PREFILL_MODULES[0]: {"attn.prefill": 0.2, "mlp": 0.16, "hc.mix": 0.025,
                                   "hc.maps": 0.013, "hc.sinkhorn": 0.002}}
    return {"records": records, "all_records": records, "chips": 1, "model": cell.model,
            "peaks": roofline.peaks("TPU v5 lite"), "trace": reduced, "spans": {"scopes": scopes},
            "traced": {"t": (14.0, 14.25),
                       "counters": {"decode_steps": 100, "prefill_tokens": 12_000}}}


def test_the_new_readers_read_the_cell(traced):
    read = lambda metric: load_layer_metric(metric).read(traced)  # noqa: E731
    trace = traced["trace"]
    prefill_s = sum(trace["modules"][m]["seconds"] for m in PREFILL_MODULES if m in trace["modules"])
    assert prefill_s > 0
    assert read("step.prefill_ms_per_ktok.batch") == pytest.approx(prefill_s / 12_000 * 1e6)
    assert read("step.prefill_device_share.batch") == pytest.approx(
        100 * prefill_s / trace["busy_s"])
    assert read("step.hc_mix_share.batch") == pytest.approx(100 * 0.008 / 0.1)
    assert read("prefill.hc_mix_share.batch") == pytest.approx(100 * 0.04 / 0.4)


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes(traced):
    """Laid over a parent that has not the scopes, or on a run that was not
    traced, the readers return None and raise nothing."""
    bare = {**traced, "spans": {"scopes": {DECODE_MODULE: {"mlp": 0.1},
                                           PREFILL_MODULES[0]: {"mlp": 0.4}}}}
    for metric in ("step.hc_mix_share.batch", "prefill.hc_mix_share.batch"):
        assert load_layer_metric(metric).read(bare) is None
        assert load_layer_metric(metric).read({**bare, "spans": None, "traced": None}) is None
    untraced = {**traced, "trace": None}
    assert load_layer_metric("step.prefill_device_share.batch").read(untraced) is None
    assert load_layer_metric("step.prefill_ms_per_ktok.batch").read(untraced) is None
