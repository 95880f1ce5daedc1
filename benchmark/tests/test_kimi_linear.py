"""`kimi-linear-48b-a3b` (gated delta-rule linear-attention layers beside
latent attention without rotary position in the latent family, a recurrent
state and a convolution's tail in the slot's cache beside the latent rows,
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
CELL = "kimi-linear-48b-a3b.longdoc-wide"
SIBLING = "mistral-small-4.reason-batch"
NEW_READERS = ("batch.decode_kda_state_roofline", "step.kda_share.batch",
               "extend.kda_share.batch", "extend.kda_chunk_share.batch")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 4294967311
PERIOD = ["linear_attention"] * 3 + ["full_attention"]


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL)


def test_the_cell_is_the_one_the_issue_names(cell):
    assert cell.chips == 1 and cell.reference == "kimi_linear_ref"
    assert cell.model_module == "omnia_tpu.models.mla"
    assert mf.decode_kernel(cell.model) == "decode_mla_attention"
    assert mf.decode_kernel_layers(cell.model) == 2        # the latent layers alone call it
    assert cell.engine == {
        "num_slots": 64, "max_seq": 9216, "prefill_buckets": [256, 512, 1024],
        "dtype": "bfloat16", "tp": 1, "decode_chunk": 8, "decode_pipeline": 2, "max_sessions": 0}
    assert cell.traffic == {
        "generator": "closed_loop", "why": cell.traffic["why"], "clients": 96,
        "prompt_tokens": {"dist": "uniform", "min": 4096, "max": 8192},
        "output_tokens": {"dist": "fixed", "value": 512},
        # the issue's 16 first-round lengths, four slots a length (PERF.md section 6)
        "first_output_spread": 16, "first_output_spread_why": cell.traffic["first_output_spread_why"],
        "ramp_s": 20, "ramp_s_why": cell.traffic["ramp_s_why"],
        # read by run.py alone: warm-up compiles the extend programs as well
        "sessions": True, "sessions_why": cell.traffic["sessions_why"]}
    # every prompt is longer than the largest bucket: all are placed in pieces,
    # and the last piece's pad stays inside the cache
    assert cell.traffic["prompt_tokens"]["min"] > max(cell.engine["prefill_buckets"])
    assert (cell.traffic["prompt_tokens"]["max"] + max(cell.engine["prefill_buckets"])
            <= cell.engine["max_seq"])
    assert cell.engine["max_seq"] % 1024 == 0              # the latent kernel's largest block
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s_chip", "setup_s"}
    listed = [name for name, _ in cell.layer_metrics]
    sibling = [name for name, _ in Cell(SIBLING).layer_metrics]
    assert listed == (sibling + ["step.extend_ms_per_ktok.batch", "step.extend_device_share.batch"]
                      + list(NEW_READERS))
    assert len(listed) == 25
    added = {"stack.dense_kda", "stack.sparse_kda", "stack.sparse_mla", "attn.kda",
             "kda.conv", "kda.gates", "kda.chunk", "kda.state", "kda.out"}
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
        **dataclasses.asdict(type(mc)()), "name": "kimi-linear-48b-a3b", "vocab_size": 40960,
        "hidden_size": 2304, "num_layers": 8, "num_heads": 32, "num_kv_heads": 32,
        "head_dim": 72, "ffn_hidden_size": 9216, "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
        "tie_embeddings": False, "num_experts": 256, "num_experts_per_tok": 8,
        "max_seq_len": 1048576, "kv_rank": 512, "q_rank": 0, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "moe_ffn_hidden_size": 1024,
        "num_shared_experts": 1, "num_experts_held": 64, "expert_rank": 0,
        "routed_scaling_factor": 2.446, "router_scoring": "sigmoid",
        "router_topk_method": "noaux_tc", "num_dense_layers": 1,
        "layer_types": tuple(PERIOD * 6 + ["linear_attention"] * 2 + ["full_attention"]),
        "rope_on_full_layers": False, "kda_num_heads": 32, "kda_head_dim": 128,
        "kda_conv_kernel": 4, "kda_gate_rank": 128}
    assert mc.is_latent and mc.router_bias and mc.experts_held == 64 and mc.has_state_layers
    assert mc.attention_kinds == ("kda", "kda", "kda", "full") * 2
    from omnia_tpu.models import mla, model_module

    assert model_module(mc) is mla and mla.has_kinds(mc)
    assert mla.stack_kinds(mc) == ("dense_kda", "sparse_kda", "sparse_mla")
    assert mla.layer_order(mc) == ((0, 0), (1, 0), (1, 1), (2, 0), (1, 2), (1, 3), (1, 4), (2, 1))
    cache = jax.eval_shape(lambda: mla.init_kv_cache(mc, 64, 9216))
    assert [(c.shape, str(c.dtype)) for c in cache] == [
        ((2, 64, 9216, 640), "bfloat16"), ((6, 64, 32, 128, 128), "float32"),
        ((6, 64, 3, 12288), "bfloat16")]
    # the issue's arithmetic: rows 1.51 GB, states 0.81 GB, tails 0.03 GB
    assert [round(c.size * c.dtype.itemsize / 1e9, 2) for c in cache] == [1.51, 0.81, 0.03]
    tiny = cell.model_config(rehearse=True)
    assert (tiny.hidden_size, tiny.num_layers, tiny.num_dense_layers, tiny.kda_head_dim,
            tiny.num_experts, tiny.num_experts_held, tiny.num_experts_per_tok) == (
                64, 4, 1, 16, 8, 4, 2)
    assert tiny.attention_kinds == ("kda", "kda", "full", "kda") and tiny.q_rank == 0


def test_the_flat_copies_equal_what_the_reference_reads(cell):
    """`assumed` copies `linear_attn_config`'s lists (which count layers from
    1) and sizes to flat keys for ModelConfig; the reference reads the
    source's group. One model, one set of numbers, at both sizes."""
    m = cell.model
    # the floor counts an even router's experts, never a reading of the program,
    # which stands beside it
    assert m["expected_experts_hit"] == round(64 * (1 - (1 - 8 / 256) ** 64), 1) == 55.6
    assert 8 <= m["moe_experts_hit_read"] <= m["expected_experts_hit"]
    assert m["expected_live_slots"] == cell.engine["num_slots"]
    for rehearse in (False, True):
        mc = cell.model_config(rehearse)
        run = cell.config_as_run(rehearse)
        sizes = reference_sizes(mc, run)
        ref = mf.load_reference(cell.reference)
        from omnia_tpu.models import mla

        assert ref.layer_order(sizes) == mla.layer_order(mc)
        assert tuple(f"{ffn}_{a}" for ffn, a in ref.stack_kinds(sizes)) == mla.stack_kinds(mc)
        linear = run["linear_attn_config"]
        assert (linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"]) == (
            mc.kda_num_heads, mc.kda_head_dim, mc.kda_conv_kernel)
        assert mc.attention_kinds == tuple(
            "kda" if l + 1 in linear["kda_layers"] else "full" for l in range(mc.num_layers))
        assert all((l + 1 in linear["kda_layers"]) != (l + 1 in linear["full_attn_layers"])
                   for l in range(mc.num_layers))
        assert (run["num_kda_layers"], run["num_mla_layers"]) == (
            mc.attention_kinds.count("kda"), mc.attention_kinds.count("full"))
        assert run["mla_use_nope"] is (not mc.rope_on_full_layers) is True
        assert sizes["config"]["num_experts"] == mc.experts_held
        assert sizes["num_experts_per_tok"] == run["num_experts_per_token"]
        assert ref._key(sizes["config"], "l2norm_eps") == 1e-6


def test_the_file_keeps_every_published_number_but_the_reduced(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == cell.model["source"])
    entry = next(c for c in mf.benchmark_json()["configs"] if c["name"] == "kimi-linear-48b-a3b")
    assert entry["source"] == cell.model["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(cell.model["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert cell.model["reduced"][key]["source"] == value
            assert cell.model["reduced"][key]["here"] == cell.model[key] != value
        else:
            assert cell.model[key] == value, key
    assert cell.model["reduced"]["num_hidden_layers"]["here"] == 8       # two whole periods
    assert cell.model["num_experts_source"] == row["config"]["num_experts"] == 256
    assert cell.model["num_experts"] * cell.model["chips_sharing_a_layer"] == 256
    assert cell.model["vocab_size"] * cell.model["chips_sharing_a_layer"] == 163840
    deployment = cell.model["deployment"]
    assert deployment["pipeline_stages"] * deployment["chips_a_stage"] == deployment["chips"] == 16
    assumed = cell.model["assumed"]
    for key in ("layer_types", "kda_gate_rank", "q_rank", "rope_on_full_layers", "topk_method",
                "l2norm_eps", "state_dtype"):
        assert key in assumed and len(assumed[key + "_why"]) > 40, key
    assert assumed["state_dtype"] == "float32" and cell.model["num_nextn_predict_layers"] == 0
    assert "3 : 1" in cell.model["stands_for"] and "16-chip" in cell.model["stands_for"]


@pytest.mark.parametrize("rehearse", [True, False])
def test_the_byte_counts_equal_the_parameter_trees_and_the_issues_arithmetic(cell, rehearse):
    """Everything but the embedding table (gathered, not streamed), summed
    over the stacks `models/mla.py::init_params` makes, with the held experts
    counted as the file's expected hit and not all of them, and the states
    of the expected live slots read and written on top."""
    from omnia_tpu.models import mla

    mc, m = cell.model_config(rehearse), cell.config_as_run(rehearse)
    tree = jax.eval_shape(lambda: mla.init_params(mc, jax.random.key(0), jnp.bfloat16))
    streamed = {k: v for k, v in tree.items() if k != "embed"}
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(streamed))
    counts = mf.load_decode_bytes(m)
    sparse = mc.num_layers - mc.num_dense_layers
    kda = mc.attention_kinds.count("kda")
    not_hit = (mc.experts_held - m["expected_experts_hit"]) * counts.expert_bytes(m)
    # (the tree keeps dt_bias and a_log in float32, counted at two bytes here)
    small = kda * (mc.kda_num_heads * mc.kda_head_dim + mc.kda_num_heads) * 2
    states = m["expected_live_slots"] * kda * counts.state_bytes(m)
    assert counts.decode_weight_bytes(m) == int(nbytes - small - sparse * not_hit + states)
    assert counts.state_bytes(m) == 2 * mc.kda_num_heads * mc.kda_head_dim ** 2 * 4
    assert counts.kv_bytes_per_token(m) == (
        mc.attention_kinds.count("full") * (mc.kv_rank + mc.qk_rope_head_dim) * 2)
    if rehearse:
        return
    d = cell.model["deployment"]["published_parameters"]
    assert d["kda_attention_a_layer"] == counts._kda_params(m) == 39_514_272
    assert d["latent_attention_a_layer"] == counts._mla_params(m) == 29_114_880
    assert d["routed_expert"] == d["shared_expert_a_layer"] == 3 * 2304 * 1024
    assert d["dense_ffn_a_layer"] == 3 * 2304 * 9216 and d["router_a_layer"] == 2304 * 256
    assert d["embedding_and_head"] == 2 * 163840 * 2304
    sparse_kda = (d["kda_attention_a_layer"] + 64 * d["routed_expert"]
                  + d["shared_expert_a_layer"] + d["router_a_layer"])
    sparse_mla = sparse_kda - d["kda_attention_a_layer"] + d["latent_attention_a_layer"]
    dense = d["kda_attention_a_layer"] + d["dense_ffn_a_layer"]
    assert [round(x / 1e6, 1) for x in (sparse_kda, sparse_mla, dense)] == [500.2, 489.8, 103.2]
    held = 2 * (dense + 5 * sparse_kda + 2 * sparse_mla + d["embedding_and_head"] // 4)
    assert round(held / 1e9, 2) == 7.54
    # the tree is that, the norms and the float32 leaves' other two bytes more
    assert 0 < nbytes + tree["embed"].size * 2 - held < 1e6
    assert counts.kv_bytes_per_token(m) == 2 * 1152 and counts.state_bytes(m) == 2 * 2_097_152
    # a step: about 8.1 GB before the latent rows, of which the states 1.6 and
    # an even router's experts 5.5 (the issue's count; 3.1 and 5.7 in all at
    # the 31.0 experts the seeded model was read to hit)
    assert round(counts.decode_weight_bytes(m) / 1e9, 1) == 8.1
    assert round(states / 1e9, 2) == 1.61
    assert round(7 * m["expected_experts_hit"] * counts.expert_bytes(m) / 1e9, 1) == 5.5
    assert round(7 * m["moe_experts_hit_read"] * counts.expert_bytes(m) / 1e9, 1) == 3.1


def _engine(cell):
    mc = cell.model_config(rehearse=True)
    params = seeded_params(mc, cell.engine_config(), None, SEED, jnp.bfloat16,
                           model_module=cell.model_module)
    sizes = reference_sizes(mc, cell.config_as_run(rehearse=True))
    return types.SimpleNamespace(params=params, _mesh=None), mc, sizes


def test_the_check_passes_at_rehearsal_and_a_state_never_reset_fails_it(cell, monkeypatch):
    """Seeded weights and `correct.check` through `omnia_tpu.models.mla` and
    `kimi_linear_ref`: every layer of the three stacks alone, the dense and
    the first sparse layer together through a cache of three arrays. With
    the decay applied behind the update instead of before it the check
    fails."""
    engine, mc, sizes = _engine(cell)
    assert isinstance(engine.params["layers"], list) and len(engine.params["layers"]) == 3
    check = lambda cfg: correct.check(engine, cfg, sizes, SEED,  # noqa: E731
                                      reference=cell.reference, model_module=cell.model_module)
    sound = check(mc)
    assert sound["ok"] is True, sound
    assert sound["decided_positions"] >= correct.PREFILL + correct.DECODE + correct.MIN_DECIDED
    assert sound["layers_noise_ratio_max"] < 1.6 and sound["layers_decode_max_over_range"] < 1e-2
    assert sound["pair_decode_median_worst_over_range"] < correct.PAIR_TOL / 5
    from omnia_tpu.models import mla
    from omnia_tpu.ops import kda

    def decay_behind(S, q, k, v, g, beta):
        f32 = jnp.float32
        q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
        r = jnp.einsum("...kv,...k->...v", S, k)
        S = S + k[..., :, None] * (beta[..., None] * (v - r))[..., None, :]
        S = S * jnp.exp(g)[..., :, None]
        return jnp.einsum("...kv,...k->...v", S, q), S

    monkeypatch.setattr(kda, "kda_step", decay_behind)
    monkeypatch.setattr(mla, "kda_chunked", kda.kda_recurrent)
    wrong = check(mc)
    assert wrong["ok"] is False, wrong


def test_an_engine_says_it_serves_the_model_by_mla(cell):
    from omnia_tpu.engine.engine import InferenceEngine
    from omnia_tpu.engine.types import EngineConfig

    ecfg = EngineConfig(num_slots=2, max_seq=256, prefill_buckets=(64,), max_sessions=0)
    engine = InferenceEngine(cell.model_config(rehearse=True), ecfg)
    assert mf.served_by(engine) == cell.model_module == "omnia_tpu.models.mla"
    assert [c.shape for c in engine._cache] == [(1, 2, 256, 128), (3, 2, 4, 16, 16),
                                                (3, 2, 3, 192)]
    assert {"moe_assignments_held", "moe_experts_hit", "decode_kda_slots"} <= set(engine.metrics)


@pytest.fixture(scope="module")
def traced(cell):
    """The recorded one-chip trace (its 56 calls of the pair family's decode
    kernel stand for 56 steps), with this model's kernels' calls (the latent
    kernel in two layers a step, the state kernel in six) and the extend
    modules put beside what it holds, the counters a traced run of this cell
    would have, and a scope table in place of the trace directory's."""
    with gzip.open(os.path.join(HERE, "trace_sample.json.gz"), "rt") as f:
        reduced = tr.reduce(json.load(f))
    ops = reduced["ops_in_module"][DECODE_MODULE]
    steps = sum(n for name, (n, _s) in ops.items() if name.split(".")[0] == "decode_gqa_attention")
    assert steps == 56
    ops["decode_mla_attention.2"] = (2 * steps, 2 * steps * 600e-6)
    ops["decode_kda_state.7"] = (6 * steps, 6 * steps * 450e-6)      # 450 us a layer a step
    reduced["modules"]["jit_extend_nosample"] = {"calls": 50, "seconds": 1.5}
    reduced["modules"]["jit_extend"] = {"calls": 10, "seconds": 0.3}
    records = [
        Record(i, "wide", 6000 + 10 * i, 512, due=10.0 + i, sent=10.001 + i,
               first=10.3 + i, last=12.8 + i, done=12.8 + i, tokens=512,
               finish="length", request_id=f"req-{i}")
        for i in range(20)
    ]
    scopes = {DECODE_MODULE: {"mlp": 0.06, "attn.kda": 0.004, "kda.state": 0.01, "kda.conv": 0.002,
                              "kda.gates": 0.003, "kda.out": 0.001, "attn.decode": 0.01,
                              "lm_head": 0.01},
              "jit_extend_nosample": {"mlp": 0.9, "attn.kda": 0.1, "kda.chunk": 0.2,
                                      "kda.conv": 0.02, "attn.prefill": 0.28},
              "jit_extend": {"mlp": 0.18, "attn.kda": 0.02, "kda.chunk": 0.04, "kda.conv": 0.004,
                             "attn.prefill": 0.056}}
    return {"records": records, "all_records": records, "chips": 1, "model": cell.model,
            "peaks": roofline.peaks("TPU v5 lite"), "trace": reduced, "spans": {"scopes": scopes},
            "traced": {"t": (14.0, 14.25),
                       "counters": {"decode_steps": 100, "prefill_tokens": 40_000,
                                    "decode_kda_slots": 100 * 6 * 62}}}


def test_the_new_readers_read_the_cell(traced):
    read = lambda metric: load_layer_metric(metric).read(traced)  # noqa: E731
    assert read("step.kda_share.batch") == pytest.approx(100 * 0.02 / 0.1)
    assert read("extend.kda_share.batch") == pytest.approx(100 * 0.384 / 1.8)
    assert read("extend.kda_chunk_share.batch") == pytest.approx(100 * 0.24 / 1.8)
    # 62 live slots' states, 4 MB each read and written, six layers, over 819
    # GB/s, against the 6 x 450 us a step the kernel took
    floor = 62 * 6 * 4_194_304 / traced["peaks"]["hbm_bytes_per_s"]
    assert read("batch.decode_kda_state_roofline") == pytest.approx(100 * floor / (6 * 450e-6))
    assert 0 < read("batch.decode_kda_state_roofline") < 100
    # the accepted readers this cell lists read it too: the latent kernel over
    # the latent layers' rows alone, the step against a floor that holds the states
    assert 0 < load_layer_metric("batch.decode_mla_attention_roofline").read(traced) < 100
    assert roofline.kv_bytes_per_token(traced["model"]) == 2 * 1152
    assert load_layer_metric("step.extend_ms_per_ktok.batch").read(traced) == pytest.approx(45.0)


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes(traced):
    """Laid over a parent that has not the scopes, the counter or the
    kernel, or on a run that was not traced, the readers return None and
    raise nothing."""
    bare = {**traced, "spans": {"scopes": {DECODE_MODULE: {"mlp": 0.1},
                                           "jit_extend": {"mlp": 0.4}}}}
    for metric in NEW_READERS[1:]:
        assert load_layer_metric(metric).read(bare) is None
        assert load_layer_metric(metric).read({**bare, "spans": None, "traced": None}) is None
    no_counter = {**traced, "traced": {**traced["traced"],
                                       "counters": {"decode_steps": 100, "prefill_tokens": 9}}}
    assert load_layer_metric("batch.decode_kda_state_roofline").read(no_counter) is None
    ops = {k: v for k, v in traced["trace"]["ops_in_module"][DECODE_MODULE].items()
           if not k.startswith("decode_kda_state")}
    no_kernel = {**traced, "trace": {**traced["trace"], "ops_in_module": {DECODE_MODULE: ops}}}
    assert load_layer_metric("batch.decode_kda_state_roofline").read(no_kernel) is None
    # another configuration's byte counts know no state
    other = {**traced, "model": Cell(SIBLING).model}
    assert load_layer_metric("batch.decode_kda_state_roofline").read(other) is None
    for metric in NEW_READERS:
        assert load_layer_metric(metric).read({**traced, "trace": None, "spans": None}) is None
