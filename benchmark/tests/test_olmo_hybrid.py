"""`olmo-hybrid-7b` (the pair family with linear-attention layers: the gated
delta rule with one decay a head over 30 heads of 96 x 192 beside un-rotated
30-head full attention with a whole-width QK-norm, both norms of a block on
its sublayers' outputs) through the manifest, the weights, the check and the
readers at the rehearsal's widths on the CPU, and what the configuration file,
the traffic file and the cell promise about themselves."""
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
CELL = "olmo-hybrid-7b.think-batch"
SIBLING = "kimi-linear-48b-a3b.longdoc-wide"
NEW_READERS = ("batch.decode_delta_state_roofline", "step.delta_share.batch",
               "extend.delta_share.batch", "extend.delta_chunk_share.batch")
JOINED = ("batch.decode_gqa_attention_roofline", "step.full_attn_share.batch",
          "extend.full_attn_share.batch", "step.prefill_ms_per_ktok.batch",
          "step.prefill_device_share.batch", "step.extend_ms_per_ktok.batch",
          "step.extend_device_share.batch", "placement.fresh_token_share.batch")
KIMIS = ("batch.decode_kda_state_roofline", "step.kda_share.batch", "extend.kda_share.batch",
         "extend.kda_chunk_share.batch")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 4294967311
PERIOD = ["linear_attention"] * 3 + ["full_attention"]


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL)


def test_the_cell_is_the_one_the_issue_names(cell):
    assert cell.chips == 1 and cell.reference == "olmo_hybrid_ref"
    assert cell.model_module == "omnia_tpu.models.llama"   # the pair family
    assert mf.decode_kernel(cell.model) == "decode_gqa_attention"
    assert mf.decode_kernel_layers(cell.model) == 2         # the two full layers call it
    assert cell.engine == {
        "num_slots": 64, "max_seq": 3072, "prefill_buckets": [256, 512, 1024],
        "dtype": "bfloat16", "tp": 1, "decode_chunk": 8, "decode_pipeline": 2, "max_sessions": 0}
    assert cell.traffic == {
        "generator": "closed_loop", "why": cell.traffic["why"], "clients": 96,
        "prompt_tokens": {"dist": "uniform", "min": 512, "max": 2048},
        "output_tokens": {"dist": "fixed", "value": 1024},
        "first_output_spread": 32,
        "first_output_spread_why": cell.traffic["first_output_spread_why"],
        "ramp_s": 20, "ramp_s_why": cell.traffic["ramp_s_why"],
        # read by run.py alone: warm-up compiles the extend programs as well
        "sessions": True, "sessions_why": cell.traffic["sessions_why"]}
    assert (cell.traffic["prompt_tokens"]["max"] + cell.traffic["output_tokens"]["value"]
            == cell.engine["max_seq"])
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s_chip", "setup_s"}
    listed = [name for name, _ in cell.layer_metrics]
    # the fifteen readers every batch cell lists, the eight it joins, its own four
    assert listed == ([name for name, _ in Cell(SIBLING).layer_metrics][:15]
                      + list(JOINED) + list(NEW_READERS))
    assert "programs.warmup_s" in listed[:15] and not set(listed) & set(KIMIS)
    added = {"stack.dense_delta", "stack.dense_full", "attn.full", "attn.qk_norm", "attn.delta",
             "delta.conv", "delta.gates", "delta.chunk", "delta.state", "delta.out"}
    assert spans.scopes_of(cell.model) == (
        spans.SCOPES | added, spans.SCANS | {s for s in added if s.startswith("stack.")})
    entry = next(w for w in mf.benchmark_json()["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and (entry["config"], entry["traffic"]) == (
        "olmo-hybrid-7b", "think-batch")


def test_the_benchmark_holds_the_cell_and_kimis_readers_do_not():
    """Membership alone: a later PR appends cells, configurations and names to
    these lists, so nothing here counts them or asks who stands last."""
    bench = mf.benchmark_json()
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    assert [c["name"] for c in bench["configs"]].count("olmo-hybrid-7b") == 1
    for metric in KIMIS:  # the latent family's state readers do not read this cell
        entry = next(m for m in bench["per_layer"] if m["name"] == metric)
        assert CELL not in entry["workloads"]
    for metric in JOINED:
        entry = next(m for m in bench["per_layer"] if m["name"] == metric)
        assert entry["workloads"].count(CELL) == 1


def test_the_traffic_is_the_mix_the_issue_computed(cell):
    """Of a round's 96 stratified prompts a third fit the largest bucket (one
    fresh prefill from a zero state each); two thirds go in pieces that hand
    the state and the tail on at an offset."""
    prompts = stratified(cell.traffic["prompt_tokens"], cell.traffic["clients"], base=2)
    largest = max(cell.engine["prefill_buckets"])
    short = [n for n in prompts if n <= largest]
    assert len(prompts) == 96 and 30 <= len(short) <= 34
    assert 1250 <= sum(prompts) / 96 <= 1310 and min(prompts) >= 512 and max(prompts) <= 2048


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
        **dataclasses.asdict(type(mc)()), "name": "olmo-hybrid-7b", "vocab_size": 100352,
        "hidden_size": 3840, "num_layers": 8, "num_heads": 30, "num_kv_heads": 30,
        "head_dim": 128, "ffn_hidden_size": 11008, "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
        "tie_embeddings": False, "num_experts": 0, "num_experts_per_tok": 2,
        "max_seq_len": 65536, "layer_types": tuple(PERIOD * 8), "rope_on_full_layers": False,
        "qk_norm": True, "qk_norm_whole": True, "norm_placement": "post",
        "linear_num_heads": 30, "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel": 4, "linear_allow_neg_eigval": True}
    assert not mc.is_latent and mc.has_state_layers and not mc.has_window_layers
    assert mc.attention_kinds == ("delta", "delta", "delta", "full") * 2
    from omnia_tpu.models import llama, model_module

    assert model_module(mc) is llama
    assert llama.stack_kinds(mc) == ("dense_full", "dense_delta")
    assert llama.layer_order(mc) == ((1, 0), (1, 1), (1, 2), (0, 0), (1, 3), (1, 4), (1, 5), (0, 1))
    assert llama.rope_tables(mc, jnp.zeros((1, 1), jnp.int32)).keys() == {"window"}  # unused
    tiny = cell.model_config(rehearse=True)
    assert (tiny.hidden_size, tiny.num_layers, tiny.num_heads, tiny.head_dim) == (192, 4, 6, 32)
    assert (tiny.linear_num_heads, tiny.linear_key_head_dim, tiny.linear_value_head_dim) == (
        6, 16, 32)
    assert tiny.attention_kinds == ("delta", "delta", "delta", "full")
    assert tiny.norm_placement == "post" and tiny.qk_norm_whole and tiny.linear_allow_neg_eigval


def test_the_flat_copies_equal_what_the_reference_reads(cell):
    """`assumed` holds the flat switches that ModelConfig is built from where
    the source has a group or no key; the reference reads the source's own
    keys. One model, one set of numbers, at both sizes."""
    m, assumed = cell.model, cell.model["assumed"]
    assert m["rope_parameters"] == {"rope_theta": None} and assumed["rope_on_full_layers"] is False
    assert assumed["head_dim"] == m["hidden_size"] // m["num_attention_heads"] == 128
    run = m["num_hidden_layers"]
    assert m["layer_types"] == PERIOD * 8
    assert m["layer_types"][:run].count("full_attention") == m["num_full_attention_layers"] == 2
    assert m["layer_types"][:run].count("linear_attention") == (
        m["num_linear_attention_layers"]) == 6
    ref = mf.load_reference(cell.reference)
    from omnia_tpu.models import llama

    for rehearse in (False, True):
        mc = cell.model_config(rehearse)
        config = cell.config_as_run(rehearse)
        sizes = reference_sizes(mc, config)
        assert ref.layer_order(sizes) == llama.layer_order(mc)
        kinds = tuple("dense_delta" if a == "linear_attention" else "dense_full"
                      for a in ref.stack_kinds(sizes))
        assert kinds == llama.stack_kinds(mc)
        assert config["linear_num_key_heads"] == config["linear_num_value_heads"] == (
            mc.linear_num_heads)
        assert config["linear_conv_kernel_dim"] == mc.linear_conv_kernel
        assert config["linear_allow_neg_eigval"] is mc.linear_allow_neg_eigval is True
        assert assumed["norm_placement"] == mc.norm_placement
        assert assumed["qk_norm_whole"] is mc.qk_norm_whole is assumed["qk_norm"] is True
        assert ref._key(config, "head_dim") == mc.head_dim


def test_the_file_keeps_every_published_number_but_the_depth(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == cell.model["source"])
    entry = next(c for c in mf.benchmark_json()["configs"] if c["name"] == "olmo-hybrid-7b")
    assert entry["source"] == cell.model["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == list(cell.model["reduced"]) == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert cell.model["reduced"][key]["source"] == value == 32
            assert cell.model["reduced"][key]["here"] == cell.model[key] == 8
        else:
            assert cell.model[key] == value, key
    deployment = cell.model["deployment"]
    assert deployment["layers_a_stage"] == [8, 8, 8, 8]
    assert deployment["pipeline_stages"] == deployment["chips"] == 4
    assumed = cell.model["assumed"]
    for key in ("head_dim", "rope_theta", "rope_on_full_layers", "norm_placement", "qk_norm",
                "l2norm_eps", "state_dtype", "extend_matmul_precision"):
        assert key in assumed and len(assumed[key + "_why"]) > 40, key
    for key in ("a_log", "beta_range", "conv_bias", "output_gate", "final_norm", "weights",
                "dtype"):
        assert len(assumed[key]) > 40, key
    for key in ("norm_placement_why", "qk_norm_why"):  # each says that it is a convention
        assert "convention" in assumed[key]
    assert "not on this machine" in assumed["norm_placement_why"]
    assert assumed["state_dtype"] == "float32" and assumed["extend_matmul_precision"] == "default"
    for said in ("four-stage pipeline", "8 layers a chip", "held here too", "four times"):
        assert said in cell.model["stands_for"], said


@pytest.mark.parametrize("rehearse", [True, False])
def test_the_byte_counts_equal_the_parameter_trees_and_the_issues_arithmetic(cell, rehearse):
    """Everything but the embedding table (gathered, not streamed), summed
    over the stacks `models/llama.py::init_params` makes, and the states of
    the expected live slots; a state's and a row's bytes against the cache's
    own shapes."""
    from omnia_tpu.models import llama

    mc, m = cell.model_config(rehearse), cell.config_as_run(rehearse)
    tree = jax.eval_shape(lambda: llama.init_params(mc, jax.random.key(0), jnp.bfloat16))
    streamed = {k: v for k, v in tree.items() if k != "embed"}
    nbytes = sum(x.size * (2 if x.dtype == jnp.bfloat16 else 4)
                 for x in jax.tree.leaves(streamed))
    counts = mf.load_decode_bytes(m)
    slots, delta = m["expected_live_slots"], mc.attention_kinds.count("delta")
    # (a_log and dt_bias are float32 in the tree and counted at two bytes: 2 x 30 x 2 B a layer)
    assert counts.decode_weight_bytes(m) == (
        nbytes - delta * 2 * mc.linear_num_heads * 2 + slots * delta * counts.state_bytes(m))
    assert mc.num_params() == sum(x.size for x in jax.tree.leaves(tree))
    k, v, states, tails = jax.eval_shape(lambda: llama.init_kv_cache(mc, 4, 64))
    assert states.dtype == jnp.float32 and states.shape[0] == delta
    assert counts.state_bytes(m) == 2 * states.size * 4 // (4 * delta)
    # a row's published bytes; the cache holds `cache_kv_heads` of them
    assert counts.kv_bytes_per_token(m) == k.shape[0] * 2 * mc.num_kv_heads * mc.head_dim * 2
    assert k.shape == (mc.attention_kinds.count("full"), 4, 64, llama.cache_kv_heads(mc),
                       mc.head_dim)
    assert tails.shape == (delta, 4, mc.linear_conv_kernel - 1, llama.conv_width(mc))
    if rehearse:
        return
    d = cell.model["deployment"]["published_parameters"]
    assert d["linear_attention_a_layer"] == counts.linear_attention_params(m) == 88_750_332
    assert d["full_attention_a_layer"] == counts.full_attention_params(m) == 4 * 3840 * 3840 + 7680
    assert d["swiglu_a_layer"] == 3 * 3840 * 11008
    assert round(d["linear_layer"] / 1e6, 1) == 215.6 and round(d["full_layer"] / 1e6, 1) == 185.8
    assert round((3 * d["linear_layer"] + d["full_layer"]) / 4e6, 1) == 208.1  # the catalog's
    assert d["embedding_and_head"] == 2 * 100352 * 3840
    assert d["model"] == (24 * d["linear_layer"] + 8 * d["full_layer"]
                          + d["embedding_and_head"] + 3840)
    assert round(d["model"] / 1e9, 2) == 7.43 and round(2 * d["held_here"] / 1e9, 2) == 4.87
    assert d["held_here"] == sum(x.size for x in jax.tree.leaves(tree))
    assert counts.kv_bytes_per_token(m) == 30_720 and counts.state_bytes(m) == 4_423_680
    assert counts.decode_attention_row(m) == {"flops": 30 * 4 * 128, "bytes": 15_360}
    # the cache at the cell's 64 slots x 3072 rows
    cache = jax.eval_shape(lambda: llama.init_kv_cache(mc, 64, 3072))
    sizes = [c.size * c.dtype.itemsize for c in cache]
    assert [c.shape for c in cache] == [(2, 64, 3072, 32, 128)] * 2 + [
        (6, 64, 30, 96, 192), (6, 64, 3, 11520)]
    assert round(sum(sizes[:2]) / 1e9, 2) == 6.44 and round(sizes[2] / 1e9, 2) == 0.85
    assert round(64 * 3072 * 30_720 / 1e9, 2) == 6.04              # the published rows alone
    assert 0.7 < (2 * d["held_here"] + sum(sizes)) / 16e9 < 0.8


def _engine(cell):
    mc = cell.model_config(rehearse=True)
    params = seeded_params(mc, cell.engine_config(), None, SEED, jnp.bfloat16,
                           model_module=cell.model_module)
    sizes = reference_sizes(mc, cell.config_as_run(rehearse=True))
    return types.SimpleNamespace(params=params, _mesh=None), mc, sizes


def test_the_check_passes_at_rehearsal_and_planted_faults_fail_it(cell):
    """Seeded weights and `correct.check` through `omnia_tpu.models.llama` and
    `olmo_hybrid_ref`: a dense model, judged whole (136 tokens through a cache
    of four arrays). With beta left in (0, 1), the norms in front of the
    sublayers, or the QK-norm a head, the check fails."""
    engine, mc, sizes = _engine(cell)
    assert isinstance(engine.params["layers"], list) and len(engine.params["layers"]) == 2
    check = lambda cfg: correct.check(engine, cfg, sizes, SEED,  # noqa: E731
                                      reference=cell.reference, model_module=cell.model_module)
    sound = check(mc)
    assert sound["ok"] is True, sound
    for wrong_cfg in (dataclasses.replace(mc, linear_allow_neg_eigval=False),
                      dataclasses.replace(mc, norm_placement="pre"),
                      dataclasses.replace(mc, rope_on_full_layers=True)):
        wrong = check(wrong_cfg)
        assert wrong["ok"] is False, wrong


def test_an_engine_says_it_serves_the_model_by_llama(cell):
    from omnia_tpu.engine.engine import InferenceEngine
    from omnia_tpu.engine.types import EngineConfig

    ecfg = EngineConfig(num_slots=2, max_seq=256, prefill_buckets=(64,), max_sessions=0)
    engine = InferenceEngine(cell.model_config(rehearse=True), ecfg)
    assert mf.served_by(engine) == cell.model_module == "omnia_tpu.models.llama"
    assert [c.shape for c in engine._cache] == [(1, 2, 256, 6, 32)] * 2 + [
        (3, 2, 6, 16, 32), (3, 2, 3, 384)]
    assert {"decode_delta_slots", "decode_kda_slots", "extend_tokens"} <= set(engine.metrics)


@pytest.fixture(scope="module")
def traced(cell):
    """The recorded one-chip trace (its 56 calls of `decode_gqa_attention`
    are 28 steps of this model, whose two full layers call it), with the
    state kernel's calls put beside what it holds, the counters a run of this
    cell would have, and a scope table in place of the trace directory's."""
    with gzip.open(os.path.join(HERE, "trace_sample.json.gz"), "rt") as f:
        reduced = tr.reduce(json.load(f))
    ops = reduced["ops_in_module"][DECODE_MODULE]
    calls = sum(n for name, (n, _s) in ops.items() if name.split(".")[0] == "decode_gqa_attention")
    assert calls == 56
    ops["decode_delta_state.3"] = (6 * 28, 6 * 28 * 500e-6)   # 500 us a layer a step
    records = [
        Record(i, "think", 1200 + 10 * i, 1024, due=10.0 + i, sent=10.001 + i,
               first=10.3 + i, last=22.8 + i, done=22.8 + i, tokens=1024,
               finish="length", request_id=f"req-{i}")
        for i in range(20)
    ]
    scopes = {DECODE_MODULE: {"mlp": 0.06, "attn.delta": 0.004, "delta.conv": 0.002,
                              "delta.gates": 0.003, "delta.state": 0.018, "delta.out": 0.003,
                              "attn.full": 0.005, "lm_head": 0.005},
              "jit_extend_nosample": {"mlp": 0.05, "attn.delta": 0.01, "delta.conv": 0.004,
                                      "delta.gates": 0.002, "delta.chunk": 0.02,
                                      "delta.out": 0.004, "attn.full": 0.01}}
    return {"records": records, "all_records": records, "chips": 1, "model": cell.model,
            "peaks": roofline.peaks("TPU v5 lite"), "trace": reduced, "spans": {"scopes": scopes},
            "counters_window": {"prefill_tokens": 500_000, "extend_tokens": 451_000,
                                "decode_steps": 4000},
            "traced": {"t": (14.0, 14.25),
                       "counters": {"decode_steps": 100, "prefill_tokens": 40_000,
                                    "decode_delta_slots": 100 * 6 * 62}}}


def test_the_new_readers_read_the_cell(traced):
    read = lambda metric: load_layer_metric(metric).read(traced)  # noqa: E731
    # 62 live slots x 6 layers a step, 4.42 MB a state read and written, over
    # 819 GB/s, against the 6 x 500 us a step the kernel took
    floor = 62 * 6 * 4_423_680 / traced["peaks"]["hbm_bytes_per_s"]
    assert read("batch.decode_delta_state_roofline") == pytest.approx(100 * floor / (6 * 500e-6))
    assert 0 < read("batch.decode_delta_state_roofline") < 100
    assert read("step.delta_share.batch") == pytest.approx(100 * 0.030 / 0.1)
    assert read("extend.delta_share.batch") == pytest.approx(100 * 0.04 / 0.1)
    assert read("extend.delta_chunk_share.batch") == pytest.approx(100 * 0.02 / 0.1)
    assert load_layer_metric("batch.decode_gqa_attention_roofline").read(traced) > 0
    assert roofline.kv_bytes_per_token(traced["model"]) == 30_720


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes_or_the_counter(traced):
    """Laid over a parent that has neither the scopes, the kernel nor the
    counter, or on a run that was not traced, the readers return None and
    raise nothing."""
    bare = {**traced, "spans": {"scopes": {DECODE_MODULE: {"mlp": 0.1},
                                           "jit_extend_nosample": {"mlp": 0.1}}}}
    for metric in NEW_READERS[1:]:
        reader = load_layer_metric(metric)
        assert reader.read(bare) is None, metric
        assert reader.read({**traced, "spans": None, "trace": None, "traced": None}) is None
    kernel = load_layer_metric(NEW_READERS[0])
    no_counter = {**traced, "traced": {**traced["traced"], "counters": {"decode_steps": 100}}}
    assert kernel.read(no_counter) is None
    assert kernel.read({**traced, "trace": None, "traced": None}) is None
    ops = dict(traced["trace"]["ops_in_module"][DECODE_MODULE])
    ops.pop("decode_delta_state.3")
    no_kernel = {**traced, "trace": {**traced["trace"], "ops_in_module": {DECODE_MODULE: ops}}}
    assert kernel.read(no_kernel) is None
    # kimi's file has its own state_bytes; this reader asks for its own counter
    kimi = {**traced, "model": Cell(SIBLING).model}
    assert kernel.read({**kimi, "traced": {**traced["traced"],
                                           "counters": {"decode_steps": 100}}}) is None
