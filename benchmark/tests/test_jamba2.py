"""`jamba2-3b` (the pair family with state-space layers: the Mamba-1 selective
scan over 5120 channels of 16 state numbers behind a biased convolution and
three inner norms, beside un-rotated attention of 20 query heads on one KV
head, 1 layer in 14, the head tied to the table) through the manifest, the
weights, the check and the readers at the rehearsal's widths on the CPU, and
what the configuration file, the traffic file and the cell promise about
themselves."""
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
CELL = "jamba2-3b.reason-wide"
SIBLING = "olmo-hybrid-7b.think-batch"
NEW_READERS = ("batch.decode_mamba_state_roofline", "step.mamba_share.batch",
               "extend.mamba_share.batch", "extend.mamba_scan_share.batch",
               "batch.mamba_scan_roofline")
OLMOS = ("batch.decode_delta_state_roofline", "step.delta_share.batch",
         "extend.delta_share.batch", "extend.delta_chunk_share.batch")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 4294967311
ORDER = ["mamba"] * 7 + ["full_attention"] + ["mamba"] * 13 + ["full_attention"] + ["mamba"] * 6


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL)


def test_the_cell_is_the_one_the_issue_names(cell):
    assert cell.chips == 1 and cell.reference == "jamba_ref"
    assert cell.model_module == "omnia_tpu.models.llama"   # the pair family
    assert mf.decode_kernel(cell.model) == "decode_gqa_attention"
    assert mf.decode_kernel_layers(cell.model) == 2         # the two attention layers call it
    assert cell.engine == {
        "num_slots": 256, "max_seq": 2560, "prefill_buckets": [256, 512, 1024],
        "dtype": "bfloat16", "tp": 1, "decode_chunk": 8, "decode_pipeline": 2, "max_sessions": 0}
    assert cell.traffic == {
        "generator": "closed_loop", "why": cell.traffic["why"], "clients": 384,
        "prompt_tokens": {"dist": "uniform", "min": 256, "max": 1536},
        "output_tokens": {"dist": "fixed", "value": 1024},
        "first_output_spread": 128,
        "first_output_spread_why": cell.traffic["first_output_spread_why"],
        "ramp_s": 20, "ramp_s_why": cell.traffic["ramp_s_why"],
        # read by run.py alone: warm-up compiles the extend programs as well
        "sessions": True, "sessions_why": cell.traffic["sessions_why"]}
    assert (cell.traffic["prompt_tokens"]["max"] + cell.traffic["output_tokens"]["value"]
            == cell.engine["max_seq"])
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s_chip", "setup_s"}
    listed = [name for name, _ in cell.layer_metrics]
    # olmo-hybrid-7b.think-batch's readers with these five in place of the delta readers
    assert listed == [name for name, _ in Cell(SIBLING).layer_metrics
                      if name not in OLMOS] + list(NEW_READERS)
    assert "programs.warmup_s" in listed and "batch.decode_gqa_attention_roofline" in listed
    assert "batch.decode_step_roofline" in listed and not set(listed) & set(OLMOS)
    added = {"stack.dense_mamba", "stack.dense_full", "attn.full", "attn.mamba", "mamba.in",
             "mamba.conv", "mamba.gates", "mamba.scan", "mamba.state", "mamba.out"}
    assert spans.scopes_of(cell.model) == (
        spans.SCOPES | added, spans.SCANS | {s for s in added if s.startswith("stack.")})
    entry = next(w for w in mf.benchmark_json()["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and (entry["config"], entry["traffic"]) == (
        "jamba2-3b", "reason-wide")


def test_the_benchmark_holds_the_cell_and_the_delta_readers_do_not():
    """Membership alone: a later PR appends cells, configurations and names to
    these lists, so nothing here counts them or asks who stands last."""
    bench = mf.benchmark_json()
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    assert [c["name"] for c in bench["configs"]].count("jamba2-3b") == 1
    for metric in OLMOS:  # the delta rule's readers do not read this cell
        entry = next(m for m in bench["per_layer"] if m["name"] == metric)
        assert CELL not in entry["workloads"]
    for metric in Cell(CELL).spec["per_layer"]:
        entry = next(m for m in bench["per_layer"] if m["name"] == metric)
        assert entry.get("workloads", [CELL]).count(CELL) == 1
    e2e = next(m for m in bench["end_to_end"] if m["name"] == "out_tokens_per_s_chip")
    assert e2e["workloads"].count(CELL) == 1


def test_the_traffic_is_the_mix_the_issue_computed(cell):
    """Of a round's 384 stratified prompts three fifths fit the largest bucket
    (one fresh prefill from a zero state each); two fifths go in two pieces
    that hand the state and the tail on at an offset."""
    prompts = stratified(cell.traffic["prompt_tokens"], cell.traffic["clients"], base=2)
    largest = max(cell.engine["prefill_buckets"])
    long = [n for n in prompts if n > largest]
    assert len(prompts) == 384 and 0.38 <= len(long) / 384 <= 0.42
    assert max(long) <= 2 * largest                      # two pieces at most
    assert 880 <= sum(prompts) / 384 <= 912 and min(prompts) >= 256 and max(prompts) <= 1536
    assert cell.traffic["clients"] == cell.engine["num_slots"] * 3 // 2
    assert cell.engine["num_slots"] == 2 * cell.traffic["first_output_spread"]


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
        **dataclasses.asdict(type(mc)()), "name": "jamba2-3b", "vocab_size": 65536,
        "hidden_size": 2560, "num_layers": 28, "num_heads": 20, "num_kv_heads": 1,
        "head_dim": 128, "ffn_hidden_size": 8192, "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
        "tie_embeddings": True, "num_experts": 0, "num_experts_per_tok": 1,
        "max_seq_len": 262144, "layer_types": tuple(ORDER), "rope_on_full_layers": False,
        "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_dt_rank": 160, "mamba_expand": 2,
        "mamba_conv_bias": True, "mamba_proj_bias": False, "mamba_inner_norms": True}
    assert not mc.is_latent and mc.has_state_layers and not mc.has_window_layers
    assert mc.attention_kinds.count("mamba") == 26 and mc.attention_kinds.count("full") == 2
    assert mc.attention_kinds[7] == mc.attention_kinds[21] == "full" and mc.mamba_channels == 5120
    from omnia_tpu.models import llama, model_module, stacks

    assert model_module(mc) is llama
    assert llama.stack_kinds(mc) == ("dense_full", "dense_mamba")
    assert [(kind, n) for _, kind, _, n, _ in stacks._runs(mc)] == [
        ("dense_mamba", 7), ("dense_full", 1), ("dense_mamba", 13), ("dense_full", 1),
        ("dense_mamba", 6)]
    assert llama.rope_tables(mc, jnp.zeros((1, 1), jnp.int32)).keys() == {"window"}  # unused
    tiny = cell.model_config(rehearse=True)
    assert (tiny.hidden_size, tiny.num_layers, tiny.num_heads, tiny.num_kv_heads,
            tiny.head_dim) == (256, 4, 4, 1, 64)
    assert (tiny.mamba_channels, tiny.mamba_d_state, tiny.mamba_dt_rank) == (512, 16, 16)
    assert tiny.attention_kinds == ("mamba", "mamba", "full", "mamba")   # both joins
    assert tiny.mamba_inner_norms and tiny.mamba_conv_bias and tiny.tie_embeddings


def test_the_flat_copies_equal_what_the_reference_reads(cell):
    """`assumed` holds what ModelConfig is built from where the source has no
    key (the order of the layers from the period and the offset, the inner
    norms, no rotation); the reference reads the same. One model, one set of
    numbers, at both sizes."""
    m, assumed = cell.model, cell.model["assumed"]
    assert assumed["head_dim"] == m["hidden_size"] // m["num_attention_heads"] == 128
    assert assumed["layer_types"] == ORDER and assumed["rope_on_full_layers"] is False
    assert m["num_experts"] == 1 and "num_local_experts" not in m      # dense
    ref = mf.load_reference(cell.reference)
    from omnia_tpu.models import llama

    for rehearse in (False, True):
        mc = cell.model_config(rehearse)
        config = cell.config_as_run(rehearse)
        run = config["num_hidden_layers"]
        kinds = ref._key(config, "layer_types")[:run]
        assert kinds == ["full_attention" if l % config["attn_layer_period"]
                         == config["attn_layer_offset"] else "mamba" for l in range(run)]
        assert kinds.count("full_attention") == config["num_attention_layers"]
        assert kinds.count("mamba") == config["num_mamba_layers"]
        sizes = reference_sizes(mc, config)
        assert ref.layer_order(sizes) == llama.layer_order(mc)
        assert tuple("dense_mamba" if a == "mamba" else "dense_full"
                     for a in ref.stack_kinds(sizes)) == llama.stack_kinds(mc)
        assert (config["mamba_d_state"], config["mamba_d_conv"], config["mamba_dt_rank"],
                config["mamba_expand"]) == (mc.mamba_d_state, mc.mamba_d_conv,
                                            mc.mamba_dt_rank, mc.mamba_expand)
        assert config["mamba_conv_bias"] is mc.mamba_conv_bias is True
        assert config["mamba_proj_bias"] is mc.mamba_proj_bias is False
        assert assumed["mamba_inner_norms"] is mc.mamba_inner_norms is True
        assert sizes["tie_embeddings"] is config["tie_word_embeddings"] is True
        assert ref._key(config, "head_dim") == mc.head_dim


def test_the_file_keeps_every_published_number(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == cell.model["source"])
    entry = next(c for c in mf.benchmark_json()["configs"] if c["name"] == "jamba2-3b")
    assert entry["source"] == cell.model["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == list(cell.model["reduced"]) == []      # nothing is cut
    for key, value in row["config"].items():
        assert key in cell.model and cell.model[key] == value, key
    assert cell.model["deployment"]["chips"] == 1
    assumed = cell.model["assumed"]
    for key in ("head_dim", "layer_types", "rope_theta", "rope_on_full_layers",
                "mamba_inner_norms", "state_dtype", "scan_precision"):
        assert key in assumed and len(assumed[key + "_why"]) > 40, key
    for key in ("state_layout", "mamba_init", "weights", "dtype", "experts", "final_norm"):
        assert len(assumed[key]) > 40, key
    assert "not given" in assumed["layer_types_why"] and "1 in 14" in assumed["layer_types_why"]
    assert assumed["state_dtype"] == "float32" and assumed["scan_precision"] == "exact float32"
    for said in ("whole published model", "all 28 layers", "nothing is cut"):
        assert said in cell.model["stands_for"], said


@pytest.mark.parametrize("rehearse", [True, False])
def test_the_byte_counts_equal_the_parameter_trees_and_the_issues_arithmetic(cell, rehearse):
    """Every parameter once (the table is the head, streamed whole), summed
    over the stacks `models/llama.py::init_params` makes, and the states of
    the expected live slots; a state's and a row's bytes against the cache's
    own shapes."""
    from omnia_tpu.models import llama

    mc, m = cell.model_config(rehearse), cell.config_as_run(rehearse)
    tree = jax.eval_shape(lambda: llama.init_params(mc, jax.random.key(0), jnp.bfloat16))
    assert "lm_head" not in tree
    nbytes = sum(x.size * (2 if x.dtype == jnp.bfloat16 else 4) for x in jax.tree.leaves(tree))
    counts = mf.load_decode_bytes(m)
    slots, layers = m["expected_live_slots"], mc.attention_kinds.count("mamba")
    assert counts.decode_weight_bytes(m) == nbytes + slots * layers * counts.state_bytes(m)
    assert counts.param_count(m) == mc.num_params() == sum(
        x.size for x in jax.tree.leaves(tree))
    k, v, states, tails = jax.eval_shape(lambda: llama.init_kv_cache(mc, 4, 64))
    assert states.dtype == jnp.float32 and states.shape == (
        layers, 4, mc.mamba_d_state, mc.mamba_channels)
    assert counts.state_bytes(m) == 2 * states.size * 4 // (4 * layers)
    assert counts.kv_bytes_per_token(m) == k.shape[0] * 2 * mc.num_kv_heads * mc.head_dim * 2
    assert k.shape == (mc.attention_kinds.count("full"), 4, 64, 1, mc.head_dim)
    assert tails.shape == (layers, 4, (mc.mamba_d_conv - 1) * mc.mamba_channels)
    if rehearse:
        return
    d = cell.model["deployment"]["published_parameters"]
    assert d["mamba_mixer_a_layer"] == counts.mamba_params(m) == 41_241_792
    assert d["attention_a_layer"] == counts.attention_params(m) == 2 * 2560 * 2560 + 2 * 2560 * 128
    assert d["swiglu_a_layer"] == 3 * 2560 * 8192
    assert d["mamba_layer"] == d["mamba_mixer_a_layer"] + d["swiglu_a_layer"] + 2 * 2560
    assert d["attention_layer"] == d["attention_a_layer"] + d["swiglu_a_layer"] + 2 * 2560
    assert d["embedding_and_tied_head"] == 65536 * 2560
    assert d["model"] == counts.param_count(m) == 3_029_337_472 == (
        26 * d["mamba_layer"] + 2 * d["attention_layer"] + d["embedding_and_tied_head"] + 2560)
    assert round(2 * d["model"] / 1e9, 2) == 6.06
    assert counts.kv_bytes_per_token(m) == 1024 and counts.state_bytes(m) == 2 * 327_680
    assert counts.step_vector_bytes(m) == 61_952
    assert counts.decode_attention_row(m) == {"flops": 20 * 4 * 128, "bytes": 512}
    # the cache at the cell's 256 slots x 2560 rows
    cache = jax.eval_shape(lambda: llama.init_kv_cache(mc, 256, 2560))
    sizes = [c.size * c.dtype.itemsize for c in cache]
    assert [c.shape for c in cache] == [(2, 256, 2560, 1, 128)] * 2 + [
        (26, 256, 16, 5120), (26, 256, 15360)]
    assert round(sum(sizes[:2]) / 1e9, 2) == 0.67 and round(sizes[2] / 1e9, 2) == 2.18
    assert round(sizes[3] / 1e9, 2) == 0.20
    assert 0.55 < (2 * d["model"] + sum(sizes)) / 16e9 < 0.62
    # a full batch's step: the mixers' weights and the states read and written
    # are three fifths of what it moves
    step = counts.decode_weight_bytes(m)
    mixers = 26 * (2 * counts.mamba_params(m) + 256 * counts.state_bytes(m))
    assert round(step / 1e9, 1) == 10.4 and 0.6 < mixers / step < 0.65


def _engine(cell):
    mc = cell.model_config(rehearse=True)
    params = seeded_params(mc, cell.engine_config(), None, SEED, jnp.bfloat16,
                           model_module=cell.model_module)
    sizes = reference_sizes(mc, cell.config_as_run(rehearse=True))
    return types.SimpleNamespace(params=params, _mesh=None), mc, sizes


def test_the_check_passes_at_rehearsal_and_planted_faults_fail_it(cell):
    """Seeded weights and `correct.check` through `omnia_tpu.models.llama` and
    `jamba_ref`: a dense model, judged whole (136 tokens through a cache of
    four arrays, the head the table's). Without the inner norms, without the
    convolution's bias, or with the attention layer second and not third, the
    check fails (the file's `rehearsal_why` has the readings)."""
    engine, mc, sizes = _engine(cell)
    assert isinstance(engine.params["layers"], list) and len(engine.params["layers"]) == 2
    assert "lm_head" not in engine.params and sizes["tie_embeddings"]
    check = lambda cfg: correct.check(engine, cfg, sizes, SEED,  # noqa: E731
                                      reference=cell.reference, model_module=cell.model_module)
    sound = check(mc)
    assert sound["ok"] is True, sound
    for wrong_cfg in (dataclasses.replace(mc, mamba_inner_norms=False),
                      dataclasses.replace(mc, mamba_conv_bias=False),
                      dataclasses.replace(mc, layer_types=("mamba", "full_attention", "mamba",
                                                           "mamba"))):
        wrong = check(wrong_cfg)
        assert wrong["ok"] is False, wrong


def test_an_engine_says_it_serves_the_model_by_llama(cell):
    from omnia_tpu.engine.engine import InferenceEngine
    from omnia_tpu.engine.types import EngineConfig

    ecfg = EngineConfig(num_slots=2, max_seq=256, prefill_buckets=(64,), max_sessions=0)
    engine = InferenceEngine(cell.model_config(rehearse=True), ecfg)
    assert mf.served_by(engine) == cell.model_module == "omnia_tpu.models.llama"
    assert [c.shape for c in engine._cache] == [(1, 2, 256, 1, 64)] * 2 + [
        (3, 2, 16, 512), (3, 2, 1536)]
    assert {"decode_mamba_slots", "decode_delta_slots", "extend_tokens"} <= set(engine.metrics)


@pytest.fixture(scope="module")
def traced(cell):
    """The recorded one-chip trace (its 56 calls of `decode_gqa_attention`
    are 28 steps of this model, whose two attention layers call it), with the
    state kernel's calls put beside what it holds, the counters a run of this
    cell would have, and a scope table in place of the trace directory's."""
    with gzip.open(os.path.join(HERE, "trace_sample.json.gz"), "rt") as f:
        reduced = tr.reduce(json.load(f))
    ops = reduced["ops_in_module"][DECODE_MODULE]
    calls = sum(n for name, (n, _s) in ops.items() if name.split(".")[0] == "decode_gqa_attention")
    assert calls == 56
    ops["decode_mamba_state.3"] = (26 * 28, 26 * 28 * 300e-6)   # 300 us a layer a step
    # 40 pieces of 1,024 rows through 26 layers at 1 ms a layer's call
    reduced["ops_in_module"]["jit_extend_nosample"] = {"mamba_scan.2": (26 * 40, 26 * 40 * 1e-3),
                                                       "fusion.7": (40, 0.3)}
    records = [
        Record(i, "reason", 600 + 10 * i, 1024, due=10.0 + i, sent=10.001 + i,
               first=10.3 + i, last=22.8 + i, done=22.8 + i, tokens=1024,
               finish="length", request_id=f"req-{i}")
        for i in range(20)
    ]
    scopes = {DECODE_MODULE: {"mlp": 0.05, "attn.mamba": 0.002, "mamba.in": 0.01,
                              "mamba.conv": 0.002, "mamba.gates": 0.003, "mamba.state": 0.02,
                              "mamba.out": 0.005, "attn.full": 0.003, "lm_head": 0.005},
              "jit_extend_nosample": {"mlp": 0.02, "attn.mamba": 0.004, "mamba.in": 0.006,
                                      "mamba.scan": 0.02, "attn.full": 0.01},
              "jit_prefill_insert": {"mlp": 0.02, "mamba.in": 0.006, "mamba.conv": 0.002,
                                     "mamba.gates": 0.002, "mamba.scan": 0.02, "attn.full": 0.01}}
    return {"records": records, "all_records": records, "chips": 1, "model": cell.model,
            "peaks": roofline.peaks("TPU v5 lite"), "trace": reduced, "spans": {"scopes": scopes},
            "counters_window": {"prefill_tokens": 500_000, "extend_tokens": 200_000,
                                "decode_steps": 4000},
            "traced": {"t": (14.0, 14.25),
                       "counters": {"decode_steps": 100, "prefill_tokens": 40_000,
                                    "decode_mamba_slots": 100 * 26 * 250}}}


def test_the_new_readers_read_the_cell(traced):
    read = lambda metric: load_layer_metric(metric).read(traced)  # noqa: E731
    # 250 live slots x 26 layers a step, 655,360 B a state read and written and
    # 61,952 B of step vectors beside it, over 819 GB/s, against the 26 x 300 us
    # a step the kernel took
    floor = 250 * 26 * (655_360 + 61_952) / traced["peaks"]["hbm_bytes_per_s"]
    assert read("batch.decode_mamba_state_roofline") == pytest.approx(100 * floor / (26 * 300e-6))
    assert 0 < read("batch.decode_mamba_state_roofline") < 100
    assert read("step.mamba_share.batch") == pytest.approx(100 * 0.042 / 0.1)
    assert read("extend.mamba_share.batch") == pytest.approx(100 * 0.06 / 0.12)
    assert read("extend.mamba_scan_share.batch") == pytest.approx(100 * 0.04 / 0.12)
    # 40,000 prompt tokens x 26 layers x 77,824 B over 819 GB/s, against 1.04 s of the kernel
    floor = 40_000 * 26 * 77_824 / traced["peaks"]["hbm_bytes_per_s"]
    assert read("batch.mamba_scan_roofline") == pytest.approx(100 * floor / (26 * 40 * 1e-3))
    assert 0 < read("batch.mamba_scan_roofline") < 100
    assert load_layer_metric("batch.decode_gqa_attention_roofline").read(traced) > 0
    assert roofline.kv_bytes_per_token(traced["model"]) == 1024


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes_or_the_counter(traced):
    """Laid over a parent that has neither the scopes, the kernel nor the
    counter, or on a run that was not traced, the readers return None and
    raise nothing."""
    bare = {**traced, "spans": {"scopes": {DECODE_MODULE: {"mlp": 0.1},
                                           "jit_extend_nosample": {"mlp": 0.1}}}}
    for metric in NEW_READERS[1:4]:
        reader = load_layer_metric(metric)
        assert reader.read(bare) is None, metric
        assert reader.read({**traced, "spans": None, "trace": None, "traced": None}) is None
    kernel = load_layer_metric(NEW_READERS[0])
    no_counter = {**traced, "traced": {**traced["traced"], "counters": {"decode_steps": 100}}}
    assert kernel.read(no_counter) is None
    assert kernel.read({**traced, "trace": None, "traced": None}) is None
    ops = dict(traced["trace"]["ops_in_module"][DECODE_MODULE])
    ops.pop("decode_mamba_state.3")
    no_kernel = {**traced, "trace": {**traced["trace"], "ops_in_module": {DECODE_MODULE: ops}}}
    assert kernel.read(no_kernel) is None
    # olmo's file has its own state_bytes and no step_vector_bytes: not this reader's
    olmo = {**traced, "model": Cell(SIBLING).model}
    assert kernel.read(olmo) is None
    scan = load_layer_metric(NEW_READERS[4])
    assert scan.read(olmo) is None and scan.read(no_kernel) is None   # no prompt-side module
    assert scan.read({**traced, "trace": None, "traced": None}) is None
    assert scan.read({**traced, "trace": {**traced["trace"], "ops_in_module": {}}}) is None
    assert scan.read({**traced, "traced": {"counters": {"decode_steps": 100}}}) is None
