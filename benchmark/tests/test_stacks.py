"""A model whose layers are not all alike goes through the harness as new
files: `layers` as stacks in a stated order, a stream wider than the model,
the decode steps counted by the layers that call the kernel, and a scope the
configuration names. The family is the fixture of `stacked_family/` (its
`model.py` says what it is): `stacked-lead` is one dense layer and then four
sparse ones, `stacked-period` the same module with (sparse, dense, dense)
twice. Found as `latent_family/` is (`fixture_family.py`), never in
BENCHMARK.json."""
import dataclasses
import gzip
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness.manifest as mf
from fixture_family import as_a_model_config_pr
from harness import correct, roofline, spans, trace as tr
from harness.layer_common import DECODE_MODULE, decode_steps_in_trace
from harness.load import Record
from harness.manifest import Cell, load_layer_metric, reference_sizes
from harness.weights import seeded_params

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "stacked_family")
if HERE not in sys.path:  # the fixture's model module is `stacked_family.model`
    sys.path.insert(0, HERE)
SEED = 4294967311
LEAD = ((0, 0), (1, 0), (1, 1), (1, 2), (1, 3))
PERIOD = ((1, 0), (0, 0), (0, 1), (1, 1), (0, 2), (0, 3))


@pytest.fixture
def family(monkeypatch):
    return as_a_model_config_pr(
        monkeypatch, FIXTURE, ("stacked-lead", "stacked-period"),
        like="mistral-7b.eval-batch", but=("batch.decode_gqa_attention_roofline",))


@pytest.fixture
def model(family):
    Cell("stacked-lead.eval-batch")  # the file names the module
    return mf.load_model_module("stacked_family.model")


CUTS = {
    # (order, first, count) -> (each stack's first layer kept, each stack's count, the cut's order)
    "the-leading-layer": ((LEAD, 0, 1), ((0, 0), (1, 0), ((0, 0),))),
    "a-layer-of-the-second-stack": ((LEAD, 3, 1), ((0, 2), (0, 1), ((1, 0),))),
    "the-join-of-two-stacks": ((LEAD, 0, 2), ((0, 0), (1, 1), ((0, 0), (1, 0)))),
    "a-period-from-its-middle": ((PERIOD, 2, 3), ((1, 1), (2, 1), ((0, 0), (1, 0), (0, 1)))),
    "the-whole-model": ((PERIOD, 0, 6), ((0, 0), (4, 2), PERIOD)),
}


@pytest.mark.parametrize("case", list(CUTS))
def test_cut_layers(case):
    args, expected = CUTS[case]
    assert correct.cut_layers(*args) == expected


def test_sub_model_cuts_each_stack_and_one_tree_as_before():
    stacks = [{"w": jnp.arange(8.0).reshape(4, 2)}, {"v": jnp.arange(2.0).reshape(2, 1)}]
    params = {"embed": jnp.zeros((3, 2)), "layers": stacks, "lm_head": jnp.ones((2, 3))}
    stream = jnp.ones((5, 4))  # wider than the model: nothing reads a width
    cut = correct._sub_model(params, stream, (1, 0), (2, 0), jnp.float32)
    assert cut["embed"].shape == (5, 4) and cut["lm_head"] is params["lm_head"]
    assert cut["layers"][0]["w"].tolist() == [[2.0, 3.0], [4.0, 5.0]]
    assert cut["layers"][1]["v"].shape == (0, 1)  # a stack may be left with no layer
    one = correct._sub_model({**params, "layers": stacks[0]}, stream, 3, 1, jnp.float32)
    assert one["layers"]["w"].tolist() == [[6.0, 7.0]]
    tied = correct._sub_model({"embed": params["embed"], "layers": stacks[0]}, stream, 0, 1,
                              jnp.float32)
    assert tied["lm_head"].shape == (2, 3)  # the table it was tied to, transposed


@pytest.mark.parametrize("name,order", [("stacked-lead", LEAD), ("stacked-period", PERIOD)])
def test_both_sides_read_the_order_from_the_file(name, order, model):
    cell = Cell(name + ".eval-batch")
    mc = cell.model_config()
    assert mc.rope_scaling == tuple(cell.model["layer_kinds"]) and mc.num_kv_heads == 2
    assert (mc.num_layers, mc.num_experts, mc.moe_ffn_hidden_size) == (len(order), 8, 256)
    sizes = reference_sizes(mc, cell.config_as_run())
    assert model.layer_order(mc) == order == mf.load_reference("stacked_ref").layer_order(sizes)
    params = jax.eval_shape(lambda: model.init_params(mc, jax.random.key(0)))
    assert correct.layer_order(model, mc, params["layers"]) == order
    assert correct.layer_order(model, mc, params["layers"][0]) is None  # one tree
    cut = correct.cut_config(model, mc, ((1, 0), (0, 0)))
    assert (cut.num_layers, cut.rope_scaling, cut.tie_embeddings) == (2, ("sparse", "dense"), False)
    assert correct.cut_config(model, mc, 2) == dataclasses.replace(
        mc, num_layers=2, tie_embeddings=False)  # a module that says nothing: as before


def test_an_order_the_stacks_do_not_hold_is_refused(model):
    mc = Cell("stacked-lead.eval-batch").model_config()
    params = jax.eval_shape(lambda: model.init_params(mc, jax.random.key(0)))
    short = types.SimpleNamespace(__name__="short", layer_order=lambda cfg: LEAD[:-1])
    with pytest.raises(ValueError, match=r"names \[\[0\], \[0, 1, 2\]\] of stacks that hold \[1, 4\]"):
        correct.layer_order(short, mc, params["layers"])


def _engine(cell):
    mc = cell.model_config()
    params = seeded_params(mc, cell.engine_config(), None, SEED, jnp.bfloat16,
                           model_module=cell.model_module)
    return types.SimpleNamespace(params=params, _mesh=None), mc


def _check(cell, engine, mc):
    return correct.check(engine, mc, reference_sizes(mc, cell.config_as_run()), SEED,
                         reference=cell.reference, model_module=cell.model_module)


def _stack_by_stack(model, cfg):
    """The second stack's layers first, then the first's."""
    order = sorted(model.layer_order(cfg), key=lambda at: -at[0])
    return tuple((s, i, l) for l, (s, i) in enumerate(order))


def _cache_by_own_index(model, cfg):
    """A layer's row of the cache is its index within its stack."""
    return tuple((s, i, i) for s, i in model.layer_order(cfg))


def _kinds_exchanged(model, cfg):
    """The period run as (dense, dense, sparse) where the file says (sparse, dense, dense)."""
    turned = dataclasses.replace(cfg, rope_scaling=cfg.rope_scaling[1:] + cfg.rope_scaling[:1])
    return tuple((s, i, l) for l, (s, i) in enumerate(model.layer_order(turned)))


def _folded(model, x, cfg, expand):
    """A table of n * D taken as n copies of the mean of its copies."""
    if x.shape[-1] != cfg.hidden_size:
        x = x.reshape(*x.shape[:2], cfg.num_kv_heads, cfg.hidden_size).mean(axis=2)
    return expand(x, cfg)


def _experts_swapped(params):
    """The first two sparse layers' routed experts, each in the other's place."""
    dense, sparse = params["layers"]
    experts = jax.tree_util.tree_map(lambda a: a.at[:2].set(a[1::-1]), sparse["mlp"]["experts"])
    return {**params, "layers": [dense, {**sparse, "mlp": {**sparse["mlp"], "experts": experts}}]}


PAIR_LIMIT, MAX_LIMIT, RATIO_LIMIT = (
    "pair_median_worst_over_range", "layers_max_over_range", "layers_noise_ratio_max")
# case: (configuration, the fault on the served side, a number that is over -> its limit's name)
CHECKS = {
    "lead": ("stacked-lead", None, None),
    "period": ("stacked-period", None, None),
    "two-stacks-in-the-wrong-order": (
        "stacked-lead", ("_schedule", _stack_by_stack), ("pair_prefill_median_worst_over_range", PAIR_LIMIT)),
    "the-cache-read-at-the-wrong-layer-across-the-stacks": (
        "stacked-lead", ("_schedule", _cache_by_own_index), ("pair_decode_median_worst_over_range", PAIR_LIMIT)),
    "one-sparse-layers-experts-swapped-with-anothers": (
        "stacked-lead", ("params", _experts_swapped), ("layers_prefill_max_over_range", MAX_LIMIT)),
    "the-periods-kinds-exchanged": (
        "stacked-period", ("_schedule", _kinds_exchanged), ("pair_prefill_median_worst_over_range", PAIR_LIMIT)),
    "the-wide-stream-folded-to-the-models-width": (
        "stacked-lead", ("_expand", _folded), ("layers_noise_ratio_max", RATIO_LIMIT)),
}


@pytest.mark.parametrize("case", list(CHECKS))
def test_the_check_judges_stacks_and_each_fault_fails_it(case, model, monkeypatch):
    """Seeded weights and `correct.check` (the sparse branch) through the
    fixture: every model layer of both stacks alone on a stream of 2 * D, the
    first two together through a cache of that depth. Each fault is planted in
    the served side alone and is over the limit named."""
    name, fault, over = CHECKS[case]
    cell = Cell(name + ".eval-batch")
    engine, mc = _engine(cell)
    if fault and fault[0] == "params":
        wrong = types.SimpleNamespace(params=fault[1](engine.params), _mesh=None)
        real = correct._served_logits
        monkeypatch.setattr(correct, "_served_logits",
                            lambda engine, *a, **k: real(wrong, *a, **k))
    elif fault and fault[0] == "_expand":
        real = model._expand
        monkeypatch.setattr(model, "_expand", lambda x, cfg: fault[1](model, x, cfg, real))
    elif fault:
        monkeypatch.setattr(model, fault[0], lambda cfg: fault[1](model, cfg))
    out = _check(cell, engine, mc)
    assert out["limits"] == {
        "layers_max_over_range": 5e-2, "layers_mean_over_range": 1e-2,
        "layers_noise_ratio_max": 1.8, "pair_median_worst_over_range": 0.1,
        "decided_positions_min": 48}
    if fault:
        assert out["ok"] is False, out
        assert out[over[0]] > out["limits"][over[1]], out
        return
    assert out["ok"] is True, out
    # every position of a layer without a router is decided: 136 a dense layer
    dense = sum(kind == "dense" for kind in cell.model["layer_kinds"])
    assert out["decided_positions"] >= dense * (correct.PREFILL + correct.DECODE) + correct.MIN_DECIDED
    assert out["layers_prefill_max_over_range"] < correct.MAX_TOL / 5
    assert out["layers_noise_ratio_max"] < 1.3
    assert out["pair_prefill_median_worst_over_range"] < correct.PAIR_TOL / 5


def test_every_number_compared_stands_beside_its_limit():
    dense = {"logit_range": 12.0, "prefill_max_over_range": 0.02, "decode_mean_over_range": 0.003,
             "ok": True}
    assert correct.compared(dense) == {"prefill_max_over_range": [0.02, 5e-2],
                                       "decode_mean_over_range": [0.003, 1e-2]}
    served = np.zeros((2, 8, 4)); served[..., 0] = 1.0
    sparse = correct.judge_sparse(served, served, served + 1e-3, np.ones((2, 8), bool), 6,
                                  served[0], served[0])
    assert correct.compared(sparse) == {
        "decided_positions": [16, 48],
        "layers_prefill_max_over_range": [0.0, 5e-2], "layers_prefill_mean_over_range": [0.0, 1e-2],
        "layers_decode_max_over_range": [0.0, 5e-2], "layers_decode_mean_over_range": [0.0, 1e-2],
        "layers_noise_ratio_max": [0.0, 1.8],
        "pair_prefill_median_worst_over_range": [0.0, 0.1],
        "pair_decode_median_worst_over_range": [0.0, 0.1]}


def test_the_stream_is_twice_the_models_width(model):
    cell = Cell("stacked-lead.eval-batch")
    engine, mc = _engine(cell)
    sizes = reference_sizes(mc, cell.config_as_run())
    tokens = jnp.arange(16, dtype=jnp.int32)
    logits, margin, sigma, residual = mf.load_reference(cell.reference).forward_routed(
        engine.params, sizes, tokens)
    assert residual.shape == (6, 16, 2 * mc.hidden_size) and logits.shape == (16, mc.vocab_size)
    assert np.isinf(np.asarray(margin[0])).all() and float(sigma[0]) == 1.0  # no router: decided
    assert np.isfinite(np.asarray(margin[1:])).all()
    assert not np.allclose(residual[1, :, :256], residual[1, :, 256:])  # the copies part ways


def test_a_reference_that_reads_another_order_is_refused(model, monkeypatch):
    cell = Cell("stacked-lead.eval-batch")
    engine, mc = _engine(cell)
    monkeypatch.setattr(model, "layer_order", lambda cfg: LEAD[1:] + LEAD[:1])
    with pytest.raises(ValueError, match=r"names \[\[0\], \[0, 1, 2, 3\]\]|runs its layers as"):
        _check(cell, engine, mc)


@pytest.fixture(scope="module")
def traced():
    """The recorded one-chip trace with its decode kernel under the name
    the fixture's sparse layers call theirs by: 56 calls."""
    with gzip.open(os.path.join(HERE, "trace_sample.json.gz"), "rt") as f:
        reduced = tr.reduce(json.load(f))
    ops = reduced["ops_in_module"][DECODE_MODULE]
    reduced["ops_in_module"][DECODE_MODULE] = {
        k.replace("decode_gqa_attention", "decode_nope_attention"): v for k, v in ops.items()}
    records = [
        Record(i, "window", 400 + 10 * i, 64, due=10.0 + i, sent=10.001 + i,
               first=10.3 + i, last=12.8 + i, done=12.8 + i, tokens=64,
               finish="length", request_id=f"req-{i}")
        for i in range(20)
    ]
    return {"records": records, "all_records": records, "chips": 1, "trace": reduced,
            "peaks": roofline.peaks("TPU v5 lite"), "traced": {"t": (14.0, 14.25), "counters": {}}}


@pytest.mark.parametrize("name,calling", [("stacked-lead", 4), ("stacked-period", 2)])
def test_the_steps_are_the_kernels_calls_over_the_layers_that_call_it(name, calling, traced, family):
    model = Cell(name + ".eval-batch").model
    assert mf.decode_kernel(model) == "decode_nope_attention"
    assert mf.decode_kernel_layers(model) == calling != model["num_hidden_layers"]
    ctx = {**traced, "model": model}
    assert decode_steps_in_trace(ctx) == pytest.approx(56.0 / calling)
    step_ms = load_layer_metric("step.decode_ms.batch").read(ctx)
    assert step_ms == pytest.approx(49.293752000000005 * 4.0 / (56.0 / calling), rel=1e-9)
    counts = mf.load_decode_bytes(model)
    floor_s = (counts.decode_weight_bytes(model) + 914.0 * counts.kv_bytes_per_token(model)) / 819e9
    assert load_layer_metric("batch.decode_step_roofline").read(ctx) == pytest.approx(
        100.0 * floor_s / (step_ms / 1e3), rel=1e-9)


def test_decode_kernel_layers_defaults_to_every_layer_and_names_a_key_that_is_there():
    model = Cell("mistral-7b.eval-batch").model
    assert mf.decode_kernel_layers(model) == model["num_hidden_layers"] == 14
    assert mf.decode_kernel_layers(Cell("mistral-small-4.reason-batch").model) == 5
    with pytest.raises(KeyError, match="names 'num_global_layers', which the configuration's file"):
        mf.decode_kernel_layers({**model, "program": {"decode_kernel_layers": "num_global_layers"}})


def test_the_byte_counts_equal_the_parameter_trees(model):
    for name in ("stacked-lead", "stacked-period"):
        cell = Cell(name + ".eval-batch")
        params = jax.eval_shape(lambda: model.init_params(cell.model_config(), jax.random.key(0)))
        held = sum(a.size for a in jax.tree_util.tree_leaves(params)) - params["embed"].size
        assert mf.load_decode_bytes(cell.model).decode_weight_bytes(cell.model) == 2 * held


def test_a_configuration_names_its_scopes(family):
    """The fixture's `hc.mix` reads as itself in its own cells and as what
    encloses it in any other; a scope that wraps a scan reads its own ops
    as `<name>.scan_io`, as `layers` does."""
    path = "jit(decode_chunk)/while/body/closed_call/layers/while/body/closed_call/"
    own = spans.scopes_of(Cell("stacked-lead.eval-batch").model)
    assert spans.scope_of(path + "hc.mix/dot_general", *own) == "hc.mix"
    assert spans.scope_of(path + "mlp/hc.mix/dot_general", *own) == "hc.mix"
    assert spans.scope_of(path + "hc.mix/dot_general") == spans.SCAN_IO
    assert spans.scope_of("jit(decode_chunk)/hc.mix/dot_general") == spans.UNSCOPED
    assert spans.scope_of(path + "kv.update/scatter", *own) == "kv.update"
    for cell in ("mistral-7b.chat-steady", "mistral-small-4.reason-batch"):
        assert spans.scopes_of(Cell(cell).model) == (spans.SCOPES, spans.SCANS) == spans.scopes_of()
    scans = spans.scopes_of({"program": {"scopes": {"stack.sparse": "scan", "hc.mix": "ops"}}})
    assert spans.scope_of("jit(f)/stack.sparse/while/body/dynamic_slice", *scans) == "stack.sparse.scan_io"
    assert spans.scope_of("jit(f)/stack.sparse/while/body/closed_call/hc.mix/dot", *scans) == "hc.mix"
    assert spans.scope_of("jit(f)/layers/while/body/dynamic_slice", *scans) == spans.SCAN_IO
    with pytest.raises(ValueError, match='says "ops" or "scan"'):
        mf.program_scopes({"program": {"scopes": {"hc.mix": True}}})


def test_the_fixtures_forward_puts_its_scope_on_its_ops(model):
    mc = Cell("stacked-lead.eval-batch").model_config()
    params = jax.eval_shape(lambda: model.init_params(mc, jax.random.key(0)))
    cache = jax.eval_shape(lambda: model.init_kv_cache(mc, 1, 8))
    tokens = jax.ShapeDtypeStruct((1, 4), jnp.int32)
    text = jax.jit(lambda p, t, c: model.forward(p, mc, t, t, *c, jnp.zeros((1,), jnp.int32))).lower(
        params, tokens, cache).as_text(debug_info=True)
    assert "hc.mix" in text


@pytest.mark.parametrize("name", [w["name"] for w in mf.benchmark_json()["workloads"]])
def test_a_rehearsal_file_lays_slots_and_lengths_over_the_cell(name):
    """Every cell's slots x rows are more than the CPU keeps up with, so each
    brings `rehearsal/<cell>.json`; what the file does not name stays."""
    cell = Cell(name)
    engine, traffic = dict(cell.engine), dict(cell.traffic)
    cell.rehearse()
    assert cell.engine["num_slots"] * cell.engine["max_seq"] <= 4096 < (
        engine["num_slots"] * engine["max_seq"])
    assert cell.engine["decode_chunk"] == engine["decode_chunk"]
    assert cell.traffic["generator"] == traffic["generator"]
    longest = cell.traffic["prompt_tokens"]["max"] + cell.traffic["output_tokens"].get(
        "value", cell.traffic["output_tokens"].get("max"))
    assert longest <= cell.engine["max_seq"] - 2
    assert cell.traffic["prompt_tokens"]["max"] <= max(cell.engine["prefill_buckets"])
    assert cell.traffic.get("rate_rps", 0) <= traffic.get("rate_rps", 0)


def test_a_cell_without_a_rehearsal_file_rehearses_as_it_is(family):
    cell = Cell("stacked-lead.eval-batch")
    before = (dict(cell.engine), dict(cell.traffic))
    cell.rehearse()
    assert (cell.engine, cell.traffic) == before
