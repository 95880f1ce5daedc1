"""The served side of the harness is driven by the configuration file, as
the reference side is: a family that is not Llama's goes through it with
no edit under harness/. The family is the fixture of `latent_family/` (a
tiny model module with a one-array cache, its own decode-kernel name,
byte counts and reference, two configuration files and their cells); it is
found because the `family` fixture lets `manifest._path` look there after
the benchmark's own directory, and it is never in BENCHMARK.json. The defaults are
pinned to what the parent (efae6e2) read for `mistral-7b`."""
import gzip
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import pytest

import harness.manifest as mf
from fixture_family import as_a_model_config_pr
from harness import correct, roofline, trace as tr
from harness.layer_common import DECODE_MODULE, decode_steps_in_trace, kernel_in_decode
from harness.load import Record
from harness.manifest import Cell, load_layer_metric, reference_sizes
from harness.weights import seeded_params

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "latent_family")
if HERE not in sys.path:  # the fixture's model module is `latent_family.model`
    sys.path.insert(0, HERE)

GQA_ONLY = "batch.decode_gqa_attention_roofline"  # a kernel's own roofline lists its configurations' cells


@pytest.fixture
def family(monkeypatch):
    """BENCHMARK.json as a `model_config` PR for the fixture family would
    leave it: two `configs` entries, two `workloads` entries and the cells'
    names on the closed loop's lists but the GQA kernel's own."""
    return as_a_model_config_pr(monkeypatch, FIXTURE, ("latent-tiny", "latent-sparse"),
                                like="mistral-7b.eval-batch", but=(GQA_ONLY,))


def _with_config(monkeypatch, tmp_path, change):
    """`mistral-7b.chat-steady` with its configuration file changed by `change(dict)`."""
    bench = mf.benchmark_json()
    model = json.load(open(os.path.join(mf.ROOT, bench["configs"][0]["file"])))
    change(model)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(model))
    bench["configs"][0]["file"] = str(path)
    monkeypatch.setattr(mf, "benchmark_json", lambda: bench)
    return Cell("mistral-7b.chat-steady")


def test_the_defaults_build_the_parents_model_config():
    """Field for field what `Cell.model_config` of efae6e2 built."""
    from omnia_tpu.models.config import ModelConfig

    cell = Cell("mistral-7b.chat-steady")
    assert cell.model_module == "omnia_tpu.models.llama" and cell.reference == "llama_ref"
    assert cell.model_config() == ModelConfig(
        name="mistral-7b", vocab_size=32768, hidden_size=4096, num_layers=14, num_heads=32,
        num_kv_heads=8, head_dim=128, ffn_hidden_size=14336, rope_theta=1000000.0,
        rope_scaling=None, rms_norm_eps=1e-05, tie_embeddings=False, num_experts=0,
        num_experts_per_tok=2, max_seq_len=32768)
    assert cell.model_config(rehearse=True) == ModelConfig(
        name="mistral-7b", vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=16, ffn_hidden_size=128, rope_theta=1000000.0,
        rope_scaling=None, rms_norm_eps=1e-05, tie_embeddings=False, num_experts=0,
        num_experts_per_tok=2, max_seq_len=32768)
    assert cell.config_as_run() is cell.model and cell.config_as_run(rehearse=True)["hidden_size"] == 64
    assert roofline.decode_step_floor_s(cell.model, 0, 1, 1.0) == 6375579648
    assert roofline.kv_bytes_per_token(cell.model) == 57344
    sizes = reference_sizes(cell.model_config(), cell.config_as_run())
    assert sizes["config"] == cell.model and len(sizes) == 9 and sizes["num_kv_heads"] == 8


# case: (cell, rehearse) -> the ModelConfig fields the file must have produced
FIXTURE_CONFIGS = {
    ("latent-tiny.eval-batch", False): dict(
        hidden_size=64, ffn_hidden_size=128, num_heads=4, num_kv_heads=1, head_dim=16,
        num_layers=3, vocab_size=256, num_experts=0, num_experts_per_tok=2, max_seq_len=512),
    ("latent-tiny.eval-batch", True): dict(     # its own `rehearsal` object, not Llama's
        hidden_size=32, ffn_hidden_size=64, num_heads=4, num_kv_heads=1, head_dim=8,
        num_layers=2, vocab_size=128, num_experts=0),
    ("latent-sparse.eval-batch", False): dict(
        hidden_size=256, ffn_hidden_size=512, head_dim=64, num_layers=4, num_experts=8,
        num_experts_per_tok=2, rope_theta=1e6),
}


@pytest.mark.parametrize("name,rehearse", list(FIXTURE_CONFIGS))
def test_a_cell_builds_its_model_config_from_the_file(name, rehearse, family):
    cell = Cell(name)  # has no `head_dim`, assumed or not, nor Llama's KV-head and FFN keys
    mc = cell.model_config(rehearse=rehearse)
    assert mc.name == cell.spec["config"]
    for field, value in FIXTURE_CONFIGS[name, rehearse].items():
        assert getattr(mc, field) == value, field
    assert cell.model_module == "latent_family.model" and cell.reference == "latent_ref"
    assert reference_sizes(mc, cell.config_as_run(rehearse))["config"]["latent_dim"] == mc.head_dim


def test_a_fixture_cell_need_not_list_the_gqa_kernels_roofline(family):
    """The manifest check passes: the kernel's roofline lists the cells of the
    configurations that call it (PR 28), and the fixture's cell reports every
    other metric of a closed loop."""
    cell = Cell("latent-tiny.eval-batch")
    listed = [name for name, _ in cell.layer_metrics]
    assert not [name for name in listed if "decode_gqa_attention" in name]
    assert {"step.decode_ms.batch", "batch.decode_step_roofline"} <= set(listed)
    gqa = [m for m in family["per_layer"] if m["name"].endswith("decode_gqa_attention_roofline")]
    assert sorted(w for m in gqa for w in m["workloads"]) == sorted(
        w["name"] for w in family["workloads"] if w["config"] == "mistral-7b")


CONFIG_FAULTS = {
    "unknown-field": (
        lambda m: m.update(program={"model_config": {"kv_lora_rank": "kv_lora_rank"}}),
        ValueError, r"no field 'kv_lora_rank'.*`model_config` PR.*comes first"),
    "missing-key": (lambda m: m.pop("num_key_value_heads"), KeyError,
                    r"no 'num_key_value_heads' in its file or under its `assumed`.*'num_kv_heads'"),
    "a-key-that-is-not-there": (
        lambda m: m.update(program={"model_config": {"head_dim": "latent_dim"}}),
        KeyError, r"no 'latent_dim' in its file.*'head_dim'"),
    "a-key-with-a-default-named-and-not-there": (
        lambda m: m.update(program={"model_config": {"num_experts": "n_routed_experts"}}),
        KeyError, r"no 'n_routed_experts' in its file.*'num_experts'"),
    "a-value-where-a-key-belongs": (
        lambda m: m.update(program={"model_config": {"num_kv_heads": 1}}),
        ValueError, r"gives 'num_kv_heads' the value 1.*goes under `assumed`"),
}


@pytest.mark.parametrize("fault", list(CONFIG_FAULTS))
def test_a_configuration_the_program_cannot_take_raises(fault, monkeypatch, tmp_path):
    change, error, match = CONFIG_FAULTS[fault]
    with pytest.raises(error, match=match):
        _with_config(monkeypatch, tmp_path, change).model_config()


def test_head_dim_in_the_file_and_none_assumed_loads(monkeypatch, tmp_path):
    """efae6e2 evaluated `assumed.head_dim` before it looked for `head_dim`."""
    def change(m):
        m["head_dim"] = m["assumed"].pop("head_dim")
        m["assumed"]["scaling"] = [8.0, 1.0, 4.0, 8192]
        m["program"] = {"model_config": {"rope_scaling": "scaling"}}
    mc = _with_config(monkeypatch, tmp_path, change).model_config()
    assert mc.head_dim == 128 and mc.rope_scaling == (8.0, 1.0, 4.0, 8192)  # a list arrives hashable


def test_modules_are_found_by_name_or_fail_with_the_path(family):
    with pytest.raises(FileNotFoundError, match=r"decode_bytes.nowhere\.py does not exist"):
        mf.load_decode_bytes({"decode_bytes": "nowhere"})
    with pytest.raises(ModuleNotFoundError):
        mf.load_model_module("latent_family.nowhere")
    with pytest.raises(AttributeError, match="has no init_params"):
        mf.load_model_module("latent_family")
    tiny = Cell("latent-tiny.eval-batch").model
    # 3 layers of (2 x 64 x 64 + 64 x 16 + 3 x 64 x 128 + 2 x 64) + head + norm, in bf16
    assert mf.load_decode_bytes(tiny).decode_weight_bytes(tiny) == 2 * (3 * 33920 + 64 * 256 + 64)
    assert roofline.kv_bytes_per_token(tiny) == 3 * 16 * 2
    assert mf.decode_kernel(tiny) == "decode_latent_attention"
    assert mf.decode_kernel(Cell("mistral-7b.eval-batch").model) == "decode_gqa_attention"


def test_the_engine_says_which_module_serves():
    assert mf.served_by(types.SimpleNamespace()) == "omnia_tpu.models.llama"
    assert mf.served_by(types.SimpleNamespace(model_module="a.b")) == "a.b"
    assert mf.served_by(types.SimpleNamespace(model_module=json)) == "json"


def test_run_refuses_a_module_the_engine_does_not_dispatch_to(family, capsys):
    """The fixture's file names `latent_family.model`; the engine, as it is,
    dispatches to the default. `correct` would judge one and the window
    time the other, so the run ends before either, without a result."""
    import run

    with pytest.raises(SystemExit, match=r"names the model module 'latent_family.model'.*"
                                         r"dispatches to 'omnia_tpu.models.llama'"):
        run.main(["--workload", "latent-tiny.eval-batch", "--seed", "7", "--seconds", "1",
                  "--trace", "0", "--rehearse-cpu"])
    assert not [line for line in capsys.readouterr().out.splitlines() if line.startswith("{")]


def _engine(cell):
    """What `correct.check` reads of an engine, with the cell's seeded weights."""
    mc = cell.model_config()
    params = seeded_params(mc, cell.engine_config(), None, 4294967311, jnp.bfloat16,
                           model_module=cell.model_module)
    return types.SimpleNamespace(params=params, _mesh=None), mc


def _swap_first_two_layers(params):
    return {**params, "layers": jax.tree_util.tree_map(
        lambda a: a.at[:2].set(a[1::-1]), params["layers"])}


# case: (cell, the served side's layers 0 and 1 swapped?, ok, the number over its limit)
CHECK_CASES = {
    "dense": ("latent-tiny.eval-batch", False, True, None),
    "dense-wrong-layer-order": ("latent-tiny.eval-batch", True, False, "prefill_max_over_range"),
    "sparse": ("latent-sparse.eval-batch", False, True, None),
    "sparse-wrong-layer-order": ("latent-sparse.eval-batch", True, False,
                                 "pair_prefill_median_worst_over_range"),
}


@pytest.mark.parametrize("case", list(CHECK_CASES))
def test_the_check_runs_through_the_fixture_module(case, family, monkeypatch):
    """Seeded weights and `correct.check` through `latent_family.model` and
    `latent_ref`: a one-array cache goes round whole. The served side with
    its first two layers in the wrong order fails: at the whole depth for a
    dense model, in the two-layer model for one with a router (each layer
    alone is then still sound)."""
    name, swapped, ok, over = CHECK_CASES[case]
    cell = Cell(name)
    engine, mc = _engine(cell)
    assert len(sys.modules["latent_family.model"].init_kv_cache(mc, 1, 8)) == 1
    if swapped:
        wrong = types.SimpleNamespace(params=_swap_first_two_layers(engine.params), _mesh=None)
        real = correct._served_logits
        monkeypatch.setattr(
            correct, "_served_logits", lambda engine, *a, depth=1, layer_inputs=None, **k: real(
                wrong if layer_inputs is None or depth == correct.PAIR else engine,
                *a, depth=depth, layer_inputs=layer_inputs, **k))
    out = correct.check(engine, mc, reference_sizes(mc, cell.config_as_run()), 4294967311,
                        reference=cell.reference, model_module=cell.model_module)
    assert out["ok"] is ok, out
    if over and "limits" in out:
        assert out[over] > out["limits"]["pair_median_worst_over_range"], out
        assert out["layers_prefill_max_over_range"] <= correct.MAX_TOL
    elif over:
        assert out[over] > correct.MAX_TOL, out
    elif "limits" in out:
        assert out["decided_positions"] >= correct.MIN_DECIDED
        assert out["layers_noise_ratio_max"] < 1.3
        assert out["pair_prefill_median_worst_over_range"] < correct.PAIR_TOL / 5


@pytest.fixture(scope="module")
def traced():
    """The recorded one-chip, 14-layer trace (GQA decode kernel), and the
    same trace with the kernel under the fixture family's name."""
    with gzip.open(os.path.join(HERE, "trace_sample.json.gz"), "rt") as f:
        reduced = tr.reduce(json.load(f))
    renamed = json.loads(json.dumps(reduced))
    ops = renamed["ops_in_module"][DECODE_MODULE]
    renamed["ops_in_module"][DECODE_MODULE] = {
        k.replace("decode_gqa_attention", "decode_latent_attention"): v for k, v in ops.items()}
    records = [
        Record(i, "window", 400 + 10 * i, 64, due=10.0 + i, sent=10.001 + i,
               first=10.3 + i, last=12.8 + i, done=12.8 + i, tokens=64,
               finish="length", request_id=f"req-{i}")
        for i in range(20)
    ]
    ctx = {"records": records, "all_records": records, "chips": 1,
           "peaks": roofline.peaks("TPU v5 lite"),
           "traced": {"t": (14.0, 14.25), "counters": {}}}
    return {"gqa": {**ctx, "trace": reduced}, "latent": {**ctx, "trace": renamed}}


def _read(metric, ctx):
    return load_layer_metric(metric).read(ctx)


def test_the_decode_readers_read_what_the_parent_read(traced):
    """`mistral-7b` on the recorded trace, pinned to efae6e2's values: 56
    calls of the GQA kernel over 14 layers, live context 914 tokens."""
    ctx = {**traced["gqa"], "model": Cell("mistral-7b.eval-batch").model}
    assert kernel_in_decode(ctx) == pytest.approx((56.0, 0.010241656999999998), rel=1e-12)
    assert decode_steps_in_trace(ctx) == 4.0
    assert _read("step.decode_ms", ctx) == pytest.approx(49.293752000000005, rel=1e-12)
    assert _read("decode_step_roofline", ctx) == pytest.approx(15.922070932499027, rel=1e-12)
    assert _read("decode_gqa_attention_roofline", ctx) == pytest.approx(
        2.4994246119206665, rel=1e-12)
    # The same model on a trace whose decode kernel has another name: no steps.
    other = {**traced["latent"], "model": ctx["model"]}
    assert kernel_in_decode(other) is None and decode_steps_in_trace(other) is None
    assert _read("step.decode_ms", other) is None
    assert _read("decode_step_roofline", other) is None


def test_the_decode_readers_count_the_kernel_the_configuration_names(traced, family):
    """The fixture family: its kernel's 56 calls over its 3 layers, and its
    own byte counts under the step's roofline."""
    model = Cell("latent-tiny.eval-batch").model
    ctx = {**traced["latent"], "model": model}
    steps = 56.0 / 3
    assert decode_steps_in_trace(ctx) == pytest.approx(steps)
    step_ms = _read("step.decode_ms", ctx)
    assert step_ms == pytest.approx(49.293752000000005 * 4.0 / steps, rel=1e-9)
    counts = mf.load_decode_bytes(model)
    floor_s = (counts.decode_weight_bytes(model) + 914.0 * counts.kv_bytes_per_token(model)) / 819e9
    assert _read("decode_step_roofline", ctx) == pytest.approx(
        100.0 * floor_s / (step_ms / 1e3), rel=1e-9)
    assert decode_steps_in_trace({**traced["gqa"], "model": model}) is None


def test_harness_names_the_program_only_as_defaults():
    """Under harness/ only manifest.py names a module of the program's model
    or a model family, and only as a default or in a comment on one."""
    import re

    named = re.compile(r"llama|gqa", re.IGNORECASE)
    harness = os.path.join(mf.BENCH_DIR, "harness")
    for file in sorted(os.listdir(harness)):
        if not file.endswith(".py"):
            continue
        with open(os.path.join(harness, file)) as f:
            hits = [line for line in f if named.search(line)]
        if file == "manifest.py":
            assert all(re.search(r"DEFAULT_|LLAMA_KEYS|default|Llama", h) for h in hits), hits
        else:
            assert hits == [], (file, hits)
