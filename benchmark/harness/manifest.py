"""Finds a cell's files by the names in BENCHMARK.json and checks that they
hang together. Jax-free except `model_config`/`engine_config`, which import
the program's config types, and `load_model_module`, which imports the
program's model.

    BENCHMARK.json workloads[name] -> cells/<name>.json
    cell.config  -> configs/<config>.json    (its `reference` -> reference/<r>.py,
                    its `decode_bytes` -> decode_bytes/<b>.py, its `program` ->
                    the program's model module, ModelConfig fields and decode kernel)
    cell.traffic -> traffic/<traffic>.json   (its `generator` -> generators/<g>.py)
    cell.per_layer[] -> layer_metrics/<metric>.py
    cell name    -> rehearsal/<name>.json    (optional; `--rehearse-cpu` only)

Whatever of the program or of a model family the harness has to name is
named here, once, as the default of a key that a configuration file may
set (README, "A configuration"); no other file under harness/ names one.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

# The defaults a configuration file may replace: Llama's family, as the
# program serves it.
DEFAULT_REFERENCE = "llama_ref"                 # reference/<name>.py
DEFAULT_DECODE_BYTES = "llama_bytes"            # decode_bytes/<name>.py
DEFAULT_MODEL_MODULE = "omnia_tpu.models.llama"
DEFAULT_DECODE_KERNEL = "decode_gqa_attention"  # ops/decode_attention.py, as XLA prints it
# The file's key that counts the layers which call that kernel once a step:
# every layer, unless the model's layers are of several kinds.
DEFAULT_DECODE_KERNEL_LAYERS = "num_hidden_layers"
# ModelConfig field -> (the file's key, cast[, default where the key may be absent]).
LLAMA_KEYS = {
    "vocab_size": ("vocab_size", int),
    "hidden_size": ("hidden_size", int),
    "num_layers": ("num_hidden_layers", int),
    "num_heads": ("num_attention_heads", int),
    "num_kv_heads": ("num_key_value_heads", int),
    "head_dim": ("head_dim", int),
    "ffn_hidden_size": ("intermediate_size", int),
    "rope_theta": ("rope_theta", float),
    "rms_norm_eps": ("rms_norm_eps", float),
    "tie_embeddings": ("tie_word_embeddings", bool),
    "num_experts": ("num_local_experts", int, 0),
    "num_experts_per_tok": ("num_experts_per_tok", int, 2),
    "max_seq_len": ("max_position_embeddings", int),
}


def _rehearsal_default(chips: int) -> dict:
    """Sandbox rehearsal only: tiny widths, the same code paths."""
    return dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=4 if chips == 4 else 2,
                head_dim=16, vocab_size=256, num_hidden_layers=2)


def _load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _path(kind: str, filename: str) -> str:
    path = os.path.join(BENCH_DIR, kind, filename)
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no {kind.rstrip('s')} {os.path.splitext(filename)[0]!r}: {path} does not exist")
    return path


def _load_module(kind: str, name: str):
    path = _path(kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_json() -> dict:
    return _load_json(ROOT, "BENCHMARK.json")


def load_generator(name: str):
    return _load_module("generators", name).schedule


def load_reference(name: str = DEFAULT_REFERENCE):
    """The plain reference a configuration names (`"reference"` in its file).
    `forward(params, sizes, tokens)` for every model, `forward_routed` as well
    for one with a router: see README, "Adding things"."""
    mod = _load_module("reference", name)
    if not hasattr(mod, "forward"):
        raise AttributeError(f"reference {name!r} has no forward(params, sizes, tokens)")
    return mod


def load_decode_bytes(model: dict):
    """The byte counts of the configuration whose file `model` is
    (`"decode_bytes"`, default `llama_bytes`): `decode_weight_bytes(m,
    itemsize=2)` and `kv_bytes_per_token(m, itemsize=2)`, from shapes."""
    name = model.get("decode_bytes", DEFAULT_DECODE_BYTES)
    mod = _load_module("decode_bytes", name)
    for attr in ("decode_weight_bytes", "kv_bytes_per_token"):
        if not hasattr(mod, attr):
            raise AttributeError(f"decode_bytes module {name!r} has no {attr}(m, itemsize=2)")
    return mod


def load_model_module(name: str = DEFAULT_MODEL_MODULE):
    """The program's module that serves a configuration (`program.module`
    in its file), by its import path. The contract is README's."""
    mod = importlib.import_module(name)
    for attr in ("init_params", "param_specs", "init_kv_cache", "kv_cache_specs", "forward"):
        if not hasattr(mod, attr):
            raise AttributeError(f"model module {name!r} has no {attr} (benchmark/README.md)")
    return mod


def served_by(engine) -> str:
    """Import path of the program's module that `engine` dispatches to. The
    program owns that choice: an engine says it by a `model_module`
    attribute (the module or its path); one without it, as today's, serves
    every ModelConfig by the default."""
    module = getattr(engine, "model_module", None) or DEFAULT_MODEL_MODULE
    return getattr(module, "__name__", module)


def decode_kernel(model: dict) -> str:
    """The kernel whose calls count the decode steps in a trace of the
    configuration whose file `model` is, once a layer a step:
    `program.decode_kernel` (default the GQA decode kernel)."""
    return model.get("program", {}).get("decode_kernel", DEFAULT_DECODE_KERNEL)


def decode_kernel_layers(model: dict) -> int:
    """How many layers of the configuration whose file `model` is call
    `decode_kernel(model)` once a step: the file's number under the key that
    `program.decode_kernel_layers` names (default `num_hidden_layers`: every
    layer does). A model whose layers are of several kinds names the count
    of the kind that calls it."""
    key = model.get("program", {}).get("decode_kernel_layers", DEFAULT_DECODE_KERNEL_LAYERS)
    if key not in model:
        raise KeyError(f"program.decode_kernel_layers names {key!r}, which the "
                       f"configuration's file does not hold")
    return int(model[key])


def program_scopes(model: dict) -> dict:
    """The named scopes the configuration whose file `model` is adds to
    `spans.SCOPES` for its cells (`program.scopes`, default none): name ->
    "ops", or "scan" for one that wraps a `lax.scan`, so that an op directly
    under it reads as `<name>.scan_io`."""
    scopes = model.get("program", {}).get("scopes", {})
    wrong = {k: v for k, v in scopes.items() if v not in ("ops", "scan")}
    if wrong:
        raise ValueError(f"program.scopes: each name says \"ops\" or \"scan\", not {wrong}")
    return dict(scopes)


def load_layer_metric(name: str):
    mod = _load_module("layer_metrics", name)
    for attr in ("LAYER", "UNIT", "MOVES", "SOURCE", "BETTER", "read"):
        if not hasattr(mod, attr):
            raise AttributeError(f"layer metric {name!r} declares no {attr}")
    return mod


class Cell:
    """One workload: its cell, configuration and traffic files, checked
    against BENCHMARK.json."""

    def __init__(self, name: str) -> None:
        bench = benchmark_json()
        entry = next((w for w in bench["workloads"] if w["name"] == name), None)
        if entry is None:
            raise KeyError(f"workload {name!r} is not in BENCHMARK.json")
        self.name = name
        self.spec = _load_json(_path("cells", name + ".json"))
        for key in ("config", "traffic", "chips"):
            if self.spec[key] != entry[key]:
                raise ValueError(
                    f"cell file and BENCHMARK.json disagree on {key}: "
                    f"{self.spec[key]!r} != {entry[key]!r}")
        self.chips = int(self.spec["chips"])
        cfg_entry = next(c for c in bench["configs"] if c["name"] == self.spec["config"])
        self.model = _load_json(ROOT, cfg_entry["file"])
        assumed = self.model.get("assumed", {})
        if "head_dim" in assumed:
            self.model.setdefault("head_dim", assumed["head_dim"])
        self.reference = self.model.get("reference", DEFAULT_REFERENCE)
        self.model_module = self.model.get("program", {}).get("module", DEFAULT_MODEL_MODULE)
        self.traffic = _load_json(_path("traffic", self.spec["traffic"] + ".json"))
        if "rate" in self.spec:
            self.traffic = {**self.traffic, "rate_rps": self.spec["rate"]}
        self.engine = dict(self.spec["engine"])

        self.end_to_end = [m for m in bench["end_to_end"]
                           if "workloads" not in m or name in m["workloads"]]
        e2e_names = {m["name"] for m in self.end_to_end}
        # A per-layer metric without a list is due wherever what it moves is reported.
        declared = {m["name"]: m for m in bench["per_layer"]
                    if name in m.get("workloads", ()) or
                    ("workloads" not in m and m["moves"] in e2e_names)}
        listed = list(self.spec["per_layer"])
        if set(listed) != set(declared):
            raise ValueError(
                f"cell {name}: per_layer in the cell file {sorted(listed)} and "
                f"in BENCHMARK.json {sorted(declared)} differ")
        self.layer_metrics = []
        for metric in listed:
            mod = load_layer_metric(metric)
            if mod.MOVES not in e2e_names:
                raise ValueError(
                    f"cell {name} lists per-layer metric {metric}, which moves "
                    f"{mod.MOVES}, but does not report {mod.MOVES}")
            d = declared[metric]
            mine = (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE, mod.BETTER)
            theirs = (d["layer"], d["unit"], d["moves"], d["source"], d["better"])
            if mine != theirs:
                raise ValueError(
                    f"layer metric {metric}: its file says {mine}, "
                    f"BENCHMARK.json says {theirs}")
            self.layer_metrics.append((metric, mod))

    def rehearse(self) -> None:
        """Sandbox rehearsal only: lay `rehearsal/<cell>.json`'s `engine` and
        `traffic` over the cell's, where that file exists (a cell whose slots
        x rows the CPU cannot hold names smaller ones there, and lengths that
        fit them). Without the file the cell's own are run."""
        try:
            over = _load_json(_path("rehearsal", self.name + ".json"))
        except FileNotFoundError:
            return
        self.engine = {**self.engine, **over.get("engine", {})}
        self.traffic = {**self.traffic, **over.get("traffic", {})}

    def config_as_run(self, rehearse: bool = False) -> dict:
        """The configuration file's keys as run: under `rehearse` with the
        file's `rehearsal` object (default: tiny Llama-family widths) laid
        over them."""
        if not rehearse:
            return self.model
        return {**self.model,
                **self.model.get("rehearsal", _rehearsal_default(self.chips))}

    def model_config(self, rehearse: bool = False):
        """The program's ModelConfig, each field from a key of the file:
        the Llama-family table, with the file's `program.model_config`
        (field -> key) laid over it. A key is looked for in the file, then
        under its `assumed`, where a constant the source lacks belongs. So a
        number is written once, and the rehearsal's stands in its place
        under `rehearse`."""
        from omnia_tpu.models.config import ModelConfig

        m = self.config_as_run(rehearse)
        config = self.spec["config"]
        overlay = m.get("program", {}).get("model_config", {})
        known = {f.name for f in dataclasses.fields(ModelConfig)}
        table = dict(LLAMA_KEYS)
        for field, key in overlay.items():
            if field not in known:
                raise ValueError(
                    f"configuration {config}: the program's ModelConfig has no field "
                    f"{field!r} (program.model_config in its file); the `model_config` PR "
                    f"that teaches the program this architecture comes first")
            if not isinstance(key, str):
                raise ValueError(
                    f"configuration {config}: program.model_config gives {field!r} the "
                    f"value {key!r}, and takes the name of a key of the file; a constant "
                    f"the source lacks goes under `assumed`")
            table[field] = (key, LLAMA_KEYS[field][1] if field in LLAMA_KEYS else _hashable)
        assumed = m.get("assumed", {})
        fields = {"name": config}
        for field, (key, cast, *default) in table.items():
            if key in m or key in assumed:
                fields[field] = cast(m[key] if key in m else assumed[key])
            elif default:
                fields[field] = default[0]
            else:
                raise KeyError(
                    f"configuration {config}: no {key!r} in its file or under its "
                    f"`assumed`, for the ModelConfig field {field!r}")
        return ModelConfig(**fields)

    def engine_config(self, flight_events: int = 0):
        from omnia_tpu.engine.types import EngineConfig

        e = {k: (tuple(v) if isinstance(v, list) else v)
             for k, v in self.engine.items()}
        return EngineConfig(**e, flight_events=flight_events)


def _hashable(value):
    return tuple(_hashable(v) for v in value) if isinstance(value, list) else value


def reference_sizes(mc, config: dict | None = None) -> dict:
    """The plain reference's view of a configuration: eight fields of its
    ModelConfig, and under `config` its file whole as run (`Cell.config_as_run`),
    where a reference of another family reads its own keys. Its depth is
    the file's: a reference takes the depth of the parameter tree it is
    handed, which `correct` also cuts to one and two layers."""
    return {
        "config": dict(config or {}),
        "num_heads": mc.num_heads, "num_kv_heads": mc.num_kv_heads,
        "head_dim": mc.head_dim, "rope_theta": mc.rope_theta,
        "rms_norm_eps": mc.rms_norm_eps, "num_experts": mc.num_experts,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "tie_embeddings": mc.tie_embeddings,
    }
