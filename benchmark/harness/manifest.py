"""Finds a cell's files by the names in BENCHMARK.json and checks that they
hang together. Jax-free except `model_config`/`engine_config`, which import
the program's config types.

    BENCHMARK.json workloads[name] -> cells/<name>.json
    cell.config  -> configs/<config>.json    (its `reference` -> reference/<r>.py)
    cell.traffic -> traffic/<traffic>.json   (its `generator` -> generators/<g>.py)
    cell.per_layer[] -> layer_metrics/<metric>.py
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _load_module(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind.rstrip('s')} {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_json() -> dict:
    return _load_json(ROOT, "BENCHMARK.json")


def load_generator(name: str):
    return _load_module("generators", name).schedule


DEFAULT_REFERENCE = "llama_ref"


def load_reference(name: str = DEFAULT_REFERENCE):
    """The plain reference a configuration names (`"reference"` in its file).
    `forward(params, sizes, tokens)` for every model, `forward_routed` as well
    for one with a router: see README, "Adding things"."""
    mod = _load_module("reference", name)
    if not hasattr(mod, "forward"):
        raise AttributeError(f"reference {name!r} has no forward(params, sizes, tokens)")
    return mod


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
        self.spec = _load_json(BENCH_DIR, "cells", name + ".json")
        for key in ("config", "traffic", "chips"):
            if self.spec[key] != entry[key]:
                raise ValueError(
                    f"cell file and BENCHMARK.json disagree on {key}: "
                    f"{self.spec[key]!r} != {entry[key]!r}")
        self.chips = int(self.spec["chips"])
        cfg_entry = next(c for c in bench["configs"] if c["name"] == self.spec["config"])
        self.model = _load_json(ROOT, cfg_entry["file"])
        self.model.setdefault("head_dim", self.model["assumed"]["head_dim"])
        self.reference = self.model.get("reference", DEFAULT_REFERENCE)
        self.traffic = _load_json(BENCH_DIR, "traffic", self.spec["traffic"] + ".json")
        if "rate" in self.spec:
            self.traffic = {**self.traffic, "rate_rps": self.spec["rate"]}
        self.engine = dict(self.spec["engine"])

        def reported(metrics):
            return [m for m in metrics
                    if "workloads" not in m or name in m["workloads"]]

        self.end_to_end = reported(bench["end_to_end"])
        declared = {m["name"]: m for m in reported(bench["per_layer"])}
        listed = list(self.spec["per_layer"])
        if set(listed) != set(declared):
            raise ValueError(
                f"cell {name}: per_layer in the cell file {sorted(listed)} and "
                f"in BENCHMARK.json {sorted(declared)} differ")
        e2e_names = {m["name"] for m in self.end_to_end}
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

    def model_config(self, rehearse: bool = False):
        from omnia_tpu.models.config import ModelConfig

        m = dict(self.model)
        if rehearse:
            # Sandbox rehearsal only: tiny widths, the same code paths.
            m.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                     num_key_value_heads=4 if self.chips == 4 else 2,
                     head_dim=16, vocab_size=256, num_hidden_layers=2)
        return ModelConfig(
            name=self.spec["config"],
            vocab_size=m["vocab_size"],
            hidden_size=m["hidden_size"],
            num_layers=m["num_hidden_layers"],
            num_heads=m["num_attention_heads"],
            num_kv_heads=m["num_key_value_heads"],
            head_dim=m["head_dim"],
            ffn_hidden_size=m["intermediate_size"],
            rope_theta=float(m["rope_theta"]),
            rms_norm_eps=float(m["rms_norm_eps"]),
            tie_embeddings=bool(m["tie_word_embeddings"]),
            num_experts=int(m.get("num_local_experts", 0)),
            num_experts_per_tok=int(m.get("num_experts_per_tok", 2)),
            max_seq_len=int(m["max_position_embeddings"]),
        )

    def engine_config(self, flight_events: int = 0):
        from omnia_tpu.engine.types import EngineConfig

        e = {k: (tuple(v) if isinstance(v, list) else v)
             for k, v in self.engine.items()}
        return EngineConfig(**e, flight_events=flight_events)


def reference_sizes(mc) -> dict:
    """The plain reference's view of a ModelConfig."""
    return {
        "num_heads": mc.num_heads, "num_kv_heads": mc.num_kv_heads,
        "head_dim": mc.head_dim, "rope_theta": mc.rope_theta,
        "rms_norm_eps": mc.rms_norm_eps, "num_experts": mc.num_experts,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "tie_embeddings": mc.tie_embeddings,
    }
