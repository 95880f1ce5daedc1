"""Layers of several kinds, for either family: which stack a layer lies in,
the order the model runs them in, and the runs a forward pass scans.

A layer's kind is ``<FFN kind>_<attention kind>``: *dense* (SwiGLU of
``ffn_hidden_size``) for the ``num_dense_layers`` leading layers of a model
with experts and *sparse* behind them, and the attention kind the family
names for the layer's ``cfg.layer_types`` word. ``params["layers"]`` is a
sequence of stacks, one for each kind the model has, in the order of the
family's ``kinds``; benchmark/README.md ("`layers`: one tree, or stacks")
sets out ``layer_order`` / ``with_layer_order``. A family passes its own
``kinds`` (every kind a layer of it can be) and ``names`` (its name for
each of ``cfg.attention_kinds``' words, where they differ): models/stacks.py
the pair family's window, full, linear-attention ("delta") and state-space
("mamba") layers, models/mla.py the latent family's linear-attention ("kda") and latent ones.
"""

from __future__ import annotations

import dataclasses

from omnia_tpu.models.config import ModelConfig

#: ``cfg.attention_kinds``' word for each of ``cfg.layer_types``'.
_TYPES = {"window": "sliding_attention", "full": "full_attention", "kda": "linear_attention",
          "delta": "linear_attention", "mamba": "mamba"}


def layer_kinds(cfg: ModelConfig, names=None) -> tuple:
    """The kind of model layer 0, 1, ..."""
    names = names or {}
    sparse_from = cfg.num_dense_layers if cfg.moe_ffn_hidden_size else cfg.num_layers
    return tuple(f"{'dense' if l < sparse_from else 'sparse'}_{names.get(kind, kind)}"
                 for l, kind in enumerate(cfg.attention_kinds))


def stack_kinds(cfg: ModelConfig, kinds: tuple, names=None) -> tuple:
    """The kind of each stack of ``params["layers"]``: those of ``kinds``
    that the model has a layer of (a cut model: the stacks of the model it
    was cut out of, ``cfg.layer_stacks``)."""
    if cfg.layer_stacks is not None:
        return cfg.layer_stacks
    have = set(layer_kinds(cfg, names))
    return tuple(kind for kind in kinds if kind in have)


def layer_order(cfg: ModelConfig, kinds: tuple, names=None) -> tuple:
    """((stack, index), ...) for model layer 0, 1, ...: a layer lies in the
    stack of its kind, behind the earlier layers of that kind."""
    stacks = stack_kinds(cfg, kinds, names)
    seen = [0] * len(stacks)
    order = []
    for kind in layer_kinds(cfg, names):
        stack = stacks.index(kind)
        order.append((stack, seen[stack]))
        seen[stack] += 1
    return tuple(order)


def with_layer_order(cfg: ModelConfig, order, kinds: tuple, names=None) -> ModelConfig:
    """The same model with the layers ``order`` names: its own order over
    the cut stacks, any of which may be left with none."""
    stacks = stack_kinds(cfg, kinds, names)
    words = {(names or {}).get(kind, kind): word for kind, word in _TYPES.items()}
    cut_kinds = [stacks[stack] for stack, _ in order]
    dense = (sum(kind.startswith("dense") for kind in cut_kinds)
             if cfg.moe_ffn_hidden_size else 0)
    cut = dataclasses.replace(
        cfg, num_layers=len(order), num_dense_layers=dense, layer_stacks=stacks,
        layer_types=tuple(words[kind.split("_")[1]] for kind in cut_kinds))
    if tuple(map(tuple, order)) != layer_order(cut, kinds, names):
        raise ValueError(f"{order}: this family runs its dense layers first, and a "
                         f"stack's layers in the order of its axis")
    return cut


def stack_counts(cfg: ModelConfig, kinds: tuple, names=None) -> list:
    """How many layers each stack holds."""
    counts = [0] * len(stack_kinds(cfg, kinds, names))
    for stack, _ in layer_order(cfg, kinds, names):
        counts[stack] += 1
    return counts


def runs(cfg: ModelConfig, kinds: tuple, names=None) -> list:
    """The scans of a forward pass: (stack, kind, the run's first index in
    its stack, its length, its first layer among the layers of its
    attention kind: the index into that kind's cache arrays) for each run
    of consecutive layers of one kind."""
    stacks = stack_kinds(cfg, kinds, names)
    found, cached = [], {}
    for stack, index in layer_order(cfg, kinds, names):
        kind = stacks[stack]
        attention = kind.split("_")[1]
        if found and found[-1][0] == stack:
            found[-1][3] += 1
        else:
            found.append([stack, kind, index, 1, cached.get(attention, 0)])
        cached[attention] = cached.get(attention, 0) + 1
    return [tuple(run) for run in found]
