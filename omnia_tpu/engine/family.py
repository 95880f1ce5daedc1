"""What differs between the model families the engine serves.

The engine holds a family's cache as ``_cache``, the tuple of arrays its
model module makes (``models.model_module(cfg).init_kv_cache``): Llama's K
and V (with window layers, their rings' K and V besides), the latent
family's one array of rows, or with linear-attention layers three: the
latent layers' rows, a recurrent state a slot a layer (float32 matrices,
no row axis at all: a slot's view takes its axis 2, the heads, whole) and
the short convolution's tail. The programs and mixins that every
family has (prefill, extend, decode, warmup) take and return it whole, and
never index it: which rows and steps may touch a slot's state is the model
module's word (models/mla.py::_kda_layer, models/stacks.py::_delta_mixer and
_mamba_mixer for the pair family's linear-attention and state-space layers,
whose cache is K and V of the full layers beside such states and tails), since
nothing masks a state by position afterwards. What exists for the pair family alone names the
pair's two arrays, and is refused for another family when the engine is
built."""

from __future__ import annotations

from omnia_tpu.engine.types import EngineConfig
from omnia_tpu.models import ModelConfig, llama
from omnia_tpu.ops.attention import (
    _kernel_on, decode_block_rows, prefill_kernel_on, window_kernel_on,
)
from omnia_tpu.ops.decode_attention import flat_rows


def refuse_unported(model_cfg: ModelConfig, cfg: EngineConfig) -> None:
    """Raise, naming the feature, where ``cfg`` asks a model for something
    only the programs and mixins of the pair family's plain shape do (one
    tree of layers all alike, a K and a V whose row s is position s):
    nothing falls through to a (K, V) pair silently. Two kinds of model are
    refused so: one of the latent family (models/mla.py), and one of the
    pair family whose layers are of several kinds (models/llama.py's stacks:
    window layers, linear-attention layers, state-space layers, a share of the
    routed experts, leading dense layers). What a recurrent state rules out is
    said for a
    model of either family that has one."""
    if model_cfg.is_latent:
        family, why = "the latent-attention family (models/mla.py", {}
        # (its blocks norm a sublayer's input, and no q or k has a whole width)
        for name, plain in (("norm_placement", "pre"), ("qk_norm_whole", False)):
            if getattr(model_cfg, name) != plain:
                raise NotImplementedError(
                    f"ModelConfig.{name}={getattr(model_cfg, name)!r} is not ported to "
                    f"{family}; model {model_cfg.name!r})")
    elif llama.is_stacked(model_cfg):
        family = "a model of several kinds of layers (models/llama.py's stacks"
        rings = ("its window layers' cache is a ring, whose row is not a position"
                 if model_cfg.has_window_layers else "its cache is written by the stacks' "
                 "own layer index")
        why = {
            "kv_quant": f"{rings}: the int8 rows and scales are not made for it",
            "kv_pages": f"{rings}: a page table maps positions to rows",
            "max_sessions": f"{rings}: a session's rows are offloaded and restored by position",
            "prefix_cache_slots": f"{rings}: a shared prefix is copied by position, and a "
                                  "ring holds the end of a prompt, not its start",
            "spec_decode": f"{rings}: a rejected proposal's rows have overwritten rows "
                           "still inside the window, and the model's multi-token-prediction "
                           "module is not built",
            "prefill_chunk_tokens": "the mixed step takes the pair's two arrays, and a dead "
                                    "slot's decode write between a placement's pieces is "
                                    "what a ring must not get",
            "quant": "the stacks' projections and experts are plain matmuls, not qdot",
            "sp": "ring-attention prefill returns rows by position for `insert`",
            "tp": "the stacks and the expert share are replicated whole; the exchange "
                  "between expert-parallel ranks is not built",
            "dp": "the stacks and the expert share are replicated whole, and the decode "
                  "kernels run without a mesh",
        }
    else:
        return
    if model_cfg.has_state_layers:  # of either family: these reasons come first
        layers, what = (("state-space", "some numbers a channel") if "mamba" in
                        model_cfg.attention_kinds else ("linear-attention", "a matrix a head"))
        state = (f"its {layers} layers keep a recurrent state a slot, {what} "
                 "that every token of the context is summed into, with no rows")
        why = {
            **why,
            "max_sessions": f"{state}: a session's rows are offloaded and restored "
                            "by position, and a state has no rows to offload",
            "prefix_cache_slots": f"{state}: a shared prefix is seeded by copying its "
                                  "rows, and the state at the prefix's end is kept nowhere",
            "kv_pages": f"{state}: a page table maps positions to rows, and a state "
                        "has none to page",
            "spec_decode": f"{state}: a rejected proposal is rolled back by moving the "
                           "frontier, and a state that has taken a token cannot give it back",
            "prefill_chunk_tokens": "the mixed step takes the pair's two arrays, and "
                                    "runs a decode step over a slot between its "
                                    "placement's pieces, which a state must not get",
        }
    asked = {
        "kv_quant": cfg.kv_quant, "kv_pages": cfg.kv_pages > 0,
        "max_sessions": cfg.max_sessions > 0,
        "prefix_cache_slots": cfg.prefix_cache_slots > 0,
        "spec_decode": cfg.spec_decode > 0,
        "prefill_chunk_tokens": cfg.prefill_chunk_tokens > 0,
        "quant": cfg.quant, "sp": cfg.sp > 1, "tp": cfg.tp > 1, "dp": cfg.dp > 1,
    }
    for name, on in asked.items():
        if on:
            reason = f": {why[name]}" if name in why else ""
            raise NotImplementedError(
                f"EngineConfig.{name}={getattr(cfg, name)!r} is not ported to "
                f"{family}; model {model_cfg.name!r}){reason}"
            )


def prefill_blocked(model_cfg: ModelConfig, cfg: EngineConfig, mesh, bucket: int,
                    fresh: bool) -> bool:
    """Whether the engine's program over ``bucket`` prompt rows runs its
    attention through the blocked kernel (ops/prefill_attention.py): the
    route's own function over what programs.py hands the model. A ``fresh``
    prefill (``prefill_insert``) attends over its own chunk, plain rows
    whatever the cache holds; an extend piece or a mixed step
    (``_extend_slot``) over one slot's view of ``max_seq`` rows, int8 under
    ``kv_quant`` and plain else (a paged pool's view is gathered); each
    with the engine's mesh. A model with rings runs it through the kernel
    only if its window layers' chunk takes it too (the window route's own
    function): the attention layers of every kind, or the program does not
    count. ``prefill_tokens_blocked`` counts by it, and
    tests/test_prefill_attention.py holds it to the traced programs."""
    rows, plain = (bucket, True) if fresh else (cfg.max_seq, not cfg.kv_quant)
    width = model_cfg.attn_value_width
    return prefill_kernel_on(bucket, rows, width, plain, mesh) and (
        not model_cfg.has_window_layers
        or window_kernel_on(bucket, model_cfg.sliding_window, model_cfg.num_heads, width,
                            fresh, mesh))


def decode_blocks(model_cfg: ModelConfig, cfg: EngineConfig, dtype) -> str:
    """The K and V block a grid step of the pair family's decode kernels
    takes, a cache kind, for the start-up line: "full=1024x128" where the
    heads lie among the rows (``flat_rows``: rows · a device's KV heads x
    lanes), "full=256x32x128" where they keep an axis, a ring's after
    "window=". "" with the kernels routed off, and for the latent family
    (ops/decode_mla_attention.py has one block)."""
    if not _kernel_on() or model_cfg.is_latent:
        return ""
    stacked = llama.is_stacked(model_cfg)
    heads = (llama.cache_kv_heads(model_cfg) if stacked else model_cfg.num_kv_heads) // cfg.tp
    D = model_cfg.head_dim
    dtype = "int8" if cfg.kv_quant else dtype
    caches = {"full": decode_block_rows(cfg.max_seq, cfg.kv_page_tokens if cfg.kv_pages else 0)}
    if model_cfg.has_window_layers:
        caches["window"] = decode_block_rows(llama.ring_rows(model_cfg))
    return ",".join(
        f"{kind}={rows * heads}x{D}" if flat_rows(heads, D, dtype, rows)
        else f"{kind}={rows}x{heads}x{D}" for kind, rows in caches.items())


class _PairCacheMixin:
    """``_ck`` / ``_cv``: the two arrays of the pair family's ``_cache``,
    for what is not ported to another family (sessions, the prefix pool,
    pages, speculation, the mixed step)."""

    @property
    def _ck(self):
        return self._cache[0]

    @_ck.setter
    def _ck(self, value):
        self._cache = (value,) + tuple(self._cache[1:])

    @property
    def _cv(self):
        return self._cache[1]

    @_cv.setter
    def _cv(self, value):
        self._cache = (self._cache[0], value)
