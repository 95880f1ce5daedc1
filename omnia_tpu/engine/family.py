"""What differs between the model families the engine serves.

The engine holds a family's cache as ``_cache``, the tuple of arrays its
model module makes (``models.model_module(cfg).init_kv_cache``): Llama's K
and V, the latent family's one array. The programs and mixins that every
family has (prefill, extend, decode, warmup) take and return it whole.
What exists for the pair family alone names the pair's two arrays, and is
refused for another family when the engine is built."""

from __future__ import annotations

from omnia_tpu.engine.types import EngineConfig
from omnia_tpu.models import ModelConfig


def refuse_unported(model_cfg: ModelConfig, cfg: EngineConfig) -> None:
    """Raise, naming the feature, where ``cfg`` asks a model of the latent
    family (models/mla.py) for something only the pair family's programs
    and mixins do: nothing falls through to a (K, V) pair silently."""
    if not model_cfg.is_latent:
        return
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
            raise NotImplementedError(
                f"EngineConfig.{name}={getattr(cfg, name)!r} is not ported to the "
                f"latent-attention family (models/mla.py; model {model_cfg.name!r})"
            )


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
