from omnia_tpu.models.config import ModelConfig, PRESETS, get_config


def model_module(cfg: ModelConfig):
    """The module that serves ``cfg``: latent attention (``kv_rank``)
    is models/mla.py, everything else models/llama.py. The contract both
    keep is benchmark/README.md's ("The model module")."""
    if cfg.is_latent:
        from omnia_tpu.models import mla

        return mla
    from omnia_tpu.models import llama

    return llama


def cache_arrays(cfg: ModelConfig, kv_quant=None) -> int:
    """How many arrays the cache tuple of ``cfg`` has (K and V; with window
    layers their rings besides, with linear-attention or state-space layers
    their float32 states and convolution tails; the latent family's one, or with
    linear-attention layers its rows, their recurrent states and their
    convolutions' tails), counted on the module's own ``init_kv_cache``: the
    operands right behind ``params`` of every program that takes the cache
    whole. Not every array has a row axis: a state is ``[L, B, heads, dk,
    dv]``, and a slot's view of it is the whole of axis 2 like any other's."""
    import jax

    module = model_module(cfg)
    return len(jax.eval_shape(lambda: module.init_kv_cache(cfg, 1, 1, kv_quant=kv_quant)))


def decode_counters(cfg: ModelConfig) -> tuple:
    """The counters a decode step of ``cfg`` sums on the device over its
    layers, in the order its module's ``forward(..., counters=True)``
    returns them (engine.metrics keys): the module's ``decode_counters(cfg)``
    where what it counts depends on the model (the expert layer's; the
    states a step updates, ``decode_kda_slots`` in the latent family and
    ``decode_delta_slots`` or ``decode_mamba_slots`` in the pair family, for
    a model with linear-attention or state-space layers), else its
    ``DECODE_COUNTERS``, else none."""
    module = model_module(cfg)
    own = getattr(module, "decode_counters", None)
    return tuple(own(cfg)) if own else tuple(getattr(module, "DECODE_COUNTERS", ()))


__all__ = ["ModelConfig", "PRESETS", "cache_arrays", "decode_counters", "get_config", "model_module"]
