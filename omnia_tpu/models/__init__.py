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


__all__ = ["ModelConfig", "PRESETS", "get_config", "model_module"]
