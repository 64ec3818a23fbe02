from fei_tpu.models.configs import ModelConfig, get_model_config, MODEL_CONFIGS


def family(cfg: ModelConfig):
    """The module whose step functions serve ``cfg``: ``models.sala`` for a
    model whose layers are of several kinds (``cfg.layer_kinds``),
    ``models.deepseek`` for one with latent attention
    (``cfg.kv_lora_rank``), else ``models.llama``."""
    if cfg.layer_kinds:
        from fei_tpu.models import sala

        return sala
    if cfg.is_latent:
        from fei_tpu.models import deepseek

        return deepseek
    from fei_tpu.models import llama

    return llama


__all__ = ["ModelConfig", "get_model_config", "MODEL_CONFIGS", "family"]
