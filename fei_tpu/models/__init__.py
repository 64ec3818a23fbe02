from fei_tpu.models.configs import ModelConfig, get_model_config, MODEL_CONFIGS


def family(cfg: ModelConfig):
    """The module whose step functions serve ``cfg``: ``models.sala`` for a
    model whose layers are of several kinds (``cfg.layer_kinds``),
    ``models.falcon_h1`` for one with a state-space mixer beside attention
    in every layer (``cfg.mamba_d_ssm``), ``models.deepseek`` for one with
    latent attention (``cfg.kv_lora_rank``), else ``models.llama``."""
    if cfg.mamba_d_ssm:
        from fei_tpu.models import falcon_h1

        return falcon_h1
    if cfg.layer_kinds:
        from fei_tpu.models import sala

        return sala
    if cfg.is_latent:
        from fei_tpu.models import deepseek

        return deepseek
    from fei_tpu.models import llama

    return llama


__all__ = ["ModelConfig", "get_model_config", "MODEL_CONFIGS", "family"]
