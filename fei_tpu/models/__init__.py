from fei_tpu.models.configs import ModelConfig, get_model_config, MODEL_CONFIGS


def family(cfg: ModelConfig):
    """The module whose step functions serve ``cfg``. A model whose layers
    are of several kinds (``cfg.layer_kinds``): ``models.granite_hybrid``
    where some of them are state-space mixers (``cfg.mamba_d_ssm``), else
    ``models.sala``. A model of one kind of layer: ``models.falcon_h1``
    with a state-space mixer beside attention in every layer,
    ``models.deepseek`` with latent attention (``cfg.kv_lora_rank``), else
    ``models.llama``."""
    if cfg.layer_kinds:
        if cfg.mamba_d_ssm:
            from fei_tpu.models import granite_hybrid

            return granite_hybrid
        from fei_tpu.models import sala

        return sala
    if cfg.mamba_d_ssm:
        from fei_tpu.models import falcon_h1

        return falcon_h1
    if cfg.is_latent:
        from fei_tpu.models import deepseek

        return deepseek
    from fei_tpu.models import llama

    return llama


__all__ = ["ModelConfig", "get_model_config", "MODEL_CONFIGS", "family"]
