from fei_tpu.models.configs import ModelConfig, get_model_config, MODEL_CONFIGS


def family(cfg: ModelConfig):
    """The module whose step functions serve ``cfg``: ``models.sala`` for a
    model whose layers are of several kinds (``cfg.layer_kinds``), else
    ``models.llama``."""
    if cfg.layer_kinds:
        from fei_tpu.models import sala

        return sala
    from fei_tpu.models import llama

    return llama


__all__ = ["ModelConfig", "get_model_config", "MODEL_CONFIGS", "family"]
