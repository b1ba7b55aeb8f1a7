"""Model definitions: config registry + unified functional API."""

from __future__ import annotations

from typing import Callable, NamedTuple

from . import encdec, transformer
from .config import ModelConfig, get_config, list_configs, register


class ModelApi(NamedTuple):
    cfg: ModelConfig
    init_params: Callable
    forward: Callable
    loss_fn: Callable


def get_model(cfg: ModelConfig) -> ModelApi:
    mod = encdec if cfg.is_encoder_decoder else transformer
    return ModelApi(
        cfg=cfg,
        init_params=lambda key, dtype=None: mod.init_params(cfg, key, dtype),
        forward=lambda params, batch: mod.forward(cfg, params, batch),
        loss_fn=lambda params, batch: mod.loss_fn(cfg, params, batch),
    )


__all__ = ["ModelConfig", "ModelApi", "get_model", "get_config",
           "list_configs", "register"]
