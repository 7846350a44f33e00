"""A configuration file's ``model`` object as the program's ``ModelConfig``."""
from __future__ import annotations


def model_config(model: dict):
    from repro.configs.base import LayerSpec, ModelConfig
    fields = dict(model)
    fields["block"] = tuple(LayerSpec(**b) for b in fields["block"])
    return ModelConfig(**fields)
