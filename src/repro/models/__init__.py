"""Model zoo: the class each architecture names (``repro.configs.arch``)."""
from __future__ import annotations

import importlib

from repro.configs.arch import arch_of
from repro.configs.registry import ModelConfig


def build_model(cfg: ModelConfig, impl: str = "ref"):
    module, cls = arch_of(cfg).model.split(":")
    return getattr(importlib.import_module(module), cls)(cfg, impl)
