"""Architecture registry: ``--arch <id>`` -> (ModelConfig, model class).

Full configs match the assignment table exactly; ``smoke()`` returns a
reduced same-family config for CPU tests. ``build(cfg)`` instantiates the
right model class for the family.

Arch modules may also register a named :class:`PrivacyPolicy` preset
(``register_policy``) — the per-parameter-group DP recipe for that model
(e.g. deepseek-moe-16b clips expert weights group-wise, separately from the
dense trunk). ``get_policy(name, **overrides)`` materializes it with
engine-level fields (mode / sigma / noise / use_kernels) replaced.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Callable

from repro.configs.base import ModelConfig

_REGISTRY: dict = {}
_POLICIES: dict = {}


def register(fn: Callable[[], ModelConfig]):
    cfg = fn()
    _REGISTRY[cfg.name] = fn
    return fn


def register_policy(name: str):
    """Decorator: register ``fn() -> PrivacyPolicy`` as preset ``name``."""
    def deco(fn):
        _POLICIES[name] = fn
        return fn
    return deco


def get_policy(name: str, **overrides):
    """Named PrivacyPolicy preset, with engine-level field overrides
    (mode=..., sigma=..., noise=..., use_kernels=...)."""
    try:
        policy = _POLICIES[name]()
    except KeyError:
        raise KeyError(f"no policy preset for {name!r}; known: "
                       f"{sorted(_POLICIES)}")
    return dataclasses.replace(policy, **overrides) if overrides else policy


def has_policy(name: str) -> bool:
    return name in _POLICIES


def list_policies():
    return sorted(_POLICIES)


def get_config(name: str) -> ModelConfig:
    try:
        return _REGISTRY[name]()
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")


def list_archs():
    return sorted(_REGISTRY)


def build(cfg: ModelConfig):
    if cfg.family in ("dense", "moe", "vlm"):
        from repro.models.transformer import TransformerLM
        return TransformerLM(cfg)
    if cfg.family == "ssm":
        from repro.models.rwkv6 import Rwkv6LM
        return Rwkv6LM(cfg)
    if cfg.family == "hybrid":
        from repro.models.hymba import HymbaLM
        return HymbaLM(cfg)
    if cfg.family == "encdec":
        from repro.models.whisper import WhisperLM
        return WhisperLM(cfg)
    raise ValueError(f"unknown family {cfg.family!r}")


def smoke_config(name: str) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    cfg = get_config(name)
    kw = dict(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
              d_ff=48, vocab=64, max_t=64)
    if cfg.family == "moe":
        kw.update(n_experts=4, top_k=2, experts_held=0, moe_d_ff=16,
                  first_k_dense=min(1, cfg.first_k_dense),
                  n_shared=min(1, cfg.n_shared))
    if cfg.family == "ssm":
        kw.update(d_model=128, n_heads=2, head_dim=64)  # rwkv head size 64
    if cfg.family == "hybrid":
        kw.update(n_layers=5, ssm_heads=4, ssm_state=4, window=8,
                  full_attn_layers=(0, 2, 4), meta_tokens=4)
    if cfg.family == "encdec":
        kw.update(encoder_layers=2, decoder_len=16, frame_dim=24,
                  n_kv_heads=4)
    if cfg.family == "vlm":
        kw.update(patch_tokens=4, vit_dim=16)
    return cfg.with_(**kw)


# import arch modules so registration runs
for _m in ("whisper_small", "llama3_405b", "qwen2_1_5b", "qwen3_14b",
           "qwen2_5_3b", "moonshot_v1_16b_a3b", "deepseek_moe_16b",
           "internvl2_26b", "rwkv6_3b", "hymba_1_5b"):
    importlib.import_module(f"repro.configs.{_m}")
