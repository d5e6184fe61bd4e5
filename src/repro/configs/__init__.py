"""Config registry: ``get_config(arch_id)`` / ``all_configs()``.

``ARCH_IDS`` / ``all_configs()`` are the assignment table's archs exactly
(``--arch <id>``); ``get_config`` also finds the configurations served
beyond it (the on-chip benchmark's).
"""
from __future__ import annotations

from typing import Dict, List

from repro.configs.base import ModelConfig
from repro.configs.shapes import SHAPES, InputShape, iter_cells, shape_applicability

from repro.configs.command_r_plus_104b import CONFIG as _command_r_plus
from repro.configs.phi3_mini_3p8b import CONFIG as _phi3
from repro.configs.qwen3_4b import CONFIG as _qwen3
from repro.configs.olmo_1b import CONFIG as _olmo
from repro.configs.mixtral_8x22b import CONFIG as _mixtral
from repro.configs.llama4_scout_17b_a16e import CONFIG as _llama4
from repro.configs.whisper_base import CONFIG as _whisper
from repro.configs.paligemma_3b import CONFIG as _paligemma
from repro.configs.hymba_1p5b import CONFIG as _hymba
from repro.configs.mamba2_130m import CONFIG as _mamba2
from repro.configs.granite_4_0_h_micro import CONFIG as _granite_h_micro

_ASSIGNED: Dict[str, ModelConfig] = {
    cfg.name: cfg
    for cfg in (
        _command_r_plus, _phi3, _qwen3, _olmo, _mixtral,
        _llama4, _whisper, _paligemma, _hymba, _mamba2,
    )
}
_SERVED: Dict[str, ModelConfig] = {cfg.name: cfg for cfg in (_granite_h_micro,)}
_REGISTRY: Dict[str, ModelConfig] = {**_ASSIGNED, **_SERVED}

ARCH_IDS: List[str] = list(_ASSIGNED)


def get_config(arch: str) -> ModelConfig:
    if arch not in _REGISTRY:
        raise KeyError(f"unknown arch {arch!r}; available: {list(_REGISTRY)}")
    return _REGISTRY[arch]


def all_configs() -> List[ModelConfig]:
    return list(_ASSIGNED.values())


def reduced_config(arch: str) -> ModelConfig:
    """A smoke-test-sized config of the same family (CPU-runnable).

    Small layers/width/experts/vocab as appropriate, per the assignment.
    """
    import dataclasses

    cfg = get_config(arch)
    heads = min(cfg.num_heads, 4) if cfg.num_heads else 0
    kvh = min(cfg.num_kv_heads, heads) if heads else 0
    if heads and kvh and heads % kvh:
        kvh = 1
    head_dim = 16 if heads else 0
    d_model = 64
    changes = dict(
        name=cfg.name + "-reduced",
        num_layers=2,
        d_model=d_model,
        num_heads=heads,
        num_kv_heads=kvh,
        head_dim=head_dim,
        d_ff=0 if cfg.d_ff == 0 else 128,
        vocab_size=256,
        num_experts=min(cfg.num_experts, 4),
        num_experts_per_tok=min(cfg.num_experts_per_tok, 2),
        ssm_state_size=min(cfg.ssm_state_size, 16),
        ssm_head_dim=16 if cfg.ssm_state_size else cfg.ssm_head_dim,
        sliding_window=64 if cfg.sliding_window else None,
        encoder_layers=2 if cfg.is_encoder_decoder else 0,
        encoder_seq=24 if cfg.is_encoder_decoder else 0,
        num_patches=8 if cfg.num_patches else 0,
    )
    if cfg.layer_typed:   # one layer of each kind, attention first
        changes.update(layer_types=("attention", "mamba"))
    return dataclasses.replace(cfg, **changes)
