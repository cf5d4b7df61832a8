"""Architecture registry: ``get_arch(id)`` + per-arch smoke variants."""
from __future__ import annotations

import dataclasses
from typing import Dict

from repro.configs.base import (
    GNNConfig, LMConfig, MoEConfig, RecSysConfig, ShapeSpec,
    LM_SHAPES, LM_SHAPES_SKIPPED, GNN_SHAPES, RECSYS_SHAPES, shapes_for,
)
from repro.configs.lm_archs import (
    LM_ARCHS, QWEN2_MOE_A2_7B, LLAMA4_SCOUT_17B_A16E, MINITRON_8B, GLM4_9B,
    QWEN3_1_7B,
)
from repro.configs.other_archs import (
    GNN_ARCHS, RECSYS_ARCHS, GRAPHSAGE_REDDIT, SASREC, MIND, BST, WIDE_DEEP,
)

ARCHS: Dict[str, object] = {}
ARCHS.update(LM_ARCHS)
ARCHS.update(GNN_ARCHS)
ARCHS.update(RECSYS_ARCHS)


def get_arch(arch_id: str):
    if arch_id not in ARCHS:
        raise KeyError(
            f"unknown arch {arch_id!r}; available: {sorted(ARCHS)}")
    return ARCHS[arch_id]


def get_shape(cfg, shape_name: str) -> ShapeSpec:
    for s in shapes_for(cfg):
        if s.name == shape_name:
            return s
    raise KeyError(f"{cfg.name} has no shape {shape_name!r}; "
                   f"available: {[s.name for s in shapes_for(cfg)]}")


def all_cells():
    """Every runnable (arch, shape) pair — the dry-run matrix."""
    for arch_id, cfg in ARCHS.items():
        for s in shapes_for(cfg):
            yield arch_id, s.name


def smoke_config(arch_id: str):
    """A reduced same-family config that runs one step on a laptop CPU."""
    cfg = get_arch(arch_id)
    if isinstance(cfg, LMConfig):
        moe = cfg.moe
        if moe is not None:
            moe = dataclasses.replace(
                moe, n_experts=4, top_k=min(2, moe.top_k),
                n_shared_experts=min(1, moe.n_shared_experts), d_ff_expert=64)
        return dataclasses.replace(
            cfg, name=cfg.name + "-smoke", n_layers=2, d_model=64,
            n_heads=4, n_kv_heads=max(1, 4 // (cfg.n_heads // cfg.n_kv_heads)),
            head_dim=16, d_ff=128, vocab_size=512, moe=moe, attn_chunk=32)
    if isinstance(cfg, GNNConfig):
        return dataclasses.replace(
            cfg, name=cfg.name + "-smoke", d_hidden=16, d_feat=8, n_classes=5)
    if isinstance(cfg, RecSysConfig):
        return dataclasses.replace(
            cfg, name=cfg.name + "-smoke",
            embed_dim=max(8, cfg.embed_dim // 8), n_items=128,
            sparse_vocab=64, seq_len=min(cfg.seq_len, 8) if cfg.seq_len else 0,
            mlp_dims=tuple(d // 16 for d in cfg.mlp_dims) if cfg.mlp_dims
            else ())
    raise TypeError(type(cfg))


def lm_config(name: str) -> LMConfig:
    """An LM config by name: a registered arch (``qwen3-1.7b``) at its
    published widths, or ``<arch>-smoke`` for its reduced CPU variant
    (:func:`smoke_config`). The name alone decides; nothing here looks
    at the device."""
    base = name[:-len("-smoke")] if name.endswith("-smoke") else name
    cfg = smoke_config(base) if base != name else get_arch(name)
    if not isinstance(cfg, LMConfig):
        raise TypeError(f"{name!r} is not an LM arch")
    return cfg


__all__ = [
    "ARCHS", "get_arch", "get_shape", "all_cells", "smoke_config",
    "lm_config",
    "LMConfig", "MoEConfig", "GNNConfig", "RecSysConfig", "ShapeSpec",
    "LM_SHAPES", "LM_SHAPES_SKIPPED", "GNN_SHAPES", "RECSYS_SHAPES",
    "shapes_for",
    "QWEN2_MOE_A2_7B", "LLAMA4_SCOUT_17B_A16E", "MINITRON_8B", "GLM4_9B",
    "QWEN3_1_7B", "GRAPHSAGE_REDDIT", "SASREC", "MIND", "BST", "WIDE_DEEP",
]
