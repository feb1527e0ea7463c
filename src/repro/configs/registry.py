"""Architecture registry: ``--arch <id>`` resolution + smoke reductions."""
from __future__ import annotations

import importlib
from typing import Dict

from repro.configs.base import (MLACfg, ModelConfig, MoECfg, RGLRUCfg,
                                RWKVCfg, SHAPES, ShapeCfg, shape_applicable)

_ARCH_MODULES = {
    "recurrentgemma-2b": "repro.configs.recurrentgemma_2b",
    "gemma3-27b": "repro.configs.gemma3_27b",
    "stablelm-1.6b": "repro.configs.stablelm_1_6b",
    "nemotron-4-15b": "repro.configs.nemotron_4_15b",
    "gemma3-1b": "repro.configs.gemma3_1b",
    "musicgen-medium": "repro.configs.musicgen_medium",
    "deepseek-v2-236b": "repro.configs.deepseek_v2_236b",
    "kimi-k2-1t-a32b": "repro.configs.kimi_k2_1t",
    "llava-next-34b": "repro.configs.llava_next_34b",
    "rwkv6-3b": "repro.configs.rwkv6_3b",
}

ARCH_IDS = tuple(_ARCH_MODULES)


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(_ARCH_MODULES[arch_id])
    return mod.CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}


def get_shape(name: str) -> ShapeCfg:
    return SHAPES[name]


def cells(include_skipped: bool = False):
    """Yield (arch_id, shape, applicable) for the 40-cell table."""
    for a in ARCH_IDS:
        for s in SHAPES.values():
            ok = shape_applicable(a, s)
            if ok or include_skipped:
                yield a, s, ok


# ---------------------------------------------------------------------------
# Smoke reductions: same family / same layer pattern / same sub-configs,
# tiny widths, so one fwd+train step runs on CPU in a test.
# ---------------------------------------------------------------------------

def smoke_config(arch_id: str) -> ModelConfig:
    cfg = get_config(arch_id)
    d = 64
    heads = 4
    kv = min(cfg.num_kv_heads, heads) if cfg.num_kv_heads > 1 else 1
    kw: dict = dict(
        num_layers=min(cfg.num_layers, 2 * cfg.period + cfg.prologue_layers + 1),
        d_model=d,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=16,
        d_ff=128,
        vocab_size=512,
        window_size=min(cfg.window_size, 16) if cfg.window_size else 0,
        param_dtype="float32",
        dtype="float32",
        remat="none",
    )
    if cfg.moe is not None:
        kw["moe"] = MoECfg(num_experts=8, top_k=2, d_ff_expert=32,
                           num_shared=min(cfg.moe.num_shared, 1),
                           d_ff_dense=128, first_k_dense=cfg.moe.first_k_dense,
                           capacity_factor=2.0, norm_topk=cfg.moe.norm_topk)
    if cfg.mla is not None:
        kw["mla"] = MLACfg(q_lora_rank=32, kv_lora_rank=16,
                           qk_nope_dim=16, qk_rope_dim=8, v_dim=16)
    if cfg.rglru is not None:
        kw["rglru"] = RGLRUCfg(lru_width=d, conv_width=4, num_blocks=4)
    if cfg.rwkv is not None:
        kw["rwkv"] = RWKVCfg(head_dim=16, decay_lora=8, mix_lora=8)
        kw["num_heads"] = d // 16
        kw["num_kv_heads"] = d // 16
    return cfg.replace(**kw)


SMOKE_SHAPE = ShapeCfg("smoke", "train", 32, 2)
