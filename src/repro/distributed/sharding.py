"""Logical-axis sharding rules (MaxText-style) -> concrete NamedShardings.

Rules are (arch x shape x mesh)-aware:

* training: FSDP over ``data`` (+ pure DP over ``pod``), TP over ``model``;
* serving:  TP over ``model``; FSDP only if the model cannot fit
  model-sharded weights in HBM (bf16, 16 GiB/chip v5e budget);
* any logical dim that does not divide its mesh axes falls back to
  replicated (e.g. 10 heads on a 16-way model axis).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeCfg

HBM_BYTES = 16 * 1024 ** 3        # TPU v5e
FSDP_THRESHOLD = 0.5              # use FSDP when weights > 50% HBM


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def base_rules(cfg: ModelConfig, shape: Optional[ShapeCfg],
               mesh: Mesh) -> Dict[str, Any]:
    """logical axis name -> mesh axis (str | tuple | None)."""
    n_model = mesh.shape["model"] if "model" in mesh.axis_names else 1
    n_data = mesh.shape["data"] if "data" in mesh.axis_names else 1

    train = shape is None or shape.kind == "train"
    # serving: only FSDP when TP-sharded weights don't fit
    param_bytes = cfg.param_count() * 2  # bf16
    need_fsdp = train or (param_bytes / max(n_model, 1)
                          > FSDP_THRESHOLD * HBM_BYTES)

    def div(n, axis_size):
        return n % axis_size == 0

    rules: Dict[str, Any] = {
        "batch": batch_axes(mesh),
        "embed": "data" if (need_fsdp and div(cfg.d_model, n_data)) else None,
        "vocab": "model" if div(cfg.vocab_size, n_model) else None,
        "heads": "model" if div(cfg.num_heads, n_model) else None,
        "kv_heads": "model" if div(cfg.num_kv_heads, n_model) else None,
        "head_dim": None,
        "mlp": "model" if div(cfg.d_ff, n_model) else None,
        "experts": "model",
        "experts_dp": "data",     # ep_a2a layout: experts over data...
        "expert_tp": "model",     # ...expert FFN dim over model
        "q_lora": None,
        "kv_lora": None,
        "lru": None,
        "layers": None,
    }
    if cfg.moe is not None:
        f = cfg.moe.d_ff_expert
        if cfg.moe.n_held % max(n_data, 1):
            rules["experts_dp"] = None
        if f % max(n_model, 1):
            rules["expert_tp"] = None
    if cfg.rglru is not None:
        lw = cfg.rglru.lru_width or cfg.d_model
        rules["lru"] = "model" if div(lw, n_model) else None
    if cfg.moe is not None and not div(cfg.moe.n_held, n_model):
        rules["experts"] = None
    # GQA: sharding q-heads while kv replicated is fine; but if q-heads
    # can't shard, keep kv replicated too (avoids asymmetric layouts).
    if rules["heads"] is None:
        rules["kv_heads"] = None
    return rules


def spec_from_axes(axes: Tuple[Optional[str], ...],
                   rules: Dict[str, Any]) -> P:
    parts = []
    used = set()
    for ax in axes:
        m = rules.get(ax) if ax is not None else None
        # one mesh axis may appear only once in a PartitionSpec
        if m is None:
            parts.append(None)
            continue
        key = tuple(m) if isinstance(m, (tuple, list)) else (m,)
        if any(k in used for k in key):
            parts.append(None)
            continue
        used.update(key)
        parts.append(tuple(m) if isinstance(m, (tuple, list)) else m)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_shardings(axes_tree, mesh: Mesh, rules: Dict[str, Any]):
    def f(axes):
        return NamedSharding(mesh, spec_from_axes(axes, rules))
    return jax.tree.map(f, axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and
                        all(isinstance(a, (str, type(None))) for a in x))


def batch_sharding(mesh: Mesh, global_batch: int, ndim: int,
                   rules: Dict[str, Any]) -> NamedSharding:
    axes = rules.get("batch", ())
    n = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if axes and global_batch % n == 0 and global_batch >= n:
        spec = P(tuple(axes) if len(axes) > 1 else axes[0])
    else:
        spec = P()
    return NamedSharding(mesh, spec)


def cache_sharding(cfg: ModelConfig, mesh: Mesh, rules: Dict[str, Any],
                   cache_abstract) -> Any:
    """Shard caches: batch over data axes, kv-heads over model if possible."""
    baxes = rules.get("batch", ())

    def f(leaf):
        shp = leaf.shape
        spec: list = [None] * len(shp)
        if len(shp) < 2:
            return NamedSharding(mesh, P(*spec))   # replicate 1-D leaves
        n = int(np.prod([mesh.shape[a] for a in baxes])) if baxes else 1
        # leading dims: scan-stack (layers) then batch; find batch dim as the
        # first dim whose size is divisible by the batch-axis product and >1.
        bdim = None
        for i, s in enumerate(shp[:2]):
            if baxes and s % n == 0 and s >= n and n > 1:
                spec[i] = tuple(baxes) if len(baxes) > 1 else baxes[0]
                bdim = i
                break
        # cache sequence dim (split-KV): the dim right after batch, sharded
        # over "model" when long and divisible (matches the decode-path
        # with_sharding_constraint).
        nm = mesh.shape.get("model", 1)
        if bdim is not None and len(shp) >= bdim + 2 and nm > 1:
            sdim = bdim + 1
            if shp[sdim] % nm == 0 and shp[sdim] >= 4 * nm:
                spec[sdim] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(f, cache_abstract)
