"""Model assembly: embeddings + [prologue | scanned periods | epilogue] + head.

The layer stack is organized as ``cfg.stack_plan()`` dictates:

    prologue (unrolled)  ->  lax.scan over n_periods x layer_pattern  ->  epilogue

Scanned parameters are stacked on a leading ``layers`` axis per
position-in-period, so heterogeneous periods (e.g. gemma3's 5 local + 1
global) scan cleanly.  KV caches mirror the same structure.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.common import (ParamSpec, abstract_params, init_params,
                                 param_axes, rms_norm, softcap, stack_specs)
from repro.models.mlp import mlp_apply, mlp_specs


def _constrain(x, mesh, spec_dims, seq_shard: bool = False):
    from repro.models.common import constrain_batch
    return constrain_batch(x, mesh, seq_shard=seq_shard,
                           vocab_last=spec_dims)


# ---------------------------------------------------------------------------
# spec tree
# ---------------------------------------------------------------------------

def _layer_specs(cfg: ModelConfig, kind: str, use_moe: bool) -> dict:
    sp: Dict[str, Any] = {"ln1": ParamSpec((cfg.d_model,), ("embed",),
                                           init="zeros")}
    if kind in ("full", "local"):
        sp["attn"] = (mla_mod.mla_specs(cfg) if cfg.mla is not None
                      else attn.attn_specs(cfg))
    elif kind == "rglru":
        sp["rglru"] = rglru_mod.rglru_specs(cfg)
    elif kind == "rwkv":
        sp["rwkv_tm"] = rwkv_mod.rwkv_tm_specs(cfg)
    else:
        raise ValueError(kind)

    sp["ln2"] = ParamSpec((cfg.d_model,), ("embed",), init="zeros")
    if kind == "rwkv":
        sp["rwkv_cm"] = rwkv_mod.rwkv_cm_specs(cfg)
    elif use_moe:
        sp["moe"] = moe_mod.moe_specs(cfg)
    else:
        d_ff = cfg.d_ff
        if cfg.moe is not None:   # dense prologue layer of an MoE arch
            d_ff = cfg.moe.d_ff_dense or cfg.d_ff
        sp["mlp"] = mlp_specs(cfg, d_ff=d_ff)
    if cfg.sandwich_norm:
        sp["ln1_post"] = ParamSpec((cfg.d_model,), ("embed",), init="zeros")
        sp["ln2_post"] = ParamSpec((cfg.d_model,), ("embed",), init="zeros")
    return sp


def model_specs(cfg: ModelConfig) -> dict:
    pro, n_periods, epi = cfg.stack_plan()
    sp: Dict[str, Any] = {}
    if cfg.input_kind == "tokens":
        sp["embed"] = ParamSpec((cfg.vocab_size, cfg.d_model),
                                ("vocab", "embed"), init="normal",
                                scale=cfg.d_model ** -0.5)
    else:
        sp["in_norm"] = ParamSpec((cfg.d_model,), ("embed",), init="zeros")
    if not cfg.tie_embeddings or cfg.input_kind != "tokens":
        sp["unembed"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                  ("embed", "vocab"))
    sp["final_norm"] = ParamSpec((cfg.d_model,), ("embed",), init="zeros")

    def moe_at(global_idx: int) -> bool:
        return cfg.layer_uses_moe(global_idx)

    sp["prologue"] = [
        _layer_specs(cfg, k, moe_at(i)) for i, k in enumerate(pro)]
    base = len(pro)
    if n_periods:
        period = [
            _layer_specs(cfg, k, moe_at(base + i))
            for i, k in enumerate(cfg.layer_pattern)]
        sp["scan"] = [stack_specs(s, n_periods, "layers") for s in period]
    else:
        sp["scan"] = []
    epi_base = base + n_periods * cfg.period
    sp["epilogue"] = [
        _layer_specs(cfg, k, moe_at(epi_base + i)) for i, k in enumerate(epi)]
    return sp


def init_model(cfg: ModelConfig, key) -> Any:
    return init_params(model_specs(cfg), key, cfg.param_dtype)


def abstract_model(cfg: ModelConfig) -> Any:
    return abstract_params(model_specs(cfg), cfg.param_dtype)


def model_axes(cfg: ModelConfig) -> Any:
    return param_axes(model_specs(cfg))


# ---------------------------------------------------------------------------
# caches (mirror the stack structure)
# ---------------------------------------------------------------------------

def _layer_cache(cfg, kind, batch, max_len, dtype, abstract: bool):
    if kind in ("full", "local"):
        if cfg.mla is not None:
            f = (mla_mod.abstract_mla_cache if abstract
                 else mla_mod.init_mla_cache)
            return f(cfg, batch, max_len, dtype)
        f = (attn.abstract_attn_cache if abstract else attn.init_attn_cache)
        return f(cfg, kind, batch, max_len, dtype)
    if kind == "rglru":
        f = (rglru_mod.abstract_rglru_cache if abstract
             else rglru_mod.init_rglru_cache)
        return f(cfg, batch, dtype)
    if kind == "rwkv":
        f = (rwkv_mod.abstract_rwkv_cache if abstract
             else rwkv_mod.init_rwkv_cache)
        return f(cfg, batch, dtype)
    raise ValueError(kind)


def _stack_cache(tree, n: int, abstract: bool):
    if abstract:
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(), tree)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               abstract: bool = False):
    pro, n_periods, epi = cfg.stack_plan()
    dtype = cfg.dtype
    cache: Dict[str, Any] = {
        "pos": (jax.ShapeDtypeStruct((), jnp.int32) if abstract
                else jnp.zeros((), jnp.int32)),
        "prologue": [_layer_cache(cfg, k, batch, max_len, dtype, abstract)
                     for k in pro],
        "epilogue": [_layer_cache(cfg, k, batch, max_len, dtype, abstract)
                     for k in epi],
    }
    if n_periods:
        cache["scan"] = [
            _stack_cache(_layer_cache(cfg, k, batch, max_len, dtype, abstract),
                         n_periods, abstract)
            for k in cfg.layer_pattern]
    else:
        cache["scan"] = []
    return cache


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _zero_counts(cfg: ModelConfig) -> dict:
    """Routing counts of a layer that routes nothing: empty for a model
    without experts, so that its program carries no counts at all."""
    return moe_mod.zero_counts() if cfg.moe is not None else {}


def _apply_layer(cfg: ModelConfig, kind: str, use_moe: bool, p: dict, x, *,
                 positions, mode: str, cache, mesh):
    """One residual block. Returns (x, new_cache, aux_loss, counts)."""
    aux = jnp.zeros((), jnp.float32)
    counts = _zero_counts(cfg)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in ("full", "local"):
        if cfg.mla is not None:
            o, cache = mla_mod.mla_layer(cfg, p["attn"], h, positions=positions,
                                         mode=mode, cache=cache, mesh=mesh)
        else:
            o, cache = attn.attention_layer(cfg, kind, p["attn"], h,
                                            positions=positions, mode=mode,
                                            cache=cache, mesh=mesh)
    elif kind == "rglru":
        o, cache = rglru_mod.rglru_layer(cfg, p["rglru"], h, mode=mode,
                                         cache=cache)
    else:  # rwkv time-mix
        o, cache = rwkv_mod.rwkv_time_mix(cfg, p["rwkv_tm"], h, mode=mode,
                                          cache=cache)
    if cfg.sandwich_norm:
        o = rms_norm(o, p["ln1_post"], cfg.norm_eps)
    x = x + o

    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if kind == "rwkv":
        o, cache = rwkv_mod.rwkv_channel_mix(cfg, p["rwkv_cm"], h, mode=mode,
                                             cache=cache)
    elif use_moe:
        o, aux, counts = moe_mod.moe_apply(cfg, p["moe"], h, mesh=mesh,
                                           train=(mode == "train"))
    else:
        o = mlp_apply(cfg, p["mlp"], h)
    if cfg.sandwich_norm:
        o = rms_norm(o, p["ln2_post"], cfg.norm_eps)
    x = x + o
    return x, cache, aux, counts


def _remat_wrap(cfg: ModelConfig, fn, mode: str):
    if mode != "train" or cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)   # "full": save only the period carry


def forward(cfg: ModelConfig, params, batch: Dict[str, Any], *, mode: str,
            cache=None, mesh=None, return_hidden: bool = False,
            with_counts: bool = False):
    """Returns (logits, new_cache, aux_loss) — or (hidden, cache, aux) when
    ``return_hidden`` (the chunked-CE path computes logits itself); with
    ``with_counts``, the step's routing counts summed over the MoE layers
    (``moe.COUNTS``; empty without experts) come fourth.

    batch: {"tokens": (B,S) int32} or {"embeds": (B,S,d)};
    decode mode uses cache["pos"] for positions.
    """
    pro, n_periods, epi = cfg.stack_plan()
    kinds = cfg.expanded_kinds()
    dt = jnp.dtype(cfg.dtype)

    if cfg.input_kind == "tokens":
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(dt)
    else:
        x = batch["embeds"].astype(dt)
        x = rms_norm(x, params["in_norm"], cfg.norm_eps)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
    x = _constrain(x, mesh, spec_dims=False, seq_shard=cfg.seq_shard)

    B, S = x.shape[:2]
    if mode == "decode":
        pos0 = cache["pos"]
        positions = jnp.broadcast_to(pos0[None, None], (B, 1)).astype(jnp.int32)
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))

    aux_total = jnp.zeros((), jnp.float32)
    counts_total = _zero_counts(cfg)
    new_cache = None if cache is None else dict(cache)

    def add(a, b):
        return jax.tree.map(jnp.add, a, b)

    def run_layer(i_global, kind, p, x, c):
        return _apply_layer(cfg, kind, cfg.layer_uses_moe(i_global), p, x,
                            positions=positions, mode=mode, cache=c, mesh=mesh)

    # prologue
    pro_caches = []
    for i, k in enumerate(pro):
        c = None if cache is None else cache["prologue"][i]
        x, c, aux, counts = run_layer(i, k, params["prologue"][i], x, c)
        aux_total += aux
        counts_total = add(counts_total, counts)
        pro_caches.append(c)

    # scanned periods
    scan_caches = cache["scan"] if cache is not None else None
    if n_periods:
        base = len(pro)

        def period_body(carry, xs):
            x, aux_acc, counts_acc = carry
            x = _constrain(x, mesh, spec_dims=False,
                           seq_shard=cfg.seq_shard)
            p_list, c_list = xs
            new_c = []
            for j, kind in enumerate(cfg.layer_pattern):
                cj = None if c_list is None else c_list[j]
                x, cj, aux, counts = run_layer(base + j, kind, p_list[j], x,
                                               cj)
                aux_acc = aux_acc + aux
                counts_acc = add(counts_acc, counts)
                new_c.append(cj)
            return (x, aux_acc, counts_acc), new_c

        body = _remat_wrap(cfg, period_body, mode)
        xs = (params["scan"], scan_caches)
        (x, aux_total, counts_total), scan_caches = jax.lax.scan(
            body, (x, aux_total, counts_total), xs)

    # epilogue
    epi_caches = []
    epi_base = len(pro) + n_periods * cfg.period
    for i, k in enumerate(epi):
        c = None if cache is None else cache["epilogue"][i]
        x, c, aux, counts = run_layer(epi_base + i, k, params["epilogue"][i],
                                      x, c)
        aux_total += aux
        counts_total = add(counts_total, counts)
        epi_caches.append(c)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    x = _constrain(x, mesh, spec_dims=False, seq_shard=cfg.seq_shard)
    if return_hidden:
        if new_cache is not None:
            new_cache["prologue"] = pro_caches
            new_cache["scan"] = scan_caches
            new_cache["epilogue"] = epi_caches
            step = jnp.asarray(1 if mode == "decode" else S, jnp.int32)
            new_cache["pos"] = cache["pos"] + step
        return ((x, new_cache, aux_total, counts_total) if with_counts
                else (x, new_cache, aux_total))
    if cfg.tie_embeddings and cfg.input_kind == "tokens":
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(dt))
    else:
        logits = x @ params["unembed"].astype(dt)
    logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    logits = _constrain(logits, mesh, spec_dims=True,
                        seq_shard=cfg.seq_shard)

    if new_cache is not None:
        new_cache["prologue"] = pro_caches
        new_cache["scan"] = scan_caches
        new_cache["epilogue"] = epi_caches
        step = jnp.asarray(1 if mode == "decode" else S, jnp.int32)
        new_cache["pos"] = cache["pos"] + step
    if with_counts:
        return logits, new_cache, aux_total, counts_total
    return logits, new_cache, aux_total
