"""Step functions: train_step / prefill_step / decode_step builders.

Each builder returns a pure function suitable for ``jax.jit`` with explicit
in/out shardings derived from the logical-axis rules, plus the matching
abstract input specs (``input_specs``) for the dry-run.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeCfg
from repro.distributed import sharding as shd
from repro.models import moe as moe_mod
from repro.models import transformer as tfm
from repro.optim import (AdamWConfig, OptState, abstract_opt_state,
                         apply_updates, global_norm, init_opt_state,
                         linear_warmup_cosine)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def cross_entropy(logits, labels, z_loss: float = 1e-4):
    """logits (B,S,V) fp32, labels (B,S) int32. Mean over tokens."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    loss = nll.mean()
    if z_loss:
        loss = loss + z_loss * jnp.square(lse).mean()
    return loss


def chunked_cross_entropy(cfg: ModelConfig, params, hidden, labels,
                          z_loss: float = 1e-4, n_chunks: int = 8):
    """CE without materializing (B,S,V): scan over sequence chunks, each
    chunk's logits recomputed in backward (jax.checkpoint)."""
    from repro.models.common import softcap as _softcap
    B, S, d = hidden.shape
    while S % n_chunks:
        n_chunks //= 2
    C = S // n_chunks
    dt = hidden.dtype
    w = (params["embed"].T if (cfg.tie_embeddings and
                               cfg.input_kind == "tokens")
         else params["unembed"]).astype(dt)

    @jax.checkpoint
    def chunk(h_c, y_c):
        logits = (h_c @ w).astype(jnp.float32)
        logits = _softcap(logits, cfg.final_logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, y_c[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - ll), jnp.sum(jnp.square(lse))

    hs = hidden.reshape(B, n_chunks, C, d).transpose(1, 0, 2, 3)
    ys = labels.reshape(B, n_chunks, C).transpose(1, 0, 2)

    def body(carry, xs):
        nll, zs = carry
        a, b = chunk(*xs)
        return (nll + a, zs + b), None

    (nll, zs), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                (hs, ys))
    loss = nll / (B * S)
    if z_loss:
        loss = loss + z_loss * zs / (B * S)
    return loss


def compute_params(cfg: ModelConfig, params):
    """Optionally cast fp32 master weights to the activation dtype before
    the forward pass — halves FSDP all-gather and weight-read traffic
    (§Perf knob ``cast_params_bf16``)."""
    if not cfg.cast_params_bf16:
        return params
    dt = jnp.dtype(cfg.dtype)

    def f(p):
        if hasattr(p, "dtype") and p.dtype == jnp.float32 and p.ndim >= 2:
            return p.astype(dt)
        return p
    return jax.tree.map(f, params)


def loss_fn(cfg: ModelConfig, params, batch, *, mesh=None):
    """Returns (loss, metrics); an MoE model's metrics carry the step's
    routing counts (``moe.COUNTS``), a dense model's none."""
    fwd_params = compute_params(cfg, params)
    labels = batch["labels"]
    if cfg.chunked_ce:
        hidden, _, aux, counts = tfm.forward(
            cfg, fwd_params, batch, mode="train", mesh=mesh,
            return_hidden=True, with_counts=True)
        loss = chunked_cross_entropy(cfg, fwd_params, hidden, labels)
    else:
        logits, _, aux, counts = tfm.forward(
            cfg, fwd_params, batch, mode="train", mesh=mesh,
            with_counts=True)
        loss = cross_entropy(logits, labels)
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_weight * aux
    return loss, {"loss": loss, "aux": aux, **counts}


# ---------------------------------------------------------------------------
# abstract inputs (dry-run stand-ins; no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeCfg) -> Dict[str, Any]:
    """ShapeDtypeStructs for every model input of this (arch, shape) cell."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        if cfg.input_kind == "tokens":
            return {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                    "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        return {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                               jnp.dtype(cfg.dtype)),
                "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if shape.kind == "prefill":
        if cfg.input_kind == "tokens":
            return {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        return {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                               jnp.dtype(cfg.dtype))}
    # decode: one new token; the KV cache of length S is a separate arg
    if cfg.input_kind == "tokens":
        return {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
    return {"embeds": jax.ShapeDtypeStruct((B, 1, cfg.d_model),
                                           jnp.dtype(cfg.dtype))}


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig, *, mesh=None,
                    total_steps: int = 10_000, warmup: int = 100):
    def train_step(params, opt_state: OptState, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch, mesh=mesh), has_aux=True)(params)
        lr_scale = linear_warmup_cosine(opt_state.step + 1, warmup=warmup,
                                        total=total_steps)
        params, opt_state, om = apply_updates(params, grads, opt_state,
                                              opt_cfg, lr_scale)
        metrics = dict(metrics)
        metrics.update(om)
        if cfg.moe is not None:
            # the routed experts' part of the gradient, before clipping
            metrics["moe_expert_gnorm"] = global_norm(
                moe_mod.expert_leaves(grads))
        return params, opt_state, metrics
    return train_step


def make_prefill_step(cfg: ModelConfig, max_len: int, *, mesh=None):
    def prefill_step(params, batch, cache):
        logits, cache, _ = tfm.forward(cfg, compute_params(cfg, params),
                                       batch, mode="prefill", cache=cache,
                                       mesh=mesh)
        return logits[:, -1], cache
    return prefill_step


def make_decode_step(cfg: ModelConfig, *, mesh=None):
    def decode_step(params, batch, cache):
        logits, cache, _ = tfm.forward(cfg, compute_params(cfg, params),
                                       batch, mode="decode", cache=cache,
                                       mesh=mesh)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, cache
    return decode_step


# ---------------------------------------------------------------------------
# sharding assembly for a (cfg, shape, mesh) cell
# ---------------------------------------------------------------------------

def cell_shardings(cfg: ModelConfig, shape: ShapeCfg, mesh: Mesh,
                   opt_cfg: Optional[AdamWConfig] = None):
    """Returns dict with param/opt/batch/cache shardings + abstract values."""
    rules = shd.base_rules(cfg, shape, mesh)
    axes = tfm.model_axes(cfg)
    aparams = tfm.abstract_model(cfg)
    pshard = shd.tree_shardings(axes, mesh, rules)

    out: Dict[str, Any] = {"rules": rules, "params": aparams,
                           "params_sharding": pshard}
    bsh = shd.batch_sharding(mesh, shape.global_batch, 2, rules)
    binputs = input_specs(cfg, shape)
    out["batch"] = binputs
    out["batch_sharding"] = jax.tree.map(lambda _: bsh, binputs)

    if shape.kind == "train" and opt_cfg is not None:
        aopt = abstract_opt_state(aparams, opt_cfg)
        # moments shard like their params; quantized moments are 2-D blocks
        # that follow the flattened layout -> shard rows if big.
        def opt_shard(ps):
            return ps
        mshard = jax.tree.map(lambda s: s, pshard)
        if opt_cfg.quantized_moments:
            def qshard(leaf):
                return NamedSharding(mesh, P())
            msh = jax.tree.map(qshard, aopt.m)
            vsh = jax.tree.map(qshard, aopt.v)
        else:
            msh, vsh = mshard, mshard
        out["opt"] = aopt
        out["opt_sharding"] = OptState(
            step=NamedSharding(mesh, P()), m=msh, v=vsh)
    if shape.kind in ("prefill", "decode"):
        acache = tfm.init_cache(cfg, shape.global_batch, shape.seq_len,
                                abstract=True)
        out["cache"] = acache
        out["cache_sharding"] = shd.cache_sharding(cfg, mesh, rules, acache)
    return out
