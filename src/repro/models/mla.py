"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

Train/prefill use the naive (decompressed) form; decode uses the
*absorbed* form where W_UK / W_UV are folded into the query / output so
the KV cache is just the (kv_lora + rope) latent per token — the paper's
serving-memory contribution.  Queries are compressed through a LoRA
(``wq_a`` -> ``q_norm`` -> ``wq_b``) or, with ``q_lora_rank`` 0 or None
(DeepSeek-V2-Lite), projected directly by ``wq``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec, apply_rope, rms_norm
from repro.models.attention import (NEG_INF, blockwise_attention,
                                    reference_attention)


def mla_specs(cfg: ModelConfig) -> dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    if m.q_lora_rank:
        q = {"wq_a": ParamSpec((d, m.q_lora_rank), ("embed", "q_lora")),
             "q_norm": ParamSpec((m.q_lora_rank,), ("q_lora",), init="zeros"),
             "wq_b": ParamSpec((m.q_lora_rank, H, qk),
                               ("q_lora", "heads", None))}
    else:
        q = {"wq": ParamSpec((d, H, qk), ("embed", "heads", None))}
    return {
        **q,
        "wkv_a": ParamSpec((d, m.kv_lora_rank + m.qk_rope_dim),
                           ("embed", "kv_lora")),
        "kv_norm": ParamSpec((m.kv_lora_rank,), ("kv_lora",), init="zeros"),
        "wkv_b": ParamSpec((m.kv_lora_rank, H, m.qk_nope_dim + m.v_dim),
                           ("kv_lora", "heads", None)),
        "wo": ParamSpec((H, m.v_dim, d), ("heads", None, "embed"),
                        fan_dims=(0, 1)),
    }


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "kr": jnp.zeros((batch, max_len, m.qk_rope_dim), dtype),
        "t": jnp.full((max_len,), -(2 ** 30), jnp.int32),
    }


def abstract_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    m = cfg.mla
    dt = jnp.dtype(dtype)
    return {
        "ckv": jax.ShapeDtypeStruct((batch, max_len, m.kv_lora_rank), dt),
        "kr": jax.ShapeDtypeStruct((batch, max_len, m.qk_rope_dim), dt),
        "t": jax.ShapeDtypeStruct((max_len,), jnp.int32),
    }


def _project_q(cfg, p, x):
    m = cfg.mla
    dt = x.dtype
    if m.q_lora_rank:
        cq = x @ p["wq_a"].astype(dt)
        cq = rms_norm(cq, p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"].astype(dt))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    return q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]


def _project_kv_latent(cfg, p, x, positions):
    """Returns (ckv_normed (B,S,R), k_rope (B,S,rope))."""
    m = cfg.mla
    dt = x.dtype
    ckv = x @ p["wkv_a"].astype(dt)
    c, kr = ckv[..., :m.kv_lora_rank], ckv[..., m.kv_lora_rank:]
    c = rms_norm(c, p["kv_norm"], cfg.norm_eps)
    kr = apply_rope(kr[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return c, kr


@jax.named_scope("mla")
def mla_layer(cfg: ModelConfig, p: dict, x, *, positions, mode: str,
              cache: Optional[dict], mesh=None):
    m = cfg.mla
    H = cfg.num_heads
    dt = x.dtype
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5

    if mode in ("train", "prefill"):
        qn, qr = _project_q(cfg, p, x)
        qr = apply_rope(qr, positions, cfg.rope_theta)
        c, kr = _project_kv_latent(cfg, p, x, positions)
        kv = jnp.einsum("bsr,rhk->bshk", c, p["wkv_b"].astype(dt))
        kn, v = kv[..., :m.qk_nope_dim], kv[..., m.qk_nope_dim:]
        q = jnp.concatenate([qn, qr], axis=-1)
        k = jnp.concatenate(
            [kn, jnp.broadcast_to(kr[:, :, None, :], qr.shape[:2] + (H, m.qk_rope_dim))],
            axis=-1)
        qp = positions[0] if positions.ndim > 1 else positions
        S = x.shape[1]
        if S > 2048 and cfg.attn_impl != "reference":
            fn = lambda q_, k_, v_: blockwise_attention(
                q_, k_, v_, q_pos=qp, k_pos=qp, scale=scale)
            o = jax.checkpoint(fn)(q, k, v) if mode == "train" \
                else fn(q, k, v)
        else:
            o = reference_attention(q, k, v, q_pos=qp, k_pos=qp, scale=scale)
        new_cache = cache
        if mode == "prefill" and cache is not None:
            L = cache["ckv"].shape[1]
            padn = L - S
            t = jnp.pad(jnp.arange(S, dtype=jnp.int32), (0, padn),
                        constant_values=-(2 ** 30))
            new_cache = {
                "ckv": jnp.pad(c, ((0, 0), (0, padn), (0, 0))).astype(cache["ckv"].dtype),
                "kr": jnp.pad(kr, ((0, 0), (0, padn), (0, 0))).astype(cache["kr"].dtype),
                "t": t,
            }
        out = jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(dt))
        return out, new_cache

    # ---- decode: absorbed form over the latent cache ----
    assert mode == "decode" and cache is not None
    pos = positions.reshape(-1)[0]
    qn, qr = _project_q(cfg, p, x)                       # (B,1,H,*)
    qr = apply_rope(qr, positions, cfg.rope_theta)
    c_new, kr_new = _project_kv_latent(cfg, p, x, positions)
    from repro.models.common import constrain_batch
    ckv = jax.lax.dynamic_update_slice_in_dim(
        cache["ckv"], c_new.astype(cache["ckv"].dtype), pos, axis=1)
    kr = jax.lax.dynamic_update_slice_in_dim(
        cache["kr"], kr_new.astype(cache["kr"].dtype), pos, axis=1)
    # split-KV: latent cache sequence sharded over "model" (see attention)
    ckv = constrain_batch(ckv, mesh, seq_shard=True)
    kr = constrain_batch(kr, mesh, seq_shard=True)
    t = jax.lax.dynamic_update_slice_in_dim(
        cache["t"], pos[None].astype(jnp.int32), pos, axis=0)

    w_uk = p["wkv_b"][..., :m.qk_nope_dim].astype(dt)    # (R,H,nope)
    w_uv = p["wkv_b"][..., m.qk_nope_dim:].astype(dt)    # (R,H,v)
    q_lat = jnp.einsum("bshk,rhk->bshr", qn, w_uk)       # absorb W_UK
    s = jnp.einsum("bshr,btr->bhst", q_lat, ckv.astype(dt))
    s = s + jnp.einsum("bshk,btk->bhst", qr, kr.astype(dt))
    s = (s * scale).astype(jnp.float32)
    valid = (t >= 0) & (t <= pos)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhst,btr->bshr", pr.astype(dt), ckv.astype(dt))
    o = jnp.einsum("bshr,rhv->bshv", ctx, w_uv)          # absorb W_UV
    out = jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(dt))
    return out, {"ckv": ckv, "kr": kr, "t": t}
