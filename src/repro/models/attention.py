"""Attention layers: full/global, sliding-window local, GQA, decode paths.

Two implementations share one math definition (``ref`` in
``repro.kernels.ref`` mirrors these):

* ``reference`` — plain einsum + mask; O(S^2) materialized (small S only).
* ``blockwise`` — lax.scan over KV blocks with online softmax; flash-style
  peak memory, used for long sequences and as the dry-run lowering path.

The Pallas flash-attention kernel (``repro.kernels.flash_attention``)
implements the same math, but no layer here calls it.

Local (sliding-window) attention uses an exact two-chunk banded layout so
FLOPs scale with S*W, not S^2.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec, apply_rope, rms_norm, softcap

NEG_INF = -2.0 ** 30


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def attn_specs(cfg: ModelConfig) -> dict:
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    sp = {
        "wq": ParamSpec((d, H, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, K, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, K, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((H, hd, d), ("heads", "head_dim", "embed"),
                        fan_dims=(0, 1)),
    }
    if cfg.qk_norm:
        sp["q_norm"] = ParamSpec((hd,), ("head_dim",), init="zeros")
        sp["k_norm"] = ParamSpec((hd,), ("head_dim",), init="zeros")
    return sp


# ---------------------------------------------------------------------------
# core math
# ---------------------------------------------------------------------------

def _grouped_scores(q, k):
    """q: (B,Sq,K,G,D)  k: (B,Sk,K,D) -> scores (B,K,G,Sq,Sk)."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                      preferred_element_type=jnp.float32)


def _grouped_out(p, v):
    """p: (B,K,G,Sq,Sk)  v: (B,Sk,K,D) -> (B,Sq,K,G,D)."""
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(p.dtype))


def _causal_mask(q_pos, k_pos, window: int = 0):
    """(Sq,1) x (Sk,) position tensors -> bool mask (Sq,Sk). True=keep."""
    m = k_pos[None, :] <= q_pos[:, None]
    if window:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    return m


def reference_attention(q, k, v, *, q_pos, k_pos, window=0, cap=0.0,
                        scale=None):
    """q: (B,Sq,H,D), k/v: (B,Sk,K,D). Returns (B,Sq,H,D)."""
    B, Sq, H, D = q.shape
    K = k.shape[2]
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    qg = q.reshape(B, Sq, K, G, D) * scale
    s = _grouped_scores(qg, k)                              # (B,K,G,Sq,Sk)
    s = softcap(s, cap)
    mask = _causal_mask(q_pos, k_pos, window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = _grouped_out(p.astype(v.dtype), v)
    return o.reshape(B, Sq, H, v.shape[-1])


def blockwise_attention(q, k, v, *, q_pos, k_pos, window=0, cap=0.0,
                        scale=None, block_kv=1024):
    """Online-softmax over KV blocks (flash-style peak memory).

    The block body is rematerialised: the backward pass keeps only the
    running max, sum and output of each KV block, O(S*D) per block, and
    recomputes the block's scores (the FlashAttention backward trade).
    Without it the scan's backward keeps every block's scores and
    probabilities, O(S^2) in float32.
    """
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    nb = -(-Sk // block_kv)
    pad = nb * block_kv - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=2 ** 30)
    Dv = v.shape[-1]
    qg = (q.reshape(B, Sq, K, G, D) * scale)
    kb = k.reshape(B, nb, block_kv, K, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block_kv, K, Dv).transpose(1, 0, 2, 3, 4)
    pb = k_pos.reshape(nb, block_kv)

    def body(carry, blk):
        m, l, acc = carry
        kc, vc, pc = blk
        s = _grouped_scores(qg, kc)                         # (B,K,G,Sq,c)
        s = softcap(s, cap)
        msk = _causal_mask(q_pos, pc, window)
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        # zero fully-masked entries explicitly (NEG_INF - NEG_INF == 0 trap)
        p = jnp.exp(s - m_new[..., None]) * msk[None, None, None]
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, K, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, K, G, Sq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, a0),
                                  (kb, vb, pb))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv).astype(q.dtype)


def local_attention_chunked(q, k, v, *, window: int, cap=0.0, scale=None,
                            q_offset=0):
    """Exact causal sliding-window attention in banded two-chunk form.

    FLOPs ~ S * 2W.  Requires S % W == 0 (callers pad).
    q: (B,S,H,D), k/v: (B,S,K,D), window W = chunk size.
    """
    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    W = window
    assert S % W == 0, (S, W)
    n = S // W
    scale = scale if scale is not None else D ** -0.5
    qc = (q.reshape(B, n, W, K, G, D) * scale)
    kc = k.reshape(B, n, W, K, D)
    vc = v.reshape(B, n, W, K, D)
    # previous chunk (zeros before chunk 0)
    kp = jnp.pad(kc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    vp = jnp.pad(vc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    k2 = jnp.concatenate([kp, kc], axis=2)                  # (B,n,2W,K,D)
    v2 = jnp.concatenate([vp, vc], axis=2)
    s = jnp.einsum("bnqkgd,bnskd->bnkgqs", qc, k2,
                   preferred_element_type=jnp.float32)      # (B,n,K,G,W,2W)
    s = softcap(s, cap)
    qpos = jnp.arange(W)[:, None] + W                       # within 2W frame
    kpos = jnp.arange(2 * W)[None, :]
    m = (kpos <= qpos) & (kpos > qpos - W)
    # chunk 0 has no previous chunk: mask the zero-padding keys
    first = jnp.arange(n)[:, None, None] == 0
    valid = jnp.where(first, kpos[None] >= W, True)         # (n,W,2W) broadcast
    msk = m[None] & valid                                   # (n,W,2W)
    s = jnp.where(msk[None, :, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnkgqs,bnskd->bnqkgd", p.astype(v2.dtype), v2)
    return o.reshape(B, S, H, D)


def decode_attention(q, k_cache, v_cache, *, key_mask, cap=0.0, scale=None):
    """Single-token decode. q: (B,1,H,D), caches: (B,S,K,D), key_mask: (B,S)."""
    B, _, H, D = q.shape
    K = k_cache.shape[2]
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    qg = q.reshape(B, K, G, D) * scale
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32)
    s = softcap(s, cap)
    s = jnp.where(key_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(B, 1, H, D)


# ---------------------------------------------------------------------------
# layer: projections + rope + cache handling
# ---------------------------------------------------------------------------

def _maybe_qk_norm(cfg, p, q, k):
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k


def _rope_theta(cfg: ModelConfig, kind: str) -> float:
    if kind == "full" and cfg.rope_theta_global is not None:
        return cfg.rope_theta_global
    return cfg.rope_theta


def attention_layer(cfg: ModelConfig, kind: str, p: dict, x, *, positions,
                    mode: str, cache: Optional[dict], mesh=None):
    """Returns (out (B,S,d), new_cache)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    q, k = _maybe_qk_norm(cfg, p, q, k)
    theta = _rope_theta(cfg, kind)
    q = apply_rope(q, positions, theta, cfg.rope_fraction)
    k = apply_rope(k, positions, theta, cfg.rope_fraction)
    window = cfg.window_size if kind == "local" else 0
    cap = cfg.attn_logit_softcap
    scale = cfg.head_dim ** -0.5

    new_cache = cache
    if mode == "train":
        S = x.shape[1]
        qp = positions[0] if positions.ndim > 1 else positions
        if kind == "local" and window and S % window == 0 and S > window:
            fn = lambda q_, k_, v_: local_attention_chunked(
                q_, k_, v_, window=window, cap=cap, scale=scale)
            o = jax.checkpoint(fn)(q, k, v)
        elif S > 2048 and cfg.attn_impl != "reference":
            fn = lambda q_, k_, v_: blockwise_attention(
                q_, k_, v_, q_pos=qp, k_pos=qp, window=window, cap=cap,
                scale=scale)
            o = jax.checkpoint(fn)(q, k, v)
        else:
            o = reference_attention(q, k, v, q_pos=qp, k_pos=qp,
                                    window=window, cap=cap, scale=scale)
    elif mode == "prefill":
        S = x.shape[1]
        qp = positions[0] if positions.ndim > 1 else positions
        if kind == "local" and window and S % window == 0 and S > window:
            o = local_attention_chunked(q, k, v, window=window, cap=cap,
                                        scale=scale)
        else:
            o = blockwise_attention(q, k, v, q_pos=qp, k_pos=qp,
                                    window=window, cap=cap, scale=scale)
        new_cache = _write_prefill_cache(cfg, kind, cache, k, v, positions)
    elif mode == "decode":
        o, new_cache = _decode_with_cache(cfg, kind, cache, q, k, v,
                                          positions, cap, scale, mesh=mesh)
    else:
        raise ValueError(mode)

    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt))
    return out, new_cache


# -- caches -----------------------------------------------------------------

def init_attn_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                    dtype) -> dict:
    K, hd = cfg.num_kv_heads, cfg.head_dim
    W = cfg.window_size if kind == "local" else max_len
    W = min(W, max_len) or max_len
    return {
        "k": jnp.zeros((batch, W, K, hd), dtype),
        "v": jnp.zeros((batch, W, K, hd), dtype),
        "t": jnp.full((W,), -(2 ** 30), jnp.int32),   # global time per slot
    }


def abstract_attn_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                        dtype) -> dict:
    K, hd = cfg.num_kv_heads, cfg.head_dim
    W = cfg.window_size if kind == "local" else max_len
    W = min(W, max_len) or max_len
    return {
        "k": jax.ShapeDtypeStruct((batch, W, K, hd), jnp.dtype(dtype)),
        "v": jax.ShapeDtypeStruct((batch, W, K, hd), jnp.dtype(dtype)),
        "t": jax.ShapeDtypeStruct((W,), jnp.int32),
    }


def _write_prefill_cache(cfg, kind, cache, k, v, positions):
    if cache is None:
        return None
    W = cache["k"].shape[1]
    S = k.shape[1]
    if W >= S:
        kw = jnp.pad(k, ((0, 0), (0, W - S), (0, 0), (0, 0)))
        vw = jnp.pad(v, ((0, 0), (0, W - S), (0, 0), (0, 0)))
        t = jnp.pad(jnp.arange(S, dtype=jnp.int32), (0, W - S),
                    constant_values=-(2 ** 30))
        return {"k": kw.astype(cache["k"].dtype),
                "v": vw.astype(cache["v"].dtype), "t": t}
    # keep last W keys (ring layout: slot = t % W)
    tail_t = jnp.arange(S - W, S, dtype=jnp.int32)
    roll = (S - W) % W
    kt = jnp.roll(k[:, -W:], roll, axis=1)
    vt = jnp.roll(v[:, -W:], roll, axis=1)
    t = jnp.roll(tail_t, roll)
    return {"k": kt.astype(cache["k"].dtype), "v": vt.astype(cache["v"].dtype),
            "t": t}


def _decode_with_cache(cfg, kind, cache, q, k, v, positions, cap, scale,
                       mesh=None):
    """positions: (B,1) current global position (uniform across batch)."""
    pos = positions.reshape(-1)[0]
    W = cache["k"].shape[1]
    slot = pos % W
    from repro.models.common import constrain_batch
    kc = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
    vc = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
    # pin caches: batch over (pod,data), cache *sequence* over "model"
    # (flash-decode split-KV: each model shard scans 1/16 of the cache;
    # softmax over the sharded axis reduces with tiny per-head scalars).
    # Stops SPMD from partially sharding kv heads and re-gathering the
    # whole cache as one giant all-gather.
    kc = constrain_batch(kc, mesh, seq_shard=True)
    vc = constrain_batch(vc, mesh, seq_shard=True)
    t = jax.lax.dynamic_update_slice_in_dim(
        cache["t"], pos[None].astype(jnp.int32), slot, axis=0)
    window = cfg.window_size if kind == "local" else 0
    valid = (t >= 0) & (t <= pos)
    if window:
        valid &= t > pos - window
    key_mask = jnp.broadcast_to(valid[None, :], (q.shape[0], W))
    o = decode_attention(q, kc, vc, key_mask=key_mask, cap=cap, scale=scale)
    return o, {"k": kc, "v": vc, "t": t}
