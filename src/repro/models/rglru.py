"""Real-Gated LRU recurrent block (Griffin / RecurrentGemma, arXiv:2402.19427).

Block:  x -> [W_x -> causal conv1d(4) -> RG-LRU]  ⊙ GeLU(W_gate x) -> W_out

RG-LRU cell (all elementwise over the lru width):
    r_t = sigmoid(blockdiag(W_a) x_t + b_a)          recurrence gate
    i_t = sigmoid(blockdiag(W_i) x_t + b_i)          input gate
    log a_t = -c * softplus(Lambda) * r_t
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

Training/prefill lower to ``lax.associative_scan`` (log-depth, parallel);
decode is a single fused step.  The Pallas TPU kernel in
``repro.kernels.rglru_scan`` computes the same scan, but this block does
not call it.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec


def rglru_specs(cfg: ModelConfig) -> dict:
    g = cfg.rglru
    d = cfg.d_model
    lw = g.lru_width or d
    nb = g.num_blocks or cfg.num_heads
    bw = lw // nb
    return {
        "w_x": ParamSpec((d, lw), ("embed", "lru")),
        "w_gate": ParamSpec((d, lw), ("embed", "lru")),
        "conv_w": ParamSpec((g.conv_width, lw), (None, "lru"), fan_dims=(0,)),
        "conv_b": ParamSpec((lw,), ("lru",), init="zeros"),
        "gate_a_w": ParamSpec((nb, bw, bw), (None, None, None), fan_dims=(1,)),
        "gate_a_b": ParamSpec((nb, bw), (None, None), init="zeros"),
        "gate_i_w": ParamSpec((nb, bw, bw), (None, None, None), fan_dims=(1,)),
        "gate_i_b": ParamSpec((nb, bw), (None, None), init="zeros"),
        "lam": ParamSpec((lw,), ("lru",), init="rglru_a", dtype="float32"),
        "w_out": ParamSpec((lw, d), ("lru", "embed")),
    }


def init_rglru_cache(cfg: ModelConfig, batch: int, dtype):
    g = cfg.rglru
    lw = g.lru_width or cfg.d_model
    return {
        "h": jnp.zeros((batch, lw), jnp.float32),
        "conv": jnp.zeros((batch, g.conv_width - 1, lw), dtype),
    }


def abstract_rglru_cache(cfg: ModelConfig, batch: int, dtype):
    g = cfg.rglru
    lw = g.lru_width or cfg.d_model
    return {
        "h": jax.ShapeDtypeStruct((batch, lw), jnp.float32),
        "conv": jax.ShapeDtypeStruct((batch, g.conv_width - 1, lw),
                                     jnp.dtype(dtype)),
    }


def _gates(cfg, p, xs):
    """xs: (B,S,lw) -> (log_a, gated_input) in fp32."""
    g = cfg.rglru
    nb = g.num_blocks or cfg.num_heads
    B, S, lw = xs.shape
    xb = xs.reshape(B, S, nb, lw // nb).astype(jnp.float32)
    ra = jnp.einsum("bsnk,nkj->bsnj", xb, p["gate_a_w"].astype(jnp.float32))
    ra = jax.nn.sigmoid(ra + p["gate_a_b"].astype(jnp.float32))
    ri = jnp.einsum("bsnk,nkj->bsnj", xb, p["gate_i_w"].astype(jnp.float32))
    ri = jax.nn.sigmoid(ri + p["gate_i_b"].astype(jnp.float32))
    r = ra.reshape(B, S, lw)
    i = ri.reshape(B, S, lw)
    log_a = -cfg.rglru.c_exponent * jax.nn.softplus(p["lam"]) * r
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) \
        * i * xs.astype(jnp.float32)
    return log_a, b


def rglru_scan_ref(log_a, b, h0=None):
    """Associative scan of h_t = a_t h_{t-1} + b_t along axis 1 (fp32)."""
    a = jnp.exp(log_a)
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0)

    def comb(l, r):
        a1, b1 = l
        a2, b2 = r
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(comb, (a, b), axis=1)
    return h


def _causal_conv(p, xs, state=None):
    """Depthwise causal conv over time. xs: (B,S,lw)."""
    w = p["conv_w"].astype(xs.dtype)                 # (W, lw)
    W = w.shape[0]
    if state is None:
        pad = jnp.zeros((xs.shape[0], W - 1, xs.shape[2]), xs.dtype)
    else:
        pad = state.astype(xs.dtype)
    xp = jnp.concatenate([pad, xs], axis=1)
    out = sum(xp[:, i:i + xs.shape[1]] * w[i] for i in range(W))
    out = out + p["conv_b"].astype(xs.dtype)
    new_state = xp[:, -(W - 1):] if W > 1 else pad
    return out, new_state


def rglru_layer(cfg: ModelConfig, p: dict, x, *, mode: str,
                cache: Optional[dict]):
    """x: (B,S,d). Returns (out, new_cache)."""
    dt = x.dtype
    xs = x @ p["w_x"].astype(dt)
    gate = jax.nn.gelu(x @ p["w_gate"].astype(dt))

    if mode in ("train", "prefill"):
        conv_state = None if cache is None else cache["conv"]
        xs, new_conv = _causal_conv(p, xs, conv_state)
        log_a, b = _gates(cfg, p, xs)
        h = rglru_scan_ref(log_a, b, None if cache is None else cache["h"])
        new_cache = cache
        if mode == "prefill" and cache is not None:
            new_cache = {"h": h[:, -1].astype(jnp.float32), "conv": new_conv}
        y = (h.astype(dt) * gate) @ p["w_out"].astype(dt)
        return y, new_cache

    assert mode == "decode" and cache is not None
    # single step: xs (B,1,lw)
    w = p["conv_w"].astype(dt)
    hist = jnp.concatenate([cache["conv"].astype(dt), xs], axis=1)  # (B,W,lw)
    conv = jnp.einsum("bwl,wl->bl", hist, w) + p["conv_b"].astype(dt)
    log_a, b = _gates(cfg, p, conv[:, None, :])
    a = jnp.exp(log_a[:, 0])
    h = a * cache["h"] + b[:, 0]
    new_cache = {"h": h, "conv": hist[:, 1:]}
    y = (h[:, None, :].astype(dt) * gate) @ p["w_out"].astype(dt)
    return y, new_cache
