"""RWKV-6 "Finch" (arXiv:2404.05892): attention-free time-mix with
data-dependent decay + squared-ReLU channel-mix.

Time-mix (per head, head_dim N):
    S_t[i,j] = w_t[i] * S_{t-1}[i,j] + k_t[i] * v_t[j]
    y_t[j]   = sum_i r_t[i] * (S_{t-1}[i,j] + u[i] * k_t[i] * v_t[j])

with per-channel decay w_t = exp(-exp(ww_t)) computed from the token via a
LoRA, and the ddlerp token-shift data-dependent interpolation.

Training lowers to a chunked scan (chunk=64) — parallel within chunks,
sequential across chunk states; decode is a single state update.  The
Pallas TPU kernel in ``repro.kernels.rwkv_wkv`` computes the same WKV, but
this block does not call it.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def rwkv_tm_specs(cfg: ModelConfig) -> dict:
    r = cfg.rwkv
    d = cfg.d_model
    H = d // r.head_dim
    L = r.mix_lora
    return {
        "mu_x": ParamSpec((d,), ("embed",), init="normal", scale=0.1),
        "mu": ParamSpec((5, d), (None, "embed"), init="normal", scale=0.1),
        "maa_w1": ParamSpec((d, 5 * L), ("embed", None)),
        "maa_w2": ParamSpec((5, L, d), (None, None, "embed"), fan_dims=(1,)),
        "decay_base": ParamSpec((d,), ("embed",), init="normal", scale=0.5),
        "td_w1": ParamSpec((d, r.decay_lora), ("embed", None)),
        "td_w2": ParamSpec((r.decay_lora, d), (None, "embed"), fan_dims=(0,)),
        "u": ParamSpec((H, r.head_dim), (None, "head_dim"), init="normal",
                       scale=0.5),
        "wr": ParamSpec((d, d), ("embed", None)),
        "wk": ParamSpec((d, d), ("embed", None)),
        "wv": ParamSpec((d, d), ("embed", None)),
        "wg": ParamSpec((d, d), ("embed", None)),
        "ln_x_w": ParamSpec((d,), ("embed",), init="ones"),
        "ln_x_b": ParamSpec((d,), ("embed",), init="zeros"),
        "wo": ParamSpec((d, d), (None, "embed")),
    }


def rwkv_cm_specs(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "mu_k": ParamSpec((d,), ("embed",), init="normal", scale=0.1),
        "mu_r": ParamSpec((d,), ("embed",), init="normal", scale=0.1),
        "wk": ParamSpec((d, f), ("embed", "mlp")),
        "wv": ParamSpec((f, d), ("mlp", "embed")),
        "wr": ParamSpec((d, d), ("embed", None)),
    }


def init_rwkv_cache(cfg: ModelConfig, batch: int, dtype):
    r = cfg.rwkv
    d = cfg.d_model
    H = d // r.head_dim
    return {
        "state": jnp.zeros((batch, H, r.head_dim, r.head_dim), jnp.float32),
        "x_tm": jnp.zeros((batch, d), dtype),
        "x_cm": jnp.zeros((batch, d), dtype),
    }


def abstract_rwkv_cache(cfg: ModelConfig, batch: int, dtype):
    r = cfg.rwkv
    d = cfg.d_model
    H = d // r.head_dim
    dt = jnp.dtype(dtype)
    return {
        "state": jax.ShapeDtypeStruct((batch, H, r.head_dim, r.head_dim),
                                      jnp.float32),
        "x_tm": jax.ShapeDtypeStruct((batch, d), dt),
        "x_cm": jax.ShapeDtypeStruct((batch, d), dt),
    }


# ---------------------------------------------------------------------------
# time-mix
# ---------------------------------------------------------------------------

def _token_shift(x, last):
    """previous-token x; ``last`` is (B,d) carry or None (zeros)."""
    if last is None:
        last = jnp.zeros_like(x[:, 0])
    return jnp.concatenate([last[:, None], x[:, :-1]], axis=1)


def _ddlerp(p, x, xprev):
    """Data-dependent interpolation -> (xw, xk, xv, xr, xg)."""
    dt = x.dtype
    sx = xprev - x
    base = x + sx * p["mu_x"].astype(dt)
    B, S, d = x.shape
    L5 = p["maa_w1"].shape[1]
    a = jnp.tanh(base @ p["maa_w1"].astype(dt))          # (B,S,5L)
    a = a.reshape(B, S, 5, L5 // 5)
    m = jnp.einsum("bsfl,fld->bsfd", a, p["maa_w2"].astype(dt))  # (B,S,5,d)
    mix = p["mu"].astype(dt)[None, None] + m             # (B,S,5,d)
    outs = tuple(x + sx * mix[:, :, i] for i in range(5))
    return outs                                          # w,k,v,r,g


def _decay(p, xw):
    """per-token per-channel log decay ww (fp32, ~negative)."""
    dt = xw.dtype
    lora = jnp.tanh(xw @ p["td_w1"].astype(dt)) @ p["td_w2"].astype(dt)
    ww = (p["decay_base"].astype(jnp.float32) - 6.0) + lora.astype(jnp.float32)
    return -jnp.exp(ww)                                  # log w_t  (<0)


def wkv_chunked_ref(r, k, v, logw, u, state0=None, chunk: int = 32):
    """Chunked WKV recurrence (fp32).

    r,k,v: (B,S,H,N); logw: (B,S,H,N) log decay; u: (H,N).
    Returns y (B,S,H,N), final state (B,H,N,N) where state[i,j] keys i vals j.
    """
    B, S, H, N = r.shape
    C = min(chunk, S)
    while S % C:
        C //= 2
    nc = S // C
    f32 = jnp.float32
    rs = r.astype(f32).reshape(B, nc, C, H, N)
    ks = k.astype(f32).reshape(B, nc, C, H, N)
    vs = v.astype(f32).reshape(B, nc, C, H, N)
    lw = logw.astype(f32).reshape(B, nc, C, H, N)

    # cumulative decay within chunk: W[t] = exp(sum_{s<=t} logw_s)
    cum = jnp.cumsum(lw, axis=2)                          # (B,nc,C,H,N)
    total = cum[:, :, -1]                                 # (B,nc,H,N)

    def chunk_step(state, inp):
        rc, kc, vc, lwc, cumc, totc = inp                 # (B,C,H,N)...
        # intra-chunk pair (s < t): decay prod_{s<m<=t-1} w_m
        #   = exp(cum_{t-1} - cum_s) = exp((cum_t - logw_t) - cum_s)
        # plus diagonal bonus u for s == t.
        q = rc * jnp.exp(cumc - lwc)                      # (B,C,H,N)
        kk = kc * jnp.exp(-cumc)
        att = jnp.einsum("bthn,bshn->bhts", q, kk)
        tri = jnp.tril(jnp.ones((C, C), bool), k=-1)
        att = att * tri[None, None]
        diag = jnp.einsum("bthn,hn,bthn->bth", rc, u.astype(f32), kc)
        y = jnp.einsum("bhts,bshn->bthn", att, vc)
        y = y + diag[..., None] * vc
        # inter-chunk: carried state decayed to t-1 within the chunk
        y = y + jnp.einsum("bthn,bhnm->bthm", q, state)
        # state update: S' = diag(exp(tot)) S + sum_t k_t exp(tot - cum_t) v_t^T
        kw = kc * jnp.exp(totc[:, None] - cumc)
        state = jnp.exp(totc)[..., None] * state + \
            jnp.einsum("bthn,bthm->bhnm", kw, vc)
        return state, y

    state = (jnp.zeros((B, H, N, N), f32) if state0 is None
             else state0.astype(f32))
    inps = tuple(a.transpose(1, 0, 2, 3, 4) for a in (rs, ks, vs, lw, cum)) \
        + (total.transpose(1, 0, 2, 3),)
    state, ys = jax.lax.scan(chunk_step, state, inps)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, S, H, N)
    return y, state


def _group_norm(x, w, b, H, eps=64e-5):
    """Per-head LayerNorm over head_dim. x: (B,S,d)."""
    B, S, d = x.shape
    xh = x.reshape(B, S, H, d // H).astype(jnp.float32)
    mu = xh.mean(-1, keepdims=True)
    var = xh.var(-1, keepdims=True)
    xh = (xh - mu) * jax.lax.rsqrt(var + eps)
    out = xh.reshape(B, S, d) * w.astype(jnp.float32) + b.astype(jnp.float32)
    return out


def rwkv_time_mix(cfg: ModelConfig, p: dict, x, *, mode: str,
                  cache: Optional[dict]):
    r = cfg.rwkv
    d = cfg.d_model
    H = d // r.head_dim
    N = r.head_dim
    B, S, _ = x.shape
    dt = x.dtype

    last = None if cache is None else cache["x_tm"]
    xprev = _token_shift(x, last) if mode != "decode" else (
        last[:, None] if last is not None else jnp.zeros_like(x))
    xw, xk, xv, xr, xg = _ddlerp(p, x, xprev)
    rr = (xr @ p["wr"].astype(dt)).reshape(B, S, H, N)
    kk = (xk @ p["wk"].astype(dt)).reshape(B, S, H, N)
    vv = (xv @ p["wv"].astype(dt)).reshape(B, S, H, N)
    g = jax.nn.silu(xg @ p["wg"].astype(dt))
    logw = _decay(p, xw).reshape(B, S, H, N)

    state0 = None if cache is None else cache["state"]
    if mode == "train":
        fn = lambda *a: wkv_chunked_ref(*a, state0)
        y, state = jax.checkpoint(fn)(rr, kk, vv, logw, p["u"])
    elif mode == "prefill":
        y, state = wkv_chunked_ref(rr, kk, vv, logw, p["u"], state0)
    else:
        st = state0 if state0 is not None else jnp.zeros((B, H, N, N),
                                                         jnp.float32)
        r1 = rr[:, 0].astype(jnp.float32)
        k1 = kk[:, 0].astype(jnp.float32)
        v1 = vv[:, 0].astype(jnp.float32)
        w1 = jnp.exp(logw[:, 0])
        y1 = jnp.einsum("bhn,bhnm->bhm", r1, st) + \
            jnp.einsum("bhn,hn,bhn,bhm->bhm", r1, p["u"].astype(jnp.float32),
                       k1, v1)
        state = w1[..., None] * st + jnp.einsum("bhn,bhm->bhnm", k1, v1)
        y = y1[:, None].reshape(B, 1, H, N)

    y = _group_norm(y.reshape(B, S, d), p["ln_x_w"], p["ln_x_b"], H)
    y = (y.astype(dt) * g) @ p["wo"].astype(dt)
    new_cache = cache
    if cache is not None:
        new_cache = {"state": state.astype(jnp.float32),
                     "x_tm": x[:, -1].astype(cache["x_tm"].dtype),
                     "x_cm": cache["x_cm"]}
    return y, new_cache


def rwkv_channel_mix(cfg: ModelConfig, p: dict, x, *, mode: str,
                     cache: Optional[dict]):
    dt = x.dtype
    last = None if cache is None else cache["x_cm"]
    xprev = _token_shift(x, last) if mode != "decode" else (
        last[:, None] if last is not None else jnp.zeros_like(x))
    sx = xprev - x
    xk = x + sx * p["mu_k"].astype(dt)
    xr = x + sx * p["mu_r"].astype(dt)
    k = jnp.square(jax.nn.relu(xk @ p["wk"].astype(dt)))
    kv = k @ p["wv"].astype(dt)
    y = jax.nn.sigmoid(xr @ p["wr"].astype(dt)) * kv
    new_cache = cache
    if cache is not None:
        new_cache = dict(cache)
        new_cache["x_cm"] = x[:, -1].astype(cache["x_cm"].dtype)
    return y, new_cache
