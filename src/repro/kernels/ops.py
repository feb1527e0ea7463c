"""Jit'd public wrappers for the Pallas kernels.

The kernels compile to Mosaic unless the caller passes ``interpret=True``,
which runs them through the Pallas interpreter (CPU validation).  Nothing
here looks at the backend: a kernel asked to compile where no TPU is
present fails rather than quietly interpreting.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.group_gemm import group_gemm as _group_gemm
from repro.kernels.rglru_scan import rglru_scan as _rglru
from repro.kernels.rwkv_wkv import wkv as _wkv


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=128, block_k=128, interpret=False):
    """q: (B,H,Sq,D), k/v: (B,K,Sk,D) -> (B,H,Sq,D)."""
    return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                  block_q=block_q, block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_t", "block_l",
                                             "interpret"))
def rglru_scan(log_a, b, h0=None, *, block_t=128, block_l=256,
               interpret=False):
    """h_t = exp(log_a_t) * h_{t-1} + b_t over axis 1."""
    return _rglru(log_a, b, h0, block_t=block_t, block_l=block_l,
                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv(r, k, v, logw, u, state0=None, *, chunk=32, interpret=False):
    """RWKV-6 WKV. Returns (y, final_state)."""
    return _wkv(r, k, v, logw, u, state0, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f",
                                             "interpret"))
def group_gemm(x, w, n_valid, *, block_c=128, block_f=128, interpret=False):
    """Per-expert GEMM with padding-block skip."""
    return _group_gemm(x, w, n_valid, block_c=block_c, block_f=block_f,
                       interpret=interpret)
