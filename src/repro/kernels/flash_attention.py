"""Flash attention for TPU (Pallas): causal + sliding-window + GQA + softcap.

TPU-native design (not a CUDA port):

* grid = (batch*q_heads, q_blocks, kv_blocks); the kv dimension is the
  innermost, *sequential* ("arbitrary") grid axis so the fp32 accumulators
  live in VMEM scratch across kv steps — the TPU analogue of a CUDA
  persistent-CTA inner loop.
* BlockSpec tiles are MXU-aligned: (block_q x head_dim) Q tiles against
  (block_k x head_dim) K/V tiles (head_dim multiples of 128 on real TPUs).
* causal / sliding-window block skipping happens at the *grid* level via
  ``pl.when`` — skipped blocks issue no DMA and no MXU work, so banded
  attention costs O(S·W) not O(S²).
* GQA: the K/V BlockSpec index map folds q-head -> kv-head (h // group).
* cross-length (decode/suffix) alignment via ``q_offset = Sk - Sq``.

Validated in interpret mode on CPU against ``ref.attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, window: int, softcap: float,
                block_q: int, block_k: int, seq_k: int, num_kb: int,
                q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_first = qi * block_q + q_offset     # global key-frame position
    q_last = q_first + block_q - 1
    k_first = ki * block_k
    k_last = k_first + block_k - 1

    live = jnp.asarray(True)
    if causal:
        live &= k_first <= q_last
    if window:
        live &= k_last > q_first - window

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        qp = q_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kp = k_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kp < seq_k
        if causal:
            mask &= kp <= qp
        if window:
            mask &= kp > qp - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None]) * mask
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        m_scr[...] = m_new
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv

    @pl.when(ki == num_kb - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale=None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B,H,Sq,D), k/v: (B,K,Sk,D). Returns (B,H,Sq,D).

    When Sq != Sk the queries are suffix-aligned (query i sits at key
    position Sk - Sq + i) — the decode/chunked-prefill convention.
    """
    B, H, Sq, D = q.shape
    K, Sk = k.shape[1], k.shape[2]
    G = H // K
    scale = D ** -0.5 if scale is None else scale
    q_offset = Sk - Sq

    bq = min(block_q, max(Sq, 8))
    bk = min(block_k, max(Sk, 8))
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    qpad, kpad = nq * bq - Sq, nk * bk - Sk
    if qpad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, qpad), (0, 0)))
    if kpad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, kpad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, kpad), (0, 0)))

    qf = q.reshape(B * H, nq * bq, D)
    kf = k.reshape(B * K, nk * bk, D)
    vf = v.reshape(B * K, nk * bk, D)

    def kv_index(h, qi, ki):
        return ((h // H) * K + (h % H) // G, ki, 0)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=bq, block_k=bk, seq_k=Sk, num_kb=nk,
        q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, bk, D), kv_index),
            pl.BlockSpec((1, bk, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, qi, ki: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, nq * bq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(B, H, nq * bq, D)
    return out[:, :, :Sq]
