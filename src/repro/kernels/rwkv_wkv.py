"""RWKV-6 WKV kernel for TPU (Pallas), chunked formulation.

Per (batch, head) grid cell the (N x N) recurrent state stays resident in
VMEM scratch for the whole sequence; each time-chunk is processed with
MXU matmuls (the chunked GLA trick):

    within-chunk:   att[t,s] = Σ_i r_t[i] k_s[i] exp(cum_{t-1}-cum_s), s<t
    diagonal bonus: u
    cross-chunk:    y += (r ⊙ exp(cum - logw)) @ S
    state update:   S <- exp(tot) ⊙ S + (k ⊙ exp(tot - cum))^T V

Grid = (B*H, time_chunks), time sequential.  Returns y and final state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, sout_ref,
                s_scr, *, num_t: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    f32 = jnp.float32
    r = r_ref[0].astype(f32)            # (C,N)
    k = k_ref[0].astype(f32)
    v = v_ref[0].astype(f32)
    lw = lw_ref[0].astype(f32)
    u = u_ref[0].astype(f32)            # (1,N)

    def mm(a, b, dims, precision=None):
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   precision=precision,
                                   preferred_element_type=f32)

    C = lw.shape[0]
    ti_i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    si_i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # prefix sums over time as matmuls (Mosaic has no cumsum): the
    # inclusive one by a lower-triangular ones matrix, the chunk total
    # as a column by contracting time against ones.  HIGHEST keeps them
    # exact sums of f32 decays, which the exponentials below amplify
    exact = jax.lax.Precision.HIGHEST
    cum = mm((si_i <= ti_i).astype(f32), lw, ((1,), (0,)), exact)  # (C,N)
    tot = cum[C - 1:]                                               # (1,N)
    tot_col = mm(lw, jnp.ones((C, 1), f32), ((0,), (0,)), exact)   # (N,1)
    q = r * jnp.exp(cum - lw)
    kk = k * jnp.exp(-cum)
    att = mm(q, kk, ((1,), (1,)))                            # (C,C)
    att = jnp.where(si_i < ti_i, att, 0.0)
    y = mm(att, v, ((1,), (0,)))
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)         # (C,1)
    y = y + diag * v
    y = y + mm(q, s_scr[...], ((1,), (0,)))
    y_ref[0] = y.astype(y_ref.dtype)

    kw = k * jnp.exp(tot - cum)
    s_scr[...] = jnp.exp(tot_col) * s_scr[...] + mm(kw, v, ((0,), (0,)))

    @pl.when(ti == num_t - 1)
    def _finish():
        sout_ref[0] = s_scr[...].astype(sout_ref.dtype)


def wkv(r, k, v, logw, u, state0=None, *, chunk: int = 32,
        interpret: bool = False):
    """r,k,v,logw: (B,S,H,N); u: (H,N); state0: (B,H,N,N) or None.

    Returns (y (B,S,H,N), state (B,H,N,N)).  S is padded to a chunk
    multiple with identity steps (logw=0, k=0, r=0).
    """
    B, S, H, N = r.shape
    C = min(chunk, S)
    nt = -(-S // C)
    pad = nt * C - S

    def prep(x, fill=0.0):
        x = x.transpose(0, 2, 1, 3).reshape(B * H, S, N)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)),
                        constant_values=fill)
        return x

    rf, kf, vf = prep(r), prep(k), prep(v)
    lwf = prep(logw)
    # u rides as (B*H, 1, N): a (1, N) block of a (B*H, N) array breaks
    # the TPU's (8, 128) tiling rule, a (1, 1, N) block does not
    uf = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, 1, N)
    s0 = (jnp.zeros((B * H, N, N), jnp.float32) if state0 is None
          else state0.reshape(B * H, N, N))

    kernel = functools.partial(_wkv_kernel, num_t=nt)
    y, sout = pl.pallas_call(
        kernel,
        grid=(B * H, nt),
        in_specs=[
            pl.BlockSpec((1, C, N), lambda h, ti: (h, ti, 0)),
            pl.BlockSpec((1, C, N), lambda h, ti: (h, ti, 0)),
            pl.BlockSpec((1, C, N), lambda h, ti: (h, ti, 0)),
            pl.BlockSpec((1, C, N), lambda h, ti: (h, ti, 0)),
            pl.BlockSpec((1, 1, N), lambda h, ti: (h, 0, 0)),
            pl.BlockSpec((1, N, N), lambda h, ti: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, N), lambda h, ti: (h, ti, 0)),
            pl.BlockSpec((1, N, N), lambda h, ti: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, nt * C, N), r.dtype),
            jax.ShapeDtypeStruct((B * H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, lwf, uf, s0)
    y = y[:, :S].reshape(B, H, S, N).transpose(0, 2, 1, 3)
    return y, sout.reshape(B, H, N, N)
