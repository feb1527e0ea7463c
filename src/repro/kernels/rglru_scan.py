"""RG-LRU linear-recurrence kernel for TPU (Pallas).

h_t = a_t ⊙ h_{t-1} + b_t with a_t = exp(log_a_t), carried across
time-blocks in VMEM scratch.  Grid = (batch, lru_blocks, time_blocks) with
time innermost/sequential — the recurrence never leaves VMEM, while the
(batch x lru) dimensions parallelize across cores.

The gate computation (sigmoid projections producing log_a and the gated
input b) is done in plain JAX before the kernel: it is a dense matmul XLA
already fuses well; the kernel owns only the sequential part, which is
what XLA lowers poorly (a length-S while loop with HBM round-trips).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(loga_ref, b_ref, h0_ref, o_ref, h_scr, *, block_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = h0_ref[0]                          # (1, bl)

    def step(i, h):
        # one time row at a time, read and written through the refs: a
        # dynamic index into a loaded (bt, bl) value does not lower
        row = pl.ds(i, 1)
        a = jnp.exp(loga_ref[0, row, :].astype(jnp.float32))
        h = a * h + b_ref[0, row, :].astype(jnp.float32)
        o_ref[0, row, :] = h.astype(o_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, block_t, step, h_scr[...])


def rglru_scan(log_a, b, h0=None, *, block_t: int = 128, block_l: int = 256,
               interpret: bool = False):
    """log_a, b: (B,S,L) fp32; h0: (B,L) or None -> h (B,S,L) fp32."""
    B, S, L = log_a.shape
    if h0 is None:
        h0 = jnp.zeros((B, L), jnp.float32)
    bt = min(block_t, S)
    bl = min(block_l, L)
    nt = -(-S // bt)
    nl = -(-L // bl)
    pt, plx = nt * bt - S, nl * bl - L
    if pt or plx:
        # pad time with a=1,b=0 (identity steps); pad lru with zeros
        log_a = jnp.pad(log_a, ((0, 0), (0, pt), (0, plx)))
        b = jnp.pad(b, ((0, 0), (0, pt), (0, plx)))
        h0 = jnp.pad(h0, ((0, 0), (0, plx)))
    # h0 rides as (B, 1, L): a (1, bl) block of a (B, L) array breaks the
    # TPU's (8, 128) tiling rule, a (1, 1, bl) block of (B, 1, L) does not
    h0 = h0.reshape(B, 1, nl * bl)

    kernel = functools.partial(_rglru_kernel, block_t=bt)
    out = pl.pallas_call(
        kernel,
        grid=(B, nl, nt),
        in_specs=[
            pl.BlockSpec((1, bt, bl), lambda bi, li, ti: (bi, ti, li)),
            pl.BlockSpec((1, bt, bl), lambda bi, li, ti: (bi, ti, li)),
            pl.BlockSpec((1, 1, bl), lambda bi, li, ti: (bi, 0, li)),
        ],
        out_specs=pl.BlockSpec((1, bt, bl), lambda bi, li, ti: (bi, ti, li)),
        out_shape=jax.ShapeDtypeStruct((B, nt * bt, nl * bl), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bl), jnp.float32)],
        interpret=interpret,
    )(log_a, b, h0)
    return out[:, :S, :L]
