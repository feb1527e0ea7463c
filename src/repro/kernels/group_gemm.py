"""Grouped per-expert GEMM kernel for TPU (Pallas), MegaBlocks-style
simplified for the capacity-bucketed MoE dispatch.

x: (E, C, D) tokens bucketed per expert, w: (E, D, F) expert weights,
n_valid: (E,) number of real rows per expert.  Blocks whose rows are
entirely padding are *skipped at the grid level* (no MXU work) — with
load imbalance this saves (1 - load/capacity) of the compute, which is
the dropless-MoE insight mapped onto static TPU grids.

Grid = (E, C/bc, F/bf), D contracted in full per block (expert D is the
small fine-grained-expert dim).  n_valid is scalar-prefetched whole into
SMEM, so each grid step reads its expert's count there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gg_kernel(n_ref, x_ref, w_ref, o_ref, *, block_c: int):
    e = pl.program_id(0)
    ci = pl.program_id(1)
    n = n_ref[e]
    row0 = ci * block_c

    @pl.when(row0 < n)
    def _compute():
        x = x_ref[0].astype(jnp.float32)          # (bc, D)
        w = w_ref[0].astype(jnp.float32)          # (D, bf)
        acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        acc = jnp.where(rows < n, acc, 0.0)
        o_ref[0] = acc.astype(o_ref.dtype)

    @pl.when(row0 >= n)
    def _skip():
        o_ref[0] = jnp.zeros_like(o_ref[0])


def group_gemm(x, w, n_valid, *, block_c: int = 128, block_f: int = 128,
               interpret: bool = False):
    """x: (E,C,D) @ w: (E,D,F) with per-expert valid counts -> (E,C,F)."""
    E, C, D = x.shape
    F = w.shape[2]
    bc = min(block_c, C)
    bf = min(block_f, F)
    nc = -(-C // bc)
    nf = -(-F // bf)
    pc, pf = nc * bc - C, nf * bf - F
    if pc:
        x = jnp.pad(x, ((0, 0), (0, pc), (0, 0)))
    if pf:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, pf)))

    kernel = functools.partial(_gg_kernel, block_c=bc)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(E, nc, nf),
            in_specs=[
                pl.BlockSpec((1, bc, D), lambda e, ci, fi, n: (e, ci, 0)),
                pl.BlockSpec((1, D, bf), lambda e, ci, fi, n: (e, 0, fi)),
            ],
            out_specs=pl.BlockSpec((1, bc, bf),
                                   lambda e, ci, fi, n: (e, ci, fi)),
        ),
        out_shape=jax.ShapeDtypeStruct((E, nc * bc, nf * bf), x.dtype),
        interpret=interpret,
    )(n_valid.astype(jnp.int32), x, w)
    return out[:, :C, :F]
