"""Kernel parity benchmarks in interpret mode — wall time measures the
Pallas interpreter, so the *derived* column reports the parity error vs
the jnp oracle, which is the meaningful signal here.  No device time is
measured: ``tests/test_tpu_compile.py`` compiles the kernels for a TPU."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

SEED = 0


def _timeit(f, *args, reps=3):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else \
        f(*args).block_until_ready()
    t0 = time.time()
    for _ in range(reps):
        out = f(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.time() - t0) / reps


def bench_flash(csv=False):
    B, H, K, S, D = 1, 4, 2, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, K, S, D))
    v = jax.random.normal(ks[2], (B, K, S, D))
    us = _timeit(lambda a, b, c: ops.flash_attention(a, b, c,
                                                     interpret=True),
                 q, k, v) * 1e6
    err = float(jnp.max(jnp.abs(ops.flash_attention(q, k, v, interpret=True) -
                                ref.attention_ref(q, k, v))))
    flops = 4 * B * H * S * S * D / 2  # causal
    if not csv:
        print(f"flash_attention S={S}: {us:.0f}us  max_err={err:.2e}")
    return [("kernel_flash_attn_512", us, err)]


def bench_rglru(csv=False):
    B, S, L = 2, 512, 256
    ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
    log_a = -jnp.exp(jax.random.normal(ks[0], (B, S, L)) * 0.5 - 2)
    b = jax.random.normal(ks[1], (B, S, L))
    h0 = jax.random.normal(ks[2], (B, L))
    us = _timeit(lambda *a: ops.rglru_scan(*a, interpret=True),
                 log_a, b, h0) * 1e6
    err = float(jnp.max(jnp.abs(ops.rglru_scan(log_a, b, h0, interpret=True) -
                                ref.rglru_ref(log_a, b, h0))))
    if not csv:
        print(f"rglru_scan S={S} L={L}: {us:.0f}us  max_err={err:.2e}")
    return [("kernel_rglru_512", us, err)]


def bench_wkv(csv=False):
    B, S, H, N = 1, 256, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(SEED), 5)
    r = jax.random.normal(ks[0], (B, S, H, N)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, N)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, N)) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, N)) * 0.5 - 1.5)
    u = jax.random.normal(ks[4], (H, N)) * 0.5
    us = _timeit(lambda *a: ops.wkv(*a, interpret=True),
                 r, k, v, logw, u) * 1e6
    y, _ = ops.wkv(r, k, v, logw, u, interpret=True)
    yr, _ = ref.wkv_ref(r, k, v, logw, u)
    err = float(jnp.max(jnp.abs(y - yr)))
    if not csv:
        print(f"wkv S={S} H={H} N={N}: {us:.0f}us  max_err={err:.2e}")
    return [("kernel_wkv_256", us, err)]


def bench_group_gemm(csv=False):
    E, C, D, F = 8, 256, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
    x = jax.random.normal(ks[0], (E, C, D))
    w = jax.random.normal(ks[1], (E, D, F))
    n = jax.random.randint(ks[2], (E,), 0, C + 1)
    us = _timeit(lambda *a: ops.group_gemm(*a, interpret=True),
                 x, w, n) * 1e6
    err = float(jnp.max(jnp.abs(ops.group_gemm(x, w, n, interpret=True) -
                                ref.group_gemm_ref(x, w, n))))
    if not csv:
        print(f"group_gemm E={E} C={C}: {us:.0f}us  max_err={err:.2e}")
    return [("kernel_group_gemm", us, err)]


def main(csv: bool = False):
    return (bench_flash(csv) + bench_rglru(csv) + bench_wkv(csv)
            + bench_group_gemm(csv))


if __name__ == "__main__":
    main()
