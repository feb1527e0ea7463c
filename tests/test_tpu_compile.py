"""Each Pallas kernel compiles for a TPU v5e chip at real model widths.

The chip is described, not attached: ``get_topology_desc`` gives devices
the TPU compiler targets, and nothing runs.  Interpret-mode tests cannot
see what this catches: block shapes that break the (8, 128) tiling rule,
primitives Mosaic does not lower, and kernels that do not fit the chip.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("window", [0, 512])
def test_flash_attention_compiles_at_gemma3_1b_widths(one_chip, window):
    cfg = get_config("gemma3-1b")
    H, K, D, S = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 4096
    assert (H, K, D) == (4, 1, 256) and cfg.window_size == 512
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, window=window),
             one_chip, ((1, H, S, D), jnp.bfloat16),
             ((1, K, S, D), jnp.bfloat16), ((1, K, S, D), jnp.bfloat16))


def test_rglru_scan_compiles_at_recurrentgemma_2b_width(one_chip):
    L = get_config("recurrentgemma-2b").rglru.lru_width
    assert L == 2560
    B, S = 2, 4096
    _compile(lambda a, b, h: ops.rglru_scan(a, b, h), one_chip,
             ((B, S, L), jnp.float32), ((B, S, L), jnp.float32),
             ((B, L), jnp.float32))


def test_wkv_compiles_at_rwkv6_3b_heads(one_chip):
    cfg = get_config("rwkv6-3b")
    N = cfg.rwkv.head_dim
    H = cfg.d_model // N
    assert (H, N) == (40, 64)
    x = ((1, 4096, H, N), jnp.bfloat16)
    _compile(lambda r, k, v, w, u: ops.wkv(r, k, v, w, u), one_chip,
             x, x, x, x, ((H, N), jnp.float32))


def test_group_gemm_compiles_at_deepseek_v2_expert_widths(one_chip):
    cfg = get_config("deepseek-v2-236b")
    E, D, F = cfg.moe.num_experts, cfg.d_model, cfg.moe.d_ff_expert
    assert (E, D, F) == (160, 5120, 1536)
    C = 512
    _compile(lambda x, w, n: ops.group_gemm(x, w, n), one_chip,
             ((E, C, D), jnp.bfloat16), ((E, D, F), jnp.bfloat16),
             ((E,), jnp.int32))
