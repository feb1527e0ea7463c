"""Model FLOPs of one training step of a dense decoder.

Counts what the model requires, not what the program computes: every
matrix product of the forward pass at 2 FLOPs per multiply-add (the
output head included, the embedding gather not, since a gather does no
arithmetic), causal attention scores and their weighted sum at the
average causal length ``seq / 2``, and the backward pass as twice the
forward.  Recomputation under remat is not counted.  This is the
6 x N x tokens rule of the program's ``roofline.analysis.model_flops_for``
with N restricted to the parameters that enter a matrix product, plus
attention.
"""
from __future__ import annotations


def matmul_params(model: dict) -> int:
    """Parameters that enter a matrix product in one forward pass."""
    d, f = model["d_model"], model["d_ff"]
    q = model["num_heads"] * model["head_dim"]
    kv = model["num_kv_heads"] * model["head_dim"]
    gated = model["mlp"] in ("swiglu", "geglu")
    per_layer = d * q + 2 * d * kv + q * d + (3 if gated else 2) * d * f
    return model["num_layers"] * per_layer + d * model["vocab_size"]


def train_step_flops(model: dict, batch: int, seq: int) -> float:
    """Forward plus backward FLOPs of one step over ``batch`` x ``seq``."""
    attn = (model["num_layers"] * 2 * seq
            * model["num_heads"] * model["head_dim"])
    forward_per_token = 2 * matmul_params(model) + attn
    return 3.0 * forward_per_token * batch * seq
