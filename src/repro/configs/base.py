"""Model / experiment configuration dataclasses.

Every assigned architecture is expressed as a ``ModelConfig``.  Configs are
pure data (no jax import) so they can be loaded by the scheduler, the
launcher, and the dry-run without touching device state.

Layer stacking
--------------
``layer_pattern`` is the repeating *period* of layer kinds, e.g.
``("local", "local", "local", "local", "local", "full")`` for gemma3's
5:1 local:global mix, or ``("rglru", "rglru", "local")`` for
recurrentgemma.  The stack is laid out as::

    [prologue layers] + [n_periods x layer_pattern (lax.scan)] + [epilogue]

``prologue_layers`` pins the leading layers outside the scan (used by the
MoE archs whose first layer(s) use a dense FFN).  The epilogue holds the
remainder when ``num_layers`` is not a multiple of the period.
"""
from __future__ import annotations

import dataclasses
from collections.abc import Mapping
from dataclasses import dataclass, field
from typing import Optional, Tuple

LayerKind = str  # "full" | "local" | "rglru" | "rwkv"


@dataclass(frozen=True)
class MoECfg:
    """Mixture-of-experts FFN configuration (DeepSeek-style shared+routed).

    ``num_experts`` is the router's width.  A chip of an expert-parallel
    deployment may hold a share of them: ``held`` experts from index
    ``held_from`` (0 holds all ``num_experts``).  The layer then routes
    over all of them and computes only its held experts' part."""

    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared: int = 0
    d_ff_dense: int = 0          # FFN width of the leading dense layers
    first_k_dense: int = 0       # how many leading layers use a dense FFN
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    aux_loss_weight: float = 1e-3
    router_dtype: str = "float32"
    norm_topk: bool = True       # renormalise the top-k gates to sum to 1
    held: int = 0                # experts whose weights exist here; 0 -> all
    held_from: int = 0           # index of the first held expert

    @property
    def d_ff_shared(self) -> int:
        return self.num_shared * self.d_ff_expert

    @property
    def n_held(self) -> int:
        return self.held or self.num_experts


@dataclass(frozen=True)
class MLACfg:
    """Multi-head latent attention (DeepSeek-V2).  ``q_lora_rank`` 0 or
    None projects the queries directly, without compression."""

    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128


@dataclass(frozen=True)
class RGLRUCfg:
    """Real-Gated Linear Recurrent Unit block (Griffin / RecurrentGemma)."""

    lru_width: int = 0           # 0 -> same as d_model
    conv_width: int = 4
    num_blocks: int = 0          # block-diagonal gate heads; 0 -> num_heads
    c_exponent: float = 8.0      # the fixed "c" scaling exponent from Griffin


@dataclass(frozen=True)
class RWKVCfg:
    """RWKV-6 (Finch) time-mix / channel-mix configuration."""

    head_dim: int = 64
    decay_lora: int = 64
    mix_lora: int = 32


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense|moe|hybrid|ssm|audio|vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    layer_pattern: Tuple[LayerKind, ...] = ("full",)
    prologue_layers: int = 0

    # attention
    window_size: int = 0              # sliding window for "local" layers
    rope_theta: float = 10_000.0
    rope_theta_global: Optional[float] = None   # distinct theta on "full" layers
    rope_fraction: float = 1.0        # partial rotary (stablelm: 0.25)
    qk_norm: bool = False
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    sandwich_norm: bool = False       # gemma-style post-block norms

    # mlp
    mlp: str = "swiglu"               # swiglu|geglu|gelu|sq_relu
    # embeddings
    tie_embeddings: bool = True
    input_kind: str = "tokens"        # tokens | embeddings (audio/vlm stub frontends)
    embed_scale: bool = False         # gemma multiplies embeddings by sqrt(d)

    # sub-architectures
    moe: Optional[MoECfg] = None
    mla: Optional[MLACfg] = None
    rglru: Optional[RGLRUCfg] = None
    rwkv: Optional[RWKVCfg] = None

    norm_eps: float = 1e-6

    # systems knobs
    dtype: str = "bfloat16"           # activation dtype
    param_dtype: str = "float32"      # master parameter dtype
    remat: str = "none"               # none | dots | full
    scan_layers: bool = True
    attn_impl: str = "blockwise"      # reference | blockwise
    moe_impl: str = "ep"              # dense | ep | ep_a2a
    # perf-loop knobs (EXPERIMENTS.md §Perf)
    seq_shard: bool = False           # context parallelism: seq over "model"
    cast_params_bf16: bool = False    # cast f32 masters to bf16 pre-forward
    chunked_ce: bool = False          # never materialize full (B,S,V) logits

    def __post_init__(self):
        # a configuration read from JSON gives its sub-configurations as
        # mappings and its pattern as a list: make it the same, hashable
        # value as one built in code
        for name, cls in (("moe", MoECfg), ("mla", MLACfg),
                          ("rglru", RGLRUCfg), ("rwkv", RWKVCfg)):
            sub = getattr(self, name)
            if isinstance(sub, Mapping):
                object.__setattr__(self, name, cls(**sub))
        object.__setattr__(self, "layer_pattern", tuple(self.layer_pattern))

    # ------------------------------------------------------------------
    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @property
    def period(self) -> int:
        return len(self.layer_pattern)

    def stack_plan(self) -> tuple[tuple[LayerKind, ...], int, tuple[LayerKind, ...]]:
        """Return (prologue_kinds, n_periods, epilogue_kinds)."""
        body = self.num_layers - self.prologue_layers
        n_periods = body // self.period if self.scan_layers else 0
        pro = tuple(self.expanded_kinds()[: self.prologue_layers])
        epi_len = body - n_periods * self.period
        epi = self.layer_pattern[:epi_len] if epi_len else ()
        if not self.scan_layers:
            # everything unrolled: prologue covers all layers
            return tuple(self.expanded_kinds()), 0, ()
        return pro, n_periods, epi

    def expanded_kinds(self) -> Tuple[LayerKind, ...]:
        """Per-layer kinds for the full stack (pattern tiled)."""
        kinds = []
        for i in range(self.num_layers):
            if i < self.prologue_layers:
                kinds.append(self.layer_pattern[i % self.period])
            else:
                kinds.append(self.layer_pattern[(i - self.prologue_layers) % self.period])
        return tuple(kinds)

    def layer_uses_moe(self, layer_idx: int) -> bool:
        return self.moe is not None and layer_idx >= self.moe.first_k_dense

    # -- parameter counting (analytic; used by the economy scheduler) ----
    def param_count(self) -> int:
        d, H, K, hd, f, V = (self.d_model, self.num_heads, self.num_kv_heads,
                             self.head_dim, self.d_ff, self.vocab_size)
        total = V * d                      # embedding
        if not self.tie_embeddings:
            total += V * d
        counts = {k: 0 for k in ("full", "local", "rglru", "rwkv")}
        for k in self.expanded_kinds():
            counts[k] += 1
        n_attn = counts["full"] + counts["local"]

        if self.mla is not None:
            m = self.mla
            qk = m.qk_nope_dim + m.qk_rope_dim
            q_p = (d * m.q_lora_rank + m.q_lora_rank * H * qk if m.q_lora_rank
                   else d * H * qk)
            attn_p = (q_p + d * (m.kv_lora_rank + m.qk_rope_dim)
                      + m.kv_lora_rank * H * (m.qk_nope_dim + m.v_dim)
                      + H * m.v_dim * d)
        else:
            attn_p = d * H * hd + 2 * d * K * hd + H * hd * d
        total += n_attn * attn_p

        # mlp per layer
        gated = self.mlp in ("swiglu", "geglu")
        dense_mlp = (3 if gated else 2) * d * f
        if self.moe is None:
            total += self.num_layers * dense_mlp
        else:
            mo = self.moe
            fd = mo.d_ff_dense or f
            dense_p = (3 if gated else 2) * d * fd
            exp_p = 3 * d * mo.d_ff_expert            # gate/up/down per expert
            shared_p = 3 * d * mo.d_ff_shared if mo.num_shared else 0
            router_p = d * mo.num_experts
            n_moe = self.num_layers - mo.first_k_dense
            total += mo.first_k_dense * dense_p
            total += n_moe * (mo.n_held * exp_p + shared_p + router_p)

        if self.rglru is not None:
            g = self.rglru
            lw = g.lru_width or d
            nb = g.num_blocks or self.num_heads
            blk = 2 * nb * (lw // nb) ** 2            # block-diag input & rec gates
            rg_p = 2 * d * lw + g.conv_width * lw + lw + blk + lw * d
            total += counts["rglru"] * rg_p           # MLP counted above
        if self.rwkv is not None:
            r = self.rwkv
            tm = 4 * d * d + d * r.decay_lora + r.decay_lora * d + 6 * d \
                + 5 * (d * r.mix_lora + r.mix_lora * d) + d * d  # r,k,v,g,out + w-lora + mus + ddlerp loras
            cm_extra = d * d                          # channel-mix receptance
            total += counts["rwkv"] * (tm + cm_extra)  # 2*d*f counted above
        return int(total)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k + shared only, at most
        the held experts)."""
        if self.moe is None:
            return self.param_count()
        mo = self.moe
        full = self.param_count()
        exp_p = 3 * self.d_model * mo.d_ff_expert
        n_moe = self.num_layers - mo.first_k_dense
        inactive = n_moe * max(mo.n_held - mo.top_k, 0) * exp_p
        return int(full - inactive)


@dataclass(frozen=True)
class ShapeCfg:
    """One assigned input-shape cell."""

    name: str
    kind: str            # train | prefill | decode
    seq_len: int
    global_batch: int
    # decode shapes: seq_len is the KV-cache length, one new token generated

    @property
    def tokens_per_step(self) -> int:
        if self.kind == "decode":
            return self.global_batch
        return self.seq_len * self.global_batch


TRAIN_4K = ShapeCfg("train_4k", "train", 4096, 256)
PREFILL_32K = ShapeCfg("prefill_32k", "prefill", 32768, 32)
DECODE_32K = ShapeCfg("decode_32k", "decode", 32768, 128)
LONG_500K = ShapeCfg("long_500k", "decode", 524288, 1)

SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}

# Archs allowed to run long_500k (sub-quadratic / hybrid attention only --
# see DESIGN.md §4).  Pure full-attention archs skip it.
LONG_CONTEXT_OK = frozenset(
    {"recurrentgemma-2b", "rwkv6-3b", "gemma3-1b", "gemma3-27b"}
)


def shape_applicable(arch_name: str, shape: ShapeCfg) -> bool:
    if shape.name == "long_500k":
        return arch_name in LONG_CONTEXT_OK
    return True
