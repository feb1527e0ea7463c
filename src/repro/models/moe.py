"""Mixture-of-Experts FFN (DeepSeek-style shared + fine-grained routed).

Two implementations:

* ``dense`` — every expert computed for every token, combined with the
  top-k mask.  O(E) FLOPs; the numerical oracle for tests.
* ``ep``    — expert-parallel: experts sharded over the ``model`` mesh
  axis, expert weights FSDP-sharded over ``data`` (gathered on use),
  sort-based capacity dispatch per shard, partial outputs psum-combined
  over ``model``.  Tokens never cross data shards (no all-to-all): each
  model shard holds a replica of the activations (standard TP layout) and
  computes the (token, expert) pairs whose expert lives locally — total
  work across the model axis is exactly top_k GEMM pairs per token.

The ``ep`` path runs inside ``jax.shard_map`` (full-manual over the mesh)
and is differentiable; gradients of the FSDP all-gather transpose to
reduce-scatters automatically.

A configuration may hold a share of the routed experts (``MoECfg.held``
from ``held_from``), as one chip of a wider expert-parallel deployment
does: the router keeps its full width, and the layer computes only the
held experts' part of the result.  What the absent experts would add is
left out; nothing stands in for the chips that hold them.

Each path also returns the step's routing counts (``COUNTS``):
(token, expert) pairs routed to held experts, pairs kept under the
capacity, pairs dropped, and the largest held expert's load.  The
``ep`` path names its phases ``moe.route``, ``moe.dispatch``,
``moe.experts`` and ``moe.combine`` (``jax.named_scope``), so that a
profile's op metadata can tell them apart.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec
from repro.models.mlp import mlp_specs, mlp_apply


COUNTS = ("moe_routed", "moe_kept", "moe_dropped", "moe_max_load")
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def zero_counts() -> dict:
    return {k: jnp.zeros((), jnp.int32) for k in COUNTS}


def _counts(routed, kept, max_load) -> dict:
    return {"moe_routed": routed, "moe_kept": kept,
            "moe_dropped": routed - kept, "moe_max_load": max_load}


def expert_leaves(tree) -> list:
    """The routed experts' weights of every MoE layer of a model's
    parameter tree, or of its gradients."""
    def key(entry):
        return getattr(entry, "key", None)
    return [x for path, x in jax.tree_util.tree_leaves_with_path(tree)
            if len(path) >= 2 and key(path[-2]) == "moe"
            and key(path[-1]) in EXPERT_WEIGHTS]


def moe_specs(cfg: ModelConfig) -> dict:
    """The router spans all ``num_experts``; expert weights exist for the
    held experts only."""
    mo = cfg.moe
    d, f = cfg.d_model, mo.d_ff_expert
    E, held = mo.num_experts, mo.n_held
    if cfg.moe_impl == "ep_a2a":
        # token-routed layout: experts over "data", expert-FFN over "model"
        sp = {
            "router": ParamSpec((d, E), (None, None), dtype="float32"),
            "w_gate": ParamSpec((held, d, f),
                                ("experts_dp", None, "expert_tp"),
                                fan_dims=(1,)),
            "w_up": ParamSpec((held, d, f), ("experts_dp", None, "expert_tp"),
                              fan_dims=(1,)),
            "w_down": ParamSpec((held, f, d),
                                ("experts_dp", "expert_tp", None),
                                fan_dims=(1,)),
        }
    else:
        # weight-gathered layout: experts over "model", FSDP-d over "data"
        sp = {
            "router": ParamSpec((d, E), (None, None), dtype="float32"),
            "w_gate": ParamSpec((held, d, f), ("experts", "embed", None),
                                fan_dims=(1,)),
            "w_up": ParamSpec((held, d, f), ("experts", "embed", None),
                              fan_dims=(1,)),
            "w_down": ParamSpec((held, f, d), ("experts", None, "embed"),
                                fan_dims=(1,)),
        }
    if mo.num_shared:
        sp["shared"] = mlp_specs(cfg, d_ff=mo.d_ff_shared)
    return sp


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def router_topk(cfg: ModelConfig, router_w, x):
    """x: (T, d) -> (probs (T,k) f32, ids (T,k) i32, logits (T,E) f32).
    The gates are the top-k softmax scores, renormalised to sum to 1
    unless ``norm_topk`` is off (DeepSeek-V2: the raw scores)."""
    mo = cfg.moe
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    scores = jax.nn.softmax(logits, axis=-1)
    probs, ids = jax.lax.top_k(scores, mo.top_k)
    if mo.norm_topk:
        probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)
    return probs, ids, logits


def aux_load_balance_loss(cfg: ModelConfig, logits, ids):
    """Switch-style load-balance loss over *local* tokens (caller averages)."""
    mo = cfg.moe
    E = mo.num_experts
    scores = jax.nn.softmax(logits, axis=-1)            # (T,E)
    pe = scores.mean(axis=0)                            # mean router prob
    assign = jax.nn.one_hot(ids, E, dtype=jnp.float32).sum(axis=1)  # (T,E)
    fe = assign.mean(axis=0) / mo.top_k                 # fraction routed
    return E * jnp.sum(fe * pe)


# ---------------------------------------------------------------------------
# dense oracle
# ---------------------------------------------------------------------------

def moe_dense(cfg: ModelConfig, p: dict, x):
    """x: (B,S,d). Returns (y, aux_loss, routing counts); nothing is
    dropped."""
    mo = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    probs, ids, logits = router_topk(cfg, p["router"], xt)
    # (T,k,held): a pair whose expert is not held here is all zeros
    sel = jax.nn.one_hot(ids - mo.held_from, mo.n_held, dtype=probs.dtype)
    w = (sel * probs[..., None]).sum(axis=1)                    # (T,held)
    dt = x.dtype
    h_g = jnp.einsum("td,edf->tef", xt, p["w_gate"].astype(dt))
    h_u = jnp.einsum("td,edf->tef", xt, p["w_up"].astype(dt))
    h = jax.nn.silu(h_g) * h_u
    y_e = jnp.einsum("tef,efd->ted", h, p["w_down"].astype(dt))
    y = jnp.einsum("ted,te->td", y_e, w.astype(dt))
    aux = aux_load_balance_loss(cfg, logits, ids)
    y = y.reshape(B, S, d)
    if mo.num_shared:
        y = y + mlp_apply(cfg.replace(mlp="swiglu"), p["shared"], x)
    loads = sel.sum(axis=(0, 1)).astype(jnp.int32)
    routed = jnp.sum(loads)
    return y, aux, _counts(routed, routed, jnp.max(loads))


# ---------------------------------------------------------------------------
# expert-parallel path
# ---------------------------------------------------------------------------

def _ep_local(cfg: ModelConfig, capacity: int, n_model: int, batch_axes,
              n_batch: int, xt, router_w, w_gate, w_up, w_down):
    """Per-device body. xt: (T_loc, d) replicated over 'model';
    w_*: (E_loc, d/Dd, f) sharded over ('model','data'): this shard holds
    the E_loc experts from ``held_from + shard * E_loc``.  A pair is kept
    when its expert is held here and fewer than ``capacity`` earlier
    tokens (in token order) went to that expert."""
    mo = cfg.moe
    k = mo.top_k
    e_loc = mo.n_held // n_model
    shard = jax.lax.axis_index("model")
    dt = xt.dtype
    T = xt.shape[0]

    with jax.named_scope("moe.route"):
        probs, ids, logits = router_topk(cfg, router_w, xt)
        aux = aux_load_balance_loss(cfg, logits, ids)

    with jax.named_scope("moe.dispatch"):
        flat_ids = ids.reshape(-1)                          # (T*k,)
        flat_w = probs.reshape(-1)
        tok = jnp.arange(T * k, dtype=jnp.int32) // k
        rel = flat_ids - (mo.held_from + shard * e_loc)
        local = (rel >= 0) & (rel < e_loc)
        loc_eid = jnp.where(local, rel, e_loc)              # e_loc=overflow

        order = jnp.argsort(loc_eid, stable=True)
        sk = loc_eid[order]                                 # sorted keys
        stok = tok[order]
        sw = flat_w[order]
        # position within the expert group
        first = jnp.searchsorted(sk, sk, side="left")
        gpos = jnp.arange(T * k, dtype=jnp.int32) - first.astype(jnp.int32)
        valid = (sk < e_loc) & (gpos < capacity)
        slot = jnp.where(valid, sk * capacity + gpos, e_loc * capacity)

        xg = jnp.take(xt, stok, axis=0)                     # (T*k, d)
        buf = jnp.zeros((e_loc * capacity, xt.shape[1]), dt)
        buf = buf.at[slot].set(jnp.where(valid[:, None], xg, 0), mode="drop")
        buf = buf.reshape(e_loc, capacity, -1)

    with jax.named_scope("moe.experts"):
        # FSDP gather of expert weights over the data axis
        wg = jax.lax.all_gather(w_gate, "data", axis=1, tiled=True).astype(dt)
        wu = jax.lax.all_gather(w_up, "data", axis=1, tiled=True).astype(dt)
        wd = jax.lax.all_gather(w_down, "data", axis=2, tiled=True).astype(dt)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
            jnp.einsum("ecd,edf->ecf", buf, wu)
        y = jnp.einsum("ecf,efd->ecd", h, wd)               # (E_loc,C,d)

    with jax.named_scope("moe.combine"):
        flat_y = y.reshape(e_loc * capacity, -1)
        contrib = jnp.take(flat_y, jnp.minimum(slot, e_loc * capacity - 1),
                           axis=0)
        contrib = jnp.where(valid[:, None], contrib * sw[:, None].astype(dt),
                            0)
        out = jnp.zeros_like(xt).at[stok].add(contrib)
        out = jax.lax.psum(out, "model")

    if batch_axes:
        aux = jax.lax.psum(aux, batch_axes) / n_batch
    # counts over the whole mesh: pairs of every token shard, experts of
    # every model shard
    loads = jnp.zeros((e_loc,), jnp.int32).at[loc_eid].add(1, mode="drop")
    routed, kept = jnp.sum(local, dtype=jnp.int32), jnp.sum(valid,
                                                            dtype=jnp.int32)
    if batch_axes:
        loads, routed, kept = jax.lax.psum((loads, routed, kept), batch_axes)
    routed, kept = jax.lax.psum((routed, kept), "model")
    max_load = jax.lax.pmax(jnp.max(loads), "model")
    return out, aux, _counts(routed, kept, max_load)


def moe_ep(cfg: ModelConfig, p: dict, x, *, mesh, train: bool):
    """x: (B,S,d). Returns (y, aux_loss, routing counts). Runs under
    shard_map."""
    mo = cfg.moe
    B, S, d = x.shape
    names = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    n_model = mesh.shape["model"]
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]
    if (B * S) % n_batch:
        batch_axes, n_batch = (), 1       # tiny batches: replicate tokens
    T_loc = (B * S) // n_batch
    cf = mo.capacity_factor if train else mo.eval_capacity_factor
    if T_loc * mo.top_k <= 256:
        # tiny per-shard batches (decode): dropless — capacity covers the
        # worst case of every assignment landing on one local expert
        capacity = T_loc * mo.top_k
    else:
        capacity = max(1, int(-(-T_loc * mo.top_k * cf // mo.num_experts)))

    xt = x.reshape(B * S, d)
    body = functools.partial(_ep_local, cfg, capacity, n_model, batch_axes,
                             n_batch)
    tspec = P(batch_axes if batch_axes else None, None)
    y, aux, counts = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tspec, P(None, None), P("model", "data", None),
                  P("model", "data", None), P("model", None, "data")),
        out_specs=(tspec, P(), {c: P() for c in COUNTS}),
    )(xt, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    y = y.reshape(B, S, d)
    if mo.num_shared:
        with jax.named_scope("moe.experts"):
            y = y + mlp_apply(cfg.replace(mlp="swiglu"), p["shared"], x)
    return y, aux, counts


# ---------------------------------------------------------------------------
# token-routed expert parallelism (all-to-all over "data"): experts sharded
# over "data", expert FFN dim over "model".  Tokens move (k*d bytes each)
# instead of weights (E_loc*d*f per layer) — wins when tokens-per-chip is
# small (decode); the weight-gathered "ep" path wins for training.
# ---------------------------------------------------------------------------

def _a2a_local(cfg: ModelConfig, cap_out: int, cap_exp: int, n_data: int,
               n_model: int, batch_axes, n_batch: int, xt, router_w,
               w_gate, w_up, w_down):
    """xt: (T_loc, d) batch-sharded over (pod,data), replicated over model;
    w_*: (E/n_data, d, f/n_model) resident (no gather)."""
    mo = cfg.moe
    E, k = mo.num_experts, mo.top_k
    e_loc = E // n_data
    dt = xt.dtype
    T = xt.shape[0]
    d = xt.shape[1]

    probs, ids, logits = router_topk(cfg, router_w, xt)
    flat_ids = ids.reshape(-1)
    flat_w = probs.reshape(-1)
    tok = jnp.arange(T * k, dtype=jnp.int32) // k
    dest = flat_ids // e_loc                                # owning data shard

    # bucket assignments by destination shard (capacity cap_out per peer)
    order = jnp.argsort(dest, stable=True)
    sd = dest[order]
    stok = tok[order]
    sw = flat_w[order]
    seid = (flat_ids % e_loc)[order]
    first = jnp.searchsorted(sd, sd, side="left")
    gpos = jnp.arange(T * k, dtype=jnp.int32) - first.astype(jnp.int32)
    valid = gpos < cap_out
    slot = jnp.where(valid, sd * cap_out + gpos, n_data * cap_out)

    send_x = jnp.zeros((n_data * cap_out, d), dt)
    send_x = send_x.at[slot].set(
        jnp.where(valid[:, None], jnp.take(xt, stok, axis=0), 0),
        mode="drop")
    send_e = jnp.full((n_data * cap_out,), -1, jnp.int32)
    send_e = send_e.at[slot].set(jnp.where(valid, seid, -1), mode="drop")

    rx = jax.lax.all_to_all(send_x.reshape(n_data, cap_out, d), "data",
                            split_axis=0, concat_axis=0, tiled=False)
    re = jax.lax.all_to_all(send_e.reshape(n_data, cap_out), "data",
                            split_axis=0, concat_axis=0, tiled=False)
    rx = rx.reshape(n_data * cap_out, d)
    re = re.reshape(n_data * cap_out)

    # bucket received tokens by local expert
    key2 = jnp.where(re >= 0, re, e_loc)
    order2 = jnp.argsort(key2, stable=True)
    sk2 = key2[order2]
    first2 = jnp.searchsorted(sk2, sk2, side="left")
    gpos2 = jnp.arange(sk2.shape[0], dtype=jnp.int32) - first2.astype(jnp.int32)
    valid2 = (sk2 < e_loc) & (gpos2 < cap_exp)
    slot2 = jnp.where(valid2, sk2 * cap_exp + gpos2, e_loc * cap_exp)
    buf = jnp.zeros((e_loc * cap_exp, d), dt)
    buf = buf.at[slot2].set(
        jnp.where(valid2[:, None], jnp.take(rx, order2, axis=0), 0),
        mode="drop")
    buf = buf.reshape(e_loc, cap_exp, d)

    wg, wu, wd = (w.astype(dt) for w in (w_gate, w_up, w_down))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
        jnp.einsum("ecd,edf->ecf", buf, wu)
    y = jnp.einsum("ecf,efd->ecd", h, wd)                   # partial over f
    y = jax.lax.psum(y, "model")

    flat_y = y.reshape(e_loc * cap_exp, d)
    y_sorted = jnp.take(flat_y, jnp.minimum(slot2, e_loc * cap_exp - 1),
                        axis=0)
    y_sorted = jnp.where(valid2[:, None], y_sorted, 0)
    y_rx = jnp.zeros((n_data * cap_out, d), dt).at[order2].set(y_sorted)

    y_back = jax.lax.all_to_all(y_rx.reshape(n_data, cap_out, d), "data",
                                split_axis=0, concat_axis=0, tiled=False)
    y_back = y_back.reshape(n_data * cap_out, d)

    contrib = jnp.take(y_back, jnp.minimum(slot, n_data * cap_out - 1),
                       axis=0)
    contrib = jnp.where(valid[:, None], contrib * sw[:, None].astype(dt), 0)
    out = jnp.zeros_like(xt).at[stok].add(contrib)

    aux = aux_load_balance_loss(cfg, logits, ids)
    # a pair is kept when it passed both the send and the expert capacity;
    # the receivers' counts are alike over "model"
    loads = jnp.zeros((E,), jnp.int32).at[flat_ids].add(1)
    routed = jnp.asarray(T * k, jnp.int32)
    kept = jnp.sum(valid2, dtype=jnp.int32)
    if batch_axes:
        aux = jax.lax.psum(aux, batch_axes) / n_batch
        loads, routed, kept = jax.lax.psum((loads, routed, kept), batch_axes)
    return out, aux, _counts(routed, kept, jnp.max(loads))


def moe_a2a(cfg: ModelConfig, p: dict, x, *, mesh, train: bool):
    """x: (B,S,d). Returns (y, aux_loss, routing counts)."""
    mo = cfg.moe
    if mo.n_held != mo.num_experts:
        raise NotImplementedError("ep_a2a holds every expert of the layer")
    B, S, d = x.shape
    names = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    n_model = mesh.shape["model"]
    n_data = mesh.shape.get("data", 1)
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]
    if (B * S) % n_batch:
        batch_axes, n_batch = (), 1
    T_loc = (B * S) // n_batch
    cf = mo.capacity_factor if train else mo.eval_capacity_factor
    if T_loc * mo.top_k <= 256:
        cap_out = T_loc * mo.top_k                      # dropless decode
    else:
        cap_out = max(1, int(-(-T_loc * mo.top_k * cf // n_data)))
    cap_exp = max(1, int(-(-n_data * cap_out * 2 // max(mo.num_experts
                                                        // n_data, 1))))

    xt = x.reshape(B * S, d)
    body = functools.partial(_a2a_local, cfg, cap_out, cap_exp, n_data,
                             n_model, batch_axes, n_batch)
    tspec = P(batch_axes if batch_axes else None, None)
    y, aux, counts = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tspec, P(None, None), P("data", None, "model"),
                  P("data", None, "model"), P("data", "model", None)),
        out_specs=(tspec, P(), {c: P() for c in COUNTS}),
    )(xt, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    y = y.reshape(B, S, d)
    if mo.num_shared:
        y = y + mlp_apply(cfg.replace(mlp="swiglu"), p["shared"], x)
    return y, aux, counts


def moe_apply(cfg: ModelConfig, p: dict, x, *, mesh=None, train: bool = True):
    """Returns (y, aux_loss, routing counts)."""
    if cfg.moe_impl == "dense" or mesh is None:
        return moe_dense(cfg, p, x)
    if cfg.moe_impl == "ep_a2a":
        return moe_a2a(cfg, p, x, mesh=mesh, train=train)
    return moe_ep(cfg, p, x, mesh=mesh, train=train)
