"""Plain float32 reference of a DeepSeek-V2-style payload: multi-head
latent attention without query compression, and a mixture of experts of
which this chip holds a share.

The model is the configuration's (the program's model keywords, as
``drivers/moe_sweep.program_model`` gives them): a pre-norm decoder with
RMSNorm of zero-centred gains; ``prologue_layers`` leading layers with a
dense SwiGLU FFN, then MoE layers.  Attention (every layer):

- queries ``q = h wq`` per head, split into a ``qk_nope`` part and a
  ``qk_rope`` part that takes RoPE;
- a latent ``[c, k_r] = h wkv_a``; ``c`` is RMS-normed, ``k_r`` takes
  RoPE and is shared by every head; ``[k_nope, v] = c wkv_b`` per head;
- causal softmax attention of ``[q_nope, q_rope]`` on
  ``[k_nope, k_r]`` scaled by ``(qk_nope + qk_rope) ** -0.5``, computed
  in blocks of queries; the output projected by ``wo``.

MoE (each later layer): router logits over all ``num_experts``, softmax
scores, the top ``top_k`` of them as gates (raw, unless ``norm_topk``);
the balance loss ``E * sum_e f_e p_e`` over all experts, weighted into
the loss; the held experts (``held`` from ``held_from``) computed for
the tokens routed to them, each keeping the first ``capacity`` such
tokens in token order (a cumulative count), with ``capacity = ceil(T k
cf / E)`` for ``T`` tokens (every pair where ``T k <= 256``); the shared
experts on every token.  What the experts that are not held would add is
left out, as in the program.

It imports nothing of the program.  The weights are made from the job's
seed as the program makes them (one threefry key per parameter, split
from ``PRNGKey(seed)`` in the order of the program's parameter tree,
``param_specs``); the token stream, the precision variants, the loss's
z-loss term and AdamW with int8 block-quantized moments are
``dense_lm``'s, the benchmark's own.

``precision`` and ``quantized_moments`` are as in ``dense_lm``:
``"fp8"`` is the control, ``"bfloat16"`` a witness.  ``fault`` plants
one of ``dense_lm``'s faults or one of two of its own:
``"renorm_gates"`` (the top-k gates renormalised to sum to 1) and
``"other_share"`` (the next share of experts, 8-15 for 0-7, held with
the same weights).  The gates, the share and the head's gradient are
arguments of the compiled gradient, so that only ``"half_batch"`` and
the precisions compile programs of their own.

Beside the loss and the global gradient norm, each step's routing counts
(the program's four, summed over the MoE layers) and the norm of the
routed experts' gradients are kept, for the driver's routing checks.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from reference.dense_lm import (FAULTS as DENSE_FAULTS, TokenStream,
                                _q8, _rms_norm, _rope, _update, act_dtype,
                                make_mm)

FAULTS = DENSE_FAULTS + ("renorm_gates", "other_share")
QUERY_BLOCK = 512


def check_supported(model: dict) -> None:
    """The reference covers one family; refuse anything else."""
    want = {"layer_pattern": ["full"], "mlp": "swiglu",
            "tie_embeddings": False}
    for k, v in want.items():
        got = list(model[k]) if k == "layer_pattern" else model[k]
        if got != v:
            raise ValueError(f"reference needs {k}={v!r}, got {got!r}")
    if model["mla"]["q_lora_rank"]:
        raise ValueError("reference needs direct query projections")
    if model["prologue_layers"] != model["moe"]["first_k_dense"]:
        raise ValueError("reference needs the dense layers as prologue")


# -- weights ---------------------------------------------------------------

def param_specs(model: dict) -> List[Tuple[str, Tuple[int, ...], str, int]]:
    """(name, shape, init, fan) of every parameter, in the order of the
    program's parameter tree: sorted names, the dense layers one by one,
    the MoE layers stacked on axis 0."""
    d, H, V = model["d_model"], model["num_heads"], model["vocab_size"]
    m, mo = model["mla"], model["moe"]
    nope, rope, R, dv = (m["qk_nope_dim"], m["qk_rope_dim"],
                         m["kv_lora_rank"], m["v_dim"])
    n_dense = model["prologue_layers"]
    fd, fe = mo["d_ff_dense"], mo["d_ff_expert"]
    fs = mo["num_shared"] * fe
    held = mo["held"] or mo["num_experts"]

    def attn(pre, lead):
        return [(pre + "kv_norm", lead + (R,), "zeros", 1),
                (pre + "wkv_a", lead + (d, R + rope), "fan_in", d),
                (pre + "wkv_b", lead + (R, H, nope + dv), "fan_in", R),
                (pre + "wo", lead + (H, dv, d), "fan_in", H * dv),
                (pre + "wq", lead + (d, H, nope + rope), "fan_in", d),
                (pre + "ln1", lead + (d,), "zeros", 1),
                (pre + "ln2", lead + (d,), "zeros", 1)]

    out = [("embed", (V, d), "normal", d), ("final_norm", (d,), "zeros", 1)]
    for i in range(n_dense):
        p = f"dense{i}."
        out += attn(p, ()) + [(p + "w_down", (fd, d), "fan_in", fd),
                              (p + "w_gate", (d, fd), "fan_in", d),
                              (p + "w_up", (d, fd), "fan_in", d)]
    N = (model["num_layers"] - n_dense,)
    out += attn("moe.", N) + [
        ("moe.router", N + (d, mo["num_experts"]), "fan_in", d),
        ("moe.shared_down", N + (fs, d), "fan_in", fs),
        ("moe.shared_gate", N + (d, fs), "fan_in", d),
        ("moe.shared_up", N + (d, fs), "fan_in", d),
        ("moe.w_down", N + (held, fe, d), "fan_in", fe),
        ("moe.w_gate", N + (held, d, fe), "fan_in", d),
        ("moe.w_up", N + (held, d, fe), "fan_in", d)]
    out.append(("unembed", (d, V), "fan_in", d))
    return out


def init_params(model: dict, seed) -> Dict[str, jax.Array]:
    specs = param_specs(model)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(specs))
    out = {}
    for key, (name, shape, kind, fan) in zip(keys, specs):
        if kind == "zeros":
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            std = fan ** -0.5 if kind == "normal" else 1.0 / math.sqrt(fan)
            out[name] = (std * jax.random.normal(key, shape)
                         ).astype(jnp.float32)
    return out


# -- forward and loss ------------------------------------------------------

def _attention(mm, q, k, v, scale):
    """Causal softmax attention, one block of queries at a time, each
    block recomputed in the backward pass."""
    B, S, H, _ = q.shape
    qb = min(QUERY_BLOCK, S)
    nq = S // qb
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(args):
        qc, i = args
        s = mm("bqhd,bkhd->bhqk", qc, k, jnp.float32) * scale
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        return mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    qs = q.reshape(B, nq, qb, H, -1).transpose(1, 0, 2, 3, 4)
    o = jax.lax.map(block, (qs, jnp.arange(nq)))
    return o.transpose(1, 0, 2, 3, 4).reshape(B, S, H, -1)


def _mla(model, mm, h, p):
    m = model["mla"]
    nope, rope, R = m["qk_nope_dim"], m["qk_rope_dim"], m["kv_lora_rank"]
    theta = model["rope_theta"]
    q = mm("bsd,dhk->bshk", h, p["wq"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta, 1.0)],
                        axis=-1)
    ckv = mm("bsd,dr->bsr", h, p["wkv_a"])
    c = _rms_norm(ckv[..., :R], p["kv_norm"], model["norm_eps"])
    kr = _rope(ckv[..., None, R:], theta, 1.0)            # (B,S,1,rope)
    kv = mm("bsr,rhk->bshk", c, p["wkv_b"])
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(kr, kv.shape[:3] + (rope,))],
        axis=-1)
    o = _attention(mm, q, k, kv[..., nope:], (nope + rope) ** -0.5)
    return mm("bshv,hvd->bsd", o, p["wo"])


def _swiglu(mm, x, gate, up, down, eq="sd,df->sf", eq_down="sf,fd->sd"):
    return mm(eq_down, jax.nn.silu(mm(eq, x, gate)) * mm(eq, x, up), down)


def capacity(tokens: int, model: dict) -> int:
    """Pairs each held expert keeps: every pair where the step routes
    at most 256, else ``ceil(T k cf / E)``."""
    mo = model["moe"]
    pairs = tokens * mo["top_k"]
    if pairs <= 256:
        return pairs
    return max(1, math.ceil(pairs * mo["capacity_factor"]
                            / mo["num_experts"]))


def _moe(model, mm, held_from, norm_gates, h, p):
    """Returns (the held and shared experts' output, (balance loss,
    routing counts)); the held experts start at ``held_from``, and
    ``norm_gates`` renormalises the gates (both traced).  The counts are
    the program's: pairs routed to held experts, kept, dropped, and the
    largest held expert's load."""
    mo = model["moe"]
    E, k = mo["num_experts"], mo["top_k"]
    B, S, d = h.shape
    T = B * S
    x = h.reshape(T, d)
    logits = mm("td,de->te", x, p["router"], jnp.float32)
    scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, ids = jax.lax.top_k(scores, k)
    gates = jnp.where(norm_gates, gates / gates.sum(-1, keepdims=True), gates)
    routed = jax.nn.one_hot(ids, E, dtype=jnp.float32).sum(1)   # (T,E)
    aux = E * jnp.sum(routed.mean(0) / k * scores.mean(0))
    C = capacity(T, model)

    def expert(j, w_gate, w_up, w_down):
        chosen = ids == held_from + j                            # (T,k)
        to_it = chosen.any(-1)
        gate = jnp.sum(jnp.where(chosen, gates, 0.0), -1)
        pos = jnp.cumsum(to_it) - 1
        keep = to_it & (pos < C)
        # (T,C): token t is row pos_t of the expert's buffer, or no row
        dispatch = jax.nn.one_hot(jnp.where(keep, pos, C), C,
                                  dtype=jnp.float32)
        xe = mm("tc,td->cd", dispatch, x)
        ye = _swiglu(mm, xe, w_gate, w_up, w_down, "cd,df->cf", "cf,fd->cd")
        y = mm("tc,cd->td", dispatch, ye) * gate[:, None].astype(ye.dtype)
        return y, jnp.sum(to_it), jnp.sum(keep)

    held = p["w_gate"].shape[0]
    y, loads, kept = jax.vmap(expert)(jnp.arange(held), p["w_gate"],
                                      p["w_up"], p["w_down"])
    y = y.sum(0) + _swiglu(mm, x, p["shared_gate"], p["shared_up"],
                           p["shared_down"], "td,df->tf", "tf,fd->td")
    counts = {"moe_routed": loads.sum(), "moe_kept": kept.sum(),
              "moe_dropped": loads.sum() - kept.sum(),
              "moe_max_load": loads.max()}
    return y.reshape(B, S, d).astype(h.dtype), (aux, counts)


def _layer(model, mm, ffn, x, p):
    eps = model["norm_eps"]
    x = x + _mla(model, mm, _rms_norm(x, p["ln1"], eps), p)
    y, extra = ffn(_rms_norm(x, p["ln2"], eps), p)
    return x + y, extra


def _dense_ffn(mm, h, p):
    return _swiglu(mm, h, p["w_gate"], p["w_up"], p["w_down"],
                   "bsd,df->bsf", "bsf,fd->bsd"), 0.0


def loss_fn(model, mm, act, z_loss, held_from, norm_gates, params, tokens,
            labels):
    x = params["embed"][tokens].astype(act)
    for i in range(model["prologue_layers"]):
        pre = f"dense{i}."
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        layer = functools.partial(_layer, model, mm,
                                  functools.partial(_dense_ffn, mm))
        x, _ = jax.checkpoint(layer)(x, p)
    moe = jax.checkpoint(functools.partial(
        _layer, model, mm,
        functools.partial(_moe, model, mm, held_from, norm_gates)))
    stacked = {k[4:]: v for k, v in params.items() if k.startswith("moe.")}
    x, (auxes, counts) = jax.lax.scan(lambda c, p: moe(c, p), x, stacked)
    x = _rms_norm(x, params["final_norm"], model["norm_eps"])
    logits = mm("bsd,dv->bsv", x, params["unembed"]).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = (jnp.mean(lse - ll) + z_loss * jnp.mean(jnp.square(lse))
            + model["moe"]["aux_loss_weight"] * jnp.sum(auxes))
    # the routing counts summed over the MoE layers, as the program sums
    return loss, {k: jnp.sum(v) for k, v in counts.items()}


def _grads(model, opt, precision, half_batch, params, tokens, labels,
           held_from, norm_gates, head_grad):
    """Loss, gradients, their global norm (before clipping), the routing
    counts and the norm of the routed experts' gradients; the output
    head's gradient is scaled by ``head_grad``."""
    mm, act = make_mm(precision), act_dtype(precision)
    if half_batch:
        half = tokens.shape[0] // 2
        tokens, labels = tokens[:half], labels[:half]
    (loss, counts), grads = jax.value_and_grad(
        lambda p: loss_fn(model, mm, act, opt["z_loss"], held_from,
                          norm_gates, p, tokens, labels), has_aux=True)(params)
    grads = dict(grads, unembed=grads["unembed"] * head_grad)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    expert_gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(grads[f"moe.{w}"]))
                                for w in ("w_gate", "w_up", "w_down")))
    return loss, grads, gnorm, counts, expert_gnorm


def decayed(name: str) -> bool:
    """Every matrix is decayed; norm gains are not."""
    return name.rpartition(".")[2] not in ("final_norm", "ln1", "ln2",
                                           "kv_norm")


class Reference:
    """Follows the first steps of one job: ``follow(seed, lr, steps)``
    returns the loss and the global gradient norm (before clipping) of
    each step, as ``dense_lm.Reference`` does, and keeps each step's
    routing counts and routed experts' gradient norm in
    ``routing[seed, lr]``."""

    def __init__(self, model: dict, opt: dict, data: dict, batch: int,
                 seq: int, precision: str = "float32",
                 fault: Optional[str] = None,
                 quantized_moments: bool = True):
        check_supported(model)
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.model, self.data, self.fault = model, data, fault
        self.batch, self.seq = batch, seq
        self.names = [name for name, *_ in param_specs(model)]
        self.routing: Dict[Tuple[int, float], dict] = {}
        self._init = jax.jit(functools.partial(init_params, model))
        self._zeros = jax.jit(
            lambda p: _q8(jnp.zeros_like(p), opt["qblock"])
            if quantized_moments else jnp.zeros_like(p))
        self._grads = jax.jit(functools.partial(
            _grads, model, opt, precision, fault == "half_batch"))
        mo = model["moe"]
        self._fault_args = (
            jnp.int32(mo["held_from"] + ((mo["held"] or mo["num_experts"])
                                         if fault == "other_share" else 0)),
            jnp.bool_(mo["norm_topk"] or fault == "renorm_gates"),
            jnp.float32(fault != "drop_grad"))
        self._update = {
            decay: jax.jit(functools.partial(_update, opt,
                                             quantized_moments, decay),
                           donate_argnums=(0, 2, 3))
            for decay in (False, True)}

    def follow(self, seed: int, lr: float, steps: int
               ) -> Tuple[List[float], List[float]]:
        stream = TokenStream(self.model["vocab_size"], self.seq, self.batch,
                             seed, self.data["zipf_alpha"],
                             self.data["motif_len"], self.data["n_motifs"])
        params = self._init(seed)
        m = {k: self._zeros(x) for k, x in params.items()}
        v = {k: self._zeros(x) for k, x in params.items()}
        losses, gnorms = [], []
        routing = self.routing[seed, lr] = {"counts": [],
                                            "expert_gnorms": []}
        for k in range(steps):
            tokens, labels = stream.tokens(k)
            loss, grads, gnorm, counts, expert_gnorm = self._grads(
                params, tokens, labels, *self._fault_args)
            routing["counts"].append({c: int(v) for c, v in counts.items()})
            routing["expert_gnorms"].append(float(expert_gnorm))
            if self.fault == "no_moments":
                m = {k: self._zeros(x) for k, x in params.items()}
                v = {k: self._zeros(x) for k, x in params.items()}
            if self.fault != "unchanged":
                for name in self.names:
                    params[name], m[name], v[name] = self._update[
                        decayed(name)](
                        params[name], grads.pop(name), m[name], v[name],
                        gnorm, jnp.int32(k + 1), jnp.float32(lr))
            del grads
            losses.append(float(loss))
            gnorms.append(float(gnorm))
        del params, m, v
        return losses, gnorms
