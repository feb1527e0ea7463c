"""Plain float32 reference of the payload a sweep cell trains.

A dense decoder as the configuration file states it (pre-norm RMSNorm
with zero-centred gains, multi-head attention with partial rotary,
SwiGLU), its synthetic token stream, the cross-entropy loss with a z-loss
term, and AdamW with int8 block-quantized moments, global-norm clipping
and a linear-warmup cosine schedule.  Every matrix product runs in
float32 at ``Precision.HIGHEST``.

It imports nothing of the program and takes none of its arrays.  The
weights and batches are made from the job's seed by the same rules as
the payload's: one threefry key per parameter, split from
``PRNGKey(seed)`` in sorted-name order (``PARAM_ORDER``, ``param_shapes``),
and one numpy ``SeedSequence([seed, step, 0])`` per batch with the
configuration's ``data`` parameters.  So the reference and the program
start from the same point without sharing anything.

``precision="fp8"`` is the control: the same computation with both
operands of every matrix product rounded to float8 (e4m3 forward, e5m2
gradients, one scale per tensor), the step below the bfloat16 the
configuration computes in.  ``precision="bfloat16"`` is a witness, not a
control: activations carried in bfloat16 and every matrix product on
bfloat16 operands (scores and logits read out in float32), the
configuration's own dtype, computed independently of the program.
``quantized_moments=False`` keeps the moments in float32.

``fault`` plants one of the faults the comparison must catch:
``"unchanged"`` (the step returns its state as it came), ``"half_batch"``
(the loss and gradient over the first half of the batch only),
``"drop_grad"`` (the output head's gradient zeroed before the
optimizer) and ``"no_moments"`` (the update reads zero moments at every
step instead of the stored ones).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("unchanged", "half_batch", "drop_grad", "no_moments")

# parameter names in the order their init keys are split (sorted names
# of the program's parameter tree, scanned layers stacked on axis 0)
PARAM_ORDER = ("embed", "final_norm", "wk", "wo", "wq", "wv", "ln1", "ln2",
               "w_down", "w_gate", "w_up", "unembed")
LAYER_KEYS = ("wk", "wo", "wq", "wv", "ln1", "ln2", "w_down", "w_gate",
              "w_up")


def check_supported(model: dict) -> None:
    """The reference covers one family; refuse anything else."""
    want = {"layer_pattern": ["full"], "mlp": "swiglu",
            "tie_embeddings": False}
    for k, v in want.items():
        if model.get(k) != v:
            raise ValueError(f"reference needs {k}={v!r}, got "
                             f"{model.get(k)!r}")
    if model["num_heads"] != model["num_kv_heads"]:
        raise ValueError("reference needs multi-head attention")


# -- weights ---------------------------------------------------------------

def param_shapes(model: dict) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
    """name -> (shape, init, fan) under the recorded init rules."""
    L, d, f, V = (model["num_layers"], model["d_model"], model["d_ff"],
                  model["vocab_size"])
    H, hd = model["num_heads"], model["head_dim"]
    return {
        "embed": ((V, d), "normal", d),
        "final_norm": ((d,), "zeros", 1),
        "wk": ((L, d, H, hd), "fan_in", d),
        "wo": ((L, H, hd, d), "fan_in", H * hd),
        "wq": ((L, d, H, hd), "fan_in", d),
        "wv": ((L, d, H, hd), "fan_in", d),
        "ln1": ((L, d), "zeros", 1),
        "ln2": ((L, d), "zeros", 1),
        "w_down": ((L, f, d), "fan_in", f),
        "w_gate": ((L, d, f), "fan_in", d),
        "w_up": ((L, d, f), "fan_in", d),
        "unembed": ((d, V), "fan_in", d),
    }


def init_params(model: dict, seed) -> Dict[str, jax.Array]:
    shapes = param_shapes(model)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(PARAM_ORDER))
    out = {}
    for key, name in zip(keys, PARAM_ORDER):
        shape, kind, fan = shapes[name]
        if kind == "zeros":
            out[name] = jnp.zeros(shape, jnp.float32)
        elif kind == "normal":
            out[name] = (fan ** -0.5 * jax.random.normal(key, shape)
                         ).astype(jnp.float32)
        else:
            std = 1.0 / np.sqrt(float(fan))
            out[name] = (std * jax.random.normal(key, shape)
                         ).astype(jnp.float32)
    return out


# -- data ------------------------------------------------------------------

class TokenStream:
    """Zipfian unigrams with stamped motifs, one batch per step."""

    def __init__(self, vocab: int, seq: int, batch: int, seed: int,
                 zipf_alpha: float, motif_len: int, n_motifs: int):
        self.vocab, self.seq, self.batch, self.seed = vocab, seq, batch, seed
        self.motif_len = motif_len
        r = np.arange(1, vocab + 1, dtype=np.float64) ** (-zipf_alpha)
        self.probs = r / r.sum()
        rng = np.random.default_rng(seed)
        self.motifs = rng.integers(0, vocab, size=(n_motifs, motif_len))

    def tokens(self, step: int) -> Tuple[np.ndarray, np.ndarray]:
        rng = np.random.default_rng(np.random.SeedSequence(
            [self.seed, step, 0]))
        toks = rng.choice(self.vocab, size=(self.batch, self.seq + 1),
                          p=self.probs)
        ml = self.motif_len
        n_stamp = max(1, self.seq // (4 * ml))
        for i in range(self.batch):
            ids = rng.integers(0, len(self.motifs), size=n_stamp)
            pos = rng.integers(0, self.seq + 1 - ml, size=n_stamp)
            for m, p in zip(ids, pos):
                toks[i, p:p + ml] = self.motifs[m]
        toks = toks.astype(np.int32)
        return toks[:, :-1], toks[:, 1:]


# -- precision -------------------------------------------------------------

def _round_fp8(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return ((x / scale).astype(dtype).astype(jnp.float32)) * scale


@jax.custom_vjp
def _fp8_operand(x):
    return _round_fp8(x, jnp.float8_e4m3fn)


_fp8_operand.defvjp(lambda x: (_fp8_operand(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(x):
    return x


_fp8_cotangent.defvjp(lambda x: (x, None),
                      lambda _, g: (_round_fp8(g, jnp.float8_e5m2),))


def act_dtype(precision: str):
    """The dtype activations are carried in."""
    return jnp.bfloat16 if precision == "bfloat16" else jnp.float32


def make_mm(precision: str):
    """``mm(eq, a, b, out=None)``: a matrix product; ``out`` asks for a
    float32 result where the operands are narrower."""
    if precision == "float32":
        return lambda eq, a, b, out=None: jnp.einsum(eq, a, b,
                                                     precision=HIGHEST)
    if precision == "bfloat16":
        bf = jnp.bfloat16
        return lambda eq, a, b, out=None: jnp.einsum(
            eq, a.astype(bf), b.astype(bf), preferred_element_type=out)
    if precision == "fp8":
        return lambda eq, a, b, out=None: _fp8_cotangent(jnp.einsum(
            eq, _fp8_operand(a), _fp8_operand(b), precision=HIGHEST))
    raise ValueError(f"unknown precision {precision!r}")


# -- forward and loss ------------------------------------------------------

def _rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * (1.0 + w)).astype(x.dtype)


def _rope(x, theta, fraction):
    hd = x.shape[-1]
    rot = int(hd * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    inv = jnp.asarray(1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float64)
                                       / rot)), jnp.float32)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x[..., :rot].astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.astype(x.dtype), x[..., rot:]], axis=-1)


def _layer(model, mm, x, p):
    eps = model["norm_eps"]
    h = _rms_norm(x, p["ln1"], eps)
    q = _rope(mm("bsd,dhk->bshk", h, p["wq"]), model["rope_theta"],
              model["rope_fraction"])
    k = _rope(mm("bsd,dhk->bshk", h, p["wk"]), model["rope_theta"],
              model["rope_fraction"])
    v = mm("bsd,dhk->bshk", h, p["wv"])
    s = mm("bqhd,bkhd->bhqk", q, k, jnp.float32) * model["head_dim"] ** -0.5
    S = x.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + mm("bshk,hkd->bsd", o, p["wo"])
    h = _rms_norm(x, p["ln2"], eps)
    g = jax.nn.silu(mm("bsd,df->bsf", h, p["w_gate"]))
    u = mm("bsd,df->bsf", h, p["w_up"])
    return x + mm("bsf,fd->bsd", g * u, p["w_down"])


def loss_fn(model, mm, act, z_loss, params, tokens, labels):
    x = params["embed"][tokens].astype(act)
    layer = jax.checkpoint(functools.partial(_layer, model, mm))
    x, _ = jax.lax.scan(lambda c, p: (layer(c, p), None), x,
                        {k: params[k] for k in LAYER_KEYS})
    x = _rms_norm(x, params["final_norm"], model["norm_eps"])
    logits = mm("bsd,dv->bsv", x, params["unembed"]).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll) + z_loss * jnp.mean(jnp.square(lse))


# -- optimizer -------------------------------------------------------------

def _q8(x, block):
    flat = x.reshape(-1)
    fp = jnp.pad(flat, (0, (-flat.size) % block)).reshape(-1, block)
    scale = jnp.maximum(jnp.max(jnp.abs(fp), axis=1, keepdims=True) / 127.0,
                        1e-20)
    return {"q": jnp.clip(jnp.round(fp / scale), -127, 127).astype(jnp.int8),
            "s": scale}


def _dq8(qd, shape):
    n = int(np.prod(shape))
    return (qd["q"].astype(jnp.float32) * qd["s"]).reshape(-1)[:n].reshape(
        shape)


def lr_scale(t, opt: dict):
    """Linear warmup to 1 over ``warmup_steps``, cosine to ``min_ratio``
    by ``total_steps``; ``t`` counts from 1."""
    warm = jnp.minimum(t / max(opt["warmup_steps"], 1), 1.0)
    frac = jnp.clip((t - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1),
                    0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    return warm * (opt["min_ratio"] + (1 - opt["min_ratio"]) * cos)


def _grads(model, opt, precision, fault, params, tokens, labels):
    """Loss, gradients and their global norm (before clipping)."""
    mm, act = make_mm(precision), act_dtype(precision)
    if fault == "half_batch":
        half = tokens.shape[0] // 2
        tokens, labels = tokens[:half], labels[:half]
    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(model, mm, act, opt["z_loss"], p, tokens, labels)
    )(params)
    if fault == "drop_grad":
        grads = dict(grads, unembed=jnp.zeros_like(grads["unembed"]))
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    return loss, grads, gnorm


def _update(opt, quantized, decay, p, g, m, v, gnorm, t, lr):
    """AdamW on one parameter; ``t`` counts steps from 1."""
    g = g * jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    tf = t.astype(jnp.float32)
    if quantized:
        m, v = _dq8(m, p.shape), _dq8(v, p.shape)
    mf = opt["b1"] * m + (1 - opt["b1"]) * g
    vf = opt["b2"] * v + (1 - opt["b2"]) * g * g
    u = (mf / (1.0 - opt["b1"] ** tf)) / (
        jnp.sqrt(vf / (1.0 - opt["b2"] ** tf)) + opt["eps"])
    if decay:
        u = u + opt["weight_decay"] * p
    p = p - lr * lr_scale(tf, opt) * u
    if quantized:
        return p, _q8(mf, opt["qblock"]), _q8(vf, opt["qblock"])
    return p, mf, vf


# gains of the norms are not decayed; every matrix is
NO_DECAY = ("final_norm", "ln1", "ln2")


class Reference:
    """Follows the first steps of one job: ``follow(seed, lr, steps)``
    returns the loss and the global gradient norm (before clipping) of
    each step.  The gradients come from one compiled program and the
    update from one per parameter shape, so that no more than one
    parameter's optimizer temporaries are live beside the gradients.
    Seed, lr and step index are arguments, not constants."""

    def __init__(self, model: dict, opt: dict, data: dict, batch: int,
                 seq: int, precision: str = "float32",
                 fault: Optional[str] = None,
                 quantized_moments: bool = True):
        check_supported(model)
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.model, self.data, self.fault = model, data, fault
        self.batch, self.seq = batch, seq
        self._init = jax.jit(functools.partial(init_params, model))
        self._zeros = jax.jit(
            lambda p: _q8(jnp.zeros_like(p), opt["qblock"])
            if quantized_moments else jnp.zeros_like(p))
        self._grads = jax.jit(functools.partial(_grads, model, opt,
                                                precision, fault))
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
        for k in range(steps):
            tokens, labels = stream.tokens(k)
            loss, grads, gnorm = self._grads(params, tokens, labels)
            if self.fault == "no_moments":
                m = {k: self._zeros(x) for k, x in params.items()}
                v = {k: self._zeros(x) for k, x in params.items()}
            if self.fault != "unchanged":
                for name in PARAM_ORDER:
                    params[name], m[name], v[name] = self._update[
                        name not in NO_DECAY](
                        params[name], grads.pop(name), m[name], v[name],
                        gnorm, jnp.int32(k + 1), jnp.float32(lr))
            del grads
            losses.append(float(loss))
            gnorms.append(float(gnorm))
        del params, m, v
        return losses, gnorms
