"""MoE: expert-parallel shard_map path vs the dense oracle, routing
invariants, aux loss, capacity drops, gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MoECfg, smoke_config
from repro.models import moe as moe_mod

KEY = jax.random.PRNGKey(11)


def _cfg(top_k=2, experts=8, cf=8.0):
    cfg = smoke_config("deepseek-v2-236b")
    return cfg.replace(moe=MoECfg(num_experts=experts, top_k=top_k,
                                  d_ff_expert=32, num_shared=1,
                                  d_ff_dense=128, first_k_dense=1,
                                  capacity_factor=cf,
                                  eval_capacity_factor=cf))


def _params(cfg, key):
    from repro.models.common import init_params
    return init_params(moe_mod.moe_specs(cfg), key, "float32")


def test_ep_matches_dense_oracle_when_no_drops(local_mesh):
    cfg = _cfg(cf=8.0)   # capacity high enough that nothing drops
    p = _params(cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y_dense, aux_d, _ = moe_mod.moe_dense(cfg, p, x)
    y_ep, aux_e, _ = moe_mod.moe_ep(cfg, p, x, mesh=local_mesh, train=True)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_dense),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(float(aux_e), float(aux_d), rtol=1e-5)


def test_ep_gradients_match_dense(local_mesh):
    cfg = _cfg(cf=8.0)
    p = _params(cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, cfg.d_model))

    def loss_dense(p_):
        y, aux, _ = moe_mod.moe_dense(cfg, p_, x)
        return jnp.sum(y ** 2) + aux

    def loss_ep(p_):
        y, aux, _ = moe_mod.moe_ep(cfg, p_, x, mesh=local_mesh, train=True)
        return jnp.sum(y ** 2) + aux

    gd = jax.grad(loss_dense)(p)
    ge = jax.grad(loss_ep)(p)
    for k in ("w_gate", "w_up", "w_down", "router"):
        np.testing.assert_allclose(np.asarray(ge[k]), np.asarray(gd[k]),
                                   atol=5e-4, rtol=5e-4), k


def test_capacity_drops_zero_out_overflow(local_mesh):
    # capacity_factor so small that most assignments drop; output must be
    # finite and strictly smaller in norm than the undropped version.
    # (T*k must exceed the 256 dropless-serving threshold for capacity to
    # bind at all.)
    cfg_lo = _cfg(cf=0.25)
    cfg_hi = _cfg(cf=8.0)
    p = _params(cfg_hi, KEY)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 256, cfg_hi.d_model))
    y_lo, _, _ = moe_mod.moe_ep(cfg_lo, p, x, mesh=local_mesh, train=True)
    y_hi, _, _ = moe_mod.moe_ep(cfg_hi, p, x, mesh=local_mesh, train=True)
    assert np.isfinite(np.asarray(y_lo)).all()
    assert float(jnp.linalg.norm(y_lo)) < float(jnp.linalg.norm(y_hi))


def test_router_topk_normalized():
    cfg = _cfg(top_k=3)
    router = jax.random.normal(KEY, (cfg.d_model, cfg.moe.num_experts))
    x = jax.random.normal(jax.random.PRNGKey(4), (64, cfg.d_model))
    probs, ids, logits = moe_mod.router_topk(cfg, router, x)
    assert probs.shape == (64, 3) and ids.shape == (64, 3)
    np.testing.assert_allclose(np.asarray(probs.sum(-1)), 1.0, atol=1e-5)
    assert int(ids.max()) < cfg.moe.num_experts
    # top-k ids unique per token
    for row in np.asarray(ids):
        assert len(set(row.tolist())) == len(row)


def test_aux_loss_prefers_balance():
    cfg = _cfg(top_k=1, experts=4)
    E = 4
    balanced = jnp.eye(E)[jnp.arange(64) % E] * 10.0       # uniform routing
    skewed = jnp.broadcast_to(jnp.eye(E)[0] * 10.0, (64, E))
    ids_b = jnp.argmax(balanced, -1, keepdims=True)
    ids_s = jnp.argmax(skewed, -1, keepdims=True)
    lb = moe_mod.aux_load_balance_loss(cfg, balanced, ids_b)
    ls = moe_mod.aux_load_balance_loss(cfg, skewed, ids_s)
    assert float(lb) < float(ls)
