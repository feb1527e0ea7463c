"""DeepSeek-V2-Lite's mechanisms at a tiny size: MLA without query
compression, an expert layer that holds a share of the router's experts,
raw top-k gates, configurations read from mappings, and the routing
counts a step returns."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MLACfg, ModelConfig, MoECfg, get_config, smoke_config
from repro.models import moe as moe_mod
from repro.models import transformer as tfm
from repro.models.common import init_params

KEY = jax.random.PRNGKey(7)


def _moe_cfg(**moe):
    cfg = smoke_config("deepseek-v2-236b")
    kw = dict(num_experts=8, top_k=2, d_ff_expert=32, num_shared=1,
              d_ff_dense=128, first_k_dense=1, capacity_factor=8.0)
    return cfg.replace(moe=MoECfg(**dict(kw, **moe)))


def _share(p, held, start):
    return dict(p, **{k: p[k][start:start + held]
                      for k in ("w_gate", "w_up", "w_down")})


# -- (b) the shares of the experts add up to the uncut layer ---------------

@pytest.mark.parametrize("held", [1, 2, 4])
def test_expert_shares_sum_to_the_uncut_layer(held, local_mesh):
    cfg = _moe_cfg()
    E = cfg.moe.num_experts
    p = init_params(moe_mod.moe_specs(cfg), KEY, "float32")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    whole, aux, _ = moe_mod.moe_ep(cfg, p, x, mesh=local_mesh, train=True)
    shared = moe_mod.mlp_apply(cfg.replace(mlp="swiglu"), p["shared"], x)
    parts = []
    for start in range(0, E, held):
        c = cfg.replace(moe=dataclasses.replace(cfg.moe, held=held,
                                                held_from=start))
        assert moe_mod.moe_specs(c)["w_gate"].shape[0] == held
        assert moe_mod.moe_specs(c)["router"].shape == (cfg.d_model, E)
        y, aux_c, _ = moe_mod.moe_ep(c, _share(p, held, start), x,
                                  mesh=local_mesh, train=True)
        # every share routes over all experts: the same balance loss
        np.testing.assert_allclose(float(aux_c), float(aux), rtol=1e-6)
        parts.append(y - shared)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=2e-5, rtol=2e-5)


def test_dense_oracle_matches_a_held_share(local_mesh):
    cfg = _moe_cfg(held=2, held_from=4)
    p = init_params(moe_mod.moe_specs(cfg), KEY, "float32")
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model))
    y_d, aux_d, _ = moe_mod.moe_dense(cfg, p, x)
    y_e, aux_e, _ = moe_mod.moe_ep(cfg, p, x, mesh=local_mesh, train=True)
    np.testing.assert_allclose(np.asarray(y_e), np.asarray(y_d),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(float(aux_e), float(aux_d), rtol=1e-6)


# -- (c) MLA without query LoRA: absorbed decode against the naive form ----

def test_mla_without_q_lora_decodes_like_the_full_forward(local_mesh):
    base = smoke_config("deepseek-v2-236b")
    cfg = base.replace(attn_impl="reference",
                       mla=dataclasses.replace(base.mla, q_lora_rank=0))
    params = tfm.init_model(cfg, jax.random.PRNGKey(3))
    attn = params["prologue"][0]["attn"]
    assert "wq_a" not in attn and attn["wq"].shape == (
        cfg.d_model, cfg.num_heads, cfg.mla.qk_nope_dim + cfg.mla.qk_rope_dim)
    B, S_p, S = 2, 6, 10
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0,
                              cfg.vocab_size)
    full, _, _ = tfm.forward(cfg, params, {"tokens": toks}, mode="train",
                             mesh=local_mesh)
    cache = tfm.init_cache(cfg, B, S)
    pre, cache, _ = tfm.forward(cfg, params, {"tokens": toks[:, :S_p]},
                                mode="prefill", cache=cache, mesh=local_mesh)
    np.testing.assert_allclose(np.asarray(pre[:, -1]),
                               np.asarray(full[:, S_p - 1]),
                               atol=1e-4, rtol=1e-4)
    for t in range(S_p, S):
        step, cache, _ = tfm.forward(cfg, params, {"tokens": toks[:, t:t + 1]},
                                     mode="decode", cache=cache,
                                     mesh=local_mesh)
        np.testing.assert_allclose(np.asarray(step[:, 0]),
                                   np.asarray(full[:, t]),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("q_lora_rank", [0, None, 32])
def test_param_count_is_the_parameter_tree_less_norm_gains(q_lora_rank):
    base = smoke_config("deepseek-v2-236b")
    cfg = base.replace(
        mla=dataclasses.replace(base.mla, q_lora_rank=q_lora_rank),
        moe=dataclasses.replace(base.moe, held=4, held_from=4))
    leaves = jax.tree_util.tree_leaves_with_path(tfm.abstract_model(cfg))
    norms = ("ln1", "ln2", "final_norm", "q_norm", "kv_norm")
    matrices = sum(int(np.prod(x.shape)) for path, x in leaves
                   if path[-1].key not in norms)
    assert cfg.param_count() == matrices


# -- (d) raw gates --------------------------------------------------------

def test_raw_gates_are_the_top_k_softmax_scores():
    cfg = _moe_cfg(top_k=3, norm_topk=False)
    # small logits: the top 3 of 8 scores sum to well under 1
    router = 0.05 * jax.random.normal(KEY, (cfg.d_model, cfg.moe.num_experts))
    x = jax.random.normal(jax.random.PRNGKey(4), (64, cfg.d_model))
    probs, ids, logits = moe_mod.router_topk(cfg, router, x)
    scores = jax.nn.softmax(logits, axis=-1)
    np.testing.assert_array_equal(
        np.asarray(probs),
        np.asarray(jnp.take_along_axis(scores, ids, axis=-1)))
    assert float(jnp.max(probs.sum(-1))) < 0.9
    normed, ids_n, _ = moe_mod.router_topk(_moe_cfg(top_k=3), router, x)
    np.testing.assert_array_equal(np.asarray(ids_n), np.asarray(ids))
    np.testing.assert_allclose(np.asarray(normed),
                               np.asarray(probs / probs.sum(-1, keepdims=True)),
                               rtol=1e-6)


def test_deepseek_v2_uses_raw_gates():
    assert get_config("deepseek-v2-236b").moe.norm_topk is False
    assert smoke_config("deepseek-v2-236b").moe.norm_topk is False


# -- (e) configurations from mappings ---------------------------------------

def test_config_from_mappings_equals_one_built_in_code():
    kw = dict(name="m", family="moe", num_layers=3, d_model=64, num_heads=4,
              num_kv_heads=4, head_dim=24, d_ff=128, vocab_size=512,
              prologue_layers=1)
    mla = dict(q_lora_rank=0, kv_lora_rank=16, qk_nope_dim=16,
               qk_rope_dim=8, v_dim=16)
    moe = dict(num_experts=16, top_k=4, d_ff_expert=32, num_shared=2,
               d_ff_dense=128, first_k_dense=1, norm_topk=False, held=4)
    read = ModelConfig(**kw, layer_pattern=["full"], mla=mla, moe=moe)
    built = ModelConfig(**kw, layer_pattern=("full",), mla=MLACfg(**mla),
                        moe=MoECfg(**moe))
    assert read == built and hash(read) == hash(built)
    assert isinstance(read.moe, MoECfg) and isinstance(read.mla, MLACfg)
    assert read.moe.n_held == 4 and read.replace(name="m") == built
    assert {read: 1}[built] == 1


# -- (f) routing counts ---------------------------------------------------

def test_routing_counts_are_exact_with_capacity_one(local_mesh):
    """Token t routes to experts a_t, b_t, c_t in that order of score;
    with capacity 1 each held expert keeps the first token routed to it."""
    E, k, T = 8, 3, 128
    cfg = _moe_cfg(num_experts=E, top_k=k, capacity_factor=0.02,
                   held=4, held_from=2)
    d = cfg.d_model
    rng = np.random.default_rng(0)
    choice = np.stack([rng.permutation(E)[:k] for _ in range(T)])
    x = np.zeros((T, d), np.float32)
    for j, w in enumerate((1.0, 0.5, 0.25)):
        x[np.arange(T), choice[:, j]] += w
    p = init_params(moe_mod.moe_specs(cfg), KEY, "float32")
    p["router"] = jnp.zeros((d, E)).at[jnp.arange(E), jnp.arange(E)].set(10.0)
    _, ids, _ = moe_mod.router_topk(cfg, p["router"], jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(ids), choice)

    loads = np.array([(choice == e).sum() for e in range(2, 6)])
    assert T * k > 256 and -(-T * k * 0.02 // E) == 1   # capacity 1
    want = {"moe_routed": loads.sum(), "moe_kept": (loads > 0).sum(),
            "moe_dropped": loads.sum() - (loads > 0).sum(),
            "moe_max_load": loads.max()}
    _, _, counts = moe_mod.moe_ep(cfg, p, jnp.asarray(x)[None],
                                  mesh=local_mesh, train=True)
    assert {c: int(v) for c, v in counts.items()} == want
    _, _, dense = moe_mod.moe_dense(cfg, p, jnp.asarray(x)[None])
    assert {c: int(v) for c, v in dense.items()} == dict(
        want, moe_kept=loads.sum(), moe_dropped=0)


def test_only_a_moe_step_returns_routing_counts():
    from repro.launch.train import routing_counts, run_training
    before = routing_counts()
    r = run_training("deepseek-v2-236b", steps=2, batch=2, seq=32,
                     verbose=False)
    after = routing_counts()
    assert len(r.moe_counts) == 2 and after["steps"] == before["steps"] + 2
    for c in moe_mod.COUNTS:
        assert after[c] == before[c] + sum(s[c] for s in r.moe_counts)
    s = r.moe_counts[0]
    assert s["moe_routed"] == s["moe_kept"] + s["moe_dropped"] > 0
    assert len(r.moe_expert_gnorms) == 2
    assert all(np.isfinite(g) and g > 0 for g in r.moe_expert_gnorms)
    dense = run_training("stablelm-1.6b", steps=1, batch=2, seq=32,
                         verbose=False)
    assert dense.moe_counts == [] and routing_counts() == after
    assert dense.moe_expert_gnorms == []


def test_expert_leaves_are_the_routed_experts_of_every_moe_layer():
    cfg = smoke_config("deepseek-v2-236b")
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, held=2, held_from=1))
    params = tfm.init_model(cfg, KEY)
    leaves = moe_mod.expert_leaves(params)
    mo = cfg.moe
    n_moe = cfg.num_layers - mo.first_k_dense
    # gate, up and down of each held expert: no router, shared expert or
    # dense FFN among them
    assert sum(x.size for x in leaves) == (
        n_moe * mo.n_held * 3 * cfg.d_model * mo.d_ff_expert)
    assert all(x.shape[-3] == mo.n_held for x in leaves)


# -- attention at long sequences: the backward keeps no block's scores -----

def test_blockwise_attention_backward_matches_and_stays_linear():
    from repro.models.attention import blockwise_attention, reference_attention

    def loss(attn, S, **kw):
        pos = jnp.arange(S)
        return lambda q, k, v: jnp.sum(jnp.sin(
            attn(q, k, v, q_pos=pos, k_pos=pos, **kw)))

    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q, k, v = (jax.random.normal(kk, (2, 128, 4, 16)) for kk in ks)
    got = jax.grad(loss(blockwise_attention, 128, block_kv=32),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(reference_attention, 128), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-4, rtol=1e-4)

    B, S, H, D = 1, 4096, 2, 32
    x = jax.ShapeDtypeStruct((B, S, H, D), jnp.float32)
    grad = jax.jit(jax.grad(loss(blockwise_attention, S, block_kv=256),
                            argnums=(0, 1, 2)))
    temp = grad.lower(x, x, x).compile().memory_analysis().temp_size_in_bytes
    # under one float32 score matrix of the whole sequence (134 MB); the
    # backward used to keep every block's scores and probabilities
    assert temp < B * H * S * S * 4


def test_routing_tally_loses_no_update_under_threads():
    import sys
    import threading
    from repro.launch.train import _RoutingTally
    tally = _RoutingTally()
    one = {c: 1 for c in moe_mod.COUNTS}
    n_threads, n_adds = 32, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda: [tally.add(one) for _ in range(n_adds)])
            for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert tally.read() == dict.fromkeys(("steps",) + moe_mod.COUNTS,
                                         n_threads * n_adds)
