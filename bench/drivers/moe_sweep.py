"""Sweep cells of a DeepSeek-V2-style payload: latent attention (MLA) and
a fine-grained mixture of experts, of which this chip holds a share.

The configuration file keeps the published ``config.json`` at its top
level; ``model`` holds what this run changes (the depth, the experts held,
the vocabulary slice, each also under ``reduced`` with its published
value under ``published``) and the program's own settings, and
``assumed`` the capacity factor and the balance-loss weight.
``program_model`` turns that into the program's model configuration, in
which the router keeps its published width and the expert layer holds
``n_routed_experts`` experts from ``held_from``.

The run itself is ``payload_sweep.run``, unchanged: the same plan, grid,
window, books and comparison with the reference.  Around it, this
driver keeps what each ``run_training`` call returns of the routed
experts, and the reference object the run builds.  Then three things are
this configuration's own:

- ``moe_checks``: loss and global gradient norm barely see the routed
  experts (a few of 64 per token, many of their pairs dropped), so each
  finished job's routing counts and routed experts' gradient norm, step
  by step, are compared with the reference's (``compare_routing``);
- ``flops_per_step`` is the MLA/MoE model-FLOP count of
  ``train_step_flops`` (``payload_sweep`` counts a dense decoder);
- ``moe`` is the program's process-wide routing tally
  (``launch.train.routing_counts``) with the number of experts held: the
  window's jobs, and set-up's one-step run at each learning rate, which
  run in the same process.
"""
from __future__ import annotations

import math
from typing import Dict, List

from drivers import payload_sweep

# what the published config may say that the program does not implement
UNSUPPORTED = {"scoring_func": "softmax", "topk_method": "greedy",
               "routed_scaling_factor": 1, "moe_layer_freq": 1,
               "n_group": 1, "topk_group": 1, "attention_bias": False,
               "hidden_act": "silu"}


def program_model(config: dict) -> dict:
    """The program's model configuration (``ModelConfig`` keywords) of a
    configuration file in the published config's terms."""
    c = {**config, **config["model"]}
    for key, want in UNSUPPORTED.items():
        if c[key] != want:
            raise ValueError(f"the program needs {key}={want!r}, the "
                             f"configuration has {c[key]!r}")
    a = config["assumed"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    return {
        "name": c["name"], "family": "moe",
        "num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "num_heads": c["num_attention_heads"],
        "num_kv_heads": c["num_key_value_heads"], "head_dim": nope + rope,
        "d_ff": c["intermediate_size"], "vocab_size": c["vocab_size"],
        "layer_pattern": ["full"],
        "prologue_layers": c["first_k_dense_replace"],
        "rope_theta": float(c["rope_theta"]), "mlp": "swiglu",
        "tie_embeddings": c["tie_word_embeddings"],
        "norm_eps": c["rms_norm_eps"], "dtype": c["dtype"],
        "param_dtype": c["param_dtype"], "remat": c["remat"],
        "mla": {"q_lora_rank": c["q_lora_rank"] or 0,
                "kv_lora_rank": c["kv_lora_rank"], "qk_nope_dim": nope,
                "qk_rope_dim": rope, "v_dim": c["v_head_dim"]},
        "moe": {"num_experts": config["published"]["n_routed_experts"],
                "held": c["n_routed_experts"], "held_from": c["held_from"],
                "top_k": c["num_experts_per_tok"],
                "d_ff_expert": c["moe_intermediate_size"],
                "num_shared": c["n_shared_experts"],
                "d_ff_dense": c["intermediate_size"],
                "first_k_dense": c["first_k_dense_replace"],
                "capacity_factor": a["capacity_factor"],
                "aux_loss_weight": a["aux_loss_weight"],
                "norm_topk": c["norm_topk_prob"]},
    }


def matmul_params(model: dict) -> float:
    """Parameters that enter a matrix product in one token's forward
    pass: every layer's attention projections, the dense layers' FFN,
    each MoE layer's router and shared experts, its held experts weighted
    by their expected share of a token's pairs (``top_k * held /
    num_experts``), and the output head.  The embedding gather does no
    arithmetic."""
    d, H, V = model["d_model"], model["num_heads"], model["vocab_size"]
    m, mo = model["mla"], model["moe"]
    qk = m["qk_nope_dim"] + m["qk_rope_dim"]
    R = m["kv_lora_rank"]
    q = (d * m["q_lora_rank"] + m["q_lora_rank"] * H * qk
         if m["q_lora_rank"] else d * H * qk)
    attn = (q + d * (R + m["qk_rope_dim"])
            + R * H * (m["qk_nope_dim"] + m["v_dim"]) + H * m["v_dim"] * d)
    n_dense = mo["first_k_dense"]
    n_moe = model["num_layers"] - n_dense
    expert = 3 * d * mo["d_ff_expert"]
    held = mo["held"] or mo["num_experts"]
    routed = mo["top_k"] * held / mo["num_experts"] * expert
    moe = d * mo["num_experts"] + mo["num_shared"] * expert + routed
    return (model["num_layers"] * attn + n_dense * 3 * d * mo["d_ff_dense"]
            + n_moe * moe + d * V)


def train_step_flops(model: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one step over ``batch`` x ``seq`` tokens: 2 per
    multiply-add of ``matmul_params``, causal attention (scores over the
    query-key width, weighted sum over the value width) at the mean causal
    length ``seq / 2``, and the backward pass as twice the forward.
    Recomputation under remat is not counted."""
    m = model["mla"]
    width = m["qk_nope_dim"] + m["qk_rope_dim"] + m["v_dim"]
    attn = model["num_layers"] * 2 * (seq / 2) * model["num_heads"] * width
    return 3.0 * (2 * matmul_params(model) + attn) * batch * seq


# the routing counts compared: the dropped pairs are routed less kept
ROUTING_COUNTS = ("moe_routed", "moe_kept", "moe_max_load")


def _share(p: float, r: float) -> float:
    """``|p - r|`` as a share of ``r``."""
    if r:
        return abs(p - r) / r
    return 0.0 if p == r else math.inf


def routing_gaps(program: dict, reference: dict) -> Dict[str, List[float]]:
    """Per step, the gap of the routed experts' gradient norm and the
    largest gap of the routing counts, each a share of the reference's;
    ``program`` and ``reference`` each hold ``counts`` (a dict a step)
    and ``expert_gnorms`` (a number a step)."""
    return {
        "expert_gnorm": [_share(p, r) for p, r in
                         zip(program["expert_gnorms"],
                             reference["expert_gnorms"])],
        "routing": [max(_share(p[c], r[c]) for c in ROUTING_COUNTS)
                    for p, r in zip(program["counts"], reference["counts"])],
    }


def compare_routing(jobs: List[dict], checks: dict) -> Dict[str, float]:
    """Each check's largest gap (``of`` a key of ``routing_gaps``) over
    its steps and over the jobs, each job holding its ``routing_gaps``;
    infinite where a job lacks a step or no job was read, as
    ``payload_sweep.compare`` has it."""
    out = {name: 0.0 for name in checks}
    for j in jobs:
        for name, c in checks.items():
            by_step = j["routing_gaps"][c["of"]]
            if len(by_step) <= max(c["steps"]):
                return {name: math.inf for name in checks}
            out[name] = max(out[name], *(by_step[s] for s in c["steps"]))
    return out if jobs else {name: math.inf for name in checks}


def run(*, config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, reference_cls, peak, t_start: float) -> dict:
    from repro.launch import train
    model = program_model(config)
    programs = {}            # (seed, lr) -> the last call's routed experts
    references = []
    run_training = train.run_training

    def recorded(*args, **kw):
        r = run_training(*args, **kw)
        programs[kw["seed"], kw["lr"]] = {
            "counts": r.moe_counts, "expert_gnorms": r.moe_expert_gnorms}
        return r

    def reference(*args, **kw):
        references.append(reference_cls(*args, **kw))
        return references[-1]

    # payload_sweep imports run_training when it runs: it calls this one
    train.run_training = recorded
    try:
        out = payload_sweep.run(config=dict(config, model=model),
                                traffic=traffic, seed=seed, seconds=seconds,
                                trace=trace, reference_cls=reference,
                                peak=peak, t_start=t_start)
    finally:
        train.run_training = run_training

    record = out["record"]
    jobs = [j for j in record["jobs"] if j["error"] is None]
    followed = references[0].routing
    for j in jobs:
        key = j["seed"], j["lr"]
        if key in followed:
            j["routing_gaps"] = routing_gaps(programs[key], followed[key])
    read = [j for j in jobs if "routing_gaps" in j]
    checks = config["moe_checks"]
    out["checks"].update(compare_routing(read, checks) if len(read) ==
                         len(jobs) else {n: math.inf for n in checks})
    out["notes"] += [f"{j['job']} seed {j['seed']} lr {j['lr']} expert "
                     f"gnorm gaps {j['routing_gaps']['expert_gnorm']} "
                     f"routing gaps {j['routing_gaps']['routing']}"
                     for j in read]

    a = config["assumed"]
    record["flops_per_step"] = train_step_flops(model, a["batch"], a["seq"])
    record["moe"] = dict(train.routing_counts(), held=model["moe"]["held"])
    return out
