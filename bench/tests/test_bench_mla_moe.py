"""The DeepSeek-V2-Lite sweep at a tiny size on the CPU: the program's
jobs against ``reference/mla_moe_lm.py``, through the ``moe_sweep``
driver, and the reference's faults and float8 control judged by the
harness's own comparison.

The tiny configuration (``data/tiny-mla-moe.json``) holds experts 0-3 of
a router over 16, top-4, with a capacity that drops pairs, and computes
in float32.  Its limits are set from its own readings on the CPU (40 job
seeds of the program, 12 of the control and of each fault, through
``calibrate_moe.py``'s method): the program's largest gaps are 1e-6 nats
and 1.2e-4 at steps 0-1, 2.8e-4 nats and 5.0e-4 at step 2 (the int8
moments amplify rounding there, and the logged gradient norm has three
decimals); each fault and the control exceed a limit, the smallest
reading that does being 7.1e-4 nats at step 2 without moments.  The
routing checks (16 program seeds, 8 of the control and of each fault):
the program reads 0 on both, the routing as the reference's and the
experts' gradient norm to float32 rounding; the control reads at least
0.031 (experts' gradient norm) and 0.029 (routing counts), renormalised
gates at least 0.63 on the experts' gradient norm, the other share at
least 0.20 on the routing counts.
"""
from __future__ import annotations

import contextlib
import io
import json
import math
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.append(str(BENCH))

CONFIG = json.loads((BENCH / "tests" / "data" / "tiny-mla-moe.json")
                    .read_text())
TRAFFIC = json.loads((BENCH / "traffic" / "moe4k.json").read_text())
SEED = 2 ** 31 + 4321
STEPS = 3


def _model():
    from drivers.moe_sweep import program_model
    return program_model(CONFIG)


def _reference(**kw):
    from reference.mla_moe_lm import Reference
    a = CONFIG["assumed"]
    return Reference(_model(), CONFIG["optimizer"], CONFIG["data"],
                     a["batch"], a["seq"], **kw)


@pytest.fixture(scope="module")
def jobs():
    """Two jobs of the program, one at each learning rate of the plan."""
    from drivers.payload_sweep import STEP_LOG, job_seeds, register
    from repro.launch.train import run_training
    arch = register(_model())
    a = CONFIG["assumed"]
    out = []
    for seed, lr in zip(job_seeds(SEED, 2), TRAFFIC["lrs"]):
        log = io.StringIO()
        with contextlib.redirect_stdout(log):
            r = run_training(arch, smoke=False, steps=STEPS,
                             batch=a["batch"], seq=a["seq"], lr=lr,
                             seed=seed, log_every=1, quantized_moments=True)
        out.append({"seed": seed, "lr": lr, "losses": r.losses,
                    "gnorms": [float(m.group(3)) for m in
                               STEP_LOG.finditer(log.getvalue())],
                    "routing": {"counts": r.moe_counts,
                                "expert_gnorms": r.moe_expert_gnorms}})
    return out


def _checks(jobs, reference) -> dict:
    """The harness's loss and norm checks and the driver's routing
    checks, each beside its limit."""
    from drivers.moe_sweep import compare_routing, routing_gaps
    from drivers.payload_sweep import compare
    from run import with_limits
    checks = compare([dict(j) for j in jobs], reference, CONFIG["checks"])
    checks.update(compare_routing(
        [{"routing_gaps": routing_gaps(
            j["routing"], reference.routing[j["seed"], j["lr"]])}
         for j in jobs], CONFIG["moe_checks"]))
    return with_limits(checks, CONFIG["limits"])


def _judge(jobs, reference) -> bool:
    from run import within
    return within(_checks(jobs, reference))


def _in_programs_place(variant, jobs):
    """A reference variant's trajectories for the program's jobs."""
    out = []
    for j in jobs:
        losses, gnorms = variant.follow(j["seed"], j["lr"], STEPS)
        out.append(dict(j, losses=losses, gnorms=gnorms,
                        routing=variant.routing[j["seed"], j["lr"]]))
    return out


def test_program_matches_the_reference(jobs):
    assert _judge(jobs, _reference())


@pytest.mark.parametrize("fault, check", [
    ("renorm_gates", "expert_gnorm_gap"), ("other_share", "routing_gap")])
def test_routing_checks_catch_the_expert_faults(fault, check, jobs):
    """Gates and share of the routed experts are seen by the routing
    checks themselves."""
    subject = _in_programs_place(_reference(fault=fault), jobs)
    c = _checks(subject, _reference())[check]
    assert c["value"] > c["limit"]


def test_compare_routing_reads_nothing_as_infinite():
    from drivers.moe_sweep import compare_routing, routing_gaps
    counts = {"moe_routed": 10, "moe_kept": 8, "moe_dropped": 2,
              "moe_max_load": 4}
    one = {"counts": [counts], "expert_gnorms": [2.0]}
    other = {"counts": [dict(counts, moe_kept=6, moe_dropped=4)],
             "expert_gnorms": [2.5]}
    gaps = routing_gaps(other, one)
    assert gaps == {"expert_gnorm": [0.25], "routing": [0.25]}
    checks = {"g": {"of": "expert_gnorm", "steps": [0]},
              "r": {"of": "routing", "steps": [0]}}
    assert compare_routing([{"routing_gaps": gaps}], checks) == {
        "g": 0.25, "r": 0.25}
    # a job short of a step, or no job at all, passes nothing
    late = {"g": {"of": "expert_gnorm", "steps": [1]}}
    assert compare_routing([{"routing_gaps": gaps}], late) == {
        "g": math.inf}
    assert compare_routing([], checks) == {"g": math.inf, "r": math.inf}
    # a count the reference reads as 0 matches only 0
    none = {"counts": [dict(counts, moe_max_load=0)], "expert_gnorms": [2.0]}
    assert routing_gaps(one, none)["routing"] == [math.inf]
    assert routing_gaps(none, none)["routing"] == [0.0]


@pytest.mark.parametrize("variant", [
    {"fault": "unchanged"}, {"fault": "half_batch"}, {"fault": "drop_grad"},
    {"fault": "no_moments"}, {"fault": "renorm_gates"},
    {"fault": "other_share"}, {"precision": "fp8"}])
def test_faults_and_control_are_not_correct(variant, jobs):
    subject = _in_programs_place(_reference(**variant), jobs)
    assert not _judge(subject, _reference())


def test_the_driver_runs_the_cell(jobs):
    """The whole run through ``moe_sweep``: correct, with the MLA/MoE
    FLOP count and the routing tally in the record."""
    from drivers import moe_sweep
    from metrics import moe_drop_share, moe_load_imbalance
    from reference.mla_moe_lm import Reference
    from run import with_limits, within
    out = moe_sweep.run(config=CONFIG, traffic=TRAFFIC, seed=SEED,
                        seconds=8.0, trace=False, reference_cls=Reference,
                        peak=None, t_start=time.perf_counter())
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert within(with_limits(out["checks"], CONFIG["limits"])), out["checks"]
    assert {"expert_gnorm_gap", "routing_gap"} <= set(out["checks"])
    rec = out["record"]
    a = CONFIG["assumed"]
    assert rec["flops_per_step"] == moe_sweep.train_step_flops(
        _model(), a["batch"], a["seq"])
    m = rec["moe"]
    # set-up's one-step runs, then every step of every job in the window
    assert m["steps"] >= 2 + STEPS * len(rec["jobs"]) and m["held"] == 4
    assert m["moe_routed"] == m["moe_kept"] + m["moe_dropped"]
    assert 0 < moe_drop_share.read(rec) < 100
    assert 1 <= moe_load_imbalance.read(rec) <= 4


def test_flops_of_the_cell():
    """At 6 MoE layers, 2 x 4096 tokens: attention and the dense layer in
    full, 6 x 4 / 64 of an expert per token."""
    from drivers.moe_sweep import matmul_params, program_model, \
        train_step_flops
    config = json.loads((BENCH / "configs" / "sweep-deepseek-v2-lite.json")
                        .read_text())
    model = program_model(config)
    d, H, V, R = 2048, 16, 12800, 512
    attn = d * H * 192 + d * (R + 64) + R * H * 256 + H * 128 * d
    expert = 3 * d * 1408
    moe = d * 64 + 2 * expert + 6 * 8 / 64 * expert
    assert matmul_params(model) == 7 * attn + 3 * d * 10944 + 6 * moe + d * V
    per_token = 2 * matmul_params(model) + 7 * 2 * 2048 * H * (192 + 128)
    assert math.isclose(train_step_flops(model, 2, 4096),
                        3 * per_token * 8192)


def test_program_model_keeps_the_published_widths():
    from drivers.moe_sweep import program_model
    config = json.loads((BENCH / "configs" / "sweep-deepseek-v2-lite.json")
                        .read_text())
    m = program_model(config)
    assert (m["d_model"], m["num_heads"], m["d_ff"]) == (2048, 16, 10944)
    assert m["mla"] == {"q_lora_rank": 0, "kv_lora_rank": 512,
                        "qk_nope_dim": 128, "qk_rope_dim": 64, "v_dim": 128}
    mo = m["moe"]
    assert (mo["num_experts"], mo["held"], mo["top_k"], mo["d_ff_expert"],
            mo["num_shared"], mo["norm_topk"]) == (64, 8, 6, 1408, 2, False)
    assert (m["num_layers"], m["vocab_size"]) == (7, 12800)
    with pytest.raises(ValueError):
        program_model(dict(config, scoring_func="sigmoid"))
