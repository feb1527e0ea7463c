"""The benchmark's arithmetic and its description, on the CPU."""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.append(str(BENCH))

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")


# -- train_tokens_per_s: credit by the part of each job inside the window

@pytest.mark.parametrize("jobs, expected", [
    # wholly inside: all its tokens
    ([(2.0, 6.0)], 1000.0),
    # straddles the close at 10: the 2 of its 4 seconds inside
    ([(8.0, 12.0)], 500.0),
    # straddles the open at 0 and the close: 10 of its 20 seconds
    ([(-5.0, 15.0)], 500.0),
    # wholly outside: nothing
    ([(10.0, 14.0)], 0.0),
    # back to back, the second straddling; gaps earn nothing
    ([(0.5, 4.5), (5.0, 13.0)], 1000.0 + 625.0),
])
def test_credited_tokens(jobs, expected):
    from drivers.payload_sweep import credited_tokens
    recs = [{"start": s, "end": e, "tokens": 1000, "error": None}
            for s, e in jobs]
    assert credited_tokens(recs, 0.0, 10.0) == pytest.approx(expected)


def test_failed_job_earns_nothing():
    from drivers.payload_sweep import credited_tokens
    recs = [{"start": 1.0, "end": 2.0, "tokens": 1000, "error": "boom"}]
    assert credited_tokens(recs, 0.0, 10.0) == 0.0


# -- which steps and jobs each check compares

class _Fixed:
    def follow(self, seed, lr, steps):
        return [10.0, 9.0, 8.0][:steps], [2.0, 4.0, 8.0][:steps]


def test_compare_reads_each_check_over_its_steps():
    from drivers.payload_sweep import compare
    checks = {"loss_gap": {"of": "loss", "steps": [0, 1]},
              "gnorm_step2": {"of": "gnorm", "steps": [2]},
              "loss_step2_low_lr": {"of": "loss", "steps": [2],
                                    "lrs": [3e-4]}}
    job = {"seed": 1, "lr": 1e-3, "losses": [10.5, 9.0, 7.0],
           "gnorms": [2.0, 5.0, 9.0]}
    low = dict(job, lr=3e-4, losses=[10.0, 9.0, 8.25])
    assert compare([job, low], _Fixed(), checks) == {
        "loss_gap": 0.5, "gnorm_step2": 0.125, "loss_step2_low_lr": 0.25}
    assert job["gnorm_gaps"] == [0.0, 0.25, 0.125]
    short = dict(job, losses=[10.0, 9.0])
    assert compare([short], _Fixed(), checks)["loss_gap"] == float("inf")


# -- the FLOP count of the cut configuration

def test_flops_of_the_cut_config():
    from flops import matmul_params, train_step_flops
    model = json.loads((BENCH / "configs" / "sweep-stablelm-1.6b.json")
                       .read_text())["model"]
    d, f, V, L, H, hd = 2048, 5632, 100352, 12, 32, 64
    # q, k, v, o and the three SwiGLU matrices per layer, and the head
    assert matmul_params(model) == L * (4 * d * d + 3 * d * f) + d * V \
        == 822_083_584
    per_token = 2 * 822_083_584 + L * 2 * 1024 * H * hd
    assert train_step_flops(model, 2, 1024) == 3 * per_token * 2048 \
        == 10_411_000_725_504


def test_peak_table_refuses_unknown_devices():
    import devices
    assert devices.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(devices.UnknownDevice):
        devices.peak("cpu")


# -- job seeds and the plan

def test_job_seeds_follow_the_seed():
    from drivers.payload_sweep import job_seeds, plan_text
    from repro.core import parse_plan
    big = 2 ** 31 + 7
    assert job_seeds(big, 4) == job_seeds(big, 4)
    assert job_seeds(big, 4) != job_seeds(big + 1, 4)
    seeds = job_seeds(big, 3)
    points = parse_plan(plan_text("x", seeds, [1e-3, 3e-4], 4)).points()
    assert [(p["seed"], p["lr"]) for p in points] == [
        (s, lr) for s in seeds for lr in (1e-3, 3e-4)]


# -- BENCHMARK.json keeps to its limits, and every name finds its file

def test_names_and_units():
    entries = (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
               + SPEC["per_layer"])
    names = [e["name"] for e in entries]
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        group_names = [e["name"] for e in SPEC[group]]
        assert len(set(group_names)) == len(group_names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for e in SPEC["configs"] + SPEC["workloads"]:
        assert LINE.match(e["why"])
    for c in SPEC["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])


def test_every_name_finds_its_file():
    for c in SPEC["configs"]:
        config = json.loads((ROOT / c["file"]).read_text())
        assert config["source"] == c["source"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert all(config["model"][k] != config["published"][k]
                   for k in c["reduced"])
        assert (BENCH / "drivers" / f"{config['driver']}.py").exists()
        assert (BENCH / "reference" / f"{config['reference']}.py").exists()
        assert all(v is not None for v in config["limits"].values())
    for w in SPEC["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()


def test_every_cell_reports_what_it_must():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in SPEC["workloads"]:
        cell = w["name"]
        reported = [n for n, m in e2e.items()
                    if cell in m.get("workloads", [cell])]
        assert "setup_s" in reported and len(reported) >= 2
        layers = [m for m in SPEC["per_layer"]
                  if cell in m.get("workloads", [cell])]
        assert layers
        assert all(m["moves"] in reported for m in layers)


# -- calibrate.py judges a recorded subject by the harness's comparison

def test_calibrate_judges_recorded_subjects():
    import calibrate
    config = {"checks": {"loss_gap": {"of": "loss", "steps": [0, 1]},
                         "loss_gap_step2": {"of": "loss", "steps": [2],
                                            "lrs": [3e-4]}},
              "limits": {"loss_gap": 0.01, "loss_gap_step2": 0.01}}
    ref = [[10.0, 9.0, 8.0], [3.0, 2.0, 1.0]]
    late = [[10.0, 9.0, 8.5], [3.0, 2.0, 1.0]]
    rows = [{"seed": s, "lr": lr, "traj": {"reference": ref,
                                           "program": ref,
                                           "fault_no_moments": late}}
            for s, lr in ((1, 1e-3), (2, 3e-4))]
    out = calibrate.judge_all(rows, config)["summary"]
    assert out["program (runs of two)"]["not_correct"] == 0
    # the late fault shows at step 2 only, which the lr 3e-4 job reads
    assert out["fault_no_moments"] == {"n": 2, "not_correct": 1,
                                       "loss_gap": (0.0, 0.0),
                                       "loss_gap_step2": (0.5, 0.5)}
    assert out["fault_no_moments (runs of two)"]["not_correct"] == 1
