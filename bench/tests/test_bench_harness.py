"""The harness's whole run on the CPU, at a tiny size, with the chip check
skipped: the sound program is judged correct; the control (the
reference in float8 in the program's place) and each fault planted in
the program's train step are judged not correct.

The tiny configuration (``data/tiny-dense.json``) computes in float32,
so its limits are its own, set from its own readings: the program's
gaps to the reference are ~2e-6 nats and ~1e-4 (the logged gradient
norm has three decimals); the control and the faults read 2e-3 to 0.4.
Its checks have the names and steps of the sweep configuration's.
"""
from __future__ import annotations

import importlib.util
import io
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.append(str(BENCH))

SECONDS = 8.0
SEED = 2 ** 31 + 12345


def _bench_run():
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SPEC = {
    "configs": [{"name": "tiny-dense",
                 "file": "bench/tests/data/tiny-dense.json"}],
    "workloads": [{"name": "tiny-dense.short", "config": "tiny-dense",
                   "traffic": "short", "chips": 1}],
    "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}


def _run():
    import time
    return _bench_run().run_cell(SPEC, "tiny-dense.short", SEED, SECONDS,
                                 False, device_info={"platform": "cpu"},
                                 peak=None, t_start=time.perf_counter())


def _broken_step(kind):
    """A train-step builder with one fault planted, in the program's
    own terms."""
    import jax
    import jax.numpy as jnp
    from repro.optim import (apply_updates, init_opt_state,
                             linear_warmup_cosine)
    from repro.train import steps

    def make(cfg, opt_cfg, *, mesh=None, total_steps=10_000, warmup=100):
        sound = steps.make_train_step(cfg, opt_cfg, mesh=mesh,
                                      total_steps=total_steps, warmup=warmup)

        def train_step(params, opt_state, batch):
            if kind == "unchanged":
                _, _, metrics = sound(params, opt_state, batch)
                return params, opt_state, metrics
            if kind == "half_batch":
                half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
                return sound(params, opt_state, half)
            if kind == "no_moments":
                zeros = init_opt_state(params, opt_cfg)
                return sound(params, opt_state._replace(m=zeros.m,
                                                        v=zeros.v), batch)
            (_, metrics), grads = jax.value_and_grad(
                lambda p: steps.loss_fn(cfg, p, batch, mesh=mesh),
                has_aux=True)(params)
            grads = dict(grads, unembed=jnp.zeros_like(grads["unembed"]))
            scale = linear_warmup_cosine(opt_state.step + 1, warmup=warmup,
                                         total=total_steps)
            params, opt_state, om = apply_updates(params, grads, opt_state,
                                                  opt_cfg, scale)
            return params, opt_state, dict(metrics, **om)
        return train_step
    return make


def _control_training(config):
    """``run_training`` replaced by the reference in float8."""
    from repro.launch.train import TrainResult
    from reference.dense_lm import Reference
    a = config["assumed"]
    ref = Reference(config["model"], config["optimizer"], config["data"],
                    a["batch"], a["seq"], precision="fp8")

    def run_training(arch, *, steps, lr, seed, verbose=True, **_):
        losses, gnorms = ref.follow(seed, lr, steps)
        if verbose:
            for i, (l, g) in enumerate(zip(losses, gnorms)):
                print(f"step {i:5d} loss {l:8.4f} gnorm {g:8.3f}")
        return TrainResult(steps=steps, final_loss=losses[-1], losses=losses,
                           tokens_per_sec=0.0, compile_seconds=0.0,
                           step_seconds=[1e-3] * steps)
    return run_training


def test_sound_program_is_correct():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(r)[-2:] == ["checks", "notes"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "drop_grad",
                                   "no_moments"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    import repro.launch.train as train
    monkeypatch.setattr(train, "make_train_step", _broken_step(fault))
    r = _run()
    assert not r["correct"], r["checks"]
    assert r["checks"]["books_errors"]["value"] == 0


def test_control_is_not_correct(monkeypatch):
    import json
    import repro.launch.train as train
    config = json.loads((BENCH / "tests" / "data" / "tiny-dense.json")
                        .read_text())
    monkeypatch.setattr(train, "run_training", _control_training(config))
    r = _run()
    assert not r["correct"], r["checks"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_no_accelerator_prints_no_result(capsys):
    assert _bench_run().main(["--workload", "sweep-stablelm-1.6b.long",
                              "--seed", "1", "--seconds", "1",
                              "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""
