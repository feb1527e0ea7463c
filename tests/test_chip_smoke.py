"""chip_smoke.py on the CPU: its engine phase at smoke_config size, its
result checks, and its refusal to run without a TPU."""
import importlib.util
import math
import os

import pytest

from repro.configs import smoke_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke_run(chip_smoke, tmp_path_factory):
    journal = str(tmp_path_factory.mktemp("chip_smoke") / "journal.jsonl")
    eng, report = chip_smoke.run_engine(smoke=True, steps=4, batch=2,
                                        seq=32, journal_path=journal)
    return eng, report, journal


def test_engine_phase_runs_every_job_to_done(chip_smoke, smoke_run):
    eng, report, _ = smoke_run
    vocab = smoke_config(chip_smoke.ARCH).vocab_size
    assert chip_smoke.check(eng, report, vocab) == []
    assert report.n_done == report.n_jobs == len(chip_smoke.LRS)
    results = sorted((j.result for j in eng.jobs.values()),
                     key=lambda r: r["lr"])
    assert [r["lr"] for r in results] == sorted(chip_smoke.LRS)
    for r in results:
        assert len(r["losses"]) == len(r["step_seconds"]) == 4
        # a job compiles its step unless an earlier one left it compiled
        assert (r["compile_seconds"] > 0) != r["step_reused"]
    # the jobs differ in lr alone, which the step takes as data
    assert any(r["step_reused"] for r in results)
    # same seed, same init, same batch: lr only touches the update
    assert results[0]["losses"][0] == results[1]["losses"][0]
    assert abs(results[0]["losses"][0] - math.log(vocab)) < 1.0


def test_check_names_every_broken_claim(chip_smoke, smoke_run):
    eng, report, _ = smoke_run
    job = next(iter(eng.jobs.values()))
    saved = job.result
    try:
        job.result = dict(saved, losses=[5.0, float("nan")])
        bad = chip_smoke.check(eng, report, 512)
    finally:
        job.result = saved
    assert any("non-finite" in b for b in bad)
    assert any("step-0 losses differ" in b for b in bad)
    assert any("not within 1 nat" in b for b in bad)


def test_payload_failure_stops_the_plan_and_keeps_its_traceback(
        chip_smoke, tmp_path):
    journal = str(tmp_path / "journal.jsonl")
    # a sequence length of 0 makes the first payload raise
    eng, report = chip_smoke.run_engine(smoke=True, steps=2, batch=2, seq=0,
                                        journal_path=journal)
    assert report.n_done == 0
    assert report.stall_reason == "max_attempts_exhausted"
    reasons = chip_smoke.failure_reasons(journal)
    assert len(reasons) == 2
    assert "Traceback (most recent call last)" in reasons[0]
    assert "not run: job" in reasons[1]
    assert chip_smoke.check(eng, report, 512)


def test_main_refuses_to_run_without_a_tpu(chip_smoke, capsys):
    assert chip_smoke.main() == 1
    captured = capsys.readouterr()
    assert "no TPU" in captured.err
    assert captured.out == ""


def test_compile_cache_defers_to_the_environment(monkeypatch, tmp_path):
    import jax
    from repro.launch.compile_cache import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = use_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
