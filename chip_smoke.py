"""Bring-up smoke: the broker's real payload path on one TPU chip.

Builds what ``examples/grid_sweep.py`` builds: a one-resource grid (the
chip, one slot, so no straggler duplicate can share it), a trade server,
a ``LocalExecutor`` and a ``Dispatcher``, and a ``NimrodG`` engine with
a journal.  The engine runs a two-job plan, gemma3-1b at two learning
rates from one seed, through ``NimrodG.run_local``; each job trains the
published widths for a few steps on the chip.  The script checks the
results, prints what the run measured, and ends with one JSON line.

    python chip_smoke.py

It exits non-zero, and prints no JSON, when JAX's first device is not a
TPU or when any phase fails.
"""
from __future__ import annotations

import json
import math
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
JOURNAL = os.path.join(ROOT, "chiprun_out", "chip_smoke", "journal.jsonl")

ARCH = "gemma3-1b"
LRS = (1e-3, 3e-4)
SEED = 0
STEPS = 8
# batch x seq at which the compiled full-width step fits one v5e chip
# (memory_analysis for a described v5e: ~14.6 GB with donation and int8
# moments); seq 1024 > window 512 runs the banded local attention
BATCH, SEQ = 2, 1024


def run_engine(*, smoke: bool, steps: int, batch: int, seq: int,
               journal_path: str):
    """Run the plan through ``NimrodG.run_local``.

    Returns ``(engine, report)``.  ``smoke=True`` trains the reduced
    widths of ``smoke_config`` instead of the published ones."""
    from repro.core import (Dispatcher, Journal, JobSpec, LocalExecutor,
                            NimrodG, PriceSchedule, ResourceDirectory,
                            ResourceSpec, SchedulerConfig, TradeServer,
                            UserRequirements, parse_plan, substitute)
    from repro.launch.train import run_training

    lrs = " ".join(repr(lr) for lr in LRS)
    plan = parse_plan(f"""
parameter lr float select anyof {lrs}
task main
    execute train --arch {ARCH} --lr $lr --seed {SEED}
endtask
""")
    directory = ResourceDirectory()
    directory.register(ResourceSpec(name="chip-0", site="local", chips=1,
                                    slots=1, base_price=1.0,
                                    mtbf_hours=float("inf")))
    trade = TradeServer(directory,
                        {"chip-0": PriceSchedule(directory.spec("chip-0"))})
    executor = LocalExecutor(directory, max_workers=1)
    disp = Dispatcher(executor, directory)

    # the first payload failure ends the experiment: every later job
    # refuses to start instead of compiling the same program again
    failed = []

    def make_payload(point):
        def run():
            if failed:
                raise RuntimeError(f"not run: job {failed[0]} failed first")
            try:
                r = run_training(ARCH, smoke=smoke, steps=steps, batch=batch,
                                 seq=seq, lr=point["lr"], seed=SEED,
                                 quantized_moments=True, verbose=False)
            except Exception:
                failed.append(point["lr"])
                raise
            return {"lr": point["lr"], "losses": r.losses,
                    "compile_seconds": r.compile_seconds,
                    "step_reused": r.step_reused,
                    "step_seconds": r.step_seconds}
        return run

    jobs = []
    for i, point in enumerate(plan.points()):
        jid = f"j{i:05d}"
        jobs.append(JobSpec(job_id=jid, experiment="chip-smoke", point=point,
                            steps=tuple(substitute(s, point, jid)
                                        for s in plan.task),
                            est_seconds_base=300.0,
                            payload=make_payload(point)))

    if os.path.exists(journal_path):
        os.remove(journal_path)          # one run per journal
    journal = Journal(journal_path)
    req = UserRequirements(deadline=time.time() + 3600.0, budget=10.0,
                           strategy="time")
    eng = NimrodG("chip-smoke", jobs, req, directory, trade, disp,
                  sim=None, journal=journal,
                  sched_cfg=SchedulerConfig(interval=0.2, max_attempts=1))
    try:
        report = eng.run_local(wall_timeout=900.0)
    finally:
        executor.shutdown()
        journal.close()
    return eng, report


def check(eng, report, vocab_size: int) -> list:
    """What must hold of a finished run; returns the broken claims."""
    bad = []
    if report.n_done != report.n_jobs:
        bad.append(f"{report.n_done}/{report.n_jobs} jobs done "
                   f"(stall={report.stall_reason})")
    if not report.within_budget:
        bad.append(f"spend {report.total_cost} over budget {report.budget}")
    results = [j.result for j in eng.jobs.values() if j.result]
    for r in results:
        losses = r["losses"]
        if not all(math.isfinite(x) for x in losses):
            bad.append(f"lr={r['lr']}: non-finite loss in {losses}")
        elif not losses[-1] < losses[0]:
            bad.append(f"lr={r['lr']}: loss did not fall: {losses}")
    firsts = {r["losses"][0] for r in results}
    if len(firsts) > 1:
        bad.append(f"step-0 losses differ across jobs: {sorted(firsts)}")
    uniform = math.log(vocab_size)
    for first in firsts:
        if abs(first - uniform) > 1.0:
            bad.append(f"step-0 loss {first} not within 1 nat of "
                       f"ln({vocab_size}) = {uniform:.4f}")
    return bad


def failure_reasons(journal_path: str) -> list:
    from repro.core.persistence import load_events
    return [f"{ev['job_id']}: {ev['reason']}"
            for ev in load_events(journal_path) if ev["kind"] == "FAIL"]


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    warm = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    cfg = get_config(ARCH)
    print(f"device: {dev.device_kind} x{len(jax.devices())} "
          f"({dev.platform})", flush=True)
    print(f"compile cache: {cache_dir} ({'warm' if warm else 'cold'})")
    print(f"model: {ARCH} d_model={cfg.d_model} heads={cfg.num_heads} "
          f"kv_heads={cfg.num_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"window={cfg.window_size} params={cfg.param_count():,}")
    print(f"depth: {cfg.num_layers} layers, no cut")
    print(f"plan: lr in {LRS}, seed {SEED}, {STEPS} steps of "
          f"{BATCH}x{SEQ} tokens, int8 moments", flush=True)

    eng, report = run_engine(smoke=False, steps=STEPS, batch=BATCH, seq=SEQ,
                             journal_path=JOURNAL)
    print(report.summary())
    print(f"journal: {os.path.relpath(JOURNAL, ROOT)}")
    for job in sorted(eng.jobs.values(), key=lambda j: j.job_id):
        r = job.result
        if not r:
            print(f"{job.job_id} lr={job.spec.point['lr']}: "
                  f"{job.status.name}, no result")
            continue
        steady = statistics.median(r["step_seconds"][1:])
        print(f"{job.job_id} lr={r['lr']}: {job.status.name} "
              f"compile_s={r['compile_seconds']!r} "
              f"step_reused={r['step_reused']} "
              f"first_step_s={r['step_seconds'][0]!r} "
              f"steady_step_s={steady!r} "
              f"tokens_per_s={BATCH * SEQ / steady!r}")
        print(f"  losses: {' '.join(repr(x) for x in r['losses'])}")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')} "
          f"of bytes_limit {stats.get('bytes_limit')}", flush=True)

    bad = check(eng, report, cfg.vocab_size)
    if bad:
        for reason in failure_reasons(JOURNAL):
            print(f"payload failure {reason}", file=sys.stderr)
        for b in bad:
            print(f"chip_smoke: FAILED: {b}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
