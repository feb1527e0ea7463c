"""Sweep cells: one researcher's plan of training jobs on one chip.

The plan (``parameter seed ...``, ``parameter lr ...``, one ``execute
train`` task) is expanded by the program's plan parser into jobs, and one
``NimrodG`` engine runs them through ``run_local``: a ``TradeServer``
prices the one resource, a ``LocalExecutor`` runs each job's payload on a
worker thread, and the payload is the program's own ``run_training`` at
the configuration's widths.  The benchmark only wraps each payload to
record its host-clock start and end and what ``run_training`` returns.

Set-up registers the configuration with the program's registry, runs
``run_training`` for one step at each learning rate of the plan (each lr
is its own compiled program, since the program bakes it into the step),
and builds the grid.  The window is ``run_local`` with ``wall_timeout``
set to the window's length: the engine stops dispatching when it closes,
and the run then waits for the job that straddles the close.

Correctness, once the window has closed and the peak memory is read:

- the books: every settled job settled once, for a payload that
  finished; no payload failed; the ledger's settled total equals the sum
  of the journal's settlements and the bank's record of the user's spend,
  exactly;
- the training: for every finished job, the loss and the global
  gradient norm (as ``run_training`` logs it before clipping) of the
  first steps against the plain reference (``bench/reference``) followed
  from the same job seed and lr.  Each number of the configuration's
  ``checks`` is the largest gap of one quantity over the steps it names,
  over the jobs at the learning rates it names (all, where it names
  none).
"""
from __future__ import annotations

import contextlib
import io
import re
import sys
import tempfile
import threading
import time
import types
from typing import Dict, List

import numpy as np

from devtrace import Capture, annotate, reduce as reduce_trace
from flops import train_step_flops

STEP_LOG = re.compile(r"^step\s+(\d+) loss\s+(\S+) gnorm\s+(\S+)\s*$", re.M)
USER = "researcher"


def register(model: dict) -> str:
    """Make the configuration an architecture of the program's registry,
    under its own name, and return that name."""
    from repro.configs import registry
    from repro.configs.base import ModelConfig
    kw = dict(model, layer_pattern=tuple(model["layer_pattern"]))
    name = kw["name"]
    mod_name = "bench_config_" + re.sub(r"\W", "_", name)
    mod = types.ModuleType(mod_name)
    mod.CONFIG = ModelConfig(**kw)
    sys.modules[mod_name] = mod
    registry._ARCH_MODULES[name] = mod_name
    return name


def job_seeds(seed: int, n: int) -> List[int]:
    """``n`` consecutive job seeds starting from one drawn from ``seed``."""
    base = int(np.random.SeedSequence(abs(seed)).generate_state(1)[0])
    base %= 2 ** 30
    return list(range(base, base + n))


def plan_text(arch: str, seeds: List[int], lrs: List[float],
              steps: int) -> str:
    return (f"parameter seed integer range from {seeds[0]} to {seeds[-1]} "
            f"step 1\n"
            f"parameter lr float select anyof "
            f"{' '.join(repr(float(x)) for x in lrs)}\n"
            f"task main\n"
            f"    execute train --arch {arch} --steps {steps} --lr $lr "
            f"--seed $seed\n"
            f"endtask\n")


def credited_tokens(jobs: List[dict], t0: float, t1: float) -> float:
    """Tokens of the finished jobs, each credited in proportion to the
    part of its payload interval that lies inside ``[t0, t1]``."""
    total = 0.0
    for j in jobs:
        if j.get("error") is not None:
            continue
        length = j["end"] - j["start"]
        inside = min(j["end"], t1) - max(j["start"], t0)
        if length > 0 and inside > 0:
            total += j["tokens"] * inside / length
    return total


def books_errors(journal_events: List[dict], ledger_settled: float,
                 bank_spend: float, jobs: List[dict]) -> List[str]:
    """What does not reconcile between journal, ledger, bank and the
    payloads that ran; empty when the books are exact."""
    from repro.core.persistence import left_sum
    bad = []
    done: Dict[str, int] = {}
    costs = []
    for ev in journal_events:
        if ev["kind"] == "DONE":
            done[ev["job_id"]] = done.get(ev["job_id"], 0) + 1
            costs.append(ev["cost"])
            if not ev["cost"] > 0:
                bad.append(f"{ev['job_id']} settled at cost {ev['cost']}")
        elif ev["kind"] == "FAIL":
            bad.append(f"{ev['job_id']} failed: {ev['reason'][:300]}")
    bad += [f"{j} settled {n} times" for j, n in done.items() if n != 1]
    finished = {j["job"] for j in jobs if j.get("error") is None}
    bad += [f"{j} settled without a finished payload"
            for j in done if j not in finished]
    journal_total = left_sum(costs)
    if not (journal_total == ledger_settled == bank_spend):
        bad.append(f"settled totals differ: journal {journal_total!r}, "
                   f"ledger {ledger_settled!r}, bank {bank_spend!r}")
    return bad


def compare(jobs: List[dict], reference, checks: dict) -> Dict[str, float]:
    """Each check's largest gap over its steps and over the jobs at its
    learning rates: a loss gap in nats, a gradient-norm gap as a share of
    the reference's; infinite where no job reached it.  Each job's gaps
    by step are kept in its record."""
    steps = 1 + max(s for c in checks.values() for s in c["steps"])
    out = {name: 0.0 for name in checks}
    read = set()
    for j in jobs:
        if len(j["losses"]) < steps or len(j["gnorms"]) < steps:
            return {name: float("inf") for name in checks}
        ref_loss, ref_gnorm = reference.follow(j["seed"], j["lr"], steps)
        j["loss_gaps"] = [abs(p - r) for p, r in zip(j["losses"], ref_loss)]
        j["gnorm_gaps"] = [abs(p - r) / r
                           for p, r in zip(j["gnorms"], ref_gnorm)]
        for name, c in checks.items():
            if j["lr"] not in c.get("lrs", [j["lr"]]):
                continue
            by_step = j["loss_gaps" if c["of"] == "loss" else "gnorm_gaps"]
            out[name] = max(out[name], *(by_step[s] for s in c["steps"]))
            read.add(name)
    # a number that no job reached compares nothing, and passes nothing
    return {name: v if name in read else float("inf")
            for name, v in out.items()}


def run(*, config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, reference_cls, peak, t_start: float) -> dict:
    """One run; returns what ``bench/run.py`` prints and the record the
    per-layer readers take."""
    from repro.core import (Dispatcher, GridBank, Journal, JobSpec,
                            LocalExecutor, NimrodG, PriceSchedule,
                            ResourceDirectory, ResourceSpec, SchedulerConfig,
                            TradeServer, UserRequirements, parse_plan,
                            substitute)
    from repro.core.persistence import load_events
    from repro.launch.train import run_training

    arch = register(config["model"])
    a = config["assumed"]
    batch, seq = a["batch"], a["seq"]
    steps = traffic["steps_per_job"]
    lrs = traffic["lrs"]
    seeds = job_seeds(seed, traffic["seeds_per_lr"])
    train_kw = dict(smoke=False, batch=batch, seq=seq,
                    quantized_moments=a["quantized_moments"])

    # every program the window runs, compiled or loaded from the cache
    for lr in lrs:
        run_training(arch, steps=1, lr=lr, seed=seeds[0], verbose=False,
                     **train_kw)

    g = config["grid"]
    directory = ResourceDirectory()
    directory.register(ResourceSpec(name=g["resource"], site="local",
                                    chips=g["chips"], slots=g["slots"],
                                    base_price=g["base_price"],
                                    mtbf_hours=float("inf")))
    trade = TradeServer(directory, {g["resource"]: PriceSchedule(
        directory.spec(g["resource"]))})
    executor = LocalExecutor(directory, max_workers=g["slots"])
    bank = GridBank()
    plan = parse_plan(plan_text(arch, seeds, lrs, steps))

    jobs: List[dict] = []
    lock = threading.Lock()
    closed = threading.Event()

    def make_payload(job_id: str, point: dict):
        def payload():
            if closed.is_set():
                # dispatched just before the close, started after it: the
                # engine has stopped and nothing after the window counts
                return None
            rec = {"job": job_id, "seed": point["seed"], "lr": point["lr"],
                   "tokens": steps * batch * seq, "error": None}
            log = io.StringIO()
            rec["start"] = time.perf_counter()
            with annotate(f"payload {job_id} start", trace):
                pass
            try:
                with annotate(f"payload {job_id}", trace), \
                        contextlib.redirect_stdout(log):
                    r = run_training(arch, steps=steps, lr=point["lr"],
                                     seed=point["seed"], log_every=1,
                                     **train_kw)
            except Exception as e:
                rec["end"] = time.perf_counter()
                rec["error"] = repr(e)
                with lock:
                    jobs.append(rec)
                raise
            rec["end"] = time.perf_counter()
            rec["compile_s"] = r.compile_seconds
            rec["step_s"] = list(r.step_seconds)
            rec["losses"] = list(r.losses)
            rec["gnorms"] = [float(m.group(3))
                             for m in STEP_LOG.finditer(log.getvalue())]
            with lock:
                jobs.append(rec)
            return {"losses": r.losses}
        return payload

    specs = []
    for i, point in enumerate(plan.points()):
        jid = f"j{i:05d}"
        specs.append(JobSpec(job_id=jid, experiment="sweep", point=point,
                             steps=tuple(substitute(s, point, jid)
                                         for s in plan.task),
                             est_seconds_base=traffic["est_seconds"],
                             payload=make_payload(jid, point)))

    with tempfile.TemporaryDirectory(prefix="bench_journal_") as tmp:
        journal = Journal(f"{tmp}/journal.jsonl")
        req = UserRequirements(user=USER,
                               deadline=time.time() + g["deadline_s"],
                               budget=g["budget"], strategy=g["strategy"])
        eng = NimrodG("sweep", specs, req, directory, trade,
                      Dispatcher(executor, directory), sim=None,
                      journal=journal, bank=bank,
                      sched_cfg=SchedulerConfig(interval=g["interval"]))
        capture = Capture() if trace else None
        try:
            if capture:
                capture.start()
            t0 = time.perf_counter()
            with annotate("window", trace):
                eng.run_local(poll=0.02, wall_timeout=seconds)
            closed.set()
            t1 = t0 + seconds
            trace_path = capture.stop() if capture else None
            executor.shutdown()
            journal.close()
            events = load_events(journal.path)
            trace_summary = reduce_trace(trace_path) if capture else None
        finally:
            if capture:
                capture.close()
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")

    jobs.sort(key=lambda j: j["start"])
    rate = credited_tokens(jobs, t0, t1) / seconds
    books = books_errors(events, eng.ledger.settled, bank.user_spend(USER),
                         jobs)
    failed = sum(1 for j in jobs if j["error"] is not None)

    ref = reference_cls(config["model"], config["optimizer"], config["data"],
                        batch, seq)
    checks = compare([j for j in jobs if j["error"] is None], ref,
                     config["checks"])
    checks["books_errors"] = float(len(books))

    flops = train_step_flops(config["model"], batch, seq)
    return {
        "attempted": len(jobs),
        "failed": failed,
        "end_to_end": {"train_tokens_per_s": rate, "setup_s": t0 - t_start},
        "checks": checks,
        "notes": books + [
            f"{j['job']} seed {j['seed']} lr {j['lr']} loss gaps "
            f"{j['loss_gaps']} gnorm gaps {j['gnorm_gaps']}"
            for j in jobs if "loss_gaps" in j],
        "memory_peak_bytes": memory_peak,
        "trace": trace_summary,
        "record": {"jobs": jobs, "t0": t0, "t1": t1, "seconds": seconds,
                   "flops_per_step": flops, "peak": peak,
                   "trace": trace_summary},
    }
