"""Readings that the limits of a sweep configuration are set from.

    python3 bench/calibrate.py --config bench/configs/<name>.json \
        --seeds 12 --upper-seeds 4 --out chiprun_out/calibrate.json
    python3 bench/calibrate.py --config bench/configs/<name>.json \
        --replay chiprun_out/calibrate.json[,more.json]

For each of ``--seeds`` job seeds (learning rates alternating over the
traffic's plan) it runs the program's ``run_training`` for ``--steps``
steps, exactly as a sweep job does, and follows the plain reference from
the same seed: the lower readings.  For the first ``--upper-seeds`` it
also follows the control (the reference in float8) and each planted
fault of the reference, put in the program's place: the upper readings.
Beside them, two witnesses of where the program's gaps come from: the
reference in bfloat16 against the float32 reference at the cell's size,
and, at ``--witness-layers`` layers, the program and the reference with
int8 and with float32 moments.

Every trajectory is recorded in ``--out``.  Each subject in the
program's place is then judged by the harness's own comparison
(``payload_sweep.compare``) against the configuration's ``limits``
(``run.within``); ``--replay`` judges a recorded file again on the CPU,
after the limits have changed.  The benchmark's own runs never run
this.  Without ``--replay`` it needs the chip, like ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from drivers.payload_sweep import STEP_LOG, compare, job_seeds  # noqa: E402
from run import with_limits, within  # noqa: E402


class Replay:
    """A reference that hands back each job seed's recorded trajectory,
    all of it, so that the gaps by step cover every recorded step; the
    checks read only the steps they name."""

    def __init__(self, trajs):
        self.trajs = trajs

    def follow(self, seed, lr, steps):
        return self.trajs[seed]


def judge(rows, subject: str, against: str, config: dict) -> dict:
    """``subject``'s trajectories in the program's place, one job per
    row, compared with ``against``'s by the harness's comparison and
    limits, on the checks that read a job at these learning rates."""
    jobs = [{"seed": r["seed"], "lr": r["lr"], "losses": r["traj"][subject][0],
             "gnorms": r["traj"][subject][1]} for r in rows]
    lrs = {r["lr"] for r in rows}
    applies = {name: c for name, c in config["checks"].items()
               if lrs & set(c.get("lrs", lrs))}
    checks = compare(jobs, Replay({r["seed"]: r["traj"][against]
                                   for r in rows}), applies)
    checks = with_limits(checks, config["limits"])
    return {"correct": within(checks),
            "checks": {k: c["value"] for k, c in checks.items()},
            "loss_gaps": [j["loss_gaps"] for j in jobs],
            "gnorm_gaps": [j["gnorm_gaps"] for j in jobs]}


PAIRS = {"program": "reference", "control_fp8": "reference",
         "witness_bf16": "reference",
         "witness.program_int8": "witness.reference_int8",
         "witness.program_float": "witness.reference_float"}


def judge_all(rows, config) -> dict:
    """Verdicts and gaps of every recorded subject, each job on its own
    and each two consecutive jobs (one at each learning rate of the
    plan) together as a run holds them; and the smallest and largest
    reading of each number over the seeds."""
    groups = [[r] for r in rows] + [rows[i:i + 2]
                                    for i in range(0, len(rows) - 1, 2)]
    verdicts = []
    for group in groups:
        for subject in group[0]["traj"]:
            against = PAIRS.get(subject, "reference"
                                if subject.startswith("fault_") else None)
            if against and all(subject in r["traj"] for r in group):
                v = judge(group, subject, against, config)
                verdicts.append(dict(v, seeds=[r["seed"] for r in group],
                                     lrs=[r["lr"] for r in group],
                                     subject=subject))
    summary = {}
    for v in verdicts:
        key = v["subject"] + (" (runs of two)" if len(v["seeds"]) > 1
                              else "")
        s = summary.setdefault(key, {"n": 0, "not_correct": 0})
        s["n"] += 1
        s["not_correct"] += not v["correct"]
        for k, x in v["checks"].items():
            lo, hi = s.get(k, (x, x))
            s[k] = (min(lo, x), max(hi, x))
    return {"verdicts": verdicts, "summary": summary}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="long")
    ap.add_argument("--lrs", help="learning rates to alternate over, "
                    "comma-separated; the traffic's plan where not given")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--upper-seeds", type=int, default=4)
    ap.add_argument("--witness-layers", type=int, default=4)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--base-seed", type=int, default=20261017)
    ap.add_argument("--out")
    ap.add_argument("--replay")
    args = ap.parse_args(argv)
    config = json.loads(Path(args.config).read_text())

    if args.replay:
        rows = [r for f in args.replay.split(",")
                for r in json.loads(Path(f).read_text())["rows"]]
        print(json.dumps(judge_all(rows, config)["summary"], indent=1))
        return 0

    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from drivers.payload_sweep import register
    from reference.dense_lm import FAULTS, Reference
    from repro.launch.train import run_training

    traffic = json.loads((BENCH / "traffic" / f"{args.traffic}.json")
                         .read_text())
    lrs = ([float(x) for x in args.lrs.split(",")] if args.lrs
           else traffic["lrs"])
    a = config["assumed"]
    model = config["model"]
    small = dict(model, name=f"{model['name']}-w{args.witness_layers}",
                 num_layers=args.witness_layers)
    arch, arch_small = register(model), register(small)
    opt, data = config["optimizer"], config["data"]

    built = {}

    def ref(name, m=model, **kw):
        """The reference variant ``name``, built once."""
        if name not in built:
            built[name] = Reference(m, opt, data, a["batch"], a["seq"], **kw)
        return built[name]

    def program(name, seed, lr, qm):
        log = io.StringIO()
        with contextlib.redirect_stdout(log):
            r = run_training(name, smoke=False, steps=args.steps,
                             batch=a["batch"], seq=a["seq"], lr=lr,
                             seed=seed, log_every=1, quantized_moments=qm)
        return [r.losses, [float(m.group(3))
                           for m in STEP_LOG.finditer(log.getvalue())]]

    variants = {"control_fp8": {"precision": "fp8"},
                "witness_bf16": {"precision": "bfloat16"}}
    variants.update({f"fault_{f}": {"fault": f} for f in FAULTS})
    witness = {
        "witness.program_int8": lambda s, lr: program(arch_small, s, lr, True),
        "witness.program_float": lambda s, lr: program(arch_small, s, lr,
                                                       False),
        "witness.reference_int8": lambda s, lr: list(
            ref("small_int8", small).follow(s, lr, args.steps)),
        "witness.reference_float": lambda s, lr: list(
            ref("small_float", small, quantized_moments=False).follow(
                s, lr, args.steps)),
    }

    rows = []
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    for i, seed in enumerate(job_seeds(args.base_seed, args.seeds)):
        lr = lrs[i % len(lrs)]
        row = {"seed": seed, "lr": lr, "traj": {}, "seconds": {}}

        def record(name, fn):
            t = time.perf_counter()
            row["traj"][name] = fn()
            row["seconds"][name] = time.perf_counter() - t

        record("program", lambda: program(arch, seed, lr,
                                          a["quantized_moments"]))
        record("reference", lambda: list(
            ref("reference").follow(seed, lr, args.steps)))
        if i < args.upper_seeds:
            for name, kw in variants.items():
                record(name, lambda: list(
                    ref(name, **kw).follow(seed, lr, args.steps)))
            for name, fn in witness.items():
                record(name, lambda: fn(seed, lr))
        rows.append(row)
        print(json.dumps(row), flush=True)
        out.write_text(json.dumps({"rows": rows}, indent=1))

    result = judge_all(rows, config)
    out.write_text(json.dumps({"rows": rows, **result}, indent=1))
    print(json.dumps(result["summary"], indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
