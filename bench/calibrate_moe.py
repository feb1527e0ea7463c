"""Readings that the limits of a ``moe_sweep`` configuration are set from.

    python3 bench/calibrate_moe.py --config bench/configs/<name>.json \
        --seeds 64 --upper-seeds 4 --out chiprun_out/calibrate_moe.json
    python3 bench/calibrate_moe.py --config bench/configs/<name>.json \
        --replay chiprun_out/calibrate_moe.json[,more.json]

``calibrate.py``'s method for a configuration whose reference is
``mla_moe_lm``: for each of ``--seeds`` job seeds (learning rates
alternating over the traffic's plan) the program's ``run_training`` for
``--steps`` steps, exactly as a sweep job runs it, and the reference
followed from the same seed, the lower readings; for the first
``--upper-seeds``, the control (the reference in float8) and each of the
reference's faults in the program's place, the upper readings.  Each
subject is judged by the harness's own comparison and the
configuration's limits (``calibrate.judge_all``), with the routing
checks of ``moe_sweep`` (``compare_routing``) added to each verdict;
``--replay`` judges a recorded file again on the CPU.  Without
``--replay`` it needs the chip.
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
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import calibrate  # noqa: E402
from drivers.moe_sweep import compare_routing, routing_gaps  # noqa: E402
from drivers.payload_sweep import STEP_LOG, job_seeds  # noqa: E402
from run import with_limits, within  # noqa: E402

judge_loss = calibrate.judge


def judge(rows, subject: str, against: str, config: dict) -> dict:
    """``calibrate.judge``, and the routing checks on the same jobs."""
    v = judge_loss(rows, subject, against, config)
    jobs = [{"routing_gaps": routing_gaps(r["routing"][subject],
                                          r["routing"][against])}
            for r in rows]
    checks = compare_routing(jobs, config["moe_checks"])
    v["checks"].update(checks)
    v["correct"] = v["correct"] and within(with_limits(checks,
                                                       config["limits"]))
    return v


# judge_all judges every subject with the routing checks too
calibrate.judge = judge


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="moe4k")
    ap.add_argument("--seeds", type=int, default=64)
    ap.add_argument("--upper-seeds", type=int, default=4)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--base-seed", type=int, default=20261019)
    ap.add_argument("--out")
    ap.add_argument("--replay")
    args = ap.parse_args(argv)
    config = json.loads(Path(args.config).read_text())

    if args.replay:
        rows = [r for f in args.replay.split(",")
                for r in json.loads(Path(f).read_text())["rows"]]
        print(json.dumps(calibrate.judge_all(rows, config)["summary"],
                         indent=1))
        return 0

    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate_moe: no TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from drivers.moe_sweep import program_model
    from drivers.payload_sweep import register
    from reference.mla_moe_lm import FAULTS, Reference
    from repro.launch.train import run_training

    lrs = json.loads((BENCH / "traffic" / f"{args.traffic}.json")
                     .read_text())["lrs"]
    a = config["assumed"]
    model = program_model(config)
    arch = register(model)
    variants = {"reference": {}, "control_fp8": {"precision": "fp8"}}
    variants.update({f"fault_{f}": {"fault": f} for f in FAULTS})
    built = {}

    def follow(name, seed, lr):
        if name not in built:
            built[name] = Reference(model, config["optimizer"],
                                    config["data"], a["batch"], a["seq"],
                                    **variants[name])
        traj = list(built[name].follow(seed, lr, args.steps))
        return traj, built[name].routing[seed, lr]

    def program(seed, lr):
        log = io.StringIO()
        with contextlib.redirect_stdout(log):
            r = run_training(arch, smoke=False, steps=args.steps,
                             batch=a["batch"], seq=a["seq"], lr=lr,
                             seed=seed, log_every=1,
                             quantized_moments=a["quantized_moments"])
        return ([r.losses, [float(m.group(3))
                            for m in STEP_LOG.finditer(log.getvalue())]],
                {"counts": r.moe_counts, "expert_gnorms": r.moe_expert_gnorms})

    rows = []
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    for i, seed in enumerate(job_seeds(args.base_seed, args.seeds)):
        lr = lrs[i % len(lrs)]
        row = {"seed": seed, "lr": lr, "traj": {}, "routing": {},
               "seconds": {}}
        names = ["reference"] + (list(variants)[1:]
                                 if i < args.upper_seeds else [])
        for name in ["program"] + names:
            t = time.perf_counter()
            row["traj"][name], row["routing"][name] = (
                program(seed, lr) if name == "program"
                else follow(name, seed, lr))
            row["seconds"][name] = time.perf_counter() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
        out.write_text(json.dumps({"rows": rows}, indent=1))

    result = calibrate.judge_all(rows, config)
    out.write_text(json.dumps({"rows": rows, **result}, indent=1))
    print(json.dumps(result["summary"], indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
