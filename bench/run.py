"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  ``BENCHMARK.json`` names the cell; the
cell names a configuration (``bench/configs/<name>.json``) and a traffic
mix (``bench/traffic/<traffic>.json``); the configuration names the
driver that runs it (``bench/drivers/<driver>.py``) and its plain
reference (``bench/reference/<reference>.py``).  With ``--trace 0`` the
line carries the cell's end-to-end metrics; with ``--trace 1`` its
per-layer metrics, each read by ``bench/metrics/<metric>.py``, and the
device's busy time from a profiler trace.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced) and, last, ``checks``: each number compared for ``correct``
beside its limit.  The same numbers end standard error.  Without a TPU,
or with fewer chips than the cell asks for, it prints no result and
exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (ROOT / "src", BENCH):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import devices  # noqa: E402


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(entry: dict, cell: str) -> bool:
    return cell in entry.get("workloads", [cell])


def cell_parts(spec: dict, cell_name: str):
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise SystemExit(f"unknown workload {cell_name!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[cell_name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return cell, config, traffic


def with_limits(values: dict, limits: dict) -> dict:
    return {name: {"value": value, "limit": limits[name]}
            for name, value in values.items()}


def within(checks: dict) -> bool:
    """Every number compared is finite and within its limit."""
    return all(c["limit"] is not None and math.isfinite(c["value"])
               and c["value"] <= c["limit"] for c in checks.values())


def run_cell(spec: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, *, device_info: dict, peak,
             t_start: float = T_START) -> dict:
    """Drive one run and assemble the result object; set-up is counted
    from ``t_start``."""
    cell, config, traffic = cell_parts(spec, cell_name)
    driver = load("drivers", config["driver"])
    reference = load("reference", config["reference"])
    out = driver.run(config=config, traffic=traffic, seed=seed,
                     seconds=seconds, trace=trace,
                     reference_cls=reference.Reference, peak=peak,
                     t_start=t_start)

    metrics = {}
    if trace:
        for m in spec["per_layer"]:
            if not applies(m, cell_name):
                continue
            value = load("metrics", m["name"]).read(out["record"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = out["end_to_end"]
        for m in spec["end_to_end"]:
            if applies(m, cell_name):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    checks = with_limits(out["checks"], config["limits"])
    correct = (out["failed"] == 0 and out["attempted"] > 0
               and within(checks))
    device = dict(device_info, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        t = out["trace"]
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    result["checks"] = checks
    result["notes"] = out["notes"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_json(ROOT / "BENCHMARK.json")
    cell, _, _ = cell_parts(spec, args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU: JAX's first device is {devs[0].platform!r} "
              f"({devs[0].device_kind})", file=sys.stderr)
        return 2
    if len(devs) < cell["chips"]:
        print(f"bench: cell {args.workload} needs {cell['chips']} chips, "
              f"JAX sees {len(devs)}", file=sys.stderr)
        return 2
    peak = devices.peak(devs[0].device_kind)
    # the program's cache directory ($JAX_COMPILATION_CACHE_DIR, else a
    # fixed path in the checkout), holding the small eager programs too
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}

    result = run_cell(spec, args.workload, args.seed, args.seconds,
                      bool(args.trace), device_info=info, peak=peak)
    for note in result.pop("notes"):
        print(f"bench: {note}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
