"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus human-readable tables
before the CSV block).

    PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    results = []
    failures = []
    from benchmarks import (bench_auctions, bench_distributed,
                            bench_figure3, bench_gis, bench_kernels,
                            bench_marketplace, bench_roofline,
                            bench_scale, bench_scheduler,
                            bench_secondary, bench_telemetry,
                            bench_tournament)
    mods = [("figure3 (paper Fig.3, GUSTO deadline trial)", bench_figure3),
            ("scheduler tables (strategies / scale / faults)",
             bench_scheduler),
            ("marketplace (N concurrent brokers, contended economy)",
             bench_marketplace),
            ("auctions (negotiated contracts vs posted prices)",
             bench_auctions),
            ("GIS staleness (view TTL x site churn)", bench_gis),
            ("scale (array core: jobs x users x variant + 100k/1M tier)",
             bench_scale),
            ("secondary market (resale on/off x brokers, price discovery)",
             bench_secondary),
            ("strategy tournament (registry zoo x 4 market regimes)",
             bench_tournament),
            ("telemetry (tracer overhead, traced vs untraced)",
             bench_telemetry),
            ("distributed (wire loopback vs per-domain processes)",
             bench_distributed),
            ("kernels (pallas vs oracle)", bench_kernels),
            ("roofline (dry-run 3-term table)", bench_roofline)]
    # No JAX backend may start in this process before bench_distributed
    # forks its domain processes: on a TPU host the parent would hold the
    # chip.  The MoE crossover dry run (512 placeholder devices) is run on
    # its own: python -m benchmarks.bench_moe_crossover
    for title, mod in mods:
        print(f"\n===== {title} =====")
        try:
            results.extend(mod.main())
        except Exception:
            traceback.print_exc()
            failures.append(title)

    print("\nname,us_per_call,derived")
    for name, us, derived in results:
        print(f"{name},{us:.1f},{derived}")
    if failures:
        print(f"\nFAILED sections: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
