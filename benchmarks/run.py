"""Benchmark driver: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only speed,accuracy,...]

Emits ``name,us_per_call,derived`` CSV rows (benchmarks/common.CsvEmitter).
Datasets are cached in results/bench_data/ — the first run pays the build.
"""
from __future__ import annotations

import argparse
import time
import traceback

from benchmarks.common import CsvEmitter
from repro.launch.compile_cache import enable_compile_cache

SECTIONS = [
    ("sampler", "bench_sampler", "Fig 3/8: clip distribution + sampler"),
    ("kernels", "bench_kernels", "Pallas kernels vs oracles"),
    ("speed", "bench_speed", "Fig 7: CAPSim vs O3-oracle wall time"),
    ("training", "bench_training", "Fig 9: train/val loss curve"),
    ("accuracy", "bench_accuracy", "Fig 10: CAPSim vs LSTM vs no-ctx"),
    ("generalization", "bench_generalization", "Fig 11: 6x6 set matrix"),
    ("params", "bench_params", "Table III: microarch parameter sweep"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section names")
    args = ap.parse_args()
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    emit = CsvEmitter()
    print("name,us_per_call,derived")
    failures = []
    for name, module, desc in SECTIONS:
        if only and name not in only:
            continue
        print(f"# === {name}: {desc} ===")
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{module}", fromlist=["run"])
            mod.run(emit)
        except Exception as e:  # noqa: BLE001 — keep the suite running
            traceback.print_exc()
            failures.append((name, str(e)))
        print(f"# === {name} done in {time.time()-t0:.0f}s ===")
    if failures:
        print("# FAILURES:", failures)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
