"""Runs one cell of the on-chip benchmark once and prints its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  The last line of
standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced); the
numbers compared with the plain reference follow as the last lines of
standard error and, last, under ``checks``.  Without a TPU, or with fewer
chips than the cell needs, it exits non-zero and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="directory to keep the profiler trace in")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the system under test is not in {ROOT / 'src'}",
              file=sys.stderr)
        return 2

    from harness import runner, spec
    bench = spec.benchmark(ROOT)
    cell = spec.cell(bench, args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 3
    runner.enable_compile_cache()
    result = runner.run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_START, bench=bench,
                             keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
