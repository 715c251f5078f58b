"""Readings of the comparison that decides ``correct``, for the program
as configured and for its lower-precision controls, on several seeds in
one process: the numbers the limits in the configuration files are set
from.

    python3 bench/tools/control.py --workload spec17.engine \
        --seeds 1 2 3 [--variants program int4 fault:half_batch_mean] \
        [--seconds 0]

Each variant drives the cell's traffic (an engine cell: one pass, or a
window of ``--seconds``) and the cell's own check reads its answers.
``program`` runs the cell as configured; ``int4`` runs the program on
the weights put on the per-channel int4 grid, and the check compares
its answers with the reference of the int8 weights the configuration
states; ``fault:<name>`` plants a fault of ``harness.faults`` under the
timed path.  For engine cells the tool also prints the spread of the
per-clip errors against the reference at each precision it offers.
"""
import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))


def _context(workload, conf, seed, bench, cfg=None):
    import jax
    import numpy as np

    from harness import runner, spec, weights
    traffic = spec.traffic(spec.cell(bench, workload)["traffic"])
    drv = spec.driver(traffic["kind"])
    ctx = runner.Context(seed=seed, conf=conf, traffic=traffic,
                         model=conf["model"],
                         cfg=(cfg if cfg is not None
                              else runner.model_config(conf)),
                         params=weights.make_params(seed, conf["model"]),
                         rng=np.random.default_rng(seed),
                         annotate=jax.profiler.TraceAnnotation)
    return drv, ctx


def _spread(err):
    import numpy as np
    q = np.quantile(err, [0.5, 0.99])
    return (f"max={float(err.max())!r} p99={float(q[1])!r} "
            f"median={float(q[0])!r}")


def engine_reading(workload, conf, seed, bench, variant, seconds,
                   cfg=None):
    import numpy as np

    from harness import ref_model
    undo = _fault(variant)
    drv, ctx = _context(workload, conf, seed, bench, cfg)
    stated = _served(ctx, variant)
    state = drv.setup(ctx)
    win = drv.window(state, seconds)
    ctx.params = stated            # the check's reference: the stated weights
    for precision in ref_model.PRECISIONS:
        clip, total = [], []
        for key, got, want in drv.answers(state, precision):
            (total if key.endswith("totals") else clip).append(
                drv.rel_errors(got, want))
        print(f"reading {workload} {variant} seed={seed} "
              f"reference={precision} clips: "
              f"{_spread(np.concatenate(clip))} totals: "
              f"{_spread(np.concatenate(total))}", flush=True)
    checks = drv.check(state, win)
    if undo:
        undo()
    return checks


def service_reading(workload, conf, seed, bench, variant, seconds,
                    cfg=None):
    drv, ctx = _context(workload, conf, seed, bench, cfg)
    stated = _served(ctx, variant)
    state = drv.setup(ctx)         # the service holds the served weights
    ctx.params = stated            # the check's reference: the stated weights
    undo = _fault(variant)         # planted after the warm-up
    win = drv.window(state, seconds)
    checks = drv.check(state, win)
    if undo:
        undo()
    return checks


def _served(ctx, variant):
    """Put the weights the variant serves into ``ctx``; returns the
    stated ones."""
    from harness import ref_model
    stated = ctx.params
    if variant == "int4":
        ctx.params = ref_model.fake_quant(stated, 4)
    elif variant != "program" and not variant.startswith("fault:"):
        raise ValueError(f"unknown variant {variant!r}")
    return stated


def _fault(variant):
    from harness import faults
    if variant.startswith("fault:"):
        return faults.plant(variant.split(":", 1)[1])
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=["program"])
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    from harness import runner, spec
    runner.enable_compile_cache()
    bench = spec.benchmark()
    conf = spec.config(bench, spec.cell(bench, args.workload)["config"])
    kind = spec.traffic(spec.cell(bench, args.workload)["traffic"])["kind"]
    reading = (service_reading if kind.startswith("service")
               else engine_reading)
    for seed in args.seeds:
        for variant in args.variants:
            t0 = time.time()
            for chk in reading(args.workload, conf, seed, bench, variant,
                               args.seconds):
                print(f"reading {args.workload} {variant} seed={seed} "
                      f"{chk.name}={chk.value!r} limit={chk.limit!r} "
                      f"{'ok' if chk.ok else 'FAIL'} "
                      f"({time.time() - t0:.1f}s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
