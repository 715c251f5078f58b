"""Tiny sizes for running the harness end to end on the CPU."""
import copy
import importlib.util
import time

from harness import faults, runner, spec

SMALL = dict(d_model=32, num_heads=2, head_dim=16, d_ff=64)


def tiny(cell: str, bench=None):
    bench = bench or spec.benchmark()
    conf = copy.deepcopy(spec.config(bench, spec.cell(bench, cell)["config"]))
    conf["model"].update(SMALL)
    conf["engine"].update(interval_size=1000, warmup=200, batch_size=32)
    conf["suite"]["programs"] = conf["suite"]["programs"][:3]
    from repro.configs import get_config
    cfg = get_config("capsim").replace(dtype="float32", **SMALL)
    return bench, conf, cfg


def run(cell: str, seconds: float = 1.0, seed: int = 2**33 + 7,
        conf=None, bench=None):
    bench, c, cfg = tiny(cell, bench)
    return runner.run_cell(cell, seed, seconds, False, time.time(),
                           bench=bench, conf=conf or c, cfg=cfg)


def patch(monkeypatch, fault: str):
    """Plant ``fault`` in ``BatchedPredictor`` for one test."""
    from repro.core.engine import BatchedPredictor
    monkeypatch.setattr(BatchedPredictor, *faults.wrapped(fault))


def control_tool():
    """``bench/tools/control.py`` as a module."""
    path = spec.BENCH / "tools" / "control.py"
    mod_spec = importlib.util.spec_from_file_location("bench_control", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod
