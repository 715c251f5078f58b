"""One run of one cell: set-up, the measured window, the per-layer
readings of a traced run, and the comparison that decides ``correct``.

The result is the contract's last line of standard output; every number
compared is printed with its limit on standard error and, last, in the
result under ``checks``.
"""
from __future__ import annotations

import dataclasses
import math
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from harness import spec

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# widths of the configuration file, by the program's ArchConfig field
ARCH_FIELDS = {"d_model": "d_model", "num_heads": "num_heads",
               "head_dim": "head_dim", "d_ff": "d_ff",
               "vocab_size": "vocab_size", "l_token": "clip_tokens"}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


@dataclasses.dataclass
class Window:
    """What a traffic driver's measured window returns."""
    seconds: float
    e2e: Dict[str, float]
    attempted: int
    failed: int
    extra: Dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Context:
    """What a traffic driver's set-up gets."""
    seed: int
    conf: Dict
    traffic: Dict
    model: Dict                  # sizes of the configuration
    cfg: object                  # the program's model config
    params: dict                 # device weights from the seed
    rng: np.random.Generator
    annotate: Callable[[str], object]


class CompileCounter:
    def __init__(self):
        import jax.monitoring as mon
        self.n = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.n += 1


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, so only the first run of a cell there compiles."""
    import jax
    path = str(spec.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def model_config(conf: Dict):
    """The program's model config at the sizes the configuration file
    states; a width that differs from the program's named architecture
    is an error."""
    from repro.configs import get_config
    m = conf["model"]
    cfg = get_config(m["arch"])
    for key, field in ARCH_FIELDS.items():
        if getattr(cfg, field) != m[key]:
            raise ValueError(f"config {m['arch']}: {field} is "
                             f"{getattr(cfg, field)}, the benchmark states "
                             f"{m[key]}")
    return cfg.replace(dtype=m["dtype"], param_dtype="float32")


def device_info(chips: int) -> Dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, bench: Optional[Dict] = None,
             conf: Optional[Dict] = None, cfg=None,
             keep_trace: Optional[str] = None) -> Dict:
    """``conf``/``cfg`` replace the configuration (tests run tiny sizes
    on the CPU with them)."""
    import jax

    from harness import peaks
    from harness import reading as rd
    from harness import weights

    bench = bench or spec.benchmark()
    cell = spec.cell(bench, workload)
    conf = conf or spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    drv = spec.driver(traffic["kind"])
    cfg = cfg if cfg is not None else model_config(conf)
    dev = jax.devices()[0]
    peak = (peaks.peak(dev.device_kind) if dev.platform == "tpu"
            else {"flops_bf16": math.nan, "hbm_bytes_per_s": math.nan})
    compiles = CompileCounter()

    params = weights.make_params(seed, conf["model"])
    ctx = Context(seed=seed, conf=conf, traffic=traffic, model=conf["model"],
                  cfg=cfg, params=params,
                  rng=np.random.default_rng(seed),
                  annotate=jax.profiler.TraceAnnotation)
    state = drv.setup(ctx)
    setup_s = time.time() - t_start
    n_setup_compiles = compiles.n
    log(f"[bench] {workload} seed={seed} setup_s={setup_s!r} "
        f"compiles_in_setup={n_setup_compiles}")

    before = rd.snapshot()
    trace_dir = None
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            win: Window = drv.window(state, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    after = rd.snapshot()
    n_window_compiles = compiles.n - n_setup_compiles
    device = device_info(cell["chips"])
    log(f"[bench] window_s={win.seconds!r} attempted={win.attempted} "
        f"failed={win.failed} compiles_in_window={n_window_compiles} "
        f"memory_peak_bytes={device['memory_peak_bytes']}")

    if trace:
        from harness import trace as tr
        summary = tr.summarize(tr.find_xplane(trace_dir))
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        r = rd.Reading(window_s=win.seconds, before=before, after=after,
                       extra=win.extra, model=conf["model"], peak=peak,
                       trace=summary)
        metrics = {}
        for m in spec.per_layer(bench, workload):
            value = spec.reader(m["name"]).read(r)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        values = dict(win.e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in spec.end_to_end(bench, workload)}

    checks: List[Check] = drv.check(state, win)
    del state
    correct = bool(checks) and all(c.ok for c in checks)
    for c in checks:
        log(f"[check] {c.name} = {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAIL'}")
    out = {"correct": correct, "attempted": int(win.attempted),
           "failed": int(win.failed), "metrics": metrics, "device": device,
           "compiles_in_window": n_window_compiles}
    if trace:
        out["breakdown"] = summary.breakdown()
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out
