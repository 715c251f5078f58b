"""Serving launcher: ``python -m repro.launch.serve --arch capsim``.

Runs ``SimulationEngine`` over the synthetic suite (the CAPSim
deployment), or with ``--service`` the ``SimulationService`` front end
over requests cut from it, or a KV-cache decode loop for an LM-zoo arch
(prefill + N decode steps on the smoke config).

The capsim path is a thin wrapper over ``SimulationEngine.from_config``:
flags assemble one ``EngineConfig`` (``--engine-config`` takes a JSON
object or a path to one; individual flags override it).  ``--mesh N``
shards inference over an N-device data mesh — on CPU the launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax's
first backend init so N host devices exist.
"""
from __future__ import annotations

import argparse
import os
import time


def parse_faults(spec):
    """``kind=rate,kind=rate`` -> dict for ``EngineConfig.faults``
    (validated there against the known chaos kinds)."""
    faults = {}
    for part in filter(None, (spec or "").split(",")):
        kind, _, rate = part.partition("=")
        faults[kind.strip()] = float(rate)
    return faults


def _build_engine_config(args):
    """Resolve --engine-config JSON (inline or @file) + flag overrides
    into one EngineConfig.  Import is deferred: callers must be able to
    set XLA_FLAGS before anything touches jax."""
    from repro.core.engine_config import EngineConfig
    if args.engine_config:
        text = args.engine_config
        if not text.lstrip().startswith("{"):
            with open(text) as fh:
                text = fh.read()
        config = EngineConfig.from_json(text)
    else:
        config = EngineConfig()
    overrides = dict(
        interval_size=args.interval_size, warmup=0, max_checkpoints=1,
        l_min=100, batch_size=args.batch_size, with_oracle=False,
        rt_cache=not args.no_rt_cache, precision=args.precision,
        multicore=args.multicore,
        fused_serving=args.fused_serving)
    if args.rt_store_dir:
        overrides["rt_store_dir"] = args.rt_store_dir
    if args.mesh:
        overrides["mesh_shape"] = (args.mesh,)
    if args.faults:
        overrides["faults"] = parse_faults(args.faults)
        overrides["fault_seed"] = args.fault_seed
    if args.subsample is not None:
        from repro.core.engine_config import SamplingConfig
        overrides["sampling"] = SamplingConfig(
            fraction=args.subsample, strata=args.strata,
            seed=args.sample_seed,
            min_clips_per_stratum=args.min_clips_per_stratum,
            bootstrap_resamples=args.bootstrap_resamples)
    if args.trace_out or args.flight_dir:
        from repro.core.engine_config import ObservabilityConfig
        overrides["observability"] = ObservabilityConfig(
            trace=bool(args.trace_out), flight_dir=args.flight_dir)
    return config.replace(**overrides)


def _start_metrics(args):
    """Start the /metrics exporter when --metrics-port is given.
    Returns the server (or None); the caller shuts it down."""
    if args.metrics_port is None:
        return None
    from repro.obs.exporter import serve_metrics
    server = serve_metrics(port=args.metrics_port)
    print(f"metrics: http://{server.server_address[0]}:"
          f"{server.server_address[1]}/metrics")
    return server


def _dump_trace(args, obs) -> None:
    """Write the Chrome/Perfetto trace when --trace-out is given."""
    if args.trace_out and obs.tracer.enabled:
        obs.tracer.dump(args.trace_out)
        print(f"trace: {args.trace_out} "
              f"({len(obs.tracer.spans())} spans; open at ui.perfetto.dev)")


def serve_capsim(args) -> None:
    import jax

    from repro.configs import get_config
    from repro.core import predictor
    from repro.core import standardize as std_mod
    from repro.core.engine import SimulationEngine
    from repro.isa import multicore, progen

    config = _build_engine_config(args)
    vocab = std_mod.build_vocab()
    cfg = get_config("capsim").replace(dtype="float32")
    params = predictor.init_params(cfg, jax.random.PRNGKey(0))
    engine = SimulationEngine.from_config(params, cfg, vocab, config)
    metrics_server = _start_metrics(args)

    if args.multicore > 0:
        # multicore serving: (benchmark, core) shards through the same
        # pooled predictor; per-core results demuxed, per-benchmark summed
        mbenches = multicore.all_multicore_benchmarks(args.multicore)
        t0 = time.time()
        mresults = engine.run_multicore(mbenches)
        wall = time.time() - t0
        stats = engine.last_stats
        for mr in mresults:
            line = (f"  {mr.name:16s} x{mr.n_cores} cores "
                    f"clips={mr.n_clips:5d} "
                    f"predicted={mr.predicted_cycles:12.0f} core-cycles")
            if mr.cycles_ci is not None:
                lo, hi = mr.cycles_ci
                line += f"  [{lo:.0f}, {hi:.0f}] 95% CI"
            print(line)
            for cr in mr.cores:
                print(f"    {cr.name:16s} clips={cr.n_clips:5d} "
                      f"predicted={cr.predicted_cycles:12.0f} cycles")
        served = (f"{len(mresults)} benchmarks x {args.multicore} cores "
                  f"({sum(mr.n_cores for mr in mresults)} core shards)")
    else:
        names = list(progen.TABLE_II)[: args.n_benchmarks]
        engine.submit_names(names)
        t0 = time.time()
        results = engine.run()
        wall = time.time() - t0
        stats = engine.last_stats
        for r in results:
            line = (f"  {r.name:16s} clips={r.n_clips:5d} "
                    f"predicted={r.predicted_cycles:12.0f} cycles")
            if r.cycles_ci is not None:
                lo, hi = r.cycles_ci
                line += (f"  [{lo:.0f}, {hi:.0f}] 95% CI "
                         f"({r.clips_predicted} predicted + "
                         f"{r.clips_extrapolated} extrapolated)")
            print(line)
        served = f"{len(results)} benchmarks"
    print(f"served {served} "
          f"({stats.n_clips} clips, {stats.n_batches} device batches, "
          f"{stats.n_pad} pad rows) in {wall:.1f}s "
          f"= {stats.n_clips / max(wall, 1e-9):.0f} clips/s")
    rt = engine.last_rt_stats
    if rt is not None:
        print(f"rt-cache: {rt.n_rows_encoded} static rows encoded in "
              f"{rt.build_seconds:.2f}s served {rt.n_rows_served} dynamic "
              f"rows ({rt.rows_avoided} instruction-encoder rows avoided)")
        if rt.n_rows_loaded:
            print(f"rt-store: {rt.n_rows_loaded} rows loaded in "
                  f"{rt.store_load_seconds:.2f}s (cold encode skipped)")
    _dump_trace(args, engine.obs)
    if metrics_server is not None:
        metrics_server.shutdown()


def service_requests(config, vocab, n_benchmarks: int, n_requests: int):
    """The service's traffic: one checkpoint of each of the first
    ``n_benchmarks`` Table II programs, tokenized by ``build_dataset``
    and split into ``n_requests`` equal ``Request``s."""
    from repro.data.dataset import BuildConfig, build_dataset
    from repro.isa import progen
    from repro.serving.engine import Request

    names = list(progen.TABLE_II)[:n_benchmarks]
    bcfg = BuildConfig(interval_size=config.interval_size, warmup=0,
                       max_checkpoints=1, l_min=100,
                       l_clip=config.l_clip, l_token=config.l_token)
    ds = build_dataset(names, bcfg, vocab)
    per_req = max(1, len(ds) // max(n_requests, 1))
    requests = []
    for i in range(n_requests):
        lo = (i * per_req) % len(ds)
        hi = min(lo + per_req, len(ds))
        requests.append(Request(i, ds.clip_tokens[lo:hi],
                                ds.context_tokens[lo:hi],
                                ds.clip_mask[lo:hi]))
    return requests


def serve_service(args) -> None:
    """Run the fault-tolerant ``SimulationService`` front-end over the
    synthetic suite: requests carry per-request deadlines, admission can
    shed (typed ``overloaded``), and --faults exercises the degradation
    ladder on live traffic."""
    import jax

    from repro.configs import get_config
    from repro.core import predictor
    from repro.core import standardize as std_mod
    from repro.serving.service import ServiceSLA, SimulationService

    config = _build_engine_config(args)
    # the service owns precision/fusion (the degradation ladder) — the
    # base config only contributes the structural axes
    config = config.replace(precision=None, fused_serving=False)
    vocab = std_mod.build_vocab()
    cfg = get_config("capsim").replace(dtype="float32")
    params = predictor.init_params(cfg, jax.random.PRNGKey(0))
    requests = service_requests(config, vocab, args.n_benchmarks,
                                args.n_requests)
    sla = ServiceSLA(default_deadline_s=args.deadline_s,
                     watchdog_s=args.watchdog_s)

    metrics_server = _start_metrics(args)
    t0 = time.time()
    with SimulationService(params, cfg, config, sla=sla) as svc:
        tickets = [svc.submit(r) for r in requests]
        results = [t.result(timeout=600) for t in tickets]
        stats = svc.stats()
    wall = time.time() - t0

    for r in results:
        extra = f" [{r.error}]" if r.error else ""
        print(f"  req {r.request_id:3d} {r.status:17s} "
              f"tier={r.tier or '-':10s} clips={r.n_clips:5d} "
              f"latency={r.latency_seconds:6.2f}s{extra}")
    n_clips = sum(r.n_clips for r in results if r.ok)
    print(f"service: {stats['statuses']} tier={stats['current_tier']} "
          f"in {wall:.1f}s = {n_clips / max(wall, 1e-9):.0f} clips/s")
    if "faults_fired" in stats:
        print(f"faults fired: {stats['faults_fired']}")
    for name, ts in stats["tiers"].items():
        hits = {k: v for k, v in ts.items() if v and k != "name"}
        if hits:
            print(f"  tier {name}: {hits}")
    _dump_trace(args, svc.obs)
    if svc.obs.flight is not None and svc.obs.flight.postmortems:
        print(f"postmortems: {len(svc.obs.flight.postmortems)} written "
              f"to {args.flight_dir}")
    if metrics_server is not None:
        metrics_server.shutdown()


def serve_lm(args) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import ShapeConfig, get_smoke_config
    from repro.distributed.sharding import (
        LOGICAL_RULES_DECODE, use_mesh_and_rules)
    from repro.launch.mesh import make_test_mesh
    from repro.launch.specs import random_batch
    from repro.models import transformer as tfm

    cfg = get_smoke_config(args.arch)
    B, S = 2, 64
    mesh = make_test_mesh()
    with use_mesh_and_rules(mesh, LOGICAL_RULES_DECODE):
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        pre = random_batch(cfg, ShapeConfig("p", S // 2, B, "prefill"),
                           "prefill")
        logits, caches = jax.jit(
            lambda p, b: tfm.prefill_step(p, b, cfg))(params, pre)
        full = tfm.init_cache(cfg, B, S)
        # place prefill caches into the fixed-size decode cache
        def put(dst, src):
            if src.ndim >= 3 and src.shape[2] == S // 2:
                return jax.lax.dynamic_update_slice_in_dim(
                    dst, src.astype(dst.dtype), 0, axis=2)
            return src.astype(dst.dtype)
        caches = jax.tree_util.tree_map(put, full, caches)
        step = jax.jit(lambda p, b, c, pos: tfm.decode_step(p, b, cfg, c,
                                                            pos))
        tok = jnp.argmax(logits[:, -1:], -1)
        if cfg.num_codebooks > 1:
            tok = jnp.broadcast_to(tok[..., None],
                                   (B, 1, cfg.num_codebooks))
        t0 = time.time()
        for i in range(args.decode_steps):
            logits, caches = step(params, {"tokens": tok}, caches,
                                  jnp.int32(S // 2 + i))
            tok = jnp.argmax(logits[:, -1:], -1)
            if cfg.num_codebooks > 1:
                tok = jnp.broadcast_to(tok[..., None],
                                       (B, 1, cfg.num_codebooks))
        jax.block_until_ready(tok)
        print(f"{args.arch}: prefill {S//2} tokens + "
              f"{args.decode_steps} decode steps in {time.time()-t0:.1f}s")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="capsim")
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--interval-size", type=int, default=10_000)
    ap.add_argument("--n-benchmarks", type=int, default=4)
    ap.add_argument("--multicore", type=int, default=0, metavar="N_CORES",
                    help="serve the multi-threaded benchmark variants at "
                         "N cores per benchmark (0 = single-core suite)")
    ap.add_argument("--decode-steps", type=int, default=8)
    ap.add_argument("--no-rt-cache", action="store_true",
                    help="monolithic predict path (re-encode every "
                         "dynamic instruction row; the bitwise reference)")
    ap.add_argument("--precision", default=None,
                    choices=("fp32", "bf16", "int8"),
                    help="inference numerics; default keeps the config "
                         "dtype (fp32 here).  bf16 casts fp32 params at "
                         "dispatch; int8 per-channel fake-quantizes the "
                         "weights once at engine build (fp32 compute), "
                         "both ≤1%% rel-err gated")
    ap.add_argument("--fused-serving", action="store_true",
                    help="dedup-fused block-encoder serving step "
                         "(weighted attention over each clip's unique "
                         "context tokens + precomputed cross K/V; "
                         "tolerance-gated ≤1e-3 vs unfused)")
    ap.add_argument("--rt-store-dir", default=None, metavar="DIR",
                    help="persistent content-addressed RT-cache store: "
                         "load-or-rebuild the (row -> RT vector) table "
                         "keyed on (params, config, vocab), persisted "
                         "after each run — a restart never repays the "
                         "cold encode")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="shard inference over an N-device data mesh "
                         "(predict dispatch + RT-cache encode passes; "
                         "bitwise-equal to unsharded).  0 = no mesh")
    ap.add_argument("--subsample", type=float, default=None,
                    metavar="FRACTION",
                    help="analytical-ML fusion: predict only a "
                         "stratified FRACTION of each benchmark's clips "
                         "and extrapolate the rest from analytical "
                         "features with a bootstrap CI (default: full "
                         "prediction)")
    ap.add_argument("--strata", type=int, default=4,
                    help="--subsample: quantile strata over the "
                         "analytical cycle estimate")
    ap.add_argument("--min-clips-per-stratum", type=int, default=2,
                    help="--subsample: floor of sampled clips per "
                         "non-empty stratum")
    ap.add_argument("--bootstrap-resamples", type=int, default=200,
                    help="--subsample: bootstrap resamples behind the "
                         "95%% CI (0 disables)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="--subsample: sampling + bootstrap seed")
    ap.add_argument("--engine-config", default=None, metavar="JSON",
                    help="EngineConfig as a JSON object or a path to a "
                         "JSON file; individual flags override its "
                         "fields")
    ap.add_argument("--service", action="store_true",
                    help="serve through the fault-tolerant "
                         "SimulationService (bounded queue, deadlines, "
                         "watchdog, graceful degradation) instead of the "
                         "batch SimulationEngine")
    ap.add_argument("--n-requests", type=int, default=8,
                    help="--service: number of requests to split the "
                         "suite's clips across")
    ap.add_argument("--deadline-s", type=float, default=120.0,
                    help="--service: per-request deadline (SLA)")
    ap.add_argument("--watchdog-s", type=float, default=60.0,
                    help="--service: abort any single flush after this "
                         "many seconds and retry a tier down")
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="serve Prometheus text at "
                         "http://127.0.0.1:PORT/metrics for the run's "
                         "duration (0 = ephemeral port, printed)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable span tracing and write a Chrome/"
                         "Perfetto trace-event JSON at exit (open at "
                         "ui.perfetto.dev)")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="enable the degradation flight recorder: every "
                         "service demotion dumps a postmortem JSON "
                         "(events + recent spans + metrics) into DIR")
    ap.add_argument("--faults", default=None, metavar="KIND=RATE,...",
                    help="chaos injection on the real serving path, e.g. "
                         "'nan_output=0.1,device_error=0.05' (kinds: "
                         "device_error nan_output slow_flush "
                         "corrupt_rt_read crash_persist)")
    ap.add_argument("--fault-seed", type=int, default=0)
    args = ap.parse_args()
    if args.mesh:
        # must land before jax's first backend init: jax locks the host
        # device count the moment a backend spins up
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.mesh}").strip()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.arch == "capsim" and args.service:
        serve_service(args)
    elif args.arch == "capsim":
        serve_capsim(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
