#!/usr/bin/env python3
"""Chip smoke: the simulation engine and the service on a TPU at the
paper's full model width, checked against a float32 reference.

Run from the repository root:

    python chip_smoke.py              # one chip: phases engine, service
    python chip_smoke.py --chips 4    # four chips: phase mesh only

Model: ``get_config("capsim")`` at full width (E=128, 4 heads x 32,
4+4 layers, d_ff 512, L_clip 128, L_token 16, M 360) with float32 master
weights from ``PRNGKey(0)``; no trained checkpoint is needed to check
that the chip computes what the reference computes.  Inputs are Table II
programs generated from their seeds; nothing is read from earlier runs.

The reference is ``predictor.forward`` in float32 with XLA attention
(``attn_impl="chunked"``) under ``jax.default_matmul_precision
("highest")``, called directly, on the same clips.

Every check raises.  The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU the script exits 1 before doing anything else.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
BENCHMARKS = ("500.perlbench", "502.gcc", "503.bwaves")
REF_BATCH = 256

# Relative error limits against the reference: per-benchmark predicted
# cycles (worst seen on a v5e: 3.9e-3, from f32 matmuls at default
# precision), per-request totals at fused_int8 (v5e: 1.1e-2, the int8
# gate is 5e-2), and the 4-chip against the 1-chip engine.
ENGINE_TOL = {"rt": 1e-2, "fused": 1e-2}
SERVICE_TOL = 5e-2
MESH_TOL = 1e-3


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1.0)


# --------------------------------------------------------------------- #
# Reference: the same clips through the plain float32 forward
# --------------------------------------------------------------------- #

def engine_clips(bench, vocab, config):
    """The clips ``SimulationEngine`` cuts from ``bench`` — warmup, then
    up to ``max_checkpoints`` intervals, fixed slicing, one context row
    per clip — as monolithic (tokens, context, mask) arrays."""
    import numpy as np

    from repro.core import context as ctx_mod
    from repro.core import standardize as std_mod
    from repro.isa import funcsim, progen

    cprog = bench.compiled()
    table = cprog.token_table(vocab, config.l_token)
    st = progen.fresh_compiled_state(bench)
    _, st = funcsim.run_compiled(cprog, config.warmup, st)
    tok, ctx, mask = [], [], []
    for _ in range(min(bench.ckp_num, config.max_checkpoints)):
        trace, st = funcsim.run_compiled(cprog, config.interval_size, st,
                                         snapshot_every=config.l_min)
        if not len(trace):
            break
        t, m = std_mod.encode_fixed_clips(table, trace.pc, config.l_min,
                                          config.l_clip)
        rows = ctx_mod.context_tokens_from_matrix(trace.snapshots, vocab)
        tok.append(t)
        mask.append(m)
        ctx.append(rows[np.minimum(np.arange(len(t)), len(rows) - 1)])
    return np.concatenate(tok), np.concatenate(ctx), np.concatenate(mask)


class Reference:
    """Per-clip float32 reference times at ``highest`` precision:
    ``engine.reference_fn`` is ``predictor.forward`` with XLA attention,
    called directly rather than through ``inference_config`` (which
    would swap the Pallas kernel back in on the chip)."""

    def __init__(self, params, cfg):
        from repro.core.engine import reference_fn
        self._params = params
        self._step = reference_fn(cfg)

    def times(self, tok, ctx, mask):
        import numpy as np
        out = []
        for lo in range(0, tok.shape[0], REF_BATCH):
            t, c, m = (a[lo:lo + REF_BATCH] for a in (tok, ctx, mask))
            n = t.shape[0]
            pad = REF_BATCH - n
            if pad:
                t, c, m = (np.concatenate(
                    [a, np.zeros((pad,) + a.shape[1:], a.dtype)])
                    for a in (t, c, m))
            y = self._step(self._params, {"clip_tokens": t,
                                          "context_tokens": c,
                                          "clip_mask": m})
            out.append(np.asarray(y)[:n])
        return np.concatenate(out)


# --------------------------------------------------------------------- #
# Device checks
# --------------------------------------------------------------------- #

def assert_kernel(jitted, *args) -> None:
    """The compiled step must hold the Pallas kernel as a Mosaic custom
    call: no interpreter, no XLA twin."""
    text = jitted.lower(*args).compile().as_text()
    check("tpu_custom_call" in text,
          "compiled predict step holds no tpu_custom_call")


def report_memory(phase: str) -> None:
    import jax
    for d in jax.devices():
        stats = d.memory_stats() or {}
        log(f"[{phase}] {d} peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use')} "
            f"bytes_in_use={stats.get('bytes_in_use')}")


def fused_batch_spec(config, n_unique: int = 128):
    import jax
    import jax.numpy as jnp
    b, l = config.batch_size, config.l_clip
    return {"rt_idx": jax.ShapeDtypeStruct((b, l), jnp.int32),
            "ctx_uniq": jax.ShapeDtypeStruct((b, n_unique), jnp.int32),
            "ctx_count": jax.ShapeDtypeStruct((b, n_unique), jnp.float32),
            "clip_mask": jax.ShapeDtypeStruct((b, l), jnp.float32)}


# --------------------------------------------------------------------- #
# Phases
# --------------------------------------------------------------------- #

def phase_engine(params, cfg, vocab, ref: Reference, kernels: bool = True):
    """``SimulationEngine`` at ``EngineConfig`` defaults (oracle off) over
    three Table II programs, on the RT-cache tier and the fused tier."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import engine as eng_mod
    from repro.core.engine_config import EngineConfig
    from repro.isa import progen

    config = EngineConfig(with_oracle=False)
    benches = [progen.build_benchmark(n) for n in BENCHMARKS]
    want = {}
    for b in benches:
        tok, ctx, mask = engine_clips(b, vocab, config)
        want[b.name] = (tok.shape[0], float(ref.times(tok, ctx, mask).sum()))

    errors = {}
    for tier, tcfg in (("rt", config),
                       ("fused", config.replace(fused_serving=True))):
        engine = eng_mod.SimulationEngine.from_config(params, cfg, vocab,
                                                      tcfg)
        if kernels:
            check(engine.cfg.attn_impl == "pallas",
                  f"{tier}: attn_impl resolved to {engine.cfg.attn_impl}")
        results = engine.run(benches)
        worst = 0.0
        for r in results:
            n_ref, cyc_ref = want[r.name]
            check(r.n_clips == n_ref,
                  f"{tier} {r.name}: {r.n_clips} clips, reference {n_ref}")
            check(bool(np.isfinite(r.predicted_cycles)),
                  f"{tier} {r.name}: non-finite prediction")
            err = rel_err(r.predicted_cycles, cyc_ref)
            log(f"[engine] {tier:5s} {r.name:14s} clips={r.n_clips} "
                f"predicted={r.predicted_cycles!r} reference={cyc_ref!r} "
                f"rel_err={err:.3e}")
            worst = max(worst, err)
        errors[tier] = worst
        check(worst <= ENGINE_TOL[tier],
              f"{tier}: rel err {worst:.3e} > {ENGINE_TOL[tier]:.0e}")
        if kernels:
            table = engine.rt_cache.table
            if tier == "rt":
                spec = {"rt_idx": jax.ShapeDtypeStruct(
                            (config.batch_size, config.l_clip), jnp.int32),
                        "context_tokens": jax.ShapeDtypeStruct(
                            (config.batch_size, cfg.context_tokens),
                            jnp.int32),
                        "clip_mask": jax.ShapeDtypeStruct(
                            (config.batch_size, config.l_clip),
                            jnp.float32)}
                assert_kernel(eng_mod.predict_cached_fn(engine.cfg, True),
                              engine.params, table, spec)
            else:
                plan = eng_mod.serving_plan_fn(engine.cfg)(engine.params,
                                                           table)
                assert_kernel(eng_mod.predict_cached_fused_fn(engine.cfg),
                              engine.params, plan,
                              fused_batch_spec(config))
            log(f"[engine] {tier}: tpu_custom_call in the compiled step")
    return errors


def phase_service(params, cfg, vocab, ref: Reference):
    """``SimulationService`` with the default SLA answers 8 requests built
    as ``launch/serve.py`` builds them, one at a time, so the 8th flush
    is spot-checked."""
    from repro.core.engine_config import EngineConfig
    from repro.launch.serve import service_requests
    from repro.serving.service import SimulationService

    config = EngineConfig(interval_size=10_000, warmup=0,
                          max_checkpoints=1, with_oracle=False)
    requests = service_requests(config, vocab, n_benchmarks=4,
                                n_requests=8)
    svc = SimulationService(params, cfg, config)
    top = svc.current_tier
    check(top == "fused_int8", f"service starts at {top}")
    with svc:
        results = [svc.submit(r).result(timeout=600) for r in requests]
        snap = svc.snapshot()

    worst = 0.0
    for req, res in zip(requests, results):
        check(res.status == "ok" and res.tier == top,
              f"request {res.request_id}: {res.status} at {res.tier} "
              f"({res.error})")
        want = float(ref.times(req.clip_tokens, req.context_tokens,
                               req.clip_mask).sum())
        err = rel_err(res.total_cycles, want)
        worst = max(worst, err)
        log(f"[service] req {res.request_id} {res.tier} clips={res.n_clips} "
            f"total={res.total_cycles!r} reference={want!r} "
            f"rel_err={err:.3e} latency={res.latency_seconds:.3f}s")
    check(worst <= SERVICE_TOL,
          f"service {top}: rel err {worst:.3e} > {SERVICE_TOL:.0e}")
    trips = {k: sum(t[k] for t in snap.tiers.values())
             for k in ("demotions", "promotions", "fault_trips",
                       "watchdog_trips", "nan_trips", "relerr_trips")}
    log(f"[service] statuses={snap.statuses} tier events={trips} "
        f"spot_checks={snap.tiers[top]['spot_checks']}")
    check(not any(trips.values()), f"tier transitions or trips: {trips}")
    check(snap.current_tier == top, f"ended at {snap.current_tier}")
    check(snap.tiers[top]["spot_checks"] >= 1, "no spot check ran")

    audit = svc.audit(requests[0])
    for tier, err in audit.items():
        tol = svc.sla.tier_tolerances[tier]
        log(f"[service] audit {tier:10s} max clip rel_err={err:.3e} "
            f"(tolerance {tol:.0e})")
        check(err <= tol, f"audit {tier}: {err:.3e} > {tol:.0e}")
    return {top: worst, **{f"audit_{k}": v for k, v in audit.items()}}


def phase_mesh(params, cfg, vocab):
    """The fused-tier engine sharded over a 4-chip data mesh against the
    same engine on one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import engine as eng_mod
    from repro.core.engine_config import EngineConfig
    from repro.isa import progen

    check(len(jax.devices()) >= 4,
          f"--chips 4 needs 4 devices, JAX found {len(jax.devices())}")
    config = EngineConfig(with_oracle=False, fused_serving=True)
    benches = [progen.build_benchmark(n) for n in BENCHMARKS]
    one = eng_mod.SimulationEngine.from_config(params, cfg, vocab, config)
    r1 = one.run(benches)
    mesh = eng_mod.SimulationEngine.from_config(
        params, cfg, vocab, config.replace(mesh_shape=(4,)))
    r4 = mesh.run(benches)
    worst = 0.0
    for a, b in zip(r1, r4):
        check(bool(np.isfinite(b.predicted_cycles)),
              f"mesh {b.name}: non-finite prediction")
        err = rel_err(b.predicted_cycles, a.predicted_cycles)
        worst = max(worst, err)
        log(f"[mesh] {a.name:14s} one_chip={a.predicted_cycles!r} "
            f"four_chips={b.predicted_cycles!r} rel_err={err:.3e} "
            f"bitwise={a.predicted_cycles == b.predicted_cycles}")
    check(worst <= MESH_TOL, f"mesh: rel err {worst:.3e} > {MESH_TOL:.0e}")

    plan = eng_mod.serving_plan_fn(mesh.cfg)(mesh.params,
                                             mesh.rt_cache.table)
    b, l, u = config.batch_size, config.l_clip, 128
    batch = {"rt_idx": jnp.zeros((b, l), jnp.int32),
             "ctx_uniq": jnp.zeros((b, u), jnp.int32),
             "ctx_count": jnp.ones((b, u), jnp.float32),
             "clip_mask": jnp.ones((b, l), jnp.float32)}
    out = eng_mod.predict_cached_fused_mesh_fn(mesh.cfg, 4)(
        mesh.params, plan, batch)
    out.block_until_ready()
    n_dev = len(out.sharding.device_set)
    log(f"[mesh] predict output sharding spans {n_dev} devices")
    check(n_dev == 4, f"predict output on {n_dev} devices, not 4")
    for d in jax.devices()[:4]:
        used = (d.memory_stats() or {}).get("bytes_in_use", 0)
        check(used > 0, f"{d} reports no bytes in use")
    return {"mesh_vs_one_chip": worst}


# --------------------------------------------------------------------- #

def run_phase(name, fn, *args):
    from repro.obs.compiles import compile_monitor
    mon = compile_monitor()
    c0 = mon.counts()
    t0 = time.time()
    out = fn(*args)
    secs = time.time() - t0
    c1 = mon.counts()
    d = {k: c1[k] - c0[k] for k in c1}
    log(f"[{name}] seconds={secs!r} compiles={d['compiles']} "
        f"cache_hits={d['cache_hits']} cache_misses={d['cache_misses']}")
    log(f"[{name}] max rel err vs reference: "
        + ", ".join(f"{k}={v:.3e}" for k, v in out.items()))
    report_memory(name)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: engine and service phases on one chip; "
                         "4: the 4-chip mesh phase and its 1-chip "
                         "comparison only")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found {len(devices)} "
              f"{dev.platform} device(s) ({dev.device_kind}); this smoke "
              "runs only on the chip", file=sys.stderr)
        return 1
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: the repro package is not under {ROOT}/src",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    from repro.configs import get_config
    from repro.core import predictor
    from repro.core.standardize import build_vocab

    cfg = get_config("capsim").replace(dtype="float32")
    params = predictor.init_params(cfg, jax.random.PRNGKey(0))
    vocab = build_vocab()
    if args.chips == 4:
        run_phase("mesh", phase_mesh, params, cfg, vocab)
    else:
        ref = Reference(params, cfg)
        run_phase("engine", phase_engine, params, cfg, vocab, ref)
        run_phase("service", phase_service, params, cfg, vocab, ref)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
