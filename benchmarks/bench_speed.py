"""Fig 7: CAPSim (functional sim + batched predictor) vs the O3 oracle.

Honest accounting on this host: the paper compares gem5 (~10^5 inst/s on a
Xeon) against an RTX 4090; here BOTH paths share one CPU core and our
greedy O3 oracle is itself ~5x10^5 inst/s — ~500x faster than gem5 — so an
absolute wall-clock speedup is not reproducible and is reported as-is.
What does reproduce is the *structure* of the paper's claim:

  1. the oracle is inherently sequential: its wall time grows linearly
     with instruction count (measured below),
  2. the predictor path is embarrassingly parallel over clips: per-clip
     cost falls with batch size (measured below, compile amortized),
  3. on the target accelerator the clip batch is one dry-run cell:
     the compiled capsim x serve_clips artifact bounds throughput at
     16384 clips (~2.1M instructions) per step-time (derived below from
     results/dryrun), which is what the paper's Fig-7 GPU bars measure.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

if __package__ in (None, ""):     # direct `python benchmarks/bench_speed.py`
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.common import (BENCH_SCHEMA_VERSION,
                               MESH_BENCH_SCHEMA_VERSION,
                               SUBSAMPLE_BENCH_SCHEMA_VERSION, bench_cfg,
                               full_cfg)
from repro.core import context as ctx_mod
from repro.core import predictor
from repro.core import slicer as slicer_mod
from repro.core import standardize as std_mod
from repro.core.engine import SimulationEngine
from repro.core.engine_config import EngineConfig, SamplingConfig
from repro.core.simulate import capsim_simulate
from repro.core.standardize import build_vocab
from repro.isa import funcsim, multicore, progen, timing
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import REGISTRY

BENCHES = ["503.bwaves", "505.mcf", "548.exchange2"]


def bench_scale_config(quick: bool) -> EngineConfig:
    """The one scale declaration shared by every engine-based pass
    (--multi / --multicore / --mesh) — previously each pass re-declared
    this as its own kwarg dict."""
    return EngineConfig(interval_size=2_000 if quick else 10_000,
                        max_checkpoints=1 if quick else 2,
                        l_min=100, l_clip=128, l_token=16,
                        batch_size=32 if quick else 64)


def resolve_engine_config(arg, quick: bool) -> EngineConfig:
    """--engine-config as a JSON object (inline or a file path) layered
    over the quick/full scale defaults."""
    config = bench_scale_config(quick)
    if arg:
        text = arg
        if not text.lstrip().startswith("{"):
            text = Path(text).read_text()
        config = config.replace(**json.loads(text))
    return config


def run(emit) -> None:
    vocab = build_vocab()
    cfg = full_cfg()
    params = predictor.init_params(cfg, jax.random.PRNGKey(0))

    # 1. oracle sequential scaling
    bench = progen.build_benchmark("505.mcf")
    times = []
    for n in (5_000, 10_000, 20_000):
        trace, _, _ = funcsim.run(bench.program, n,
                                  state=progen.fresh_state(bench))
        t0 = time.time()
        timing.simulate(trace)
        times.append(time.time() - t0)
    emit.emit("speed.oracle_scaling", times[-1] * 1e6 / 20_000,
              f"oracle seconds for 5k/10k/20k insts: "
              f"{times[0]:.3f}/{times[1]:.3f}/{times[2]:.3f} (linear — "
              "sequential, cannot parallelize)")

    # 2. predictor batch amortization (compile amortized by warmup)
    rng = np.random.RandomState(0)
    def batch(B):
        return {
            "clip_tokens": jnp.asarray(
                rng.randint(0, vocab.size, (B, 128, cfg.clip_tokens)),
                jnp.int32),
            "context_tokens": jnp.asarray(
                rng.randint(0, vocab.size, (B, cfg.context_tokens)),
                jnp.int32),
            "clip_mask": jnp.ones((B, 128), jnp.float32)}
    pred = jax.jit(lambda p, b: predictor.predict_step(p, b, cfg))
    per_clip = {}
    for B in (8, 32):
        b = batch(B)
        jax.block_until_ready(pred(params, b))          # compile+warm
        t0 = time.time()
        jax.block_until_ready(pred(params, b))
        per_clip[B] = (time.time() - t0) / B * 1e6
    emit.emit("speed.predictor_batching", per_clip[32],
              f"us/clip at batch 8 vs 32: {per_clip[8]:.0f} -> "
              f"{per_clip[32]:.0f}: flat per-clip cost on 1 core — the "
              "batch dimension is free parallelism on real accelerators "
              "(see v5e_projection)")

    # 3. end-to-end on this host (compile already amortized above)
    for name in BENCHES:
        bench = progen.build_benchmark(name)
        r = capsim_simulate(bench, params, cfg, vocab,
                            EngineConfig(interval_size=10_000,
                                         max_checkpoints=1,
                                         batch_size=32))
        emit.emit(f"speed.{name}",
                  r.capsim_seconds * 1e6 / max(r.n_instructions, 1),
                  f"oracle {r.oracle_seconds:.2f}s vs capsim "
                  f"{r.capsim_seconds:.2f}s = {r.speedup:.3f}x on 1 CPU "
                  f"core ({r.n_instructions} insts; paper: 2.2-8.3x with "
                  "gem5-vs-GPU cost ratio)")

    # 4. target-accelerator projection from the compiled dry-run cell
    rec_path = Path("results/dryrun/capsim__serve_clips__pod_16x16.json")
    if rec_path.exists():
        rec = json.loads(rec_path.read_text())
        m = rec["scanned"]["memory"]
        traffic = (m["argument_bytes"] + m["output_bytes"]
                   + 2 * m["temp_bytes"])
        step_s = max(traffic / 819e9,
                     (rec["scanned"]["cost"]["flops"] or 0) / 197e12)
        clips = 16_384
        insts = clips * 128
        emit.emit("speed.v5e_projection", step_s * 1e6 / clips,
                  f"serve_clips dry-run: {clips} clips "
                  f"({insts/1e6:.1f}M insts) per {step_s*1e3:.1f}ms pod "
                  f"step = {insts/step_s/1e9:.1f}G inst/s structural "
                  "bound vs oracle 5e5 inst/s/core")


# --------------------------------------------------------------------------- #
# Multi-benchmark throughput: sequential per-benchmark loop vs the engine
# --------------------------------------------------------------------------- #

def _sequential_simulate(bench, params, cfg, vocab, ec: EngineConfig, *,
                         with_oracle=False):
    """The pre-engine, pre-IR ``capsim_simulate`` inference path, kept
    verbatim as the baseline: the *object* interpreter
    (``funcsim.run_reference``), per-clip Python tokenization and context
    loops, fresh ``jax.jit`` per benchmark (re-trace + re-compile),
    per-benchmark remainder padded to a full batch, and a synchronous
    host round-trip after every device batch.  ``ec`` only supplies the
    scale knobs (interval/clip/batch sizes) — the path itself stays the
    seed loop.

    Returns ``(predicted_cycles, oracle_cycles, n_clips,
    frontend_seconds, oracle_seconds, predict_seconds)`` — front-end =
    functional sim + slice + tokenize + context (the part the columnar IR
    replaces); predict = the synchronous device loop incl. the fresh
    compile (the part the RT cache + pooled engine replace).
    """
    interval_size, max_checkpoints = ec.interval_size, ec.max_checkpoints
    l_min, l_clip, l_token = ec.l_min, ec.l_clip, ec.l_token
    batch_size = ec.batch_size
    predict = jax.jit(lambda p, b: predictor.predict_step(p, b, cfg))
    st = progen.fresh_state(bench)
    tok_l, ctx_l, mask_l = [], [], []
    oracle_cycles = 0.0
    fe_seconds = 0.0
    oracle_seconds = 0.0
    for _ in range(min(bench.ckp_num, max_checkpoints)):
        t0 = time.time()
        trace, snaps, st = funcsim.run_reference(
            bench.program, interval_size, state=st, snapshot_every=l_min)
        if not trace:
            fe_seconds += time.time() - t0
            break
        clips = slicer_mod.slice_fixed([e.inst for e in trace], l_min)
        for i, clip in enumerate(clips):
            toks, mask = std_mod.encode_clip(clip.insts, vocab, l_clip,
                                             l_token)
            tok_l.append(toks)
            ctx_l.append(ctx_mod.context_token_ids(
                snaps[min(i, len(snaps) - 1)], vocab))
            mask_l.append(mask)
        fe_seconds += time.time() - t0
        if with_oracle:
            t0 = time.time()
            oracle_cycles += timing.total_cycles(trace)
            oracle_seconds += time.time() - t0
    tok, ctx, mask = np.stack(tok_l), np.stack(ctx_l), np.stack(mask_l)
    n_real = tok.shape[0]
    pad = (-n_real) % batch_size
    if pad:
        tok = np.concatenate([tok, np.repeat(tok[-1:], pad, 0)])
        ctx = np.concatenate([ctx, np.repeat(ctx[-1:], pad, 0)])
        mask = np.concatenate([mask, np.zeros((pad,) + mask.shape[1:],
                                              mask.dtype)])
    preds = []
    t0 = time.time()
    for lo in range(0, tok.shape[0], batch_size):
        batch = {"clip_tokens": jnp.asarray(tok[lo:lo + batch_size]),
                 "context_tokens": jnp.asarray(ctx[lo:lo + batch_size]),
                 "clip_mask": jnp.asarray(mask[lo:lo + batch_size])}
        preds.append(np.asarray(predict(params, batch)))   # sync round-trip
    predict_seconds = time.time() - t0
    return (float(np.concatenate(preds)[:n_real].sum()), oracle_cycles,
            n_real, fe_seconds, oracle_seconds, predict_seconds)


def run_multi(emit, *, n_benchmarks: int = 8, quick: bool = False,
              config: "EngineConfig | None" = None,
              rt_store_dir: "str | None" = None) -> dict:
    """Sequential-vs-engine clips/sec on an n-benchmark mix.

    Sequential = one benchmark at a time through the seed inference loop
    (object interpreter + per-clip Python tokenization: the pre-IR
    baseline).  Engine = columnar trace IR front-end feeding one shared
    clip pool, cached jit, bucketed padding, async double-buffer.
    Per-benchmark predicted cycles AND O3 oracle cycles must agree
    bitwise between the two paths; the front-end (functional sim + slice
    + tokenize + context) throughput ratio is reported alongside the
    end-to-end one, with a per-stage breakdown of where engine host time
    goes.

    On top of the PR-6 passes sits the predict-stack ladder: bf16 and
    int8 precision rungs, the dedup-fused serving step, the fused+int8
    stack, and a store-restart pass that rebuilds a fresh engine against
    the persistent RT store (``rt_store_dir``; a temp dir when None) and
    must adopt the persisted table with zero re-encode, bitwise equal to
    the fp32 RT pass.
    """
    vocab = build_vocab()
    cfg = bench_cfg() if quick else full_cfg()
    # resolve the kernel choice once so the sequential baseline and every
    # engine variant compare the same numerics on any backend (on TPU all
    # paths get the Pallas kernel; on CPU this is the identity)
    cfg = predictor.inference_config(cfg)
    params = predictor.init_params(cfg, jax.random.PRNGKey(0))
    names = list(progen.TABLE_II)[:n_benchmarks]
    ec = (config or bench_scale_config(quick)).replace(
        warmup=0, with_oracle=False)
    # every RT-cached pass shares one persistent store: passes with
    # identical (params, cfg, vocab) content keys adopt each other's
    # table instead of re-paying the cold encode, and the restart pass
    # below proves a fresh process would do the same
    store_tmp = None
    if rt_store_dir is None:
        store_tmp = tempfile.TemporaryDirectory(prefix="rt_store_bench_")
        rt_store_dir = store_tmp.name

    benches = [progen.build_benchmark(name) for name in names]
    t0 = time.time()
    seq = {}
    seq_oracle = {}
    n_clips = 0
    seq_fe_seconds = 0.0
    seq_oracle_seconds = 0.0
    seq_predict_seconds = 0.0
    for bench in benches:
        cycles, ocycles, k, fe_s, o_s, p_s = _sequential_simulate(
            bench, params, cfg, vocab, ec, with_oracle=True)
        seq[bench.name] = cycles
        seq_oracle[bench.name] = ocycles
        n_clips += k
        seq_fe_seconds += fe_s
        seq_oracle_seconds += o_s
        seq_predict_seconds += p_s
    seq_seconds = time.time() - t0 - seq_oracle_seconds
    seq_cps = n_clips / max(seq_seconds, 1e-9)

    # timed engine runs stay oracle-free so the throughput accounting is
    # exact (host oracle work would overlap the async device pipeline,
    # making a wall-minus-oracle subtraction overstate the engine).  Each
    # variant runs twice: the cold pass pays jit compiles (and the RT
    # table build), the warm pass is the steady-state device cost the
    # predict gate compares.
    def engine_pass(rt_cache, precision=None, n_runs=2, fused=False,
                    store_dir=None):
        engine = SimulationEngine.from_config(
            params, cfg, vocab,
            ec.replace(rt_cache=rt_cache, precision=precision,
                       fused_serving=fused, rt_store_dir=store_dir))
        passes, results = [], None
        prev = {}
        for _ in range(n_runs):
            t0 = time.time()
            results = engine.run(benches)   # reuse the built benchmarks
            rt = engine.last_rt_stats       # (and their compiled caches)
            # cache stats are cumulative over the cache's lifetime —
            # report per-pass deltas so a 2-pass run doesn't double-count
            cum = rt.as_dict() if rt else {}
            delta = {k: v - prev.get(k, 0) for k, v in cum.items()}
            passes.append({"seconds": time.time() - t0,
                           "predict_seconds":
                               engine.last_stats.predict_seconds,
                           "rt_build_seconds":
                               delta.get("rt_build_seconds", 0.0),
                           "rt": delta})
            prev = cum
        return engine, results, passes

    _, res_nc, p_nc = engine_pass(rt_cache=False)
    engine, results, p_rt = engine_pass(rt_cache=True,
                                        store_dir=rt_store_dir)
    eng_seconds = p_rt[0]["seconds"]        # cold: end-to-end accounting
    stats = engine.last_stats
    fe = engine.frontend_stats
    eng_cps = stats.n_clips / max(eng_seconds, 1e-9)
    # per-run RT figures: all encoding happens in the cold pass; the warm
    # pass is pure gather service for one full workload
    rt_rows_encoded = sum(p["rt"]["rt_rows_encoded"] for p in p_rt)
    rt_rows_served = p_rt[-1]["rt"]["rt_rows_served"]
    rt_cache_stats = {"rt_rows_encoded": rt_rows_encoded,
                      "rt_encode_passes":
                          sum(p["rt"]["rt_encode_passes"] for p in p_rt),
                      "rt_rows_served_per_run": rt_rows_served,
                      "rt_rows_avoided_per_run":
                          max(rt_rows_served - rt_rows_encoded, 0),
                      "rt_build_seconds": p_rt[0]["rt_build_seconds"],
                      "rt_build_warm_seconds": p_rt[1]["rt_build_seconds"]}

    # opt-in low-precision mode: relative-error-bounded, never bitwise
    _, res_bf16, p_bf16 = engine_pass(rt_cache=True, precision="bf16",
                                      n_runs=1, store_dir=rt_store_dir)

    def rel_errors(res):
        return {r.name: abs(b.predicted_cycles - r.predicted_cycles)
                / max(abs(r.predicted_cycles), 1e-9)
                for r, b in zip(results, res)}

    bf16_rel = rel_errors(res_bf16)
    bf16_max_rel = max(bf16_rel.values())

    # int8: the storage/accuracy rung below bf16 — per-channel weight
    # fake-quantization at engine build, fp32 compute.  The resolved cfg
    # is the fp32 one, so the jit'd step is already warm from the rt
    # pass; one run suffices (its RT build encodes the quantized table).
    _, res_int8, p_int8 = engine_pass(rt_cache=True, precision="int8",
                                      n_runs=1, store_dir=rt_store_dir)
    int8_rel = rel_errors(res_int8)
    int8_max_rel = max(int8_rel.values())

    # fused serving step: context dedup + weighted attention +
    # precomputed cross K/V, fp32, tolerance-gated vs the unfused pass
    _, res_fused, p_fused = engine_pass(rt_cache=True, fused=True,
                                        store_dir=rt_store_dir)
    fused_rel = rel_errors(res_fused)
    fused_max_rel = max(fused_rel.values())

    # the full stack: int8 weights through the fused step
    _, res_stack, p_stack = engine_pass(rt_cache=True, precision="int8",
                                        fused=True,
                                        store_dir=rt_store_dir)
    stack_rel = rel_errors(res_stack)
    stack_max_rel = max(stack_rel.values())

    # store restart: a FRESH engine under the same content key as the rt
    # pass must adopt the persisted table (zero re-encode, sub-second
    # build) and reproduce the fp32 results bitwise — the "second
    # cold-start" the persistent store exists for
    _, res_restart, p_restart = engine_pass(rt_cache=True, n_runs=1,
                                            store_dir=rt_store_dir)
    restart_rt = p_restart[0]["rt"]
    restart_bitwise = all(
        a.predicted_cycles == b.predicted_cycles
        for a, b in zip(res_restart, results))
    if store_tmp is not None:
        store_tmp.cleanup()

    rt_warm = (p_rt[1]["predict_seconds"] + p_rt[1]["rt_build_seconds"])
    predict_speedup = p_nc[1]["predict_seconds"] / max(rt_warm, 1e-9)
    predict_speedup_cold = ((p_nc[0]["predict_seconds"])
                            / max(p_rt[0]["predict_seconds"]
                                  + p_rt[0]["rt_build_seconds"], 1e-9))
    seq_predict_speedup = seq_predict_seconds / max(rt_warm, 1e-9)

    # the predict-stack tier ladder: every warm tier normalized against
    # the monolithic pooled path so the gate compares like with like
    mono_warm = p_nc[1]["predict_seconds"]
    fused_warm = (p_fused[1]["predict_seconds"]
                  + p_fused[1]["rt_build_seconds"])
    stack_warm = (p_stack[1]["predict_seconds"]
                  + p_stack[1]["rt_build_seconds"])
    tiers = {
        "monolithic_warm_seconds": mono_warm,
        "rt_cold_seconds": (p_rt[0]["predict_seconds"]
                            + p_rt[0]["rt_build_seconds"]),
        "rt_warm_seconds": rt_warm,
        "bf16_warm_seconds": p_bf16[0]["predict_seconds"],
        "int8_warm_seconds": p_int8[0]["predict_seconds"],
        "fused_warm_seconds": fused_warm,
        "fused_int8_warm_seconds": stack_warm}
    predict_stack = {
        "tiers": tiers,
        "tier_speedups_vs_monolithic": {
            k.replace("_seconds", ""): mono_warm / max(v, 1e-9)
            for k, v in tiers.items()
            if k != "monolithic_warm_seconds"},
        "fused_speedup": rt_warm / max(fused_warm, 1e-9),
        "stack_speedup": rt_warm / max(stack_warm, 1e-9),
        "bf16_max_rel_error": bf16_max_rel,
        "int8_max_rel_error": int8_max_rel,
        "fused_max_rel_error": fused_max_rel,
        "stack_max_rel_error": stack_max_rel,
        "rt_store": {
            "store_dir_was_temp": store_tmp is not None,
            "restart_rt_build_seconds": restart_rt.get(
                "rt_build_seconds", 0.0),
            "restart_store_load_seconds": restart_rt.get(
                "rt_store_load_seconds", 0.0),
            "restart_rows_encoded": restart_rt.get("rt_rows_encoded", 0),
            "restart_rows_loaded": restart_rt.get("rt_rows_loaded", 0),
            "restart_bitwise_equal": restart_bitwise}}

    # untimed columnar-oracle pass over the same interval structure the
    # engine executes: the oracle half of the bitwise gate
    eng_oracle = {}
    t0 = time.time()
    for bench in benches:
        cprog = bench.compiled()
        cst = progen.fresh_compiled_state(bench)
        cycles = 0.0
        for _ in range(min(bench.ckp_num, ec.max_checkpoints)):
            tr, cst = funcsim.run_compiled(cprog, ec.interval_size, cst)
            if not len(tr):
                break
            cycles += timing.total_cycles_columnar(tr)
        eng_oracle[bench.name] = cycles
    eng_oracle_seconds = time.time() - t0

    per_bench = {}
    mismatches = []
    for r, r_nc in zip(results, res_nc):
        equal = seq[r.name] == r.predicted_cycles
        # the RT-cache gather path must reproduce the monolithic pooled
        # path bit for bit (fp32): the tentpole's correctness gate
        rt_equal = r_nc.predicted_cycles == r.predicted_cycles
        oracle_equal = seq_oracle[r.name] == eng_oracle[r.name]
        per_bench[r.name] = {"sequential_cycles": seq[r.name],
                             "engine_cycles": r.predicted_cycles,
                             "engine_monolithic_cycles":
                                 r_nc.predicted_cycles,
                             "bitwise_equal": equal,
                             "rt_cache_bitwise_equal": rt_equal,
                             "bf16_rel_error": bf16_rel[r.name],
                             "int8_rel_error": int8_rel[r.name],
                             "fused_rel_error": fused_rel[r.name],
                             "fused_int8_rel_error": stack_rel[r.name],
                             "sequential_oracle_cycles": seq_oracle[r.name],
                             "engine_oracle_cycles": eng_oracle[r.name],
                             "oracle_bitwise_equal": oracle_equal}
        if not (equal and rt_equal and oracle_equal):
            mismatches.append(r.name)
    assert stats.n_clips == n_clips, \
        f"engine saw {stats.n_clips} clips, sequential saw {n_clips}"

    ratio = eng_cps / max(seq_cps, 1e-9)
    fe_ratio = seq_fe_seconds / max(fe.frontend_seconds, 1e-9)
    emit.emit("speed.multi_sequential", seq_seconds * 1e6 / n_clips,
              f"{n_benchmarks} benchmarks one-at-a-time: {n_clips} clips "
              f"in {seq_seconds:.2f}s = {seq_cps:.0f} clips/s (fresh jit "
              "+ full-batch remainder pad per benchmark)")
    emit.emit("speed.multi_engine", eng_seconds * 1e6 / n_clips,
              f"shared pool: {stats.n_batches} batches, {stats.n_pad} pad "
              f"rows in {eng_seconds:.2f}s = {eng_cps:.0f} clips/s = "
              f"{ratio:.2f}x sequential; per-bench cycles "
              f"{'bitwise equal' if not mismatches else 'MISMATCH: ' + str(mismatches)}")
    emit.emit("speed.multi_frontend", fe.frontend_seconds * 1e6
              / max(n_clips, 1),
              f"columnar IR front-end {fe.frontend_seconds:.2f}s vs "
              f"object baseline {seq_fe_seconds:.2f}s = {fe_ratio:.2f}x "
              f"(interpret {fe.interpret_seconds:.2f}s / tokenize "
              f"{fe.tokenize_seconds:.2f}s / context "
              f"{fe.context_seconds:.2f}s)")
    emit.emit("speed.multi_predict", rt_warm * 1e6 / max(n_clips, 1),
              f"RT-cache predict {rt_warm:.2f}s vs monolithic pooled "
              f"{p_nc[1]['predict_seconds']:.2f}s warm = "
              f"{predict_speedup:.2f}x ({rt_rows_encoded} static "
              f"rows encoded once vs {rt_rows_served} dynamic "
              f"rows gathered per run); bf16 max rel err "
              f"{bf16_max_rel:.4%}")
    emit.emit("speed.multi_predict_stack", stack_warm * 1e6
              / max(n_clips, 1),
              f"fused+int8 warm predict {stack_warm:.2f}s = "
              f"{predict_stack['stack_speedup']:.2f}x over warm RT "
              f"({predict_stack['fused_speedup']:.2f}x fused alone); "
              f"rel err fused {fused_max_rel:.2e} int8 "
              f"{int8_max_rel:.4%} stack {stack_max_rel:.4%}; restart "
              f"loaded {predict_stack['rt_store']['restart_rows_loaded']}"
              f" rows, encoded "
              f"{predict_stack['rt_store']['restart_rows_encoded']}, "
              f"build "
              f"{predict_stack['rt_store']['restart_rt_build_seconds']:.2f}s")
    predict = {
        "sequential_seconds": seq_predict_seconds,
        "monolithic_cold_seconds": p_nc[0]["predict_seconds"],
        "monolithic_warm_seconds": p_nc[1]["predict_seconds"],
        "rt_cache_cold_seconds": p_rt[0]["predict_seconds"],
        "rt_cache_warm_seconds": p_rt[1]["predict_seconds"],
        "rt_build_cold_seconds": p_rt[0]["rt_build_seconds"],
        "rt_build_warm_seconds": p_rt[1]["rt_build_seconds"],
        "predict_speedup": predict_speedup,
        "predict_speedup_cold": predict_speedup_cold,
        "sequential_predict_speedup": seq_predict_speedup,
        "monolithic_clips_per_s":
            n_clips / max(p_nc[1]["predict_seconds"], 1e-9),
        "rt_cache_clips_per_s": n_clips / max(rt_warm, 1e-9),
        "bf16_predict_seconds": p_bf16[0]["predict_seconds"],
        "bf16_max_rel_error": bf16_max_rel,
        "rt_cache": rt_cache_stats}
    return {"schema_version": BENCH_SCHEMA_VERSION,
            "n_benchmarks": n_benchmarks, "n_clips": n_clips,
            "quick": quick,
            "predict_stack": {"schema_version": BENCH_SCHEMA_VERSION,
                              "quick": quick, "n_clips": n_clips,
                              **predict_stack},
            "sequential_seconds": seq_seconds,
            "engine_seconds": eng_seconds,
            "sequential_clips_per_s": seq_cps,
            "engine_clips_per_s": eng_cps,
            "engine_speedup": ratio,
            "engine_batches": stats.n_batches,
            "engine_pad_rows": stats.n_pad,
            "all_bitwise_equal": not mismatches,
            "predict": predict,
            "frontend": {
                "schema_version": BENCH_SCHEMA_VERSION,
                "sequential_seconds": seq_fe_seconds,
                "engine": fe.as_dict(),
                "predict_seconds": stats.predict_seconds,
                "sequential_oracle_seconds": seq_oracle_seconds,
                "columnar_oracle_seconds": eng_oracle_seconds,
                "frontend_speedup": fe_ratio,
                **rt_cache_stats,
                "predict_speedup": predict_speedup},
            "per_bench": per_bench,
            # the full registry at end of run: span totals, histograms,
            # per-instance predictor/rt counters — one artifact carries
            # both the derived figures above and their raw source
            "metrics": REGISTRY.snapshot()}


# --------------------------------------------------------------------------- #
# Dataset-build throughput: per-stage breakdown, single- vs multicore
# --------------------------------------------------------------------------- #

def _build_report(stats, seconds: float, n_clips: int) -> dict:
    return {"seconds": seconds,
            "n_clips": n_clips,
            "clips_per_s": n_clips / max(seconds, 1e-9),
            "instructions_per_s":
                stats.n_instructions / max(seconds, 1e-9),
            "stages": stats.as_dict()}


def run_dataset_build(emit, *, quick: bool = False,
                      n_cores: int = 2) -> dict:
    """Dataset-build throughput breakdown (training-side front end).

    Builds the single-core Table-II clip dataset and the N-core mt.*
    dataset through the shared tokenize/sample/shard pipeline, reporting
    build seconds per stage (interpret / oracle / slice / sample /
    replay / tokenize / context) and clips/sec — the perf-trajectory
    artifact for the training subsystem, alongside the inference-side
    front-end breakdown.
    """
    from repro.data.dataset import BuildConfig, BuildStats, build_dataset
    from repro.data.multicore_dataset import (MulticoreBuildConfig,
                                              build_multicore_dataset)

    vocab = build_vocab()
    kw = dict(interval_size=2_000 if quick else 10_000,
              warmup=200 if quick else 1_000,
              max_checkpoints=1 if quick else 2,
              l_min=50, l_clip=64, l_token=16, threshold=50, coef=0.1)
    names = list(progen.TABLE_II)[: 4 if quick else 8]

    stats = BuildStats()
    t0 = time.time()
    ds = build_dataset(names, BuildConfig(**kw), vocab, stats=stats)
    single = _build_report(stats, time.time() - t0, len(ds))
    emit.emit("speed.dataset_build_single",
              single["seconds"] * 1e6 / max(len(ds), 1),
              f"{len(names)} benchmarks -> {len(ds)} clips in "
              f"{single['seconds']:.2f}s = {single['clips_per_s']:.0f} "
              f"clips/s (oracle {stats.oracle_seconds:.2f}s interpret "
              f"{stats.interpret_seconds:.2f}s replay "
              f"{stats.replay_seconds:.2f}s)")

    mc_stats = BuildStats()
    mc_cfg = MulticoreBuildConfig(n_cores=n_cores, **kw)
    t0 = time.time()
    mds = build_multicore_dataset(list(multicore.MULTICORE_NAMES),
                                  mc_cfg, vocab, stats=mc_stats)
    mc = _build_report(mc_stats, time.time() - t0, len(mds))
    mc["n_cores"] = n_cores
    mc["context_len"] = mds.context_len
    emit.emit("speed.dataset_build_multicore",
              mc["seconds"] * 1e6 / max(len(mds), 1),
              f"{len(multicore.MULTICORE_NAMES)} mt benchmarks x "
              f"{n_cores} cores -> {len(mds)} clips in "
              f"{mc['seconds']:.2f}s = {mc['clips_per_s']:.0f} clips/s "
              f"(multicore oracle {mc_stats.oracle_seconds:.2f}s)")
    return {"schema_version": BENCH_SCHEMA_VERSION, "quick": quick,
            "single": single, "multicore": mc}


# --------------------------------------------------------------------------- #
# Multicore: engine (benchmark, core) shards vs sequential per-core path
# --------------------------------------------------------------------------- #

def _sequential_multicore(mb, params, cfg, vocab, ec: EngineConfig, *,
                          quantum, timing_params):
    """The no-engine multicore reference: the SAME interleaved front-end
    (``run_multicore``), but each (core, checkpoint) clip batch predicts
    through its own synchronous monolithic loop with full-batch padding —
    no pooling, no RT cache.  Accumulation mirrors the engine exactly:
    one ``float(chunk.sum())`` per (core, checkpoint) segment, so per-core
    AND summed cycles must agree bitwise with the pooled RT-cache path.
    Returns per-core predicted cycles, per-core oracle cycles
    (``simulate_multicore`` over the recorded interleave), clip counts,
    and the predict wall time.
    """
    interval_size, max_checkpoints = ec.interval_size, ec.max_checkpoints
    l_min, l_clip, l_token = ec.l_min, ec.l_clip, ec.l_token
    batch_size = ec.batch_size
    predict = jax.jit(lambda p, b: predictor.predict_step(p, b, cfg))
    cprogs = mb.compiled()
    tables = [cp.token_table(vocab, l_token) for cp in cprogs]
    states = mb.fresh_states()
    n = mb.n_cores
    pred_cycles = [0.0] * n
    oracle_cycles = [0.0] * n
    clips = [0] * n
    predict_seconds = 0.0
    oracle_seconds = 0.0
    for _ in range(min(mb.ckp_num, max_checkpoints)):
        mtrace = multicore.run_multicore(
            cprogs, interval_size, states, snapshot_every=l_min,
            quantum=quantum)
        if len(mtrace) == 0:
            break
        for c, trace in enumerate(mtrace.cores):
            if not len(trace):
                continue
            tok, mask = std_mod.encode_fixed_clips(
                tables[c], trace.pc, l_min, l_clip)
            ctx_all = ctx_mod.context_tokens_from_matrix(
                trace.snapshots, vocab, core_id=c)
            rows = np.minimum(np.arange(tok.shape[0]), len(ctx_all) - 1)
            ctx = ctx_all[rows]
            k = tok.shape[0]
            pad = (-k) % batch_size
            if pad:
                tok = np.concatenate(
                    [tok, np.zeros((pad,) + tok.shape[1:], tok.dtype)])
                ctx = np.concatenate(
                    [ctx, np.zeros((pad,) + ctx.shape[1:], ctx.dtype)])
                mask = np.concatenate(
                    [mask, np.zeros((pad,) + mask.shape[1:], mask.dtype)])
            preds = []
            t0 = time.time()
            for lo in range(0, tok.shape[0], batch_size):
                batch = {
                    "clip_tokens": jnp.asarray(tok[lo:lo + batch_size]),
                    "context_tokens": jnp.asarray(ctx[lo:lo + batch_size]),
                    "clip_mask": jnp.asarray(mask[lo:lo + batch_size])}
                preds.append(np.asarray(predict(params, batch)))
            predict_seconds += time.time() - t0
            pred_cycles[c] += float(np.concatenate(preds)[:k].sum())
            clips[c] += k
        t0 = time.time()
        totals = timing.total_cycles_multicore(
            mtrace.cores, mtrace.schedule, timing_params)
        oracle_seconds += time.time() - t0
        for c, cyc in enumerate(totals):
            oracle_cycles[c] += cyc
    return (pred_cycles, oracle_cycles, clips, predict_seconds,
            oracle_seconds)


def _columnar_oracle_n1(mb, *, interval_size, max_checkpoints, l_min,
                        timing_params):
    """Single-core anchor: the same intervals through plain
    ``run_compiled`` + ``simulate_columnar`` (no multicore machinery at
    all) — ``simulate_multicore`` at N=1 must match this bitwise."""
    assert mb.n_cores == 1
    cprog = mb.compiled()[0]
    st = mb.fresh_states()[0]
    cycles = 0.0
    for _ in range(min(mb.ckp_num, max_checkpoints)):
        trace, st = funcsim.run_compiled(cprog, interval_size, st,
                                         snapshot_every=l_min)
        if not len(trace):
            break
        cycles += timing.total_cycles_columnar(trace, timing_params)
    return cycles


def run_multicore_bench(emit, *, core_counts=(1, 2, 4),
                        quick: bool = False,
                        config: "EngineConfig | None" = None) -> dict:
    """Engine-vs-sequential equality and throughput at 1/2/4 cores.

    Engine = ``SimulationEngine.run_multicore``: interleaved per-core
    functional sims -> (benchmark, core) shards through one pooled
    RT-cached predictor -> demuxed per-core sums.  Sequential = the same
    front-end with per-(core, checkpoint) monolithic predict loops.  The
    gates (CI-enforced): per-core AND summed predicted cycles bitwise
    equal at every core count; oracle cycles equal between both paths;
    and at N=1 the multicore oracle bitwise equal to
    ``simulate_columnar``.
    """
    vocab = build_vocab()
    cfg = predictor.inference_config(bench_cfg() if quick else full_cfg())
    params = predictor.init_params(cfg, jax.random.PRNGKey(0))
    names = list(multicore.MULTICORE_NAMES)
    tp = timing.TimingParams()
    ec = (config or bench_scale_config(quick)).replace(
        warmup=0, with_oracle=False, rt_cache=True)
    quantum = multicore.DEFAULT_QUANTUM

    per_count = {}
    mismatches = []
    for n_cores in core_counts:
        mbenches = [multicore.build_multicore_benchmark(n, n_cores)
                    for n in names]
        engine = SimulationEngine.from_config(params, cfg, vocab, ec)
        t0 = time.time()
        results = engine.run_multicore(mbenches, quantum=quantum)
        eng_seconds = time.time() - t0
        fe = engine.frontend_stats
        stats = engine.last_stats
        n_clips = stats.n_clips

        t0 = time.time()
        per_bench = {}
        seq_predict_seconds = 0.0
        seq_oracle_seconds = 0.0
        prior_mismatches = len(mismatches)
        for mb, r in zip(mbenches, results):
            seq_pred, seq_oracle, seq_clips, p_s, o_s = \
                _sequential_multicore(mb, params, cfg, vocab, ec,
                                      quantum=quantum, timing_params=tp)
            seq_predict_seconds += p_s
            seq_oracle_seconds += o_s
            cores = []
            core_equal = True
            for c, cr in enumerate(r.cores):
                eq = cr.predicted_cycles == seq_pred[c]
                core_equal &= eq
                assert cr.n_clips == seq_clips[c], \
                    (cr.name, cr.n_clips, seq_clips[c])
                cores.append({"core": c,
                              "engine_cycles": cr.predicted_cycles,
                              "sequential_cycles": seq_pred[c],
                              "oracle_cycles": seq_oracle[c],
                              "n_clips": cr.n_clips,
                              "bitwise_equal": eq})
            summed_seq = 0.0
            for v in seq_pred:
                summed_seq += v
            summed_equal = r.predicted_cycles == summed_seq
            entry = {"cores": cores,
                     "summed_engine_cycles": r.predicted_cycles,
                     "summed_sequential_cycles": summed_seq,
                     "summed_bitwise_equal": summed_equal,
                     "oracle_cycles_total": sum(seq_oracle)}
            if not (core_equal and summed_equal):
                mismatches.append(f"{mb.name}@{n_cores}")
            per_bench[mb.name] = entry
        seq_seconds = (time.time() - t0 - seq_oracle_seconds)
        if n_cores == 1:
            # the single-core oracle anchor runs OUTSIDE the timed
            # window: it is a correctness reference, not part of the
            # sequential path's throughput accounting
            for mb in mbenches:
                entry = per_bench[mb.name]
                ref = _columnar_oracle_n1(
                    mb, interval_size=ec.interval_size,
                    max_checkpoints=ec.max_checkpoints,
                    l_min=ec.l_min, timing_params=tp)
                entry["n1_oracle_columnar_cycles"] = ref
                entry["n1_oracle_bitwise_equal"] = \
                    ref == entry["oracle_cycles_total"]
                if not entry["n1_oracle_bitwise_equal"]:
                    mismatches.append(f"{mb.name}@1:oracle")
        eng_cps = n_clips / max(eng_seconds, 1e-9)
        per_count[str(n_cores)] = {
            "n_clips": n_clips,
            "engine_seconds": eng_seconds,
            "sequential_seconds": seq_seconds,
            "engine_clips_per_s": eng_cps,
            "per_core_clips_per_s": eng_cps / n_cores,
            "sequential_clips_per_s": n_clips / max(seq_seconds, 1e-9),
            "sequential_predict_seconds": seq_predict_seconds,
            "engine_predict_seconds": stats.predict_seconds,
            "frontend": fe.as_dict(),
            "rt": (engine.last_rt_stats.as_dict()
                   if engine.last_rt_stats else {}),
            "per_bench": per_bench}
        emit.emit(f"speed.multicore_{n_cores}", eng_seconds * 1e6
                  / max(n_clips, 1),
                  f"{len(names)} mt benchmarks x {n_cores} cores: "
                  f"{n_clips} clips in {eng_seconds:.2f}s = "
                  f"{eng_cps:.0f} clips/s ({eng_cps / n_cores:.0f}/core) "
                  f"vs sequential {seq_seconds:.2f}s; cycles "
                  f"{'bitwise equal' if len(mismatches) == prior_mismatches else 'MISMATCH'}")

    return {"schema_version": BENCH_SCHEMA_VERSION,
            "quick": quick,
            "quantum": quantum,
            "core_counts": list(core_counts),
            "benchmarks": names,
            "all_bitwise_equal": not mismatches,
            "mismatches": mismatches,
            "per_core_count": per_count}


# --------------------------------------------------------------------------- #
# Mesh scaling: sharded engine vs the unsharded reference at 1/2/N devices
# --------------------------------------------------------------------------- #

def run_mesh(emit, *, max_mesh: int = 8, quick: bool = False,
             n_benchmarks: int = 4,
             config: "EngineConfig | None" = None) -> dict:
    """Data-mesh scaling of the sharded inference engine.

    For each mesh size in {1, 2, max_mesh} (capped at the visible device
    count): a fresh engine with ``mesh_shape=(n,)`` runs the single-core
    suite twice (cold pass pays jit + the sharded RT-table build, warm
    pass is steady state) plus the 2-core multicore suite, and every
    predicted AND oracle cycle count — per benchmark, per core, and
    summed — must be bitwise equal to the unsharded (``mesh_shape=()``)
    reference engine.  The JSON (schema v3) reports clips/sec per mesh
    size and the cold RT-build scaling ratio vs the 1-device mesh; on a
    single physical core the forced host devices timeshare, so the
    ratios are reported, not gated — the gate is bitwise equality.
    """
    vocab = build_vocab()
    cfg = predictor.inference_config(bench_cfg() if quick else full_cfg())
    params = predictor.init_params(cfg, jax.random.PRNGKey(0))
    ec = (config or bench_scale_config(quick)).replace(
        warmup=0, with_oracle=True, rt_cache=True, mesh_shape=())
    names = list(progen.TABLE_II)[:n_benchmarks]
    benches = [progen.build_benchmark(name) for name in names]
    mbenches = [multicore.build_multicore_benchmark(n, 2)
                for n in multicore.MULTICORE_NAMES]

    n_devices = len(jax.devices())
    sizes = [s for s in sorted({1, 2, max_mesh}) if 0 < s <= min(
        max_mesh, n_devices)]

    def one(engine_config):
        engine = SimulationEngine.from_config(params, cfg, vocab,
                                              engine_config)
        t0 = time.time()
        engine.run(benches)               # cold: jit + RT-table build
        cold = time.time() - t0
        build = (engine.last_rt_stats.build_seconds
                 if engine.last_rt_stats else 0.0)
        t0 = time.time()
        results = engine.run(benches)     # warm: steady-state throughput
        warm = time.time() - t0
        n_clips = engine.last_stats.n_clips
        mresults = engine.run_multicore(mbenches)
        return results, mresults, cold, warm, build, n_clips

    ref, ref_mc, ref_cold, ref_warm, ref_build, n_clips = one(ec)

    per_mesh = {}
    mismatches = []
    for n in sizes:
        results, mresults, cold, warm, build, _ = one(
            ec.replace(mesh_shape=(n,)))
        equal = all(r.predicted_cycles == s.predicted_cycles
                    and r.oracle_cycles == s.oracle_cycles
                    for r, s in zip(ref, results))
        mc_equal = all(
            mr.predicted_cycles == ms.predicted_cycles
            and mr.oracle_cycles == ms.oracle_cycles
            and all(a.predicted_cycles == b.predicted_cycles
                    for a, b in zip(mr.cores, ms.cores))
            for mr, ms in zip(ref_mc, mresults))
        if not equal:
            mismatches.append(f"mesh{n}:single-core")
        if not mc_equal:
            mismatches.append(f"mesh{n}:multicore")
        cps = n_clips / max(warm, 1e-9)
        per_mesh[str(n)] = {
            "cold_seconds": cold,
            "warm_seconds": warm,
            "clips_per_s": cps,
            "rt_build_seconds": build,
            "bitwise_equal": equal,
            "multicore_bitwise_equal": mc_equal}
        emit.emit(f"speed.mesh_{n}", warm * 1e6 / max(n_clips, 1),
                  f"{n}-device mesh: {n_clips} clips in {warm:.2f}s warm "
                  f"= {cps:.0f} clips/s, cold RT build {build:.2f}s; "
                  f"cycles vs unsharded "
                  f"{'bitwise equal' if equal and mc_equal else 'MISMATCH'}")

    build_1 = per_mesh.get("1", {}).get("rt_build_seconds", ref_build)
    scaling = {k: build_1 / max(v["rt_build_seconds"], 1e-9)
               for k, v in per_mesh.items()}
    return {"schema_version": MESH_BENCH_SCHEMA_VERSION,
            "quick": quick,
            "n_devices": n_devices,
            "requested_max_mesh": max_mesh,
            "mesh_sizes": sizes,
            "n_benchmarks": n_benchmarks,
            "multicore_n_cores": 2,
            "n_clips": n_clips,
            "unsharded": {"cold_seconds": ref_cold,
                          "warm_seconds": ref_warm,
                          "rt_build_seconds": ref_build,
                          "clips_per_s": n_clips / max(ref_warm, 1e-9)},
            "per_mesh": per_mesh,
            "rt_build_scaling": scaling,
            "all_bitwise_equal": not mismatches,
            "mismatches": mismatches}


# --------------------------------------------------------------------------- #
# Observability overhead: traced vs untraced warm fused+int8 predict
# --------------------------------------------------------------------------- #

def run_obs_overhead(emit, *, quick: bool = False, repeats: int = 3,
                     n_benchmarks: int = 8,
                     config: "EngineConfig | None" = None,
                     trace_out: "str | None" = None) -> dict:
    """Measure what span tracing costs on the hot path.

    Runs the warm fused+int8 suite twice — observability default (metrics
    registry only, tracer disabled) and with ``trace=True`` — taking the
    min of ``repeats`` warm passes each, so one GC pause or CI-runner
    hiccup cannot fake a regression.  The two runs must also stay bitwise
    equal: tracing must never perturb numerics.  ``--max-obs-overhead``
    gates the relative overhead (full scale target: <= 2%).
    """
    vocab = build_vocab()
    cfg = predictor.inference_config(bench_cfg() if quick else full_cfg())
    params = predictor.init_params(cfg, jax.random.PRNGKey(0))
    names = list(progen.TABLE_II)[:n_benchmarks]
    benches = [progen.build_benchmark(name) for name in names]
    ec = (config or bench_scale_config(quick)).replace(
        warmup=0, with_oracle=False, rt_cache=True,
        fused_serving=True, precision="int8")

    def best_warm(engine_config):
        engine = SimulationEngine.from_config(params, cfg, vocab,
                                              engine_config)
        engine.run(benches)               # cold: jit + RT-table build
        best, results = float("inf"), None
        for _ in range(repeats):
            t0 = time.perf_counter()
            results = engine.run(benches)
            best = min(best, time.perf_counter() - t0)
        return best, results, engine

    from repro.core.engine_config import ObservabilityConfig
    off_s, off_res, _ = best_warm(ec)
    on_s, on_res, traced = best_warm(ec.replace(
        observability=ObservabilityConfig(trace=True)))
    if trace_out:
        traced.obs.tracer.dump(trace_out)
    overhead = on_s / max(off_s, 1e-9) - 1.0
    bitwise = all(a.predicted_cycles == b.predicted_cycles
                  for a, b in zip(off_res, on_res))
    n_clips = sum(r.n_clips for r in off_res)
    emit.emit("speed.obs_overhead", on_s * 1e6 / max(n_clips, 1),
              f"warm fused+int8 min-of-{repeats}: untraced {off_s:.3f}s "
              f"vs traced {on_s:.3f}s = {overhead:+.2%} overhead "
              f"({len(traced.obs.tracer.spans())} spans recorded); "
              f"cycles {'bitwise equal' if bitwise else 'MISMATCH'}")
    return {"schema_version": BENCH_SCHEMA_VERSION, "quick": quick,
            "repeats": repeats, "n_clips": n_clips,
            "untraced_warm_seconds": off_s,
            "traced_warm_seconds": on_s,
            "overhead_ratio": overhead,
            "spans_recorded": len(traced.obs.tracer.spans()),
            "bitwise_equal": bitwise}


# --------------------------------------------------------------------------- #
# Subsample fusion: stratified clip subsampling vs the full fused+int8 path
# --------------------------------------------------------------------------- #

def run_subsample(emit, *, n_benchmarks: int = 8, quick: bool = False,
                  config: "EngineConfig | None" = None,
                  fraction: "float | None" = None, strata: int = 4,
                  min_clips_per_stratum: int = 2,
                  bootstrap_resamples: int = 200, seed: int = 0) -> dict:
    """Analytical-ML fusion accuracy/cost trade-off (ROADMAP item 4).

    Runs the Table-II suite twice through the SAME fused+int8 rung: once
    predicting every clip (the reference), once with stratified clip
    subsampling + ridge extrapolation + bootstrap CI.  Reports, per
    benchmark and in aggregate: the clip-prediction ratio
    (n_clips / clips_predicted), the ADDED relative cycles error of the
    fused estimate vs the full prediction (not vs the oracle — the gate
    is about what subsampling costs on top of the model), the bootstrap
    CI width, and whether the CI covers the full-prediction estimate.
    The full-scale targets: >= 10x fewer predicted clips at <= 2% added
    total-cycles error with the summed CI covering the full total.
    """
    vocab = build_vocab()
    cfg = predictor.inference_config(bench_cfg() if quick else full_cfg())
    params = predictor.init_params(cfg, jax.random.PRNGKey(0))
    names = list(progen.TABLE_II)[:n_benchmarks]
    benches = [progen.build_benchmark(name) for name in names]
    if fraction is None:
        # quick scale has ~20 clips/bench: a paper-scale fraction would
        # degenerate to the min-per-stratum floor, so quick exercises the
        # machinery at 0.25 and the full run targets the 10x reduction
        fraction = 0.25 if quick else 0.08
    scfg = SamplingConfig(fraction=fraction, strata=strata,
                          min_clips_per_stratum=min_clips_per_stratum,
                          bootstrap_resamples=bootstrap_resamples,
                          seed=seed)
    ec = (config or bench_scale_config(quick)).replace(
        warmup=0, with_oracle=False, rt_cache=True,
        fused_serving=True, precision="int8")

    def one(engine_config):
        engine = SimulationEngine.from_config(params, cfg, vocab,
                                              engine_config)
        engine.run(benches)               # cold: jit + RT-table build
        t0 = time.time()
        results = engine.run(benches)     # warm: steady state
        return results, time.time() - t0, engine.last_stats

    full_res, full_seconds, full_stats = one(ec)
    sub_res, sub_seconds, sub_stats = one(ec.replace(sampling=scfg))

    per_bench = {}
    tot_full = tot_sub = tot_lo = tot_hi = 0.0
    tot_clips = tot_predicted = 0
    n_covered = 0
    for f, s in zip(full_res, sub_res):
        lo, hi = s.cycles_ci
        err = abs(s.predicted_cycles - f.predicted_cycles) \
            / max(abs(f.predicted_cycles), 1e-9)
        covered = lo <= f.predicted_cycles <= hi
        n_covered += covered
        tot_full += f.predicted_cycles
        tot_sub += s.predicted_cycles
        tot_lo += lo
        tot_hi += hi
        tot_clips += f.n_clips
        tot_predicted += s.clips_predicted
        per_bench[f.name] = {
            "full_cycles": f.predicted_cycles,
            "fused_cycles": s.predicted_cycles,
            "added_rel_error": err,
            "n_clips": f.n_clips,
            "clips_predicted": s.clips_predicted,
            "clips_extrapolated": s.clips_extrapolated,
            "clip_ratio": f.n_clips / max(s.clips_predicted, 1),
            "ci": [lo, hi],
            "ci_width": hi - lo,
            "ci_covers_full": covered}

    clip_ratio = tot_clips / max(tot_predicted, 1)
    total_err = abs(tot_sub - tot_full) / max(abs(tot_full), 1e-9)
    per_errs = [v["added_rel_error"] for v in per_bench.values()]
    res = {
        "schema_version": SUBSAMPLE_BENCH_SCHEMA_VERSION,
        "quick": quick,
        "n_benchmarks": len(names),
        "sampling": scfg.to_dict(),
        "per_bench": per_bench,
        "total_full_cycles": tot_full,
        "total_fused_cycles": tot_sub,
        "total_ci": [tot_lo, tot_hi],
        "total_ci_covers_full": tot_lo <= tot_full <= tot_hi,
        "ci_coverage_fraction": n_covered / max(len(names), 1),
        "clip_ratio": clip_ratio,
        "total_clips": tot_clips,
        "total_clips_predicted": tot_predicted,
        "added_rel_error_total": total_err,
        "added_rel_error_max": max(per_errs),
        "added_rel_error_mean": sum(per_errs) / len(per_errs),
        "timing": {"full_seconds": full_seconds,
                   "subsample_seconds": sub_seconds,
                   "full_predict_seconds": full_stats.predict_seconds,
                   "subsample_predict_seconds": sub_stats.predict_seconds,
                   "n_predicted_full": full_stats.n_predicted,
                   "n_predicted_subsample": sub_stats.n_predicted}}
    emit.emit("speed.subsample_fusion", sub_seconds * 1e6
              / max(tot_predicted, 1),
              f"{len(names)} benchmarks: {tot_predicted}/{tot_clips} "
              f"clips predicted ({clip_ratio:.1f}x fewer), total added "
              f"err {total_err:.3%} (max per-bench {max(per_errs):.3%}), "
              f"summed CI {'covers' if res['total_ci_covers_full'] else 'MISSES'} "
              f"the full estimate; warm {full_seconds:.2f}s -> "
              f"{sub_seconds:.2f}s")
    return res


if __name__ == "__main__":
    from benchmarks.common import CsvEmitter
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi", action="store_true",
                    help="multi-benchmark sequential-vs-engine throughput")
    ap.add_argument("--multicore", action="store_true",
                    help="multicore engine-vs-sequential equality + "
                         "per-core throughput at 1/2/4 cores")
    ap.add_argument("--core-counts", type=int, nargs="+",
                    default=[1, 2, 4],
                    help="core counts for --multicore")
    ap.add_argument("--dataset-build", action="store_true",
                    help="dataset-build throughput breakdown (build "
                         "seconds per stage, clips/sec) for the single- "
                         "and multicore training builds")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="mesh-scaling pass: sharded engine at 1/2/N "
                         "devices, bitwise-gated against the unsharded "
                         "reference.  Sets XLA_FLAGS to force N host "
                         "devices if too few are visible")
    ap.add_argument("--subsample", action="store_true",
                    help="analytical-ML fusion pass: stratified clip "
                         "subsampling + ridge extrapolation vs the full "
                         "fused+int8 prediction, with clip-ratio and "
                         "added-error gates")
    ap.add_argument("--subsample-fraction", type=float, default=None,
                    help="per-stratum sampling fraction for --subsample "
                         "(default: 0.25 quick / 0.08 full)")
    ap.add_argument("--strata", type=int, default=4,
                    help="number of analytical-feature strata for "
                         "--subsample")
    ap.add_argument("--min-clip-ratio", type=float, default=0.0,
                    help="fail if total n_clips / clips_predicted falls "
                         "below this (0 disables; full-scale target is "
                         ">= 10x, quick gates >= 2x)")
    ap.add_argument("--max-added-rel-err", type=float, default=0.0,
                    help="fail if the subsampled total cycles diverge "
                         "from the full fused+int8 prediction by more "
                         "than this relative error (0 disables; "
                         "full-scale target is <= 2%%, quick <= 5%%)")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="observability-overhead pass: warm fused+int8 "
                         "suite with tracing on vs off (min-of-3), "
                         "bitwise-gated; see --max-obs-overhead")
    ap.add_argument("--max-obs-overhead", type=float, default=0.0,
                    help="--obs-overhead: fail if the traced warm pass "
                         "is slower than the untraced one by more than "
                         "this fraction (0 disables; full-scale target "
                         "is <= 0.02, quick runs use a lenient bound — "
                         "shared CI runners jitter more than 2%%)")
    ap.add_argument("--obs-repeats", type=int, default=3,
                    help="--obs-overhead: warm passes per arm (min "
                         "taken)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="--obs-overhead: dump the traced arm's "
                         "Chrome/Perfetto trace JSON here (open at "
                         "ui.perfetto.dev)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke scale (small model, short intervals)")
    ap.add_argument("--n-benchmarks", type=int, default=8)
    ap.add_argument("--engine-config", default=None, metavar="JSON",
                    help="EngineConfig overrides as a JSON object (inline "
                         "or a file path) layered over the --quick/full "
                         "scale defaults; shared by --multi, --multicore "
                         "and --mesh")
    ap.add_argument("--min-speedup", type=float, default=1.5,
                    help="fail if engine/sequential clips/s falls below "
                         "this (the CI gate; pass 0 for measurement runs)")
    ap.add_argument("--min-frontend-speedup", type=float, default=0.0,
                    help="fail if columnar/object front-end throughput "
                         "falls below this (0 disables; full-scale target "
                         "is >= 3x)")
    ap.add_argument("--min-predict-speedup", type=float, default=0.0,
                    help="fail if ANY warm predict tier (RT cache, int8, "
                         "fused, fused+int8) falls below this speedup "
                         "over the monolithic warm path (0 disables; "
                         "full-scale target is >= 2x).  The cold tier is "
                         "gated separately: the store-restart pass must "
                         "rebuild in < 1s with zero re-encode")
    ap.add_argument("--min-stack-speedup", type=float, default=0.0,
                    help="fail if the fused+int8 warm predict falls "
                         "below this speedup over the warm RT-cache "
                         "path (0 disables; full-scale target is >= 2x)")
    ap.add_argument("--max-int8-rel-err", type=float, default=0.01,
                    help="fail if the int8 (or fused+int8) predicted "
                         "cycles diverge from fp32 by more than this "
                         "relative error.  Quantization error shrinks "
                         "with model width: the full-scale model gates "
                         "at the default 1%%; the --quick CI model is 4x "
                         "narrower and gates at 5%%")
    ap.add_argument("--rt-store-dir", default=None, metavar="DIR",
                    help="persistent RT-cache store directory shared by "
                         "every --multi RT pass and the store-restart "
                         "gate (default: a fresh temp dir, so the cold "
                         "encode is always paid once in-process)")
    ap.add_argument("--json", default=None,
                    help="write the --multi result dict to this path")
    ap.add_argument("--breakdown-json", default=None,
                    help="also write just the front-end breakdown dict "
                         "(interpret/slice/tokenize/context/predict "
                         "seconds) to this path — the CI artifact that "
                         "tracks where host time goes across PRs")
    ap.add_argument("--predict-stack-json", default=None,
                    help="also write just the predict-stack tier "
                         "breakdown (monolithic/rt/bf16/int8/fused warm "
                         "seconds, speedups, rel errors, rt_store "
                         "restart block) to this path")
    args = ap.parse_args()
    if args.mesh > 1:
        # must happen before jax's first backend init (importing jax does
        # not lock the device count; the first device query/op does)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.mesh}").strip()
    enable_compile_cache()
    emitter = CsvEmitter()
    engine_config = resolve_engine_config(args.engine_config, args.quick)
    if args.obs_overhead:
        res = run_obs_overhead(emitter, quick=args.quick,
                               repeats=args.obs_repeats,
                               n_benchmarks=args.n_benchmarks,
                               config=engine_config,
                               trace_out=args.trace_out)
        if args.json:
            Path(args.json).write_text(json.dumps(res, indent=2))
        if not res["bitwise_equal"]:
            raise SystemExit(
                "traced run predicted cycles diverged from the "
                "untraced run — tracing must never perturb numerics")
        if args.max_obs_overhead and \
                res["overhead_ratio"] > args.max_obs_overhead:
            raise SystemExit(
                f"observability overhead {res['overhead_ratio']:+.2%} > "
                f"{args.max_obs_overhead:.2%} — tracing is intruding on "
                "the hot path")
    elif args.dataset_build:
        res = run_dataset_build(emitter, quick=args.quick)
        if args.json:
            Path(args.json).write_text(json.dumps(res, indent=2))
    elif args.mesh:
        res = run_mesh(emitter, max_mesh=args.mesh, quick=args.quick,
                       n_benchmarks=min(args.n_benchmarks, 4),
                       config=engine_config)
        if args.json:
            Path(args.json).write_text(json.dumps(res, indent=2))
        if args.mesh not in res["mesh_sizes"]:
            raise SystemExit(
                f"requested --mesh {args.mesh} but only "
                f"{res['n_devices']} devices are visible — XLA_FLAGS "
                "was set too late (jax backend already initialized?)")
        if not res["all_bitwise_equal"]:
            raise SystemExit(
                "sharded engine cycles diverged from the unsharded "
                f"reference: {res['mismatches']}")
    elif args.subsample:
        res = run_subsample(emitter, n_benchmarks=args.n_benchmarks,
                            quick=args.quick, config=engine_config,
                            fraction=args.subsample_fraction,
                            strata=args.strata)
        if args.json:
            Path(args.json).write_text(json.dumps(res, indent=2))
        if not res["total_ci_covers_full"]:
            raise SystemExit(
                f"summed bootstrap CI {res['total_ci']} does not cover "
                f"the full-prediction total {res['total_full_cycles']}")
        if args.min_clip_ratio and res["clip_ratio"] < args.min_clip_ratio:
            raise SystemExit(
                f"clip-prediction ratio {res['clip_ratio']:.2f}x < "
                f"{args.min_clip_ratio}x — subsampling is not reducing "
                "predicted clips enough")
        if args.max_added_rel_err and \
                res["added_rel_error_total"] > args.max_added_rel_err:
            raise SystemExit(
                f"subsampled total cycles added rel error "
                f"{res['added_rel_error_total']:.4%} > "
                f"{args.max_added_rel_err:.4%} vs the full fused+int8 "
                "prediction")
    elif args.multicore:
        res = run_multicore_bench(emitter, core_counts=args.core_counts,
                                  quick=args.quick, config=engine_config)
        if args.json:
            Path(args.json).write_text(json.dumps(res, indent=2))
        if not res["all_bitwise_equal"]:
            raise SystemExit(
                "multicore engine/sequential/oracle cycles diverged: "
                f"{res['mismatches']}")
    elif args.multi:
        res = run_multi(emitter, n_benchmarks=args.n_benchmarks,
                        quick=args.quick, config=engine_config,
                        rt_store_dir=args.rt_store_dir)
        if args.json:
            Path(args.json).write_text(json.dumps(res, indent=2))
        if args.breakdown_json:
            Path(args.breakdown_json).write_text(
                json.dumps(res["frontend"], indent=2))
        if args.predict_stack_json:
            Path(args.predict_stack_json).write_text(
                json.dumps(res["predict_stack"], indent=2))
        if not res["all_bitwise_equal"]:
            raise SystemExit("engine/sequential/RT-cache predicted or "
                             "oracle cycles diverged from the reference")
        ps = res["predict_stack"]
        bf16_err = res["predict"]["bf16_max_rel_error"]
        if bf16_err > 0.01:
            raise SystemExit(
                f"bf16 predict mode rel error {bf16_err:.4%} > 1%")
        # the fused step is an fp32 refactoring of the same math: only
        # reassociation separates it from the unfused path
        if ps["fused_max_rel_error"] > 1e-3:
            raise SystemExit(
                f"fused serving rel error "
                f"{ps['fused_max_rel_error']:.2e} > 1e-3 vs unfused")
        for tier in ("int8", "stack"):
            err = ps[f"{tier}_max_rel_error"]
            if err > args.max_int8_rel_err:
                raise SystemExit(
                    f"{tier} predict rel error {err:.4%} > "
                    f"{args.max_int8_rel_err:.4%}")
        store = ps["rt_store"]
        if store["restart_rows_encoded"] != 0:
            raise SystemExit(
                f"store restart re-encoded "
                f"{store['restart_rows_encoded']} rows (persistent "
                "store should have served all of them)")
        if not store["restart_bitwise_equal"]:
            raise SystemExit(
                "store restart predicted cycles diverged from the "
                "fp32 RT pass (persisted table not byte-identical?)")
        if store["restart_rt_build_seconds"] >= 1.0:
            raise SystemExit(
                f"store restart rt_build_seconds "
                f"{store['restart_rt_build_seconds']:.2f}s >= 1s — the "
                "persistent store is not killing the cold encode")
        if res["engine_speedup"] < args.min_speedup:
            raise SystemExit(
                f"engine speedup {res['engine_speedup']:.2f}x < "
                f"{args.min_speedup}x")
        fe_ratio = res["frontend"]["frontend_speedup"]
        if fe_ratio < args.min_frontend_speedup:
            raise SystemExit(
                f"front-end speedup {fe_ratio:.2f}x < "
                f"{args.min_frontend_speedup}x")
        warm_tiers = ("rt_warm", "int8_warm", "fused_warm",
                      "fused_int8_warm")
        tier_speedups = ps["tier_speedups_vs_monolithic"]
        worst_tier = min(warm_tiers, key=lambda k: tier_speedups[k])
        if tier_speedups[worst_tier] < args.min_predict_speedup:
            raise SystemExit(
                f"predict tier {worst_tier} speedup "
                f"{tier_speedups[worst_tier]:.2f}x < "
                f"{args.min_predict_speedup}x vs monolithic warm")
        if ps["stack_speedup"] < args.min_stack_speedup:
            raise SystemExit(
                f"fused+int8 stack speedup {ps['stack_speedup']:.2f}x "
                f"< {args.min_stack_speedup}x over warm RT")
    else:
        run(emitter)
