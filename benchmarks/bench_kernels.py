"""Kernel microbenchmarks: Pallas flash-attention / SSD vs their oracles.

On this CPU host the Pallas kernels execute in interpret mode (Python), so
their wall time is NOT a TPU performance signal — correctness drift is the
payload here.  The XLA paths (chunked attention / chunked SSD), which are
what actually runs on CPU, are timed for real.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd.ops import ssd_scan
from repro.kernels.ssd.ref import ssd_ref
from repro.models.attention import _causal_attention_chunked
from repro.models.mamba2 import ssd_chunked


def _time(fn, *args, n=5) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / n * 1e6


def run(emit) -> None:
    rng = np.random.RandomState(0)
    B, S, H, D = 2, 256, 4, 64
    q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))

    ref = attention_ref(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    drift = float(jnp.max(jnp.abs(out - ref)))
    us = _time(jax.jit(lambda a, b, c: _causal_attention_chunked(
        a, b, c, 128)), q, k, v)
    emit.emit("kernels.attn_chunked_xla", us,
              f"B{B}xS{S}xH{H}xD{D} causal (CPU execution path)")
    emit.emit("kernels.attn_pallas_drift", 0.0,
              f"flash kernel vs ref max|err| {drift:.2e} (interpret mode)")

    Bt, S2, H2, P, N = 2, 256, 4, 64, 128
    x = jnp.asarray(rng.randn(Bt, S2, H2, P).astype(np.float32) * 0.5)
    dt = jnp.asarray(np.abs(rng.randn(Bt, S2, H2)).astype(np.float32) * 0.3
                     + 0.01)
    Bm = jnp.asarray(rng.randn(Bt, S2, N).astype(np.float32) * 0.3)
    Cm = jnp.asarray(rng.randn(Bt, S2, N).astype(np.float32) * 0.3)
    A = jnp.asarray(-np.abs(rng.randn(H2)).astype(np.float32) - 0.1)

    y_ref, st_ref = ssd_ref(x, dt, Bm, Cm, A)
    y_k, st_k = ssd_scan(x, dt, Bm, Cm, A, chunk=64)
    drift2 = float(jnp.max(jnp.abs(y_k - y_ref)))
    us2 = _time(jax.jit(lambda *a: ssd_chunked(*a, 64)), x, dt, Bm, Cm, A)
    emit.emit("kernels.ssd_chunked_xla", us2,
              f"Bt{Bt}xS{S2}xH{H2}xP{P}xN{N} (CPU execution path)")
    emit.emit("kernels.ssd_pallas_drift", 0.0,
              f"SSD kernel vs naive-recurrence ref max|err| {drift2:.2e}")


if __name__ == "__main__":
    from benchmarks.common import CsvEmitter
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(CsvEmitter())
