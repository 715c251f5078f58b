"""Sharded inference engine: bitwise-equality gates (PR 6 tentpole).

In-process tests cover what a 1-device session can: a ``(1,)`` mesh
still dispatches through ``shard_map`` and must be bitwise equal to the
unsharded path (predict, RT-cache build, demux), and the bucket/align
math.  The real 8-way checks run in a subprocess that forces 8 host CPU
devices before jax initializes (the main pytest process is locked to
its device count at first backend init) — unless this process already
sees 8+ devices (the CI mesh leg), in which case they also run
in-process.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import predictor
from repro.core import standardize as std_mod
from repro.core.engine import BatchedPredictor, SimulationEngine, \
    bucket_sizes
from repro.core.engine_config import EngineConfig
from repro.core.rt_cache import RTCache, encode_bucket
from repro.isa import multicore, progen
from repro.launch.mesh import make_data_mesh

SMALL_CFG = get_config("capsim").replace(d_model=32, head_dim=8, d_ff=64,
                                         dtype="float32")
EC = EngineConfig(interval_size=1_000, warmup=100, max_checkpoints=1,
                  batch_size=16)


@pytest.fixture(scope="module")
def vocab():
    return std_mod.build_vocab()


@pytest.fixture(scope="module")
def params():
    return predictor.init_params(SMALL_CFG, jax.random.PRNGKey(0))


# ------------------------------ pure math ------------------------------ #

def test_bucket_sizes_alignment():
    assert bucket_sizes(256, 1) == (256, 128, 64, 32, 16, 8)
    assert bucket_sizes(32, 8) == (32, 16, 8)
    assert bucket_sizes(16, 8) == (16, 8)
    assert bucket_sizes(64, 8) == (64, 32, 16, 8)
    # every bucket divides by the mesh size and stays >= one row/device
    for bs, align in ((256, 8), (64, 4), (48, 8), (24, 8)):
        sizes = bucket_sizes(bs, align)
        assert sizes[0] == bs
        assert all(s % align == 0 for s in sizes[1:]), (bs, align, sizes)
        assert all(a > b for a, b in zip(sizes, sizes[1:]))
        assert sizes[-1] >= align


def test_encode_bucket_alignment():
    # floor = ENCODE_STABLE_MIN: every pass stays in the shape-stable
    # kernel class (row results independent of the batch dimension)
    assert encode_bucket(5) == 32
    assert encode_bucket(32) == 32
    assert encode_bucket(33) == 64
    assert encode_bucket(100) == 128
    # sharded: align = n_shards * 32 keeps every device's shard in the
    # stable class too
    assert encode_bucket(5, 8 * 32) == 256      # 32 rows/device at n=8
    assert encode_bucket(300, 8 * 32) == 512    # pow2 512 already aligned
    assert encode_bucket(9, 3 * 32) == 96       # non-power-of-two mesh
    assert encode_bucket(9, 3 * 32) % 3 == 0


def test_make_data_mesh_too_many_devices():
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_data_mesh(len(jax.devices()) + 1)
    with pytest.raises(ValueError):
        make_data_mesh(0)


# ------------------------- 1-device mesh, in-process ------------------------- #

def test_mesh1_engine_bitwise_equal(params, vocab):
    """A (1,)-mesh engine routes through shard_map yet must be bitwise
    equal to the unsharded engine — predict AND oracle."""
    bench = progen.build_benchmark("505.mcf")
    r0 = SimulationEngine.from_config(params, SMALL_CFG, vocab,
                                      EC).run([bench])[0]
    r1 = SimulationEngine.from_config(
        params, SMALL_CFG, vocab,
        EC.replace(mesh_shape=(1,))).run([bench])[0]
    assert r1.predicted_cycles == r0.predicted_cycles
    assert r1.oracle_cycles == r0.oracle_cycles


def test_mesh1_rt_table_byte_identical(params, vocab):
    bench = progen.build_benchmark("519.lbm")
    cprog = bench.compiled()
    cfg = predictor.inference_config(SMALL_CFG)
    rows = cprog.token_table(vocab, 16)
    c0 = RTCache(params, cfg, 16)
    c1 = RTCache(params, cfg, 16, n_shards=1)
    ids0 = c0.ensure_rows(rows)
    ids1 = c1.ensure_rows(rows)
    assert np.array_equal(ids0, ids1)
    assert np.asarray(c0.table[:c0.n_rows]).tobytes() == \
        np.asarray(c1.table[:c1.n_rows]).tobytes()


def test_mesh1_pool_smaller_than_bucket(params, vocab):
    """Drain with fewer clips than the smallest bucket: the mesh path
    pads with masked zero rows and the demux drops them."""
    rng = np.random.RandomState(0)
    tok = rng.randint(0, vocab.size, (3, 128, SMALL_CFG.clip_tokens)
                      ).astype(np.int32)
    ctx = rng.randint(0, vocab.size, (3, SMALL_CFG.context_tokens)
                      ).astype(np.int32)
    mask = np.ones((3, 128), np.float32)
    ref = BatchedPredictor(params, SMALL_CFG,
                           config=EC.replace(rt_cache=False))
    ref.add(tok, ctx, mask)
    p_ref = ref.drain()
    bp = BatchedPredictor(
        params, SMALL_CFG,
        config=EC.replace(mesh_shape=(1,), rt_cache=False))
    bp.add(tok, ctx, mask)
    preds = bp.drain()
    assert preds.shape == (3,)
    assert bp.stats.n_pad == 5            # padded to the bucket floor 8
    assert np.array_equal(preds, p_ref)


# ------------------------------ 8-way subprocess ------------------------------ #

PROGRAM = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np

from repro.configs import get_config
from repro.core import predictor
from repro.core import standardize as std_mod
from repro.core.engine import BatchedPredictor, SimulationEngine
from repro.core.engine_config import EngineConfig
from repro.isa import multicore, progen

assert len(jax.devices()) == 8, jax.devices()
cfg = get_config("capsim").replace(d_model=32, head_dim=8, d_ff=64,
                                   dtype="float32")
vocab = std_mod.build_vocab()
params = predictor.init_params(cfg, jax.random.PRNGKey(0))
ec = EngineConfig(interval_size=1_000, warmup=100, max_checkpoints=1,
                  batch_size=16)      # buckets (16, 8): all 8-aligned

# 1. single-core run: 8-way mesh bitwise equal to unsharded, predict
#    AND oracle, including the remainder shard padding
benches = [progen.build_benchmark(n) for n in ("505.mcf", "541.leela")]
e0 = SimulationEngine.from_config(params, cfg, vocab, ec)
r0 = e0.run(benches)
e8 = SimulationEngine.from_config(params, cfg, vocab,
                                  ec.replace(mesh_shape=(8,)))
r8 = e8.run(benches)
for a, b in zip(r0, r8):
    assert a.predicted_cycles == b.predicted_cycles, (a.name,)
    assert a.oracle_cycles == b.oracle_cycles, (a.name,)
print("single-core 8-way OK")

# 2. cold sharded RT-cache build: byte-identical table, same row ids
assert e0._rt_cache.n_rows == e8._rt_cache.n_rows
assert np.asarray(e0._rt_cache.table[:e0._rt_cache.n_rows]).tobytes() \
    == np.asarray(e8._rt_cache.table[:e8._rt_cache.n_rows]).tobytes()
print("rt table OK")

# 3. multicore (bench, core) shards demux bitwise per core and summed
mbenches = [multicore.build_multicore_benchmark(n, 2)
            for n in multicore.MULTICORE_NAMES]
m0 = SimulationEngine.from_config(params, cfg, vocab,
                                  ec).run_multicore(mbenches)
m8 = SimulationEngine.from_config(
    params, cfg, vocab,
    ec.replace(mesh_shape=(8,))).run_multicore(mbenches)
for a, b in zip(m0, m8):
    assert a.predicted_cycles == b.predicted_cycles, (a.name,)
    assert a.oracle_cycles == b.oracle_cycles, (a.name,)
    for ca, cb in zip(a.cores, b.cores):
        assert ca.predicted_cycles == cb.predicted_cycles, (ca.name,)
print("multicore 8-way OK")

# 4. pool of 3 clips on an 8-device mesh: pads to a full shard set
#    (bucket floor 8), demux drops the pads.  One row per device is a
#    different matmul shape from the unsharded 8-row pass, and XLA CPU
#    may round it differently (measured 1 ulp, 7.8e-8 relative): equal
#    to f32 rounding, not bitwise
rng = np.random.RandomState(0)
tok = rng.randint(0, vocab.size, (3, 128, cfg.clip_tokens)).astype(np.int32)
ctx = rng.randint(0, vocab.size, (3, cfg.context_tokens)).astype(np.int32)
mask = np.ones((3, 128), np.float32)
bp8 = BatchedPredictor(params, cfg,
                       config=ec.replace(mesh_shape=(8,), rt_cache=False))
bp8.add(tok, ctx, mask)
p8 = bp8.drain()
assert p8.shape == (3,) and bp8.stats.n_pad == 5
bp0 = BatchedPredictor(params, cfg, config=ec.replace(rt_cache=False))
bp0.add(tok, ctx, mask)
np.testing.assert_allclose(p8, bp0.drain(), rtol=1e-6, atol=0)
print("tiny pool OK")
print("ALL MESH ENGINE CHECKS PASSED")
"""


def test_mesh8_engine_subprocess():
    r = subprocess.run([sys.executable, "-c", PROGRAM],
                       capture_output=True, text=True, timeout=500,
                       env={**os.environ, "PYTHONPATH": "src",
                            "JAX_PLATFORMS": "cpu",
                            "XLA_FLAGS":
                            "--xla_force_host_platform_device_count=8"})
    assert "ALL MESH ENGINE CHECKS PASSED" in r.stdout, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 devices (CI mesh leg sets "
                           "--xla_force_host_platform_device_count=8)")
def test_mesh8_engine_inprocess(params, vocab):
    """The CI 8-device leg runs the core equality in-process too (no
    subprocess indirection between the gate and the report)."""
    bench = progen.build_benchmark("505.mcf")
    r0 = SimulationEngine.from_config(params, SMALL_CFG, vocab,
                                      EC).run([bench])[0]
    r8 = SimulationEngine.from_config(
        params, SMALL_CFG, vocab,
        EC.replace(mesh_shape=(8,))).run([bench])[0]
    assert r8.predicted_cycles == r0.predicted_cycles
    assert r8.oracle_cycles == r0.oracle_cycles
    mb = multicore.build_multicore_benchmark(
        list(multicore.MULTICORE_NAMES)[0], 2)
    m0 = SimulationEngine.from_config(params, SMALL_CFG, vocab,
                                      EC).run_multicore([mb])[0]
    m8 = SimulationEngine.from_config(
        params, SMALL_CFG, vocab,
        EC.replace(mesh_shape=(8,))).run_multicore([mb])[0]
    assert m8.predicted_cycles == m0.predicted_cycles
    assert all(a.predicted_cycles == b.predicted_cycles
               for a, b in zip(m0.cores, m8.cores))
