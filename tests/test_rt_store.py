"""Persistent content-addressed RT-cache store invariants.

The store contract: a fresh cache constructed under the same content key
(params bytes + model config + l_token + vocab signature) adopts the
persisted (rows -> RT vectors) table byte for byte with ZERO re-encode;
ANY key ingredient changing silently invalidates (clean rebuild, no
warning); a store that matches the key but is corrupt warns and falls
back to cold encoding instead of crashing or serving bad vectors.
"""
import glob
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import predictor
from repro.core.engine import SimulationEngine
from repro.core.engine_config import EngineConfig
from repro.core.rt_cache import RT_STORE_VERSION, RTCache, rt_store_key
from repro.core.standardize import build_vocab
from repro.isa import progen

VOCAB = build_vocab()
SMALL_CFG = get_config("capsim").replace(
    d_model=32, head_dim=8, d_ff=64, dtype="float32")
MIX = ["503.bwaves", "541.leela"]
ENGINE_KW = dict(interval_size=1_500, warmup=200, max_checkpoints=2,
                 l_min=32, l_clip=32, l_token=16, batch_size=16,
                 with_oracle=False)


@pytest.fixture(scope="module")
def params():
    return predictor.init_params(SMALL_CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def table():
    cprog = progen.build_benchmark("505.mcf").compiled()
    return cprog.token_table(VOCAB, 16)


def _cache(params, store_dir, **kw):
    kw.setdefault("store_extra", VOCAB.signature())
    return RTCache(params, SMALL_CFG, 16, store_dir=str(store_dir), **kw)


def test_store_round_trip_byte_identical(params, table, tmp_path):
    c1 = _cache(params, tmp_path)
    ids1 = c1.ensure_rows(table)
    assert c1.stats.n_rows_loaded == 0          # nothing persisted yet
    assert c1.persist() is not None
    assert c1.persist() is None                 # no growth -> no-op

    c2 = _cache(params, tmp_path)
    assert c2.stats.n_rows_loaded == c1.n_rows
    assert c2.stats.store_load_seconds > 0.0
    # the loaded table is the persisted table, byte for byte
    np.testing.assert_array_equal(
        np.asarray(c1.table[:c1.n_rows]), np.asarray(c2.table[:c2.n_rows]))
    # serving the same rows is pure lookup: zero encodes, zero passes
    ids2 = c2.ensure_rows(table)
    np.testing.assert_array_equal(ids1, ids2)
    assert c2.stats.n_rows_encoded == 0
    assert c2.stats.n_encode_passes == 0


def test_store_growth_persists_incrementally(params, table, tmp_path):
    c1 = _cache(params, tmp_path)
    c1.ensure_rows(table[: table.shape[0] // 2])
    c1.persist()
    c2 = _cache(params, tmp_path)
    loaded = c2.stats.n_rows_loaded
    assert loaded == c1.n_rows
    c2.ensure_rows(table)                       # second half is new
    assert c2.n_rows > loaded
    assert c2.persist() is not None             # growth -> re-persist
    c3 = _cache(params, tmp_path)
    assert c3.stats.n_rows_loaded == c2.n_rows
    c3.ensure_rows(table)
    assert c3.stats.n_rows_encoded == 0


def test_params_mismatch_invalidates_silently(params, table, tmp_path):
    c1 = _cache(params, tmp_path)
    c1.ensure_rows(table)
    c1.persist()
    other = predictor.init_params(SMALL_CFG, jax.random.PRNGKey(7))
    assert rt_store_key(other, SMALL_CFG, 16) != \
        rt_store_key(params, SMALL_CFG, 16)
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # silent = no warning
        c2 = _cache(other, tmp_path)
    assert c2.stats.n_rows_loaded == 0
    c2.ensure_rows(table)                       # clean rebuild works
    assert c2.stats.n_rows_encoded > 0
    # the two stores coexist under different keys in one directory
    c1b = _cache(params, tmp_path)
    assert c1b.stats.n_rows_loaded == c1.n_rows


def test_vocab_signature_mismatch_invalidates(params, table, tmp_path):
    c1 = _cache(params, tmp_path)
    c1.ensure_rows(table)
    c1.persist()
    c2 = _cache(params, tmp_path, store_extra="some-other-vocab")
    assert c2.stats.n_rows_loaded == 0


def test_corrupt_store_warns_and_cold_encodes(params, table, tmp_path):
    c1 = _cache(params, tmp_path)
    ids1 = c1.ensure_rows(table)
    c1.persist()
    # truncate every persisted array file under the store key
    arrs = glob.glob(str(tmp_path / "*" / "step_*" / "arr_*.npy"))
    assert arrs
    for p in arrs:
        with open(p, "r+b") as fh:
            fh.truncate(os.path.getsize(p) // 2)
    with pytest.warns(UserWarning, match="falling back to cold encode"):
        c2 = _cache(params, tmp_path)
    assert c2.stats.n_rows_loaded == 0 and c2.n_rows == 0
    ids2 = c2.ensure_rows(table)                # cold path still correct
    np.testing.assert_array_equal(ids1, ids2)
    np.testing.assert_array_equal(
        np.asarray(c1.table[:c1.n_rows]), np.asarray(c2.table[:c2.n_rows]))


def test_corrupt_manifest_warns_and_cold_encodes(params, table, tmp_path):
    c1 = _cache(params, tmp_path)
    c1.ensure_rows(table)
    c1.persist()
    for p in glob.glob(str(tmp_path / "*" / "step_*" / "manifest.*.json")):
        with open(p, "w") as fh:
            fh.write("{ not json")
    with pytest.warns(UserWarning, match="falling back to cold encode"):
        c2 = _cache(params, tmp_path)
    assert c2.stats.n_rows_loaded == 0


def test_tampered_table_values_rejected(params, table, tmp_path):
    """A key-matching store whose table fails validation (non-finite
    values) must not be adopted — warn + cold encode."""
    c1 = _cache(params, tmp_path)
    c1.ensure_rows(table)
    c1.persist()
    arrs = sorted(glob.glob(str(tmp_path / "*" / "step_*" / "arr_*.npy")))
    poisoned = False
    for p in arrs:
        a = np.load(p)
        if a.dtype == np.float32:               # the table leaf
            a[0, 0] = np.nan
            np.save(p, a)
            poisoned = True
    assert poisoned
    with pytest.warns(UserWarning, match="falling back to cold encode"):
        c2 = _cache(params, tmp_path)
    assert c2.stats.n_rows_loaded == 0


def test_store_version_mismatch_invalidates(params, table, tmp_path,
                                            monkeypatch):
    c1 = _cache(params, tmp_path)
    c1.ensure_rows(table)
    c1.persist()
    monkeypatch.setattr("repro.core.rt_cache.RT_STORE_VERSION",
                        RT_STORE_VERSION + 1)
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # silent clean rebuild
        c2 = _cache(params, tmp_path)
    assert c2.stats.n_rows_loaded == 0


def test_engine_restart_bitwise_with_store(params, tmp_path):
    """SimulationEngine round trip through rt_store_dir: run 2 loads the
    persisted table, encodes nothing, and reproduces run 1 bitwise."""
    ec = EngineConfig(rt_cache=True, rt_store_dir=str(tmp_path),
                      **ENGINE_KW)
    eng1 = SimulationEngine.from_config(params, SMALL_CFG, VOCAB, ec)
    eng1.submit_names(MIX)
    res1 = eng1.run()
    assert eng1.last_rt_stats.n_rows_encoded > 0

    eng2 = SimulationEngine.from_config(params, SMALL_CFG, VOCAB, ec)
    eng2.submit_names(MIX)
    res2 = eng2.run()
    st = eng2.last_rt_stats
    assert st.n_rows_loaded == eng1.last_rt_stats.n_rows_encoded
    assert st.n_rows_encoded == 0               # pure store service
    for a, b in zip(res1, res2):
        assert a.name == b.name
        assert a.predicted_cycles == b.predicted_cycles     # bitwise


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 devices (CI mesh leg sets "
                           "xla_force_host_platform_device_count=8)")
def test_store_composes_with_mesh_sharded_encode(params, tmp_path):
    """A table built by the 8-way mesh-sharded encode persists and is
    adopted by an unsharded cache (and vice versa): the store key ignores
    the mesh because sharded encodes are byte-identical to unsharded."""
    cprog = progen.build_benchmark("505.mcf").compiled()
    table = cprog.token_table(VOCAB, 16)
    mesh_cache = RTCache(params, SMALL_CFG, 16, n_shards=8,
                         store_dir=str(tmp_path),
                         store_extra=VOCAB.signature())
    mesh_cache.ensure_rows(table)
    mesh_cache.persist()

    plain = _cache(params, tmp_path)
    assert plain.stats.n_rows_loaded == mesh_cache.n_rows
    np.testing.assert_array_equal(
        np.asarray(mesh_cache.table[:mesh_cache.n_rows]),
        np.asarray(plain.table[:plain.n_rows]))

    mesh2 = RTCache(params, SMALL_CFG, 16, n_shards=8,
                    store_dir=str(tmp_path),
                    store_extra=VOCAB.signature())
    assert mesh2.stats.n_rows_loaded == mesh_cache.n_rows
    mesh2.ensure_rows(table)
    assert mesh2.stats.n_rows_encoded == 0


def test_store_key_sensitivity(params):
    base = rt_store_key(params, SMALL_CFG, 16, extra="v")
    assert base == rt_store_key(params, SMALL_CFG, 16, extra="v")
    assert base != rt_store_key(params, SMALL_CFG, 32, extra="v")
    assert base != rt_store_key(params, SMALL_CFG, 16, extra="w")
    assert base != rt_store_key(
        params, SMALL_CFG.replace(dtype="bfloat16"), 16, extra="v")
    bumped = jax.tree_util.tree_map(lambda a: a, params)
    bumped["embed"] = jnp.asarray(np.asarray(bumped["embed"]) + 1e-3)
    assert base != rt_store_key(bumped, SMALL_CFG, 16, extra="v")


# --------------------------------------------------------------------------- #
# Concurrent persistence: many writers, one store directory
# --------------------------------------------------------------------------- #

def test_concurrent_persist_last_writer_wins(params, table, tmp_path):
    """Two caches under the SAME content key race grow+persist rounds
    against one store dir.  Writer-unique tmp names + the retrying
    atomic publish mean: no crash on the rename collision, and the
    published store is always ONE writer's complete table."""
    import threading

    n = table.shape[0]
    rows_a, rows_b = table[: 2 * n // 3], table[n // 3:]
    gate = threading.Barrier(2)
    caches, errs = {}, []

    def run(name, rows):
        try:
            c = _cache(params, tmp_path)
            caches[name] = c
            m = rows.shape[0]
            for k in range(1, 6):
                c.ensure_rows(rows[: max(1, k * m // 5)])
                gate.wait(timeout=60)       # maximize publish overlap
                c.persist()
        except Exception as exc:            # pragma: no cover
            errs.append(exc)

    threads = [threading.Thread(target=run, args=("a", rows_a)),
               threading.Thread(target=run, args=("b", rows_b))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs

    # a fresh process adopts one (whole) writer's table, not a blend
    c3 = _cache(params, tmp_path)
    assert c3.stats.n_rows_loaded in {caches["a"].n_rows,
                                      caches["b"].n_rows}
    assert np.isfinite(np.asarray(c3.table[: c3.n_rows])).all()
    # and serving the full row set from it is bitwise-equal to a cold
    # cache: the store can accelerate, never corrupt
    ids3 = c3.ensure_rows(table)
    cold = RTCache(params, SMALL_CFG, 16)
    ids_cold = cold.ensure_rows(table)
    np.testing.assert_array_equal(
        np.asarray(c3.table)[ids3], np.asarray(cold.table)[ids_cold])


def test_two_engines_share_store_dir(params, tmp_path):
    """Two simulation services flush (and persist) concurrently into one
    rt_store_dir; a third service then loads whatever generation won and
    still serves bitwise-correct results."""
    import threading

    from repro.serving.engine import Request
    from repro.serving.service import (ServiceSLA, SimulationService,
                                       build_ladder)

    ec = EngineConfig(l_clip=32, l_token=16, batch_size=16,
                      rt_store_dir=str(tmp_path))
    # serve on the f32 RT rung for the whole test: no promotion, no
    # spot check
    rt = [name for name, _ in build_ladder(ec)].index("rt")
    sla = ServiceSLA(watchdog_s=120.0, check_every=0, promote_after=1_000)

    def service():
        return SimulationService(params, SMALL_CFG, ec, sla=sla,
                                 start_tier=rt)

    def mk_req(i, seed):
        r = np.random.RandomState(seed)
        tok = r.randint(0, VOCAB.size, (6, 32, 16)).astype(np.int32)
        ctx = r.randint(0, VOCAB.size,
                        (6, SMALL_CFG.context_tokens)).astype(np.int32)
        return Request(i, tok, ctx, np.ones((6, 32), np.float32))

    results, errs = {}, []

    def serve(name, seed):
        try:
            with service() as svc:
                for rnd in range(3):        # each flush persists
                    res = svc.submit(mk_req(rnd, seed + rnd)).result(
                        timeout=300)
                    assert res.tier == "rt", res
                    results[(name, rnd)] = res.total_cycles
        except Exception as exc:            # pragma: no cover
            errs.append(exc)

    threads = [threading.Thread(target=serve, args=("e1", 100)),
               threading.Thread(target=serve, args=("e2", 200))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
        assert not t.is_alive()
    assert not errs and len(results) == 6

    with service() as svc3:
        got = [svc3.submit(mk_req(0, seed)).result(timeout=300)
               for seed in (100, 200)]
        stats = svc3._tiers[rt].cache.stats
    assert stats.n_rows_loaded > 0              # adopted a winner
    assert all(r.tier == "rt" for r in got)
    assert got[0].total_cycles == results[("e1", 0)]
    assert got[1].total_cycles == results[("e2", 0)]
