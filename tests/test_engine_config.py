"""EngineConfig: the unified construction surface (PR 6 satellite).

Covers: validation + JSON round trip (including the nested
``SamplingConfig``), the retired PR-6 kwarg shims on all four entry
points (legacy keywords must raise ``TypeError`` pointing at
``EngineConfig``), and ``from_config`` equivalence.
"""
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import predictor
from repro.core import standardize as std_mod
from repro.core.engine import BatchedPredictor, SimulationEngine
from repro.core.engine_config import (EngineConfig, SamplingConfig,
                                      reject_legacy_kwargs)
from repro.core.simulate import capsim_simulate, capsim_simulate_multicore
from repro.isa import multicore, progen
from repro.serving.engine import PredictorEngine, Request

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


# ------------------------------ the dataclass ------------------------------ #

def test_defaults_unsharded():
    ec = EngineConfig()
    assert ec.mesh_shape == ()
    assert ec.n_shards == 0
    assert ec.rt_cache and ec.use_context and ec.with_oracle
    assert ec.sampling is None


def test_mesh_shape_normalization():
    assert EngineConfig(mesh_shape=4).mesh_shape == (4,)
    assert EngineConfig(mesh_shape=[2, 2]).mesh_shape == (2, 2)
    assert EngineConfig(mesh_shape=[2, 2]).n_shards == 4
    assert EngineConfig(mesh_shape=(1,)).n_shards == 1


def test_frozen():
    ec = EngineConfig()
    with pytest.raises(Exception):
        ec.batch_size = 8


@pytest.mark.parametrize("bad", [
    dict(mesh_shape=(0,)),
    dict(mesh_shape=(-2,)),
    dict(precision="fp16"),
    dict(batch_size=0),
    dict(batch_size=10, mesh_shape=(4,)),   # not divisible
    dict(multicore=-1),
    dict(peer_channels=True),               # needs multicore >= 1
    dict(quantum=0),
    dict(sampling=42),                      # not a SamplingConfig
])
def test_validate_rejects(bad):
    with pytest.raises(ValueError):
        EngineConfig(**bad)


def test_json_round_trip():
    ec = EngineConfig(mesh_shape=(8,), precision="bf16", batch_size=64,
                      multicore=2, quantum=32, peer_channels=True)
    assert EngineConfig.from_json(ec.to_json()) == ec
    # mesh_shape serializes as a list but round-trips to a tuple
    assert isinstance(ec.to_dict()["mesh_shape"], list)


# ------------------------------ SamplingConfig ------------------------------ #

def test_sampling_defaults_and_validation():
    sc = SamplingConfig()
    assert 0.0 < sc.fraction <= 1.0
    assert sc.strata >= 1 and sc.min_clips_per_stratum >= 1
    for bad in (dict(fraction=0.0), dict(fraction=1.5),
                dict(fraction=-0.1), dict(strata=0),
                dict(min_clips_per_stratum=0),
                dict(bootstrap_resamples=-1)):
        with pytest.raises(ValueError):
            SamplingConfig(**bad)


def test_sampling_json_round_trip():
    ec = EngineConfig(sampling=SamplingConfig(fraction=0.25, strata=3,
                                              seed=7, bootstrap_resamples=9))
    rt = EngineConfig.from_json(ec.to_json())
    assert rt == ec
    assert isinstance(rt.sampling, SamplingConfig)
    # sampling=None round-trips as None
    assert EngineConfig.from_json(EngineConfig().to_json()).sampling is None


def test_sampling_from_dict_rejects_unknown():
    with pytest.raises(ValueError, match="unknown SamplingConfig fields"):
        SamplingConfig.from_dict({"fractions": 0.1})
    with pytest.raises(ValueError):
        EngineConfig.from_dict(
            {"sampling": {"fraction": 0.1, "bogus": 1}})


def test_sampling_dict_normalizes_in_engine_config():
    ec = EngineConfig(sampling={"fraction": 0.5, "strata": 2})
    assert isinstance(ec.sampling, SamplingConfig)
    assert ec.sampling.fraction == 0.5 and ec.sampling.strata == 2


def test_from_dict_rejects_unknown():
    with pytest.raises(ValueError, match="unknown EngineConfig fields"):
        EngineConfig.from_dict({"batch_sized": 4})


# ------------------------------ retired shims ------------------------------ #

def test_reject_legacy_unknown_name_is_type_error():
    with pytest.raises(TypeError, match="unexpected keyword"):
        reject_legacy_kwargs({"batch_sized": 4}, "X")


def test_reject_legacy_known_field_points_at_config():
    with pytest.raises(TypeError, match="EngineConfig\\(batch_size=\\.\\.\\."):
        reject_legacy_kwargs({"batch_size": 8}, "X")
    reject_legacy_kwargs({}, "X")           # no kwargs -> no-op


def test_capsim_simulate_legacy_kwargs_raise(params, vocab):
    bench = progen.build_benchmark("505.mcf")
    with pytest.raises(TypeError, match="EngineConfig"):
        capsim_simulate(bench, params, SMALL_CFG, vocab,
                        interval_size=1_000, batch_size=16)


def test_capsim_simulate_multicore_legacy_kwargs_raise(params, vocab):
    mb = multicore.build_multicore_benchmark(
        list(multicore.MULTICORE_NAMES)[0], 2)
    with pytest.raises(TypeError, match="EngineConfig"):
        capsim_simulate_multicore(mb, params, SMALL_CFG, vocab,
                                  interval_size=1_000, batch_size=16)


def test_simulation_engine_legacy_kwargs_raise(params, vocab):
    with pytest.raises(TypeError, match="EngineConfig"):
        SimulationEngine(params, SMALL_CFG, vocab, batch_size=16)
    with pytest.raises(TypeError):
        SimulationEngine(params, SMALL_CFG, vocab, batch_sized=4)


def test_engine_construction_does_not_warn(params, vocab):
    bench = progen.build_benchmark("541.leela")
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        SimulationEngine.from_config(params, SMALL_CFG, vocab,
                                     EC).run([bench])


def test_batched_predictor_legacy_kwargs_raise(params, vocab):
    with pytest.raises(TypeError, match="EngineConfig"):
        BatchedPredictor(params, SMALL_CFG, batch_size=16)


def test_predictor_engine_legacy_kwargs_raise(params, vocab):
    with pytest.raises(TypeError, match="EngineConfig"):
        PredictorEngine(params, SMALL_CFG, batch_size=8)
    # the config path still serves
    rng = np.random.RandomState(1)
    tok = rng.randint(0, vocab.size, (4, 128, SMALL_CFG.clip_tokens)
                      ).astype(np.int32)
    ctx = rng.randint(0, vocab.size, (4, SMALL_CFG.context_tokens)
                      ).astype(np.int32)
    req = Request(0, tok, ctx, np.ones((4, 128), np.float32))
    eng = PredictorEngine.from_config(params, SMALL_CFG,
                                      EngineConfig(batch_size=8))
    eng.submit(req)
    res = eng.flush()[0]
    assert res.n_clips == 4 and res.clips_predicted == 4
    assert res.clips_extrapolated == 0 and res.cycles_ci is None


# ------------------------------ entry points ------------------------------ #

def test_quantum_flows_from_config(params, vocab):
    mb = multicore.build_multicore_benchmark(
        list(multicore.MULTICORE_NAMES)[0], 2)
    ref = SimulationEngine.from_config(
        params, SMALL_CFG, vocab, EC).run_multicore(
            [mb], quantum=32)[0]
    via_cfg = SimulationEngine.from_config(
        params, SMALL_CFG, vocab,
        EC.replace(quantum=32)).run_multicore([mb])[0]
    assert via_cfg.predicted_cycles == ref.predicted_cycles
