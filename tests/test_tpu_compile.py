"""Compile rehearsals for one TPU v5e chip, with no chip attached.

The TPU compiler is installed next to JAX, and it compiles for a chip
that is described rather than attached (``get_topology_desc``).  These
cases compile the Pallas kernels of the main path and the whole fused
serving step at their real widths and check what interpret-mode tests
cannot: that the kernel lowers to a Mosaic ``tpu_custom_call``, that the
compiler accepts its tiling, and that the step fits the chip's memory.
Nothing runs, so they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import predictor
from repro.core.engine_config import EngineConfig
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.fused_serving import ops as wa_ops

V5E_HBM_BYTES = 16 * 1024 ** 3
HEADS, HEAD_DIM = 4, 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            from jax.experimental import topologies
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as exc:            # noqa: BLE001
                pytest.skip(f"no v5e:2x2 topology can be described "
                            f"here: {exc}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "the Pallas kernel did not lower to a Mosaic custom call"
    return compiled


def test_flash_kernel_instruction_encoder(one_chip):
    # every static instruction row attends over its L_token=16 tokens
    rows, l_token = 32_768, 16
    qkv = _spec((rows, l_token, HEADS, HEAD_DIM), jnp.float32, one_chip)
    mask = _spec((rows, l_token), jnp.float32, one_chip)
    _compile(lambda q, k, v, m: fa_ops.flash_attention(
        q, k, v, kv_mask=m, interpret=False), qkv, qkv, qkv, mask)


def test_flash_kernel_block_cross_attention(one_chip):
    # the M=360 context rows query the L_clip=128 instruction vectors
    batch, m_ctx, l_clip = 256, 360, 128
    q = _spec((batch, m_ctx, HEADS, HEAD_DIM), jnp.float32, one_chip)
    kv = _spec((batch, l_clip, HEADS, HEAD_DIM), jnp.float32, one_chip)
    mask = _spec((batch, l_clip), jnp.float32, one_chip)
    _compile(lambda q, k, v, m: fa_ops.flash_attention(
        q, k, v, kv_mask=m, interpret=False), q, kv, kv, mask)


@pytest.mark.parametrize("n_unique", [64, 192])
def test_weighted_kernel_deduped_context_bf16(one_chip, n_unique):
    # self-attention over a clip's deduped context tokens, weighted by
    # their multiplicities (the fused serving step's bf16 inner loop)
    batch = 256
    qkv = _spec((batch, n_unique, HEADS, HEAD_DIM), jnp.bfloat16, one_chip)
    w = _spec((batch, n_unique), jnp.float32, one_chip)
    _compile(lambda q, k, v, w: wa_ops.weighted_attention(
        q, k, v, w, impl="pallas", interpret=False), qkv, qkv, qkv, w)


def test_fused_serving_step_fits_one_chip(one_chip, monkeypatch):
    # the program picks the kernel and interpret mode from the backend it
    # sees; steer it to the chip so the step lowers what the TPU runs
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = predictor.inference_config(
        get_config("capsim").replace(dtype="float32"))
    assert cfg.attn_impl == "pallas"
    batch, n_unique, capacity = 256, 128, 4096
    l_clip, e = EngineConfig().l_clip, cfg.d_model

    def place(tree):
        return jax.tree.map(
            lambda a: _spec(a.shape, a.dtype, one_chip), tree)

    params = place(predictor.abstract_params(cfg))
    table = jax.ShapeDtypeStruct((capacity, e), jnp.float32)
    plan = place(jax.eval_shape(
        lambda p, t: predictor.serving_plan(p, t, cfg), params, table))
    step_batch = {
        "rt_idx": _spec((batch, l_clip), jnp.int32, one_chip),
        "ctx_uniq": _spec((batch, n_unique), jnp.int32, one_chip),
        "ctx_count": _spec((batch, n_unique), jnp.float32, one_chip),
        "clip_mask": _spec((batch, l_clip), jnp.float32, one_chip)}
    compiled = _compile(
        lambda p, plan, b: predictor.forward_cached_fused(p, plan, b, cfg),
        params, plan, step_batch)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{used / 1e9:.2f} GB > 16 GB"


@pytest.mark.parametrize("m_ctx", [369, 16 * 369])
def test_rt_step_fits_one_chip(one_chip, monkeypatch, m_ctx):
    # the engine's rt step (``forward_cached``: RT-table gather, block
    # encoder with the flash kernel, head) at the engine's batch of 256
    # over the core-tagged context (M 369) and the 16-core peer-channel
    # context (M 16 x 369 = 5,904)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = predictor.inference_config(
        get_config("capsim").replace(dtype="float32"), "int8")
    assert cfg.attn_impl == "pallas"
    batch, capacity = EngineConfig().batch_size, 4096
    l_clip, e = EngineConfig().l_clip, cfg.d_model
    params = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                          predictor.abstract_params(cfg))
    table = _spec((capacity, e), jnp.float32, one_chip)
    step_batch = {
        "rt_idx": _spec((batch, l_clip), jnp.int32, one_chip),
        "context_tokens": _spec((batch, m_ctx), jnp.int32, one_chip),
        "clip_mask": _spec((batch, l_clip), jnp.float32, one_chip)}
    compiled = _compile(
        lambda p, t, b: predictor.forward_cached(p, t, b, cfg),
        params, table, step_batch)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    print(f"rt step M={m_ctx}: {used / 1e9:.3f} GB")
    assert used < V5E_HBM_BYTES, f"{used / 1e9:.2f} GB > 16 GB"
