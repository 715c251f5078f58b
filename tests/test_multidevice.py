"""Multi-device NUMERICAL validation of the shard_map paths.

The main pytest process is locked to 1 CPU device (jax fixes the device
count at first init), so this file launches a subprocess with
``--xla_force_host_platform_device_count=8`` and compares, on a real
(2, 4) = (data, model) mesh:

  - MoE expert-parallel dispatch (shard_map) vs the meshless reference,
  - flash-decoding (sequence-sharded cache psum merge) vs full attention,
  - sequence-parallel prefill attention vs the single-device chunked path.

These are the distribution paths the dry-run exercises only structurally;
here they must agree numerically across 8 shards.
"""
import subprocess
import sys

PROGRAM = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.distributed.sharding import (LOGICAL_RULES_DECODE,
                                        LOGICAL_RULES_TRAIN,
                                        use_mesh_and_rules)
from repro.models import moe as moe_mod
from repro.models.attention import (_causal_attention_chunked, flash_decode,
                                    sp_prefill_attention)
from repro.models.layers import init_from_specs

assert len(jax.devices()) == 8, jax.devices()
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.RandomState(0)

# ---------------- MoE: shard_map EP vs meshless reference ----------------
cfg = get_smoke_config("llama4-maverick-400b-a17b").replace(
    num_experts=8, experts_per_token=1, capacity_factor=4.0)
params = init_from_specs(moe_mod.moe_specs(cfg), jax.random.PRNGKey(1),
                         "float32")
x = jnp.asarray(rng.randn(4, 8, cfg.d_model).astype(np.float32))
with use_mesh_and_rules(mesh, LOGICAL_RULES_TRAIN), mesh:
    y_mesh, lb_m, z_m = jax.jit(
        lambda p, a: moe_mod.moe_forward(p, a, cfg))(params, x)
with use_mesh_and_rules(None, None):
    y_ref, lb_r, z_r = moe_mod.moe_forward(params, x, cfg)
np.testing.assert_allclose(np.asarray(y_mesh), np.asarray(y_ref),
                           rtol=2e-4, atol=2e-5)
np.testing.assert_allclose(float(lb_m), float(lb_r), rtol=1e-4)
np.testing.assert_allclose(float(z_m), float(z_r), rtol=1e-4)
print("moe EP OK")

# ------------- flash decode: seq-sharded cache vs full attention ----------
acfg = get_smoke_config("qwen3-4b")
B, S = 4, 64
H, KV, Dh = acfg.num_heads, acfg.num_kv_heads, acfg.head_dim
q = jnp.asarray(rng.randn(B, 1, H, Dh).astype(np.float32))
kc = jnp.asarray(rng.randn(B, S, KV, Dh).astype(np.float32))
vc = jnp.asarray(rng.randn(B, S, KV, Dh).astype(np.float32))
pos = jnp.int32(37)
with use_mesh_and_rules(mesh, LOGICAL_RULES_DECODE), mesh:
    o_mesh = jax.jit(lambda *a: flash_decode(*a, acfg))(q, kc, vc, pos)
with use_mesh_and_rules(None, None):
    o_ref = flash_decode(q, kc, vc, pos, acfg)   # unsharded fallback path
np.testing.assert_allclose(np.asarray(o_mesh), np.asarray(o_ref),
                           rtol=2e-4, atol=2e-5)
print("flash decode OK")

# ------------- SP prefill attention vs single-device chunked --------------
from repro.distributed.sharding import LOGICAL_RULES_PREFILL_SP
B2, S2, H2, D2 = 2, 32, 4, 16
qq = jnp.asarray(rng.randn(B2, S2, H2, D2).astype(np.float32))
kk = jnp.asarray(rng.randn(B2, S2, 2, D2).astype(np.float32))
vv = jnp.asarray(rng.randn(B2, S2, 2, D2).astype(np.float32))
scfg = acfg.replace(num_heads=H2, num_kv_heads=2, head_dim=D2,
                    attn_chunk=8)
with use_mesh_and_rules(mesh, LOGICAL_RULES_PREFILL_SP), mesh:
    o_sp = jax.jit(lambda *a: sp_prefill_attention(*a, scfg))(qq, kk, vv)
kb = jnp.repeat(kk, H2 // 2, axis=2)
vb = jnp.repeat(vv, H2 // 2, axis=2)
o_full = _causal_attention_chunked(qq, kb, vb, 8)
np.testing.assert_allclose(np.asarray(o_sp), np.asarray(o_full),
                           rtol=2e-4, atol=2e-5)
print("sp prefill OK")
print("ALL MULTIDEVICE CHECKS PASSED")
"""


def test_multidevice_numerics():
    r = subprocess.run([sys.executable, "-c", PROGRAM], capture_output=True,
                       text=True, timeout=500,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    assert "ALL MULTIDEVICE CHECKS PASSED" in r.stdout, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
