"""Top-k MoE FFN with explicit expert parallelism (shard_map).

Design (kimi-k2: 384 experts top-8; llama4: 128 experts top-1; jamba: 16/top-2):

  - Expert weights are sharded over 'model' on the expert axis (EP) and over
    'data' on the d_model axis (FSDP storage).  Inside the shard_map the FSDP
    shards are re-assembled with a tiled all_gather — on a real pod this
    overlaps with the previous layer's compute under the scan.
  - Activations arrive batch-sharded over ('pod','data') and replicated over
    'model'.  Every model shard routes ALL of its local tokens, keeps the
    (token, slot) pairs that map to its local experts, and scatters them into
    an (E_local, capacity, d) buffer — a local, sort-free dispatch.  Combine
    is a single psum over 'model' (same collective volume as a Megatron TP
    FFN all-reduce).
  - Capacity-based dropping with renormalized top-k gates; aux losses
    (load-balance + router z-loss) are returned to the caller.

This keeps every collective explicit: one all_gather (FSDP) + one psum per
MoE layer — no XLA-SPMD surprises from scatters on sharded operands.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import axis_rules, current_mesh
from repro.models.layers import ParamSpec


def moe_specs(cfg) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    std = 1.0 / math.sqrt(d)
    return {
        "router": ParamSpec((d, E), ("embed", None), std=std, dtype="float32"),
        "w_gate": ParamSpec((E, d, f), ("expert", "embed", "expert_mlp"), std=std),
        "w_up": ParamSpec((E, d, f), ("expert", "embed", "expert_mlp"), std=std),
        "w_down": ParamSpec((E, f, d), ("expert", "expert_mlp", "embed"),
                            std=1.0 / math.sqrt(f)),
    }


def _capacity(t_loc: int, k: int, n_exp: int, cf: float) -> int:
    c = int(math.ceil(cf * t_loc * k / n_exp))
    return max(8, ((c + 7) // 8) * 8)


def _moe_local(xf, router, wg, wu, wd, *, k: int, n_exp: int, e_loc: int,
               cap: int, dp_axes: Tuple[str, ...], act: str):
    """Per-device MoE.  xf: (T_loc, d) local tokens (replicated over 'model');
    wg/wu: (E_loc, d_shard, f); wd: (E_loc, f, d_shard)."""
    # Re-assemble FSDP weight shards along d_model.
    wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
    wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
    wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)

    t_loc, d = xf.shape
    scores = (xf.astype(jnp.float32) @ router)                # (T_loc, E)
    probs = jax.nn.softmax(scores, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                      # (T_loc, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    m = jax.lax.axis_index("model")
    local_e = idx - m * e_loc                                 # (T_loc, k)
    is_local = (local_e >= 0) & (local_e < e_loc)
    e_sel = jnp.where(is_local, local_e, 0)

    # Position of each (token, slot) within its expert: exclusive running
    # count over the flattened slot order (deterministic, sort-free).
    oh = jax.nn.one_hot(jnp.where(is_local, local_e, e_loc), e_loc + 1,
                        dtype=jnp.int32).reshape(t_loc * k, e_loc + 1)
    pos = (jnp.cumsum(oh, axis=0) - oh)
    pos = jnp.sum(pos * oh, axis=-1).reshape(t_loc, k)
    keep = is_local & (pos < cap)

    buf = jnp.zeros((e_loc, cap, d), xf.dtype)
    for j in range(k):                                        # static, small
        p = jnp.where(keep[:, j], pos[:, j], cap)             # cap -> dropped
        buf = buf.at[e_sel[:, j], p].add(
            xf * keep[:, j, None].astype(xf.dtype), mode="drop")

    up = jnp.einsum("ecd,edf->ecf", buf, wu)
    if act == "swiglu":
        gate = jnp.einsum("ecd,edf->ecf", buf, wg)
        h = jax.nn.silu(gate) * up
    elif act == "squared_relu":
        r = jax.nn.relu(up)
        h = r * r
    else:
        h = jax.nn.gelu(up)
    down = jnp.einsum("ecf,efd->ecd", h, wd)

    y = jnp.zeros_like(xf)
    for j in range(k):
        p = jnp.where(keep[:, j], pos[:, j], 0)
        w = (gates[:, j] * keep[:, j]).astype(xf.dtype)
        y = y + down[e_sel[:, j], p] * w[:, None]
    y = jax.lax.psum(y, "model")

    # ---- aux losses (replicated over 'model' by construction) ----
    counts = jnp.sum(jax.nn.one_hot(idx, n_exp, dtype=jnp.float32),
                     axis=(0, 1))                             # (E,)
    if dp_axes:
        counts = jax.lax.psum(counts, dp_axes)
        mean_probs = jax.lax.pmean(jnp.mean(probs, axis=0), dp_axes)
        t_tot = t_loc * jax.lax.psum(1, dp_axes)
    else:
        mean_probs = jnp.mean(probs, axis=0)
        t_tot = t_loc
    frac = counts / (t_tot * k)
    lb_loss = n_exp * jnp.sum(frac * mean_probs)
    if dp_axes:
        z = jax.lax.pmean(
            jnp.mean(jnp.square(jax.nn.logsumexp(scores, axis=-1))), dp_axes)
    else:
        z = jnp.mean(jnp.square(jax.nn.logsumexp(scores, axis=-1)))
    return y, lb_loss, z


def moe_forward(params, x, cfg) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, lb_loss, z_loss)."""
    B, S, d = x.shape
    mesh = current_mesh()
    E, k = cfg.num_experts, cfg.experts_per_token
    xf = x.reshape(B * S, d)

    if mesh is None:
        # meshless fallback (unit tests): single "device", E_loc = E
        y, lb, z = _run_local_nomesh(params, xf, cfg)
        return y.reshape(B, S, d), lb, z

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_model = sizes.get("model", 1)
    dp_axes = tuple(a for a in ("pod", "data") if a in sizes)
    dp = 1
    for a in dp_axes:
        dp *= sizes[a]
    if E % n_model != 0:
        raise ValueError(f"{cfg.name}: experts={E} not divisible by "
                         f"model={n_model}")
    if (B * S) % dp != 0:
        # batch too small to shard over the DP axes (long-context decode):
        # replicate tokens, keep EP over 'model' only.
        dp, dp_axes = 1, ()
    t_loc = (B * S) // dp
    cap = _capacity(t_loc, k, E, cfg.capacity_factor)

    batch_axes = axis_rules(("batch",), mesh=mesh)[0] if dp_axes else None
    tok_spec = P(batch_axes, None)
    fn = jax.shard_map(
        partial(_moe_local, k=k, n_exp=E, e_loc=E // n_model, cap=cap,
                dp_axes=dp_axes, act=cfg.activation),
        mesh=mesh,
        in_specs=(tok_spec, P(None, None), P("model", "data", None),
                  P("model", "data", None), P("model", None, "data")),
        out_specs=(tok_spec, P(), P()),
        check_vma=False,
    )
    y, lb, z = fn(xf, params["router"], params["w_gate"], params["w_up"],
                  params["w_down"])
    return y.reshape(B, S, d), lb, z


def _run_local_nomesh(params, xf, cfg):
    """Reference path without a mesh — identical math, E_loc = E."""
    E, k = cfg.num_experts, cfg.experts_per_token
    t = xf.shape[0]
    cap = _capacity(t, k, E, cfg.capacity_factor)
    scores = xf.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(scores, -1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    oh = jax.nn.one_hot(idx, E, dtype=jnp.int32).reshape(t * k, E)
    pos = (jnp.cumsum(oh, 0) - oh)
    pos = jnp.sum(pos * oh, -1).reshape(t, k)
    keep = pos < cap
    buf = jnp.zeros((E, cap, xf.shape[1]), xf.dtype)
    for j in range(k):
        p = jnp.where(keep[:, j], pos[:, j], cap)
        buf = buf.at[idx[:, j], p].add(
            xf * keep[:, j, None].astype(xf.dtype), mode="drop")
    up = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    if cfg.activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])) * up
    elif cfg.activation == "squared_relu":
        r = jax.nn.relu(up)
        h = r * r
    else:
        h = jax.nn.gelu(up)
    down = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    y = jnp.zeros_like(xf)
    for j in range(k):
        p = jnp.where(keep[:, j], pos[:, j], 0)
        w = (gates[:, j] * keep[:, j]).astype(xf.dtype)
        y = y + down[idx[:, j], p] * w[:, None]
    counts = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=(0, 1))
    lb = E * jnp.sum(counts / (t * k) * jnp.mean(probs, 0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(scores, -1)))
    return y, lb, z
