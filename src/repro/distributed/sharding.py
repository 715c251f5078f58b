"""Logical-axis sharding rules (MaxText-style) for the whole framework.

Model code never names mesh axes directly.  Params and activations carry
*logical* axis names; a rules table maps logical names -> mesh axes per
execution mode (train/prefill vs decode).  This is what lets one model
definition serve a (16,16) single-pod mesh, a (2,16,16) multi-pod mesh and a
(1,1)/(1,1,1) CPU test mesh without edits.

Mesh axes (see launch/mesh.py):
    'pod'    inter-pod data parallelism (multi-pod only)
    'data'   intra-pod data parallelism + FSDP weight sharding
    'model'  tensor / expert parallelism

Conventions:
    - weight axes: 'embed' (d_model rows, FSDP over 'data'), 'qkv' (fused
      query head dim, TP), 'kv' (kv head dim; small under GQA -> replicated),
      'mlp' (FFN hidden, TP), 'expert' (MoE expert dim, EP), 'vocab'
      (unembedding columns, TP), 'layers' (scan-stacked repeats, never sharded)
    - activation axes: 'batch', 'act_seq', 'act_embed', 'act_heads', ...
    - decode caches: 'cache_batch', 'cache_seq' (seq-sharded flash-decoding),
      'cache_kv', 'cache_head_dim'
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Tuple[Tuple[str, Optional[Tuple[str, ...]]], ...]


def _norm(rules) -> Rules:
    out = []
    for name, axes in rules:
        if axes is None:
            out.append((name, None))
        elif isinstance(axes, str):
            out.append((name, (axes,)))
        else:
            out.append((name, tuple(axes)))
    return tuple(out)


# --------------------------------------------------------------------------- #
# Rule tables
# --------------------------------------------------------------------------- #

LOGICAL_RULES_TRAIN: Rules = _norm([
    # activations
    ("batch", ("pod", "data")),
    ("act_seq", None),
    ("act_embed", None),
    ("act_heads", "model"),
    ("act_mlp", "model"),
    ("act_ssm", "model"),
    ("act_vocab", "model"),
    # weights: FSDP over 'data' on the d_model rows, TP over 'model'
    ("embed", "data"),
    ("vocab", "model"),
    ("vocab_in", None),
    ("qkv", "model"),
    ("kv", None),
    ("mlp", "model"),
    ("expert", "model"),
    ("expert_mlp", None),
    ("ssm_inner", "model"),
    ("ssm_heads", "model"),
    ("ssm_state", None),
    ("conv_dim", "model"),
    ("layers", None),
    ("codebook", None),
    # decode caches (unused in train but kept total)
    ("cache_batch", ("pod", "data")),
    ("cache_seq", None),
    ("cache_kv", None),
    ("cache_head_dim", None),
])

# Decode: KV caches are sequence-sharded over 'model' (flash-decoding);
# SSM states are head-sharded.  Weights are TP-sharded but NOT FSDP'd
# ('embed' -> None): decode is latency-critical and re-gathering
# FSDP-sharded weights every token step cost ~89 MB/layer of all-gather
# on the dry-run (§Perf cell D) — resident weights cost 2-3 GB HBM and
# eliminate it.
LOGICAL_RULES_DECODE: Rules = _norm([
    ("batch", ("pod", "data")),
    ("act_seq", None),
    ("act_embed", None),
    ("act_heads", "model"),
    ("act_mlp", "model"),
    ("act_ssm", "model"),
    ("act_vocab", "model"),
    ("embed", None),
    ("vocab", "model"),
    ("vocab_in", None),
    ("qkv", "model"),
    ("kv", None),
    ("mlp", "model"),
    ("expert", "model"),
    ("expert_mlp", None),
    ("ssm_inner", "model"),
    ("ssm_heads", "model"),
    ("ssm_state", None),
    ("conv_dim", "model"),
    ("layers", None),
    ("codebook", None),
    ("cache_batch", ("pod", "data")),
    ("cache_seq", "model"),
    ("cache_kv", None),
    ("cache_head_dim", None),
])

# Long-context decode (global_batch smaller than the DP axes, e.g. the
# 500k-token single-sequence cells): no batch sharding; the KV cache / score
# sequence dim shards over the WHOLE mesh (sequence parallelism), so a 512-chip
# multi-pod mesh holds 1024 tokens of cache per chip.
LOGICAL_RULES_DECODE_LONG: Rules = tuple(
    (name, (("pod", "data", "model") if name == "cache_seq" else
            (None if name in ("batch", "cache_batch") else axes)))
    for name, axes in LOGICAL_RULES_DECODE
)


# ZeRO-3-across-pods variant (§Perf B4): identical to the train table but
# weight rows also shard over 'pod', halving resident state per chip on the
# multi-pod mesh (gathers cross the DCN boundary — viable with prefetch,
# and the only way a 400B+bf16-momentum state fits 16 GB chips).
LOGICAL_RULES_TRAIN_ZERO3: Rules = tuple(
    (name, (("pod", "data") if name == "embed" else axes))
    for name, axes in _norm([
        ("batch", ("pod", "data")),
        ("act_seq", None), ("act_embed", None), ("act_heads", "model"),
        ("act_mlp", "model"), ("act_ssm", "model"), ("act_vocab", "model"),
        ("embed", "data"), ("vocab", "model"), ("vocab_in", None),
        ("qkv", "model"), ("kv", None), ("mlp", "model"),
        ("expert", "model"), ("expert_mlp", None),
        ("ssm_inner", "model"), ("ssm_heads", "model"), ("ssm_state", None),
        ("conv_dim", "model"), ("layers", None), ("codebook", None),
        ("cache_batch", ("pod", "data")), ("cache_seq", None),
        ("cache_kv", None), ("cache_head_dim", None),
    ])
)

# Beyond-paper perf variant (§Perf iteration 1 for dense-train cells):
# pure ZeRO/FSDP — the batch shards over EVERY mesh axis (256-way DP on a
# pod) and weights shard over ('data','model') on their d_model rows with
# NO tensor parallelism.  Per-device FLOPs are identical to FSDP+TP, but
# the per-layer collectives drop from 4-6 activation all-reduces
# (O(B_loc*S*d) each) + weight gathers to ONE weight all-gather + one grad
# reduce-scatter (O(params_layer)); at train_4k sizes that is ~10x less
# wire.  Requires global_batch % chips == 0 (256 on the single pod).
LOGICAL_RULES_TRAIN_FSDP: Rules = _norm([
    ("batch", ("pod", "data", "model")),
    ("act_seq", None), ("act_embed", None), ("act_heads", None),
    ("act_mlp", None), ("act_ssm", None), ("act_vocab", None),
    ("embed", ("data", "model")),
    ("vocab", None), ("vocab_in", None),
    ("qkv", None), ("kv", None), ("mlp", None),
    ("expert", "model"),            # MoE keeps EP over 'model'
    ("expert_mlp", None),
    ("ssm_inner", None), ("ssm_heads", None), ("ssm_state", None),
    ("conv_dim", None), ("layers", None), ("codebook", None),
    ("cache_batch", ("pod", "data", "model")),
    ("cache_seq", None), ("cache_kv", None), ("cache_head_dim", None),
])

# Sequence-parallel prefill (§Perf cell E): the residual stream shards its
# SEQUENCE over 'model' — no tensor parallelism.  FFNs/norms become purely
# local; attention (models/attention.sp_prefill_attention) all-gathers the
# small GQA K/V heads per layer (O(S*KV*Dh)) instead of TP's O(B*S*d)
# all-reduces.  Weights shard over ('data','model') rows for storage and
# are gathered per layer.  Bonus: emitted KV caches are already in the
# decode layout (cache_seq='model') — no prefill->decode reshard.
LOGICAL_RULES_PREFILL_SP: Rules = _norm([
    ("batch", ("pod", "data")),
    ("act_seq", "model"),
    ("act_embed", None), ("act_heads", None), ("act_mlp", None),
    ("act_ssm", None), ("act_vocab", None),
    ("embed", ("data", "model")),
    ("vocab", None), ("vocab_in", None),
    ("qkv", None), ("kv", None), ("mlp", None),
    ("expert", "model"), ("expert_mlp", None),
    ("ssm_inner", None), ("ssm_heads", None), ("ssm_state", None),
    ("conv_dim", None), ("layers", None), ("codebook", None),
    ("cache_batch", ("pod", "data")),
    ("cache_seq", "model"),
    ("cache_kv", None), ("cache_head_dim", None),
])

# CAPSim predictor: ~2M params -> weights replicate everywhere; the clip
# batch is i.i.d. and shards over EVERY mesh axis (the paper's clip-level
# parallelism).  Gradient all-reduce of ~8 MB fp32 over 512 chips is noise.
LOGICAL_RULES_PREDICTOR: Rules = _norm([
    ("batch", ("pod", "data", "model")),
    ("act_seq", None), ("act_embed", None), ("act_heads", None),
    ("act_mlp", None), ("act_vocab", None),
    ("embed", None), ("vocab", None), ("vocab_in", None),
    ("qkv", None), ("kv", None), ("mlp", None),
    ("layers", None),
])


# --------------------------------------------------------------------------- #
# Context: active mesh + rules
# --------------------------------------------------------------------------- #

class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: Optional[Rules] = None


_CTX = _Ctx()


@contextlib.contextmanager
def use_mesh_and_rules(mesh: Optional[Mesh], rules: Optional[Rules]):
    """Activate (mesh, rules) for logical-axis constraint resolution."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, _norm(rules) if rules else None
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def current_rules() -> Optional[Rules]:
    return _CTX.rules


def axis_rules(logical_axes: Sequence[Optional[str]],
               rules: Optional[Rules] = None,
               mesh: Optional[Mesh] = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec.

    A mesh axis is consumed at most once per spec (first logical axis wins);
    mesh axes absent from the mesh (e.g. 'pod' on a single-pod mesh) are
    dropped; axes whose size does not divide the dimension are dropped by
    XLA later, so no check here.
    """
    rules = rules if rules is not None else (_CTX.rules or ())
    mesh = mesh if mesh is not None else _CTX.mesh
    table = dict(rules)
    mesh_axis_names = set(mesh.axis_names) if mesh is not None else None
    used = set()
    spec = []
    for name in logical_axes:
        if name is None:
            spec.append(None)
            continue
        axes = table.get(name)
        if axes is None:
            spec.append(None)
            continue
        picked = []
        for ax in axes:
            if mesh_axis_names is not None and ax not in mesh_axis_names:
                continue
            if ax in used:
                continue
            picked.append(ax)
            used.add(ax)
        spec.append(tuple(picked) if len(picked) > 1 else (picked[0] if picked else None))
    return P(*spec)


def logical_sharding(logical_axes: Sequence[Optional[str]],
                     mesh: Optional[Mesh] = None,
                     rules: Optional[Rules] = None) -> NamedSharding:
    mesh = mesh if mesh is not None else _CTX.mesh
    assert mesh is not None, "no active mesh; wrap in use_mesh_and_rules(...)"
    return NamedSharding(mesh, axis_rules(logical_axes, rules=rules, mesh=mesh))


def shard_logical(x, *logical_axes):
    """with_sharding_constraint by logical axis names (no-op without a mesh)."""
    if _CTX.mesh is None or _CTX.rules is None:
        return x
    spec = axis_rules(logical_axes)
    # Drop constraints that do not divide the dimension (tiny smoke meshes).
    sizes = dict(zip(_CTX.mesh.axis_names, _CTX.mesh.devices.shape))
    fixed = []
    for dim, entry in zip(x.shape, spec):
        if entry is None:
            fixed.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        total = 1
        for ax in axes:
            total *= sizes[ax]
        fixed.append(entry if (total and dim % total == 0) else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_CTX.mesh, P(*fixed)))
