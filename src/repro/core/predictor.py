"""CAPSim attention-based performance predictor (paper §III/§V, Fig 4).

Two-level architecture, exactly Eq 5-9:

  instruction encoder   4 pre-LN transformer layers of self-attention over
                        each instruction's standardized tokens (L_token, E);
                        the <REP> position's output is the instruction's
                        ideal-execution-time vector RT_i (Eq 5-8).  All
                        (B, L_clip) instructions run as one folded batch —
                        the clip-level parallelism that is the paper's speed
                        story, and on TPU one Pallas flash-attention grid.
  block encoder         sinusoidal positional encoding over the clip
                        sequence, then 4 layers in which the *context matrix*
                        (register-state rows, §V-B) self-attends and
                        cross-attends into the stacked instruction vectors
                        (Eq 9) — the learnable T_total = Σ t_i·α_i
                        factorization of Eq 3-4.
  head                  MLP -> per-row scalar -> arithmetic mean.  The mean
                        is passed through softplus and scaled by the clip's
                        instruction count, i.e. the head predicts
                        cycles-per-instruction; positivity + the length prior
                        stabilize MAPE training without changing the
                        architecture.

Loss = MAPE (Eq 11).  The no-context ablation (Fig 10) drops the context
stream: the block encoder then self-attends over the instruction vectors and
the head averages over instruction positions instead.

Sharding: the model is ~2M params — weights replicate; the batch axis shards
over EVERY mesh axis (pod, data, model): clips are i.i.d. so a 512-chip pod
group is pure clip-parallelism.  See LOGICAL_RULES_PREDICTOR.
"""
from __future__ import annotations

import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import shard_logical
from repro.models.layers import (
    ParamSpec, abstract_from_specs, dense_spec, init_from_specs, rms_norm,
    shardings_from_specs, specs_with_leading_stack)

NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# Param specs
# --------------------------------------------------------------------------- #

def _mha_specs(cfg, prefix: str = "") -> dict:
    E = cfg.d_model
    H, Dh = cfg.num_heads, cfg.head_dim
    return {
        f"{prefix}wq": dense_spec(E, H * Dh, ("embed", "qkv")),
        f"{prefix}wk": dense_spec(E, H * Dh, ("embed", "qkv")),
        f"{prefix}wv": dense_spec(E, H * Dh, ("embed", "qkv")),
        f"{prefix}wo": dense_spec(H * Dh, E, ("qkv", "embed")),
    }


def _ffn_specs(cfg) -> dict:
    E, F = cfg.d_model, cfg.d_ff
    return {"w1": dense_spec(E, F, ("embed", "mlp")),
            "w2": dense_spec(F, E, ("mlp", "embed"))}


def _norm_spec(cfg) -> ParamSpec:
    return ParamSpec((cfg.d_model,), ("embed",), std=0.0, dtype="float32")


def _encoder_layer_specs(cfg) -> dict:
    return {**_mha_specs(cfg), **_ffn_specs(cfg),
            "norm1": _norm_spec(cfg), "norm2": _norm_spec(cfg)}


def _block_layer_specs(cfg) -> dict:
    return {**_mha_specs(cfg, "self_"), **_mha_specs(cfg, "cross_"),
            **_ffn_specs(cfg),
            "norm1": _norm_spec(cfg), "norm2": _norm_spec(cfg),
            "norm3": _norm_spec(cfg)}


N_INST_LAYERS = 4
N_BLOCK_LAYERS = 4


def model_specs(cfg) -> dict:
    E, V = cfg.d_model, cfg.vocab_size
    return {
        "embed": ParamSpec((V, E), ("vocab_in", "embed"),
                           std=1.0 / math.sqrt(E)),
        "inst": specs_with_leading_stack(_encoder_layer_specs(cfg),
                                         N_INST_LAYERS),
        "block": specs_with_leading_stack(_block_layer_specs(cfg),
                                          N_BLOCK_LAYERS),
        "final_norm": _norm_spec(cfg),
        "head": {"w1": dense_spec(E, E, ("embed", "mlp")),
                 "b1": ParamSpec((E,), ("mlp",), std=0.0),
                 "w2": dense_spec(E, 1, ("mlp", None)),
                 "b2": ParamSpec((1,), (None,), std=0.0)},
    }


def init_params(cfg, key):
    return init_from_specs(model_specs(cfg), key, cfg.param_dtype)


def abstract_params(cfg):
    return abstract_from_specs(model_specs(cfg), cfg.param_dtype)


def param_shardings(cfg, mesh, rules):
    return shardings_from_specs(model_specs(cfg), mesh, rules)


# --------------------------------------------------------------------------- #
# Attention primitives
# --------------------------------------------------------------------------- #

def _heads(x, cfg):
    B, S, _ = x.shape
    return x.reshape(B, S, cfg.num_heads, cfg.head_dim)


def _w(p, name, cfg):
    """fp32 master params compute in cfg.dtype (mixed precision): without
    this cast every matmul output promotes to f32 and the backward saves
    f32 activations — 2x the HBM traffic and scan-residual memory (§Perf
    capsim iteration v2)."""
    return p[name].astype(cfg.dtype)


def _mha(p, q_in, kv_in, cfg, kv_mask=None, prefix: str = ""):
    """q_in: (B, Sq, E); kv_in: (B, Sk, E); kv_mask: (B, Sk) 1=valid."""
    q = _heads(jnp.einsum("bsd,dh->bsh", q_in, _w(p, f"{prefix}wq", cfg)),
               cfg)
    k = _heads(jnp.einsum("bsd,dh->bsh", kv_in, _w(p, f"{prefix}wk", cfg)),
               cfg)
    v = _heads(jnp.einsum("bsd,dh->bsh", kv_in, _w(p, f"{prefix}wv", cfg)),
               cfg)
    if cfg.attn_impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        o = fa_ops.flash_attention(q, k, v, causal=False, kv_mask=kv_mask)
    else:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32)
        s = s / math.sqrt(cfg.head_dim)
        if kv_mask is not None:
            s = jnp.where(kv_mask[:, None, None, :] > 0, s, NEG_INF)
        a = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        # f32 accumulation for the output matmul too: in bf16 mode the
        # weights/values stay bf16 but partial sums do not round per-step
        # (identical bits in f32 mode, where this is already the dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v,
                       preferred_element_type=jnp.float32).astype(v.dtype)
    o = o.reshape(q_in.shape[0], q_in.shape[1], -1)
    out = jnp.einsum("bsh,hd->bsd", o, _w(p, f"{prefix}wo", cfg))
    return out.astype(q_in.dtype)


def _ffn(p, x, cfg):
    h = jnp.einsum("bsd,df->bsf", x, _w(p, "w1", cfg))
    out = jnp.einsum("bsf,fd->bsd", jax.nn.gelu(h), _w(p, "w2", cfg))
    return out.astype(x.dtype)


def _scan_layers(layer_fn, stacked_params, x, *extra, remat: bool = False):
    def body(carry, lp):
        return layer_fn(lp, carry, *extra), None
    if remat:
        # recompute encoder layers in the backward: the scan then saves
        # only the layer carries instead of ~10 intermediates per layer
        # (§Perf capsim iteration v3); the predictor is memory-bound with
        # compute 30x below the HBM roof, so recompute is nearly free.
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    y, _ = jax.lax.scan(body, x, stacked_params)
    return y


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #

def _sinusoidal(n: int, e: int, dtype) -> jax.Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(e // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, 2.0 * dim / e)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)


def instruction_encoder(params, clip_tokens, cfg):
    """clip_tokens: (B, L_clip, L_token) int32 -> RT vectors (B, L_clip, E).

    The (B, L_clip) axes fold into one batch: every instruction encodes
    independently (Eq 7), which is what the TPU grid parallelizes.
    """
    B, L, T = clip_tokens.shape
    tok_mask = (clip_tokens != 0).astype(jnp.float32)   # <PAD> == 0
    flat = clip_tokens.reshape(B * L, T)
    x = params["embed"][flat].astype(cfg.dtype)          # (B*L, T, E)
    x = shard_logical(x, "batch", None, None)
    mask = tok_mask.reshape(B * L, T)

    def layer(p, h, m):
        h = h + _mha(p, rms_norm(h, p["norm1"]), rms_norm(h, p["norm1"]),
                     cfg, kv_mask=m)
        h = h + _ffn(p, rms_norm(h, p["norm2"]), cfg)
        return h

    x = _scan_layers(layer, params["inst"], x, mask,
                     remat=cfg.remat)
    rt = x[:, 0, :]                                      # <REP> slot (Eq 8)
    return rt.reshape(B, L, cfg.d_model)


def block_encoder(params, rt, ctx, clip_mask, cfg):
    """rt: (B, L_clip, E) instruction vectors; ctx: (B, M, E) context rows.

    Context stream queries the instruction stream (Eq 9).  Without context
    (ablation) the instruction stream self-attends instead.
    """
    B, L, E = rt.shape
    rt = rt + _sinusoidal(L, E, rt.dtype)[None]

    if ctx is None:                                      # no-context ablation
        def layer(p, h, m):
            h = h + _mha(p, rms_norm(h, p["norm1"]), rms_norm(h, p["norm1"]),
                         cfg, kv_mask=m, prefix="self_")
            h = h + _mha(p, rms_norm(h, p["norm2"]), rt, cfg, kv_mask=m,
                         prefix="cross_")
            h = h + _ffn(p, rms_norm(h, p["norm3"]), cfg)
            return h
        out = _scan_layers(layer, params["block"], rt, clip_mask,
                           remat=cfg.remat)
        return out, clip_mask

    def layer(p, h, m):
        h = h + _mha(p, rms_norm(h, p["norm1"]), rms_norm(h, p["norm1"]),
                     cfg, prefix="self_")
        h = h + _mha(p, rms_norm(h, p["norm2"]), rt, cfg, kv_mask=m,
                     prefix="cross_")
        h = h + _ffn(p, rms_norm(h, p["norm3"]), cfg)
        return shard_logical(h, "batch", None, None)

    out = _scan_layers(layer, params["block"], ctx, clip_mask,
                       remat=cfg.remat)
    return out, None                                     # all M rows valid


def encode_instructions(params, token_rows, cfg):
    """Static half of the split forward: (N, L_token) int32 standardized
    rows -> (N, E) RT vectors (Eq 5-8).

    Standardization (and therefore RT_i) depends only on the *static*
    instruction, so a program's ``token_table`` needs exactly one pass
    through the 4-layer instruction encoder — the RT-cache build.  Rows
    encode independently, so the result is bitwise the rows the monolithic
    ``forward`` would compute inside a (B, L_clip) clip batch.
    """
    return instruction_encoder(params, token_rows[None], cfg)[0]


def block_forward(params, rt, batch, cfg, use_context: bool = True):
    """Dynamic half of the split forward: block encoder + head over
    already-encoded RT vectors.

    rt: (B, L_clip, E) instruction vectors (from ``instruction_encoder``
    or an RT-table gather); batch supplies context_tokens (B, M) and
    clip_mask (B, L_clip).  Returns predicted clip times (B,) in cycles.

    The context stream is width-agnostic: M may be the single-core
    register matrix (``context.CONTEXT_LEN``), the core-tagged multicore
    layout, or the peer-channel layout in which every other core's
    ``<CORE>``-tagged register block is appended — the block encoder's
    self-attention then mixes rows *across cores*, which is how the
    multicore-trained predictor learns to price LLC/bus interference
    from the peers' architectural state.  Width validation lives at the
    dataset-build and engine-dispatch boundaries
    (``context.validate_context_width``), not here, so ablations and
    synthetic-spec batches stay unconstrained.
    """
    clip_mask = batch["clip_mask"].astype(jnp.float32)
    rt = shard_logical(rt, "batch", None, None)

    ctx = None
    if use_context:
        ctx = params["embed"][batch["context_tokens"]].astype(cfg.dtype)
        ctx = shard_logical(ctx, "batch", None, None)
    out, out_mask = block_encoder(params, rt, ctx, clip_mask, cfg)
    out = shard_logical(out, "batch", None, None)

    h = rms_norm(out, params["final_norm"])
    hw = params["head"]
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", h, hw["w1"].astype(cfg.dtype))
                    + hw["b1"].astype(cfg.dtype))
    y = (jnp.einsum("bsf,fo->bso", h, hw["w2"].astype(cfg.dtype))
         + hw["b2"].astype(cfg.dtype))[..., 0]           # (B, rows)
    y = y.astype(jnp.float32)
    if out_mask is None:
        cpi = jnp.mean(y, axis=-1)                       # arithmetic mean
    else:
        denom = jnp.maximum(out_mask.sum(-1), 1.0)
        cpi = (y * out_mask).sum(-1) / denom
    n_inst = jnp.maximum(clip_mask.sum(-1), 1.0)
    return jax.nn.softplus(cpi) * n_inst                 # cycles


def forward(params, batch, cfg, use_context: bool = True):
    """batch: clip_tokens (B,L,T), context_tokens (B,M), clip_mask (B,L).

    Returns predicted clip times (B,) in cycles.  Monolithic path: the
    instruction encoder runs over every dynamic clip row.  The serving
    engines use ``forward_cached`` instead, which replaces it with an
    RT-table gather.
    """
    rt = instruction_encoder(params, batch["clip_tokens"], cfg)
    return block_forward(params, rt, batch, cfg, use_context)


def forward_cached(params, rt_table, batch, cfg, use_context: bool = True):
    """RT-cache serving path: batch carries rt_idx (B, L_clip) int32 rows
    into ``rt_table`` ((C, E), from ``encode_instructions``) instead of
    clip_tokens.  Device FLOPs drop to block encoder + head only; in fp32
    the result is bitwise equal to ``forward`` on the gathered tokens.
    """
    rt = rt_table[batch["rt_idx"]]                       # (B, L_clip, E)
    return block_forward(params, rt, batch, cfg, use_context)


# --------------------------------------------------------------------------- #
# Fused serving step (EngineConfig.fused_serving)
# --------------------------------------------------------------------------- #
#
# Two exact identities collapse the per-batch work of ``forward_cached``:
#
# 1. Cross-attention K/V are linear in the kv input, and the kv input is
#    rt_table[rt_idx] + posenc — so per layer
#        K = (table @ cross_wk)[rt_idx] + (posenc @ cross_wk)
#    and ``serving_plan`` precomputes (table @ cross_wk/wv) ONCE per table
#    version.  The per-batch cost of the (B, L, E) rt gather, the posenc
#    add, and all 8 cross K/V projections drops to a (B, L, H·Dh) gather.
#
# 2. The block encoder adds no positional encoding to the context stream,
#    so it is permutation-equivariant over context rows: self-attention
#    over the M=360 context tokens equals *weighted* attention over the
#    ~64-128 unique tokens with multiplicity weights, and the head's
#    arithmetic mean equals the count-weighted mean (Σ c_u·y_u / M).  The
#    host dedupes each row (``standardize.dedupe_context_tokens``, ~2 ms
#    per batch) and the device runs the whole block stack at U instead of
#    M rows — a >5x serving win at full scale, exact up to fp
#    reassociation.

def serving_plan(params, rt_table, cfg):
    """Per-table-version precompute for ``forward_cached_fused``: the
    cross-attention K/V projections of every RT row, (L_layers, N, H·Dh).
    Rebuild whenever the RT table grows (the engine keys on table
    identity); ~ms at full scale."""
    dt = cfg.dtype
    table = rt_table.astype(dt)
    blk = params["block"]
    return {
        "cross_kt": jnp.einsum("ne,led->lnd", table,
                               blk["cross_wk"].astype(dt)),
        "cross_vt": jnp.einsum("ne,led->lnd", table,
                               blk["cross_wv"].astype(dt)),
    }


def _weighted_mha(q, k, v, weight, cfg):
    """Multi-head weighted attention over already-projected q/k/v
    ((B, S, H·Dh)); weight (B, Skv) f32 multiplicities."""
    from repro.kernels.fused_serving import ops as wa_ops
    B, Sq = q.shape[0], q.shape[1]
    o = wa_ops.weighted_attention(_heads(q, cfg), _heads(k, cfg),
                                  _heads(v, cfg), weight,
                                  impl=cfg.attn_impl)
    return o.reshape(B, Sq, -1)


def forward_cached_fused(params, plan, batch, cfg):
    """Fused serving twin of ``forward_cached`` (context path only).

    batch carries rt_idx (B, L_clip) int32, ctx_uniq (B, U) int32 deduped
    context token ids, ctx_count (B, U) f32 multiplicities (summing to M
    per row), clip_mask (B, L_clip).  ``plan`` is ``serving_plan`` for the
    current rt_table.  Returns predicted clip times (B,) in cycles, equal
    to ``forward_cached`` on the un-deduped batch up to fp reassociation
    (gated ≤1e-3 rel err; measured ~4e-7 at full scale).
    """
    idx = batch["rt_idx"]
    cw = batch["ctx_count"].astype(jnp.float32)
    clip_mask = batch["clip_mask"].astype(jnp.float32)
    L = idx.shape[1]
    dt = cfg.dtype
    blk = params["block"]

    pos = _sinusoidal(L, cfg.d_model, dt)
    pk = jnp.einsum("je,led->ljd", pos, blk["cross_wk"].astype(dt))
    pv = jnp.einsum("je,led->ljd", pos, blk["cross_wv"].astype(dt))
    k_all = plan["cross_kt"][:, idx] + pk[:, None]       # (Lyr, B, L, HDh)
    v_all = plan["cross_vt"][:, idx] + pv[:, None]
    wqkv = jnp.concatenate(
        [blk["self_wq"], blk["self_wk"], blk["self_wv"]],
        axis=-1).astype(dt)                              # (Lyr, E, 3·HDh)

    h = params["embed"][batch["ctx_uniq"]].astype(dt)    # (B, U, E)

    def layer(carry, xs):
        lp, wqkv_l, k_l, v_l = xs
        h = carry
        qkv = jnp.einsum("bud,dh->buh", rms_norm(h, lp["norm1"]), wqkv_l)
        q, sk, sv = jnp.split(qkv, 3, axis=-1)
        o = _weighted_mha(q, sk, sv, cw, cfg)
        h = h + jnp.einsum("buh,hd->bud", o,
                           _w(lp, "self_wo", cfg)).astype(h.dtype)
        q2 = jnp.einsum("bud,dh->buh", rms_norm(h, lp["norm2"]),
                        _w(lp, "cross_wq", cfg))
        o2 = _weighted_mha(q2, k_l, v_l, clip_mask, cfg)
        h = h + jnp.einsum("buh,hd->bud", o2,
                           _w(lp, "cross_wo", cfg)).astype(h.dtype)
        h = h + _ffn(lp, rms_norm(h, lp["norm3"]), cfg)
        return shard_logical(h, "batch", None, None), None

    h, _ = jax.lax.scan(layer, h, (blk, wqkv, k_all, v_all))

    h = rms_norm(h, params["final_norm"])
    hw = params["head"]
    h = jax.nn.gelu(jnp.einsum("bud,df->buf", h, hw["w1"].astype(dt))
                    + hw["b1"].astype(dt))
    y = (jnp.einsum("buf,fo->buo", h, hw["w2"].astype(dt))
         + hw["b2"].astype(dt))[..., 0]
    y = y.astype(jnp.float32)
    # head mean over the M context rows == count-weighted mean over uniques
    cpi = (y * cw).sum(-1) / jnp.maximum(cw.sum(-1), 1.0)
    n_inst = jnp.maximum(clip_mask.sum(-1), 1.0)
    return jax.nn.softplus(cpi) * n_inst


# --------------------------------------------------------------------------- #
# Multi-device sharded inference (EngineConfig.mesh_shape)
# --------------------------------------------------------------------------- #
#
# Clips (and static RT rows) are row-independent, so data-parallel
# sharding over a 1-D "data" mesh is bitwise equal to the single-device
# dispatch of the same batch: each shard computes exactly the rows it
# would compute inside the full batch, and the demux concatenates
# per-shard outputs in row order.  Params and the RT table replicate
# (P() specs) — the model is ~2M params, so replication is free and the
# only cross-device traffic is the batch scatter / result gather.

def _batch_shard_specs(mesh, token_key: str):
    data = P(mesh.axis_names[0])
    return {token_key: data, "context_tokens": data, "clip_mask": data}


def _shard_batch(f, mesh, in_specs):
    """``f`` shard_mapped over the batch axis of ``mesh``: ``in_specs``
    place the inputs, every output row stays on its shard."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=P(mesh.axis_names[0]), check_vma=False)


def sharded_predict_step(cfg, use_context: bool, mesh):
    """``predict_step`` shard_mapped over the batch axis of ``mesh``
    (monolithic path: batch carries clip_tokens)."""
    return _shard_batch(
        lambda p, b: predict_step(p, b, cfg, use_context), mesh,
        (P(), _batch_shard_specs(mesh, "clip_tokens")))


def sharded_forward_cached(cfg, use_context: bool, mesh):
    """``forward_cached`` shard_mapped over the batch axis of ``mesh``;
    the RT table replicates so every shard gathers locally."""
    return _shard_batch(
        lambda p, table, b: forward_cached(p, table, b, cfg, use_context),
        mesh, (P(), P(), _batch_shard_specs(mesh, "rt_idx")))


def sharded_forward_cached_fused(cfg, mesh):
    """``forward_cached_fused`` shard_mapped over the batch axis; params,
    RT table and serving plan replicate."""
    data = P(mesh.axis_names[0])
    specs = {"rt_idx": data, "ctx_uniq": data, "ctx_count": data,
             "clip_mask": data}
    return _shard_batch(
        lambda p, plan, b: forward_cached_fused(p, plan, b, cfg), mesh,
        (P(), P(), specs))


def sharded_encode_instructions(cfg, mesh):
    """``encode_instructions`` shard_mapped over the static-row axis:
    the RT-cache *build* divides by mesh size while the resulting table
    stays byte-identical (rows encode independently)."""
    return _shard_batch(
        lambda p, rows: encode_instructions(p, rows, cfg), mesh,
        (P(), P(mesh.axis_names[0])))


# Inference precision knob: fp32 is the bitwise-reference mode; bf16 keeps
# fp32 master params and casts at dispatch (``_w``) with fp32 softmax and
# fp32 score/output accumulation (``preferred_element_type`` above), so it
# is relative-error-bounded rather than bitwise.  int8 is *storage*
# precision: weights are per-channel fake-quantized once at engine build
# (``core.quant.quantize_dequant_params``) and all compute stays fp32 —
# measured, XLA's CPU int8 dot is ~5x slower than f32, so int8 compute
# would be a regression on this backend while fp32-on-quantized-weights
# measures exactly the deployment error (gated ≤1%).
PRECISION_DTYPES = {"fp32": "float32", "bf16": "bfloat16",
                    "int8": "float32"}


def inference_config(cfg, precision: Optional[str] = None):
    """Resolve the inference-time numerics + kernel config.

    ``precision`` None leaves cfg.dtype untouched (the bitwise-compatible
    default); "fp32"/"bf16" select the compute dtype.  On TPU the default
    XLA attention is swapped for the Pallas flash kernel (which takes the
    same ``kv_mask``) unless the config already picked an attn_impl other
    than the "chunked" default.  The kernel swap is allclose-not-bitwise
    vs XLA, so any reference comparison must resolve BOTH sides through
    this function (as ``bench_speed.run_multi`` does) — on CPU it is the
    identity for precision=None.
    """
    if precision is not None:
        try:
            cfg = cfg.replace(dtype=PRECISION_DTYPES[precision])
        except KeyError:
            raise ValueError(
                f"precision must be one of {sorted(PRECISION_DTYPES)}, "
                f"got {precision!r}") from None
    if jax.default_backend() == "tpu" and cfg.attn_impl == "chunked":
        cfg = cfg.replace(attn_impl="pallas")
    return cfg


def mape_loss(params, batch, cfg, use_context: bool = True):
    """Eq 11: |prediction - fact| / fact, averaged over the batch."""
    pred = forward(params, batch, cfg, use_context)
    fact = jnp.maximum(batch["time"].astype(jnp.float32), 1.0)
    mape = jnp.mean(jnp.abs(pred - fact) / fact)
    return mape, {"mape": mape}


def predict_step(params, batch, cfg, use_context: bool = True):
    return forward(params, batch, cfg, use_context)


# --------------------------------------------------------------------------- #
# Dry-run lowering (called from launch/dryrun.py for --arch capsim)
# --------------------------------------------------------------------------- #

def lower_cell(cfg, shape, mesh, rules, tcfg):
    """Lower the predictor's train / serve step on the production mesh."""
    from repro.distributed.sharding import (
        LOGICAL_RULES_PREDICTOR, use_mesh_and_rules)
    from repro.launch.specs import batch_shardings, input_specs
    from repro.training.train_loop import (
        abstract_train_state, make_train_step)

    rules = LOGICAL_RULES_PREDICTOR
    with use_mesh_and_rules(mesh, rules):
        batch_abs = input_specs(cfg, shape, shape.kind)
        batch_sh = batch_shardings(batch_abs, mesh, rules)
        param_abs = abstract_params(cfg)
        param_sh = param_shardings(cfg, mesh, rules)
        t0 = time.time()
        if shape.kind == "train":
            state_abs = abstract_train_state(param_abs, tcfg)
            scalar = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            if tcfg.optimizer == "sgdm":
                opt_sh = {"mu": param_sh}
            else:
                opt_sh = {"mu": param_sh, "nu": param_sh, "count": scalar}
            state_sh = {"params": param_sh, "opt": opt_sh, "step": scalar}
            if tcfg.compress_grads:
                state_sh["err_fb"] = param_sh
            step = make_train_step(
                lambda p, b: mape_loss(p, b, cfg), tcfg)
            metric_sh = {k: scalar for k in
                         ("loss", "grad_norm", "lr", "mape")}
            lowered = jax.jit(step, in_shardings=(state_sh, batch_sh),
                              out_shardings=(state_sh, metric_sh)
                              ).lower(state_abs, batch_abs)
        else:
            from repro.distributed.sharding import axis_rules
            out_sh = jax.sharding.NamedSharding(
                mesh, axis_rules(("batch",), rules=rules, mesh=mesh))
            lowered = jax.jit(
                lambda p, b: predict_step(p, b, cfg),
                in_shardings=(param_sh, batch_sh),
                out_shardings=out_sh).lower(param_abs, batch_abs)
        return lowered, time.time() - t0
