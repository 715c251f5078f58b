"""GQA attention: q-chunked training/prefill path + shard_map flash-decoding.

Three execution paths share one set of weights:

  train/prefill  full-sequence causal attention, scanned over query chunks so
                 the (chunk, S) score tile bounds transient memory (32k prefill
                 would otherwise materialize S^2 scores).  Optionally routed to
                 the Pallas flash kernel (cfg.attn_impl == "pallas").
  decode         one query token against a KV cache whose *sequence* dimension
                 is sharded over the 'model' mesh axis.  Implemented as an
                 explicit shard_map flash-decoding: every model shard computes
                 a partial softmax over its sequence slice and the partials are
                 merged with psum — collective volume is O(B*H*D), independent
                 of context length.  This is the TPU analogue of GPU
                 flash-decoding and is what makes long_500k cells viable.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (axis_rules, current_mesh,
                                        shard_logical)
from repro.models.layers import ParamSpec, apply_rope, dense_spec, rms_norm


# --------------------------------------------------------------------------- #
# Param specs
# --------------------------------------------------------------------------- #

def attn_specs(cfg) -> dict:
    d, H, KV, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    specs = {
        "wq": dense_spec(d, H * Dh, ("embed", "qkv")),
        "wk": dense_spec(d, KV * Dh, ("embed", "kv")),
        "wv": dense_spec(d, KV * Dh, ("embed", "kv")),
        "wo": dense_spec(H * Dh, d, ("qkv", "embed")),
    }
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((Dh,), (None,), std=0.0, dtype="float32")
        specs["k_norm"] = ParamSpec((Dh,), (None,), std=0.0, dtype="float32")
    return specs


# --------------------------------------------------------------------------- #
# Training / prefill attention (q-chunked, causal)
# --------------------------------------------------------------------------- #

def _causal_attention_chunked(q, k, v, chunk: int, q_start=0):
    """q,k,v: (B, Sq, H, Dh)/(B, Skv, H, Dh) with kv already broadcast.

    lax.scan over query chunks; each chunk attends over the full key range
    with a causal mask.  fp32 softmax accumulation.  Transient score tile
    is (B, H, chunk, Skv) instead of (B, H, Sq, Skv).  ``q_start`` offsets
    the query positions globally (sequence-parallel prefill: each shard
    owns rows [q_start, q_start + Sq)).
    """
    B, S, H, Dh = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(Dh)
    chunk = min(chunk, S)
    if S % chunk != 0:
        chunk = S  # fall back to a single chunk (smoke shapes)
    n_chunks = S // chunk
    kpos = jnp.arange(Skv)

    def body(_, idx):
        off = idx * chunk
        qc = jax.lax.dynamic_slice_in_dim(q, off, chunk, axis=1)
        s = jnp.einsum("bchd,bshd->bhcs", qc, k,
                       preferred_element_type=jnp.float32) * scale
        qpos = q_start + off + jnp.arange(chunk)
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        oc = jnp.einsum("bhcs,bshd->bchd", p, v)
        return _, oc

    _, out = jax.lax.scan(body, None, jnp.arange(n_chunks))
    # out: (n_chunks, B, chunk, H, Dh) -> (B, S, H, Dh)
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, H, Dh)
    return out


def _causal_attention_pallas(q, k, v):
    from repro.kernels.flash_attention import ops as fa_ops
    return fa_ops.flash_attention(q, k, v, causal=True)


# --------------------------------------------------------------------------- #
# Sequence-parallel prefill attention (§Perf cell E)
# --------------------------------------------------------------------------- #

def sp_prefill_attention(q, k, v, cfg):
    """Ring-style sequence parallelism for prefill/train attention.

    Under LOGICAL_RULES_PREFILL_SP the residual stream is sequence-sharded
    over 'model' (no tensor parallelism at all): FFNs and norms are purely
    local, and attention is the ONLY cross-shard op.  Each shard
    all-gathers the (small, GQA) K/V heads — O(S·KV·Dh) per layer instead
    of the O(B·S·d) all-reduces TP pays — and computes the causal scores
    for its own query rows with a global position offset.

    q: (B, S, H, Dh); k/v: (B, S, KV, Dh) (pre-broadcast: gathering KV=8
    heads then repeating locally is G x cheaper than gathering H=48).
    Returns (B, S, H, Dh), sequence-sharded like q.
    """
    mesh = current_mesh()
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh else {}
    n_sp = sizes.get("model", 1)
    if mesh is None or n_sp <= 1 or S % n_sp != 0:
        kb = jnp.repeat(k, G, axis=2)
        vb = jnp.repeat(v, G, axis=2)
        return _causal_attention_chunked(q, kb, vb, cfg.attn_chunk)

    batch_entry = axis_rules(("batch",), mesh=mesh)[0]
    n_batch = 1
    for a in _axes_tuple(batch_entry):
        n_batch *= sizes[a]
    if n_batch and B % n_batch != 0:
        batch_entry = None
    spec = P(batch_entry, "model", None, None)
    s_loc = S // n_sp

    def local(q_loc, k_loc, v_loc):
        m = jax.lax.axis_index("model")
        k_full = jax.lax.all_gather(k_loc, "model", axis=1, tiled=True)
        v_full = jax.lax.all_gather(v_loc, "model", axis=1, tiled=True)
        kb = jnp.repeat(k_full, G, axis=2)
        vb = jnp.repeat(v_full, G, axis=2)
        return _causal_attention_chunked(q_loc, kb, vb, cfg.attn_chunk,
                                         q_start=m * s_loc)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=spec,
                       check_vma=False)
    return fn(q, k, v)


# --------------------------------------------------------------------------- #
# Flash-decoding (shard_map over 'model'; cache seq-sharded)
# --------------------------------------------------------------------------- #

def _axes_tuple(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


def _flash_decode_local(q, k, v, cache_pos, *, s_loc, scale, seq_axes,
                        axis_sizes):
    """Local partial attention of one shard over its sequence slice.

    q: (B, KV, G, Dh) replicated over seq_axes; k,v: (B, S_loc, KV, Dh)
    local slice; returns merged (B, KV, G, Dh) after psum over seq_axes.
    """
    shard = jnp.zeros((), jnp.int32)
    for a in seq_axes:                                  # row-major combined id
        shard = shard * axis_sizes[a] + jax.lax.axis_index(a)
    kpos = shard * s_loc + jnp.arange(s_loc)            # global positions
    valid = kpos <= cache_pos                           # causal/filled mask
    s = jnp.einsum("bkgd,bskd->bkgs", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[None, None, None, :], s, -jnp.inf)
    m_loc = jnp.max(s, axis=-1)                         # (B, KV, G)
    m = jax.lax.pmax(m_loc, seq_axes)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[None, None, None, :], p, 0.0)
    l = jax.lax.psum(jnp.sum(p, axis=-1), seq_axes)     # (B, KV, G)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    o = jax.lax.psum(o, seq_axes)
    return (o / jnp.maximum(l[..., None], 1e-30)).astype(v.dtype)


def flash_decode(q, k_cache, v_cache, cache_pos, cfg):
    """q: (B, 1, H, Dh); caches: (B, S, KV, Dh), seq dim sharded per the
    active 'cache_seq' rule ('model' for batched decode; the whole mesh for
    long-context B=1 cells)."""
    mesh = current_mesh()
    B, _, H, Dh = q.shape
    KV = cfg.num_kv_heads
    G = H // KV
    S = k_cache.shape[1]
    scale = 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, KV, G, Dh)

    seq_axes = _axes_tuple(
        axis_rules(("cache_seq",), mesh=mesh)[0]) if mesh is not None else ()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh else {}
    n_seq = 1
    for a in seq_axes:
        n_seq *= sizes[a]

    if mesh is None or n_seq <= 1 or S % n_seq != 0:
        # single-device / unsharded fallback: plain masked attention
        kpos = jnp.arange(S)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where((kpos <= cache_pos)[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
        o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache)
        return o.reshape(B, 1, H, Dh)

    s_loc = S // n_seq
    batch_entry = axis_rules(("cache_batch",), mesh=mesh)[0]
    n_batch = 1
    for a in _axes_tuple(batch_entry):
        n_batch *= sizes[a]
    if n_batch == 0 or B % max(n_batch, 1) != 0:
        batch_entry = None
    q_spec = P(batch_entry, None, None, None)
    kv_spec = P(batch_entry, seq_axes, None, None)

    fn = jax.shard_map(
        partial(_flash_decode_local, s_loc=s_loc, scale=scale,
                seq_axes=seq_axes, axis_sizes=sizes),
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, P()),
        out_specs=q_spec,
        check_vma=False,
    )
    o = fn(qg, k_cache, v_cache, cache_pos)
    return o.reshape(B, 1, H, Dh)


# --------------------------------------------------------------------------- #
# Block entry point
# --------------------------------------------------------------------------- #

def init_cache_specs(cfg, batch: int, max_seq: int) -> dict:
    KV, Dh = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": ParamSpec((batch, max_seq, KV, Dh),
                       ("cache_batch", "cache_seq", "cache_kv",
                        "cache_head_dim")),
        "v": ParamSpec((batch, max_seq, KV, Dh),
                       ("cache_batch", "cache_seq", "cache_kv",
                        "cache_head_dim")),
    }


def attention_forward(params, x, positions, cfg, mode: str,
                      cache: Optional[dict] = None,
                      cache_pos=None) -> Tuple[jax.Array, Optional[dict]]:
    """x: (B, S, d).  mode: 'train' | 'prefill' | 'decode'.

    decode: S == 1; cache holds (B, S_max, KV, Dh) seq-sharded k/v and the
    query position is ``cache_pos`` (scalar int32).
    Returns (out (B, S, d), updated cache or None).
    """
    B, S, d = x.shape
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // KV

    q = jnp.einsum("bsd,dh->bsh", x, params["wq"]).reshape(B, S, H, Dh)
    k = jnp.einsum("bsd,dh->bsh", x, params["wk"]).reshape(B, S, KV, Dh)
    v = jnp.einsum("bsd,dh->bsh", x, params["wv"]).reshape(B, S, KV, Dh)

    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])

    q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)

    new_cache = None
    if mode == "decode":
        assert cache is not None and S == 1
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), cache_pos, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), cache_pos, axis=1)
        k_cache = shard_logical(k_cache, "cache_batch", "cache_seq",
                                "cache_kv", "cache_head_dim")
        v_cache = shard_logical(v_cache, "cache_batch", "cache_seq",
                                "cache_kv", "cache_head_dim")
        o = flash_decode(q, k_cache, v_cache, cache_pos, cfg)
        new_cache = {"k": k_cache, "v": v_cache}
        o = o.reshape(B, S, H * Dh)
    elif cfg.attn_impl == "sp":
        # sequence-parallel: q/k/v stay seq-sharded; KV gathered in-kernel
        q = shard_logical(q, "batch", "act_seq", None, None)
        k = shard_logical(k, "batch", "act_seq", None, None)
        v = shard_logical(v, "batch", "act_seq", None, None)
        o = sp_prefill_attention(q, k, v, cfg)
        o = o.reshape(B, S, H * Dh)
        if mode == "prefill":
            new_cache = {"k": k, "v": v}
    else:
        # Broadcast KV heads to H (Megatron-style when TP > num_kv_heads):
        # q/k/v all (B, S, H, Dh), head axis TP-sharded over 'model'.
        kb = jnp.repeat(k, G, axis=2)
        vb = jnp.repeat(v, G, axis=2)
        q = shard_logical(q, "batch", "act_seq", "act_heads", None)
        kb = shard_logical(kb, "batch", "act_seq", "act_heads", None)
        vb = shard_logical(vb, "batch", "act_seq", "act_heads", None)
        if cfg.attn_impl == "pallas":
            o = _causal_attention_pallas(q, kb, vb)
        else:
            o = _causal_attention_chunked(q, kb, vb, cfg.attn_chunk)
        o = o.reshape(B, S, H * Dh)
        if mode == "prefill":
            new_cache = {"k": k, "v": v}

    out = jnp.einsum("bsh,hd->bsd", o, params["wo"])
    return shard_logical(out, "batch", "act_seq", "act_embed"), new_cache
