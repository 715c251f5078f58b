"""Plain reference of the CAPSim predictor (paper Sec. III and V, Fig 4).

float32 ``jax.numpy`` at a stated matmul precision (``highest``, or
``default``: on the TPU one bfloat16 pass with float32 accumulation, as
the configuration's float32 runs), no kernels, no RT cache, no context
dedup, no batching tricks:

  instruction encoder  4 pre-norm layers of masked self-attention + GELU
                       FFN over each instruction's standardized tokens;
                       the <REP> slot's output is the instruction vector.
  block encoder        sinusoidal positions on the clip's instruction
                       vectors; 4 layers in which the M context rows
                       self-attend, cross-attend to the instruction
                       vectors (masked by the clip mask), and pass an FFN.
  head                 final norm, MLP to one value per context row, mean
                       over rows, softplus, times the clip's instruction
                       count.

Weights may be fake-quantized per output channel (``weight_bits``) with
the scheme the served int8 tier states: every >= 2-D leaf gets one scale
per last-axis channel, taken over all other axes.  The instruction
encoder is evaluated once per distinct token row and gathered, which is
the same arithmetic as evaluating it per instruction.
"""
from __future__ import annotations

import math
from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = {"highest": jax.lax.Precision.HIGHEST,
              "default": jax.lax.Precision.DEFAULT}
NEG_INF = -1e30
BLOCK_CLIPS = 64


def fake_quant(params, bits: Optional[int]):
    if not bits:
        return params
    qmax = float(2 ** (bits - 1) - 1)

    def q(w):
        if w.ndim < 2:
            return w
        s = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)),
                    keepdims=True)
        s = jnp.where(s == 0.0, 1.0, s)
        return jnp.clip(jnp.round(w / s * qmax), -qmax, qmax) * (s / qmax)
    return jax.tree_util.tree_map(q, params)


def _norm(x, g):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * (1.0 + g)


def _mha(p, pre, xq, xkv, heads, hi, mask=None):
    B, Sq, _ = xq.shape
    Sk = xkv.shape[1]
    hd = p[pre + "wq"].shape[-1] // heads
    q = jnp.matmul(xq, p[pre + "wq"], precision=hi).reshape(B, Sq, heads, hd)
    k = jnp.matmul(xkv, p[pre + "wk"], precision=hi).reshape(B, Sk, heads, hd)
    v = jnp.matmul(xkv, p[pre + "wv"], precision=hi).reshape(B, Sk, heads, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) / math.sqrt(hd)
    if mask is not None:
        s = jnp.where(mask[:, None, None, :] > 0, s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=hi).reshape(B, Sq, -1)
    return jnp.matmul(o, p[pre + "wo"], precision=hi)


def _ffn(p, x, hi):
    h = jax.nn.gelu(jnp.matmul(x, p["w1"], precision=hi))
    return jnp.matmul(h, p["w2"], precision=hi)


def _layer(tree, i):
    return {k: v[i] for k, v in tree.items()}


def encode_rows(params, rows, heads: int, hi):
    """(N, L_token) token rows -> (N, E) instruction vectors."""
    x = params["embed"][rows]
    mask = (rows != 0).astype(jnp.float32)
    for i in range(params["inst"]["wq"].shape[0]):
        p = _layer(params["inst"], i)
        h = _norm(x, p["norm1"])
        x = x + _mha(p, "", h, h, heads, hi, mask)
        x = x + _ffn(p, _norm(x, p["norm2"]), hi)
    return x[:, 0, :]


def _sinusoidal(n: int, e: int):
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(e // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, 2.0 * dim / e)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def clip_cycles(params, rt, ctx, clip_mask, heads: int, hi):
    """rt (B, L, E) instruction vectors, ctx (B, M) ids, clip_mask (B, L)
    -> (B,) predicted cycles."""
    rt = rt + _sinusoidal(rt.shape[1], rt.shape[2])[None]
    x = params["embed"][ctx]
    for i in range(params["block"]["self_wq"].shape[0]):
        p = _layer(params["block"], i)
        h = _norm(x, p["norm1"])
        x = x + _mha(p, "self_", h, h, heads, hi)
        x = x + _mha(p, "cross_", _norm(x, p["norm2"]), rt, heads, hi,
                     clip_mask)
        x = x + _ffn(p, _norm(x, p["norm3"]), hi)
    h = _norm(x, params["final_norm"])
    hw = params["head"]
    h = jax.nn.gelu(jnp.matmul(h, hw["w1"], precision=hi) + hw["b1"])
    y = (jnp.matmul(h, hw["w2"], precision=hi) + hw["b2"])[..., 0]
    n_inst = jnp.maximum(clip_mask.sum(-1), 1.0)
    return jax.nn.softplus(jnp.mean(y, axis=-1)) * n_inst


@lru_cache(maxsize=None)
def _jitted(heads: int, precision: str):
    hi = PRECISIONS[precision]
    return (jax.jit(lambda p, r: encode_rows(p, r, heads, hi)),
            jax.jit(lambda p, rt, c, m: clip_cycles(p, rt, c, m, heads, hi)))


def _bucket(n: int) -> int:
    b = 64
    while b < n:
        b *= 2
    return b


def predict(params, clip_tokens: np.ndarray, ctx: np.ndarray,
            clip_mask: np.ndarray, heads: int,
            precision: str = "highest") -> np.ndarray:
    """Per-clip cycles of the reference, in blocks of ``BLOCK_CLIPS``."""
    enc, blk = _jitted(heads, precision)
    n, L, T = clip_tokens.shape
    rows, inv = np.unique(clip_tokens.reshape(n * L, T), axis=0,
                          return_inverse=True)
    pad = np.zeros((_bucket(len(rows)) - len(rows), T), rows.dtype)
    table = enc(params, jnp.asarray(np.concatenate([rows, pad])))
    inv = inv.reshape(n, L)
    out = []
    for lo in range(0, n, BLOCK_CLIPS):
        hi = min(lo + BLOCK_CLIPS, n)
        k = BLOCK_CLIPS - (hi - lo)
        idx = np.concatenate([inv[lo:hi], np.zeros((k, L), inv.dtype)])
        c = np.concatenate([ctx[lo:hi], np.zeros((k,) + ctx.shape[1:],
                                                   ctx.dtype)])
        m = np.concatenate([clip_mask[lo:hi],
                            np.zeros((k, L), clip_mask.dtype)])
        y = blk(params, table[jnp.asarray(idx)], jnp.asarray(c),
                jnp.asarray(m))
        out.append(np.asarray(y)[:hi - lo])
    return np.concatenate(out).astype(np.float64)
