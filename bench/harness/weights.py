"""Predictor weights made from the seed, on the device, in one jitted call.

The tree has the layout the repository's predictor takes (an embedding,
4 stacked instruction-encoder layers, 4 stacked block-encoder layers, a
final norm and an MLP head).  Matrices are N(0, 1/fan_in); norm gains and
biases are N(0, 0.1) rather than zero, so that a path which drops one of
them shows in the comparison.

Every matrix lies on the per-output-channel int8 grid that the engine's
int8 tier and the service's fused int8 tier quantize to (scale = max |w|
over all axes but the last, 127 steps), so both serve exactly these
weights.  1-D leaves stay float32.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

NORM_STD = 0.1


def leaf_specs(m: Dict[str, int]) -> List[Tuple[Tuple[str, ...], tuple, float]]:
    """(path, shape, std) of every leaf, for model sizes ``m``."""
    E, HD, F, V = m["d_model"], m["num_heads"] * m["head_dim"], m["d_ff"], \
        m["vocab_size"]
    li, lb = m["n_inst_layers"], m["n_block_layers"]

    def mha(prefix: str, n: int):
        return [((prefix + "wq",), (n, E, HD), 1 / math.sqrt(E)),
                ((prefix + "wk",), (n, E, HD), 1 / math.sqrt(E)),
                ((prefix + "wv",), (n, E, HD), 1 / math.sqrt(E)),
                ((prefix + "wo",), (n, HD, E), 1 / math.sqrt(HD))]

    def ffn(n: int):
        return [(("w1",), (n, E, F), 1 / math.sqrt(E)),
                (("w2",), (n, F, E), 1 / math.sqrt(F))]

    def norms(n: int, k: int):
        return [((f"norm{i}",), (n, E), NORM_STD) for i in range(1, k + 1)]

    out = [(("embed",), (V, E), 1 / math.sqrt(E)),
           (("final_norm",), (E,), NORM_STD),
           (("head", "w1"), (E, E), 1 / math.sqrt(E)),
           (("head", "b1"), (E,), NORM_STD),
           (("head", "w2"), (E, 1), 1 / math.sqrt(E)),
           (("head", "b2"), (1,), NORM_STD)]
    out += [(("inst",) + p, s, d) for p, s, d in mha("", li) + ffn(li)
            + norms(li, 2)]
    out += [(("block",) + p, s, d) for p, s, d in
            mha("self_", lb) + mha("cross_", lb) + ffn(lb) + norms(lb, 3)]
    return out


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, wider than 32 bits too."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def on_int8_grid(w: jax.Array) -> jax.Array:
    if w.ndim < 2:
        return w
    s = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)), keepdims=True)
    return jnp.round(w / s * 127.0) * (s / 127.0)


def make_params(seed: int, m: Dict[str, int]) -> dict:
    """float32 weights for sizes ``m`` from ``seed``, made on the device,
    on the int8 grid."""
    specs = leaf_specs(m)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(specs))
        tree: dict = {}
        for k, (path, shape, std) in zip(keys, specs):
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = on_int8_grid(
                jax.random.normal(k, shape, jnp.float32) * std)
        return tree

    return build(seed_key(seed))
