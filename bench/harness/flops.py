"""Operations and bytes the predictor's work needs, from shapes.

Counts are of matrix-multiply FLOPs (2 per multiply-add), which is what
the MXU peak measures; softmax, norms and activations are left out.
Shapes are the work the algorithm needs, not the padded tiles a kernel
happens to use.
"""
from __future__ import annotations

from typing import Dict


def _attn(sq: int, skv: int, e: int, hd: int, kv_rows_projected: int) -> int:
    """One attention block: Q projection of sq rows, K/V projection of
    ``kv_rows_projected`` rows, scores and weighted sum, output
    projection."""
    return (2 * sq * e * hd + 2 * 2 * kv_rows_projected * e * hd
            + 2 * 2 * sq * skv * hd + 2 * sq * hd * e)


def block_step_flops(m: Dict[str, int], ctx_rows: int) -> int:
    """Block encoder + head for ONE clip: the rt-tier predict step's work
    per clip (the instruction vectors come from the RT table)."""
    e, hd, f, l = (m["d_model"], m["num_heads"] * m["head_dim"], m["d_ff"],
                   m["l_clip"])
    per_layer = (_attn(ctx_rows, ctx_rows, e, hd, ctx_rows)
                 + _attn(ctx_rows, l, e, hd, l)
                 + 2 * 2 * ctx_rows * e * f)
    head = 2 * ctx_rows * e * e + 2 * ctx_rows * e
    return m["n_block_layers"] * per_layer + head


def inst_row_flops(m: Dict[str, int]) -> int:
    """Instruction encoder for ONE standardized token row (an RT-cache
    row build)."""
    e, hd, f, t = (m["d_model"], m["num_heads"] * m["head_dim"], m["d_ff"],
                   m["l_token"])
    per_layer = _attn(t, t, e, hd, t) + 2 * 2 * t * e * f
    return m["n_inst_layers"] * per_layer


def attention_kernel_cost(bh: int, sq: int, skv: int, d: int,
                          itemsize: int, weight_bytes: int = 4
                          ) -> Dict[str, int]:
    """FLOPs and HBM bytes of one attention kernel call over (bh, sq, d)
    queries and (bh, skv, d) keys/values: QK^T and PV, reading q, k, v
    and the per-key mask or weight, writing o."""
    flops = 4 * bh * sq * skv * d
    nbytes = (itemsize * bh * d * (2 * sq + 2 * skv)
              + weight_bytes * bh * skv)
    return {"flops": flops, "bytes": nbytes}


def roofline_seconds(cost: Dict[str, int], peak: Dict[str, float]) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(cost["flops"] / peak["flops_bf16"],
               cost["bytes"] / peak["hbm_bytes_per_s"])
