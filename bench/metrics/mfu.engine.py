"""Model FLOP utilization of the window: the rt-tier step's work for
every real clip predicted (block encoder + head at the configuration's
context rows) plus the instruction encoder for every RT row built, over
the window and the chip's bf16 peak, in percent."""

from harness import flops


def read(r):
    work = (r.extra["clips"] * flops.block_step_flops(
        r.model, r.model["context_rows"])
        + r.counter("capsim_rt_rows_encoded_total")
        * flops.inst_row_flops(r.model))
    return 100.0 * work / (r.window_s * r.peak["flops_bf16"])
