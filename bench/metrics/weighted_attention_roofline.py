"""Share of its roofline that the fused serving step's weighted-attention
kernel reaches in the window, in percent: the least time the chip needs
for the attention the answered requests ask of it, over the device time
of the kernel's events in the trace.

Work per answered clip with u unique context tokens: 4 layers of
weighted self-attention over the u tokens and cross-attention from them
to the L clip rows, float32 operands, one float32 weight per key.  The
compute and memory bounds are taken over the window's totals."""

from harness import flops

KERNEL = ("_wa_kernel", "weighted_attention")


def read(r):
    if r.trace is None or "sum_u" not in r.extra:
        return None
    events = r.trace.kernel_events(KERNEL)
    device_s = sum(e.end_ns - e.start_ns for e in events) * 1e-9
    if not device_s:
        return None
    m = r.model
    h, d, lc, layers = (m["num_heads"], m["head_dim"], m["l_clip"],
                        m["n_block_layers"])
    n, su, su2 = r.extra["clips"], r.extra["sum_u"], r.extra["sum_u2"]
    total = {
        "flops": layers * 4 * h * d * (su2 + lc * su),
        "bytes": layers * (4 * h * d * (4 * su + 2 * su + 2 * lc * n)
                           + 4 * h * (su + lc * n)),
    }
    return 100.0 * flops.roofline_seconds(total, r.peak) / device_s
