"""Share of its roofline that the Pallas flash-attention kernel reaches
in the window, in percent: the least time the chip needs for the
attention the window's real work asks of it, over the device time of the
kernel's events in the trace.

Work: per real clip predicted, 4 block layers of self-attention over the
M context rows and cross-attention from them to the L clip rows; per RT
row built, 4 instruction-encoder layers of self-attention over L_token
tokens.  float32 q, k, v and output in HBM.  The compute and memory
bounds are taken over the window's totals."""

from harness import flops

KERNEL = ("_fa_kernel", "flash_attention")


def read(r):
    if r.trace is None:
        return None
    events = r.trace.kernel_events(KERNEL)
    device_s = sum(e.end_ns - e.start_ns for e in events) * 1e-9
    if not device_s:
        return None
    m = r.model
    h, d = m["num_heads"], m["head_dim"]
    rows, lc, t = m["context_rows"], m["l_clip"], m["l_token"]
    clips = r.extra["clips"]
    built = r.counter("capsim_rt_rows_encoded_total")
    calls = [(clips * m["n_block_layers"] * h, rows, rows),
             (clips * m["n_block_layers"] * h, rows, lc),
             (built * m["n_inst_layers"] * h, t, t)]
    total = {"flops": 0, "bytes": 0}
    for bh, sq, skv in calls:
        c = flops.attention_kernel_cost(int(bh), sq, skv, d, itemsize=4)
        total = {k: total[k] + c[k] for k in total}
    return 100.0 * flops.roofline_seconds(total, r.peak) / device_s
