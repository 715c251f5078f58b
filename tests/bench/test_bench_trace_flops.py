"""The trace reduction on hand-made events and on a recorded trace, and
the FLOP and byte counts against hand counts at a small width."""

import pytest
from harness import flops, peaks
from harness import trace as tr


def ev(s, e, name="op", **stats_):
    return tr.Event(float(s), float(e), name, tuple(stats_.items()))


def test_union_clip_and_busy():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    evs = [ev(0, 2e9), ev(1e9, 3e9), ev(5e9, 6e9)]
    assert tr.busy_seconds(evs, 0, 10e9) == pytest.approx(4.0)
    assert tr.busy_seconds(evs, 2e9, 5.5e9) == pytest.approx(1.5)


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    dev = [ev(0, 1e9), ev(3e9, 4e9)]
    host = [ev(0, 10e9, tr.WINDOW_SPAN), ev(0, 10e9, "bench.pass"),
            ev(1e9, 3e9, "interpret"), ev(4e9, 10e9, "tokenize")]
    gaps = dict(tr.idle_gaps(dev, host, 0, 10e9))
    assert gaps == {"tokenize": pytest.approx(6.0),
                    "interpret": pytest.approx(2.0)}


def test_top_ops_and_kernel_matching():
    devices = {"/device:TPU:0": [ev(0, 3e9, "fusion.1"),
                                 ev(3e9, 4e9, "custom-call.2",
                                    long_name="_fa_kernel(...)"),
                                 ev(4e9, 6e9, "fusion.1")]}
    assert tr.top_ops(devices, 0, 10e9) == [["fusion", 5.0],
                                            ["custom-call", 1.0]]
    got = tr.matching(devices["/device:TPU:0"], ["_fa_kernel"])
    assert [e.name for e in got] == ["custom-call.2"]
    s = tr.Summary(lo=0.0, hi=10e9, devices=devices,
                   host=[ev(0, 10e9, tr.WINDOW_SPAN)])
    assert s.busy_s == pytest.approx(6.0)
    assert s.window_s == pytest.approx(10.0)
    assert len(s.kernel_events(["_fa_kernel"])) == 1
    assert s.breakdown()["idle_gaps"] == [["no host span",
                                           pytest.approx(4.0)]]


def test_recorded_trace_has_the_window_span(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    a = jnp.ones((64, 64))
    f(a).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.pass"):
            f(a).block_until_ready()
    jax.profiler.stop_trace()
    ex = tr.extract(tr.find_xplane(str(tmp_path)))
    lo, hi = tr.window_of(ex)
    assert hi > lo
    names = {e.name for e in ex.host}
    assert "bench.pass" in names
    assert ex.devices == {}          # the CPU has no /device:TPU plane


SMALL = {"d_model": 8, "num_heads": 2, "head_dim": 4, "d_ff": 16,
         "l_clip": 4, "l_token": 3, "n_inst_layers": 2,
         "n_block_layers": 3}


def test_block_step_flops_by_hand():
    # M=5 context rows, L=4 clip rows, E=8, HD=8, F=16
    self_attn = 2*5*8*8 + 4*5*8*8 + 4*5*5*8 + 2*5*8*8        # 2720
    cross = 2*5*8*8 + 4*4*8*8 + 4*5*4*8 + 2*5*8*8            # 2944
    ffn = 4*5*8*16                                           # 2560
    head = 2*5*8*8 + 2*5*8                                   # 720
    assert flops.block_step_flops(SMALL, 5) == \
        3 * (self_attn + cross + ffn) + head


def test_inst_row_flops_by_hand():
    attn = 2*3*8*8 + 4*3*8*8 + 4*3*3*8 + 2*3*8*8             # 1824
    ffn = 4*3*8*16                                           # 1536
    assert flops.inst_row_flops(SMALL) == 2 * (attn + ffn)


def test_paper_width_step_is_about_a_gigaflop_per_clip():
    m = {"d_model": 128, "num_heads": 4, "head_dim": 32, "d_ff": 512,
         "l_clip": 128, "l_token": 16, "n_inst_layers": 4,
         "n_block_layers": 4}
    assert flops.block_step_flops(m, 360) == pytest.approx(1.066e9,
                                                            rel=1e-3)


def test_attention_kernel_cost_and_roofline():
    c = flops.attention_kernel_cost(bh=2, sq=3, skv=5, d=4, itemsize=4)
    assert c["flops"] == 4 * 2 * 3 * 5 * 4
    assert c["bytes"] == 4 * 2 * 4 * (2 * 3 + 2 * 5) + 4 * 2 * 5
    p = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds({"flops": 300, "bytes": 20}, p) == 3.0
    assert flops.roofline_seconds({"flops": 100, "bytes": 50}, p) == 5.0


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peak("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")

