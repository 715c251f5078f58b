"""Fig 3 / Fig 8: clip-content occurrence distribution and sampler behavior.

Reproduces the paper's observation that an interval's clips split into a
few heavily-repeated contents plus a long tail of rare unique contents,
and that the sampler preserves the frequent-category distribution while
thinning occurrences (frequent) / categories (rare).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.sampler import (group_by_content, occurrence_histogram,
                                sample_clips)
from repro.core.slicer import slice_trace
from repro.isa import funcsim, progen, timing


def run(emit) -> None:
    bench = progen.build_benchmark("503.bwaves")
    st = progen.fresh_state(bench)
    trace, _, _ = funcsim.run(bench.program, 50_000, state=st)
    commits = timing.simulate(trace)
    clips = slice_trace([e.inst for e in trace], commits, l_min=100)

    hist = occurrence_histogram(clips)
    n_above = sum(1 for c in hist if c > 50)
    print(f"# Fig 8: {len(clips)} clips, {len(hist)} unique contents; "
          f"occurrence head {hist[:5]}, {n_above} contents above "
          f"threshold 50")

    t0 = time.time()
    sampled, stats = sample_clips(clips, threshold=50, coef=0.1)
    us = (time.time() - t0) * 1e6

    # distribution preservation among frequent contents
    def freq_dist(cs):
        groups = group_by_content(cs)
        counts = np.array(sorted((len(v) for v in groups.values()),
                                 reverse=True), float)
        return counts / counts.sum() if counts.size else counts

    d_in = freq_dist(clips)[: stats.n_frequent_groups]
    d_out = freq_dist(sampled)[: stats.n_frequent_groups]
    k = min(len(d_in), len(d_out))
    tv = 0.5 * float(np.abs(d_in[:k] / d_in[:k].sum()
                            - d_out[:k] / d_out[:k].sum()).sum()) \
        if k else 0.0

    emit.emit("sampler.reduction", us,
              f"kept {stats.n_out}/{stats.n_in} clips "
              f"({100*stats.reduction:.1f}%)")
    emit.emit("sampler.freq_dist_tv", us,
              f"total-variation drift of frequent-category distribution "
              f"{tv:.3f}")
    emit.emit("sampler.rare_categories", us,
              f"rare groups kept {stats.n_rare_groups_kept}/"
              f"{stats.n_rare_groups}")


if __name__ == "__main__":
    from benchmarks.common import CsvEmitter
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(CsvEmitter())
