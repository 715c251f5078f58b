"""Batched multi-benchmark simulation engine.

CAPSim's speed claim rests on amortizing predictor inference over large
accelerator batches, but a per-benchmark ``capsim_simulate`` loop leaves
three factors of throughput on the floor:

  1. it re-traces/re-compiles the jit'd predict step on every call —
     ``predict_fn`` below caches the compiled step per (config, ablation);
  2. each benchmark pads its own batch remainder — the engine feeds one
     *shared global clip pool*, so clips from many programs fill one
     device batch and only the final remainder pads (to a size bucket,
     bounding compiled shapes to ~log2(batch_size) variants);
  3. the Python functional sim serializes against inference — the engine
     exploits JAX's async dispatch as a double buffer: up to
     ``max_in_flight`` device batches run while the CPU tokenizes the
     next benchmark, and ``jax.block_until_ready`` is deferred to drain
     time.

The host front-end runs entirely on the columnar trace IR
(``repro.isa.compiled``): programs are compiled once to structure-of-
arrays, the table-dispatched interpreter emits pc/ea/taken columns plus a
uint64 snapshot matrix, per-clip tokenization is one
``token_table[trace.pc]`` gather, and context matrices come from a
vectorized byte decomposition — ``FrontendStats`` breaks the host time
down by stage (interpret / slice / tokenize / context) so regressions
show up in the bench JSON artifact.

Device FLOPs are cut by the static-instruction RT cache
(``repro.core.rt_cache``, on by default): each benchmark's ``n_static``
token rows go through the 4-layer instruction encoder exactly once, and
every clip batch then ships (n, l_clip) int32 RT-table indices instead of
token tensors — the jit'd ``forward_cached`` gathers the table on device
and runs only the block encoder + head.  ``precision="bf16"`` additionally
casts the fp32 master params to bfloat16 at dispatch (fp32 softmax and
accumulation), trading bitwise equality for a relative-error bound; on
TPU the block encoder's masked cross/self-attention routes through the
Pallas flash kernel by default (``predictor.inference_config``).

Per-clip predictions in fp32 are bitwise identical to the sequential
monolithic path (XLA CPU rows are independent of batch composition, and
the RT gather returns exactly the rows the folded batch would compute),
and per-benchmark sums are taken over the same contiguous per-benchmark
arrays — so results demux back into ``SimResult``s with unchanged
semantics.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from functools import lru_cache
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import analytical
from repro.core import context as ctx_mod
from repro.core import predictor as pred_mod
from repro.core import sampler as sampler_mod
from repro.core import standardize as std_mod
from repro.core.analytical import PredictionReport
from repro.core.engine_config import EngineConfig, reject_legacy_kwargs
from repro.core.rt_cache import RTCache, RTCacheStats
from repro.isa import funcsim, multicore, progen, timing
from repro.obs import SPAN_SECONDS_TOTAL, Observability


@dataclasses.dataclass
class SimResult:
    name: str
    n_intervals: int
    n_instructions: int
    n_clips: int
    predicted_cycles: float
    oracle_cycles: Optional[float]
    func_seconds: float               # functional sim + tokenize
    predict_seconds: float            # batched predictor inference (share)
    oracle_seconds: Optional[float]   # O3 oracle wall time
    # --- PredictionReport fields (analytical-ML fusion path) ---
    # Full-prediction runs keep the old meanings exactly: every clip is
    # model-predicted (clips_predicted == n_clips, nothing
    # extrapolated) and there is no interval (cycles_ci None).  Under
    # EngineConfig.sampling, predicted_cycles becomes the stratified
    # estimate, cycles_ci its 95% bootstrap interval, and
    # clip_provenance marks model (True) vs analytical-residual (False)
    # per clip.
    cycles_ci: Optional[Tuple[float, float]] = None
    clips_predicted: Optional[int] = None
    clips_extrapolated: int = 0
    clip_provenance: Optional[np.ndarray] = dataclasses.field(
        default=None, compare=False, repr=False)

    def __post_init__(self):
        if self.clips_predicted is None:
            self.clips_predicted = self.n_clips

    @property
    def capsim_seconds(self) -> float:
        return self.func_seconds + self.predict_seconds

    @property
    def speedup(self) -> Optional[float]:
        if self.oracle_seconds is None:
            return None
        return self.oracle_seconds / max(self.capsim_seconds, 1e-9)

    @property
    def rel_error(self) -> Optional[float]:
        if not self.oracle_cycles:
            return None
        return abs(self.predicted_cycles - self.oracle_cycles) \
            / self.oracle_cycles

    @property
    def prediction_report(self) -> PredictionReport:
        """The result's fused-prediction view as one typed object."""
        ci = (self.cycles_ci if self.cycles_ci is not None
              else (self.predicted_cycles, self.predicted_cycles))
        return PredictionReport(
            total_cycles=self.predicted_cycles, cycles_ci=ci,
            clips_predicted=self.clips_predicted,
            clips_extrapolated=self.clips_extrapolated,
            clip_provenance=self.clip_provenance)


@lru_cache(maxsize=64)
def predict_fn(cfg, use_context: bool = True):
    """Cached jit'd predict step: one trace+compile per (config, ablation)
    for the whole process instead of one per ``capsim_simulate`` call.
    ``cfg`` is a frozen dataclass, so it keys the cache directly."""
    return jax.jit(lambda p, b: pred_mod.predict_step(p, b, cfg,
                                                      use_context))


@lru_cache(maxsize=64)
def reference_fn(cfg, use_context: bool = True):
    """Cached jit'd accuracy reference: the monolithic ``forward`` in
    float32, XLA attention, ``highest`` matmul precision.  On the TPU an
    f32 matmul otherwise runs at reduced precision, so every serving
    tier is held to this step rather than to another tier; on the CPU it
    computes what the monolithic fp32 step computes."""
    ref = cfg.replace(dtype="float32", attn_impl="chunked")

    def step(p, b):
        with jax.default_matmul_precision("highest"):
            return pred_mod.predict_step(p, b, ref, use_context)
    return jax.jit(step)


@lru_cache(maxsize=64)
def predict_cached_fn(cfg, use_context: bool = True):
    """Cached jit'd RT-cache predict step: the batch carries ``rt_idx``
    rows into a device-resident RT table, so only the block encoder +
    head run per clip (``predictor.forward_cached``)."""
    return jax.jit(lambda p, table, b: pred_mod.forward_cached(
        p, table, b, cfg, use_context))


@lru_cache(maxsize=64)
def serving_plan_fn(cfg):
    """Cached jit'd per-table-version precompute for the fused serving
    step (``predictor.serving_plan``): cross-attention K/V projections
    of every RT row.  Rebuilt only when the table object changes."""
    return jax.jit(lambda p, table: pred_mod.serving_plan(p, table, cfg))


@lru_cache(maxsize=64)
def predict_cached_fused_fn(cfg):
    """Cached jit'd fused serving step: deduped-context weighted
    attention over precomputed cross K/V (``forward_cached_fused``)."""
    return jax.jit(lambda p, plan, b: pred_mod.forward_cached_fused(
        p, plan, b, cfg))


@lru_cache(maxsize=64)
def predict_cached_fused_mesh_fn(cfg, n_shards: int):
    """Sharded twin of ``predict_cached_fused_fn``: the batch axis splits
    over the data mesh; params, RT table and plan replicate."""
    from repro.launch.mesh import make_data_mesh
    return jax.jit(pred_mod.sharded_forward_cached_fused(
        cfg, make_data_mesh(n_shards)))


@lru_cache(maxsize=64)
def predict_mesh_fn(cfg, use_context: bool, n_shards: int):
    """Sharded twin of ``predict_fn``: the batch axis splits over an
    n-device data mesh (params replicated) — bitwise equal to the
    single-device dispatch because clips are row-independent."""
    from repro.launch.mesh import make_data_mesh
    return jax.jit(pred_mod.sharded_predict_step(
        cfg, use_context, make_data_mesh(n_shards)))


@lru_cache(maxsize=64)
def predict_cached_mesh_fn(cfg, use_context: bool, n_shards: int):
    """Sharded twin of ``predict_cached_fn``: rt_idx/context/mask shard
    over the data mesh, the RT table replicates to every device."""
    from repro.launch.mesh import make_data_mesh
    return jax.jit(pred_mod.sharded_forward_cached(
        cfg, use_context, make_data_mesh(n_shards)))


def bucket_sizes(batch_size: int, align: int = 1) -> Tuple[int, ...]:
    """Descending pad targets for the final partial batch: the full batch
    plus halvings down to 8.  Bounds distinct compiled shapes while keeping
    remainder padding < 2x.  ``align`` (the mesh shard count) keeps every
    bucket a multiple of the mesh size — and at least one row per device —
    so a sharded dispatch never hands a device an empty or ragged shard."""
    floor = max(8, align)
    sizes = [batch_size]
    b = batch_size
    while b > floor:
        b = max((b // 2 + align - 1) // align * align, floor)
        sizes.append(b)
    return tuple(sizes)


# stage span name per FrontendStats field — the engine times these via
# obs spans and the stats view reads the registry back
_FE_SPANS = {"interpret_seconds": "engine.interpret",
             "slice_seconds": "engine.slice",
             "tokenize_seconds": "engine.tokenize",
             "context_seconds": "engine.context",
             "analytical_seconds": "engine.analytical"}


class FrontendStats:
    """Host front-end breakdown across one ``SimulationEngine.run``.

    A live *view* over the obs metrics registry: the engine writes
    stage spans + counters (the same cells ``/metrics`` serves) and a
    fresh view snapshots a baseline at construction, so each ``run``
    reads per-run deltas while the registry keeps lifetime totals.
    No-arg construction is the all-zeros stand-in.
    """

    def __init__(self, obs: Optional[Observability] = None,
                 instance: str = ""):
        self._obs = obs
        self._instance = instance
        base: Dict[str, float] = {}
        if obs is not None:
            for field, span in _FE_SPANS.items():
                base[field] = obs.metrics.value(
                    SPAN_SECONDS_TOTAL, span=span, instance=instance)
            base["n_instructions"] = obs.metrics.value(
                "capsim_frontend_instructions_total", instance=instance)
            base["n_clips"] = obs.metrics.value(
                "capsim_frontend_clips_total", instance=instance)
        self._base = base

    def _span_delta(self, field: str) -> float:
        if self._obs is None:
            return 0.0
        now = self._obs.metrics.value(
            SPAN_SECONDS_TOTAL, span=_FE_SPANS[field],
            instance=self._instance)
        return now - self._base[field]

    def _count_delta(self, name: str, key: str) -> int:
        if self._obs is None:
            return 0
        now = self._obs.metrics.value(name, instance=self._instance)
        return int(now - self._base[key])

    @property
    def interpret_seconds(self) -> float:
        return self._span_delta("interpret_seconds")

    @property
    def slice_seconds(self) -> float:
        return self._span_delta("slice_seconds")

    @property
    def tokenize_seconds(self) -> float:
        return self._span_delta("tokenize_seconds")

    @property
    def context_seconds(self) -> float:
        return self._span_delta("context_seconds")

    @property
    def analytical_seconds(self) -> float:
        return self._span_delta("analytical_seconds")

    @property
    def n_instructions(self) -> int:
        return self._count_delta("capsim_frontend_instructions_total",
                                 "n_instructions")

    @property
    def n_clips(self) -> int:
        return self._count_delta("capsim_frontend_clips_total", "n_clips")

    @property
    def frontend_seconds(self) -> float:
        return (self.interpret_seconds + self.slice_seconds
                + self.tokenize_seconds + self.context_seconds
                + self.analytical_seconds)

    def as_dict(self) -> Dict[str, float]:
        return {"interpret_seconds": self.interpret_seconds,
                "slice_seconds": self.slice_seconds,
                "tokenize_seconds": self.tokenize_seconds,
                "context_seconds": self.context_seconds,
                "analytical_seconds": self.analytical_seconds,
                "frontend_seconds": self.frontend_seconds,
                "n_instructions": self.n_instructions,
                "n_clips": self.n_clips}


class PredictorStats:
    """Live view over one predictor instance's registry cells.

    Each ``BatchedPredictor`` gets a process-unique ``instance`` label,
    so its cells start at zero and concurrent predictors (including
    flushes abandoned by the serving watchdog) can never corrupt each
    other's accounting — which keeps the drain demux assert exact.
    """

    def __init__(self, obs: Optional[Observability] = None,
                 instance: str = ""):
        self._obs = obs
        self._instance = instance

    def _val(self, name: str) -> float:
        if self._obs is None:
            return 0.0
        return self._obs.metrics.value(name, instance=self._instance)

    @property
    def n_clips(self) -> int:              # real clips fed in
        return int(self._val("capsim_predictor_clips_total"))

    @property
    def n_predicted(self) -> int:          # real clips retired
        return int(self._val("capsim_predictor_predicted_total"))

    @property
    def n_pad(self) -> int:                # padding rows dispatched
        return int(self._val("capsim_predictor_pad_rows_total"))

    @property
    def batch_shapes(self) -> Dict[int, int]:
        if self._obs is None:
            return {}
        return {int(labels["shape"]): int(v)
                for labels, v in self._obs.metrics.collect(
                    "capsim_predictor_batches_total",
                    instance=self._instance)}

    @property
    def n_batches(self) -> int:
        return sum(self.batch_shapes.values())

    @property
    def dispatch_seconds(self) -> float:
        if self._obs is None:
            return 0.0
        return self._obs.metrics.value(
            SPAN_SECONDS_TOTAL, span="predict.dispatch",
            instance=self._instance)

    @property
    def drain_seconds(self) -> float:
        if self._obs is None:
            return 0.0
        return self._obs.metrics.value(
            SPAN_SECONDS_TOTAL, span="predict.drain",
            instance=self._instance)

    @property
    def predict_seconds(self) -> float:
        return self.dispatch_seconds + self.drain_seconds


class BatchedPredictor:
    """Size-bucketed async batcher over a global clip pool.

    ``add`` buffers tokenized clips and dispatches a device batch whenever
    a full ``batch_size`` accumulates; dispatch is asynchronous, so the
    caller keeps tokenizing while the device computes.  At most
    ``max_in_flight`` batches stay un-retired (the double buffer) to bound
    host memory.  ``drain`` pads the remainder to the smallest size bucket
    with fully-masked zero rows, blocks on everything outstanding, and
    returns per-clip predictions in submission order.

    With ``rt_cache`` set, batches carry (n, l_clip) int32 RT-table
    indices instead of token tensors and dispatch through the
    block-encoder-only ``forward_cached`` step — feed them via
    ``add_indexed`` (trace engine) or plain ``add`` (tokenized requests
    are deduped through the cache first).  ``config.fused_serving``
    additionally dedupes each batch's context rows on the host and
    dispatches through ``forward_cached_fused`` over a per-table-version
    cross-K/V serving plan (tolerance-gated ≤1e-3 vs the unfused path).

    Construction is config-first: ``config`` (an ``EngineConfig``)
    supplies batch size, precision, mesh shape, context ablation and
    in-flight depth; ``rt_cache`` stays a direct object parameter (the
    cache is shared state owned by the caller, not a setting).  With a
    non-empty ``config.mesh_shape`` every device batch shard_maps over
    the data mesh: buckets stay multiples of the mesh size, so no shard
    is ever empty, and demuxed rows are bitwise the single-device rows.
    The pre-PR-6 loose keyword arguments (``batch_size=``,
    ``precision=``, ...) are retired: they raise ``TypeError`` pointing
    at the ``EngineConfig`` field to use.
    """

    def __init__(self, params, cfg, *, config: Optional[EngineConfig] = None,
                 rt_cache: Optional[RTCache] = None,
                 fault_injector=None,
                 obs: Optional[Observability] = None, **legacy):
        reject_legacy_kwargs(legacy, "BatchedPredictor")
        config = config or EngineConfig()
        self.config = config
        self.obs = (obs if obs is not None
                    else Observability.from_config(config.observability))
        m = self.obs.metrics
        self.instance = m.next_instance("predictor")
        self._c_clips = m.counter(
            "capsim_predictor_clips_total", "Real clips fed in.",
            ("instance",)).labels(instance=self.instance)
        self._c_predicted = m.counter(
            "capsim_predictor_predicted_total",
            "Real clips with a retired prediction.",
            ("instance",)).labels(instance=self.instance)
        self._c_pad = m.counter(
            "capsim_predictor_pad_rows_total",
            "Padding rows dispatched.",
            ("instance",)).labels(instance=self.instance)
        self._fam_batches = m.counter(
            "capsim_predictor_batches_total",
            "Device batches dispatched, by padded batch shape.",
            ("instance", "shape"))
        self._batch_handles: Dict[int, object] = {}
        self._g_in_flight = m.gauge(
            "capsim_predictor_in_flight",
            "Un-retired device batches (the double buffer).",
            ("instance",)).labels(instance=self.instance)
        if fault_injector is None and config.faults:
            # deferred import: repro.serving imports this module
            from repro.serving.faults import FaultInjector
            fault_injector = FaultInjector.from_config(config)
        self._faults = fault_injector
        self.params = params
        self.cfg = pred_mod.inference_config(cfg, config.precision)
        self.batch_size = config.batch_size
        self._shards = config.n_shards         # 0 = unsharded path
        self.buckets = bucket_sizes(config.batch_size,
                                    max(self._shards, 1))
        self.max_in_flight = config.max_in_flight
        use_context = config.use_context
        self._cache = rt_cache
        self._fused = config.fused_serving
        self._plan = None          # serving_plan for the current table
        self._plan_src: Optional[jax.Array] = None
        if self._fused and rt_cache is None:
            raise ValueError(
                "fused_serving requires an RTCache (the fused step IS "
                "the RT-gather + block encoder)")
        if rt_cache is not None:
            # the table is a pure function of (params, cfg numerics +
            # kernel); any mismatch silently breaks the bitwise contract
            assert rt_cache.params is params and rt_cache.cfg == self.cfg, \
                "RT cache must be built with the same params and " \
                "resolved config as the predict step"
            if self._fused:
                self._predict = (
                    predict_cached_fused_mesh_fn(self.cfg, self._shards)
                    if self._shards
                    else predict_cached_fused_fn(self.cfg))
            else:
                self._predict = (
                    predict_cached_mesh_fn(self.cfg, use_context,
                                           self._shards)
                    if self._shards
                    else predict_cached_fn(self.cfg, use_context))
        else:
            self._predict = (
                predict_mesh_fn(self.cfg, use_context, self._shards)
                if self._shards
                else predict_fn(self.cfg, use_context))
        self._tok: List[np.ndarray] = []      # token tensors OR rt_idx rows
        self._ctx: List[np.ndarray] = []
        self._mask: List[np.ndarray] = []
        self._ctx_width: Optional[int] = None  # pinned by the first add
        self._buffered = 0
        self._pending: Deque[Tuple[jax.Array, int]] = deque()
        self._retired: List[np.ndarray] = []
        self._drained = 0           # clips returned by previous drains
        self.stats = PredictorStats(self.obs, self.instance)

    def add(self, tok: np.ndarray, ctx: np.ndarray,
            mask: np.ndarray) -> None:
        """tok (n, l_clip, l_token) int32; ctx (n, M) int32;
        mask (n, l_clip) float32."""
        if tok.shape[0] == 0:
            return
        if self._cache is not None:
            self.add_indexed(self._cache.index_clips(tok), ctx, mask)
            return
        self._buffer(tok, ctx, mask)

    def add_indexed(self, rt_idx: np.ndarray, ctx: np.ndarray,
                    mask: np.ndarray) -> None:
        """RT-cache fast path: rt_idx (n, l_clip) int32 rows into the
        cache table (masked slots on the pad row); ctx/mask as ``add``."""
        assert self._cache is not None, "add_indexed needs an RT cache"
        if rt_idx.shape[0] == 0:
            return
        self._cache.record_served(int(mask.sum()))
        self._buffer(rt_idx, ctx, mask)

    def _buffer(self, tok: np.ndarray, ctx: np.ndarray,
                mask: np.ndarray) -> None:
        # dispatch-boundary width check: the pool concatenates context
        # rows across many programs/cores, so a mixed or unknown layout
        # must fail HERE with the producer on the stack, not as a shape
        # error inside a later np.concatenate or jit re-trace
        ctx_mod.validate_context_width(ctx.shape[1], "BatchedPredictor")
        if self._ctx_width is None:
            self._ctx_width = ctx.shape[1]
        elif ctx.shape[1] != self._ctx_width:
            raise ValueError(
                f"BatchedPredictor: context width {ctx.shape[1]} differs "
                f"from the pool's {self._ctx_width} — single-core, "
                "core-tagged, and peer-channel clips cannot share one "
                "batch pool")
        self._tok.append(tok)
        self._ctx.append(ctx)
        self._mask.append(mask)
        self._buffered += tok.shape[0]
        self._c_clips.inc(tok.shape[0])
        while self._buffered >= self.batch_size:
            tok_b, ctx_b, mask_b = self._take(self.batch_size)
            self._dispatch(tok_b, ctx_b, mask_b, self.batch_size)

    def _take(self, k: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Pop exactly k rows off the buffer head."""
        out = []
        for buf in (self._tok, self._ctx, self._mask):
            have, taken = 0, []
            while have < k:
                chunk = buf.pop(0)
                need = k - have
                if chunk.shape[0] > need:
                    taken.append(chunk[:need])
                    buf.insert(0, chunk[need:])
                    have = k
                else:
                    taken.append(chunk)
                    have += chunk.shape[0]
            out.append(taken[0] if len(taken) == 1
                       else np.concatenate(taken))
        self._buffered -= k
        return tuple(out)

    def reset_context_width(self) -> None:
        """Unpin the pool's context-width check between *independent*
        flushes (the pool must be empty).  A long-lived backend — the
        serving engine holds one for its whole lifetime now — calls this
        at each flush boundary so consecutive flushes may carry
        different (but internally consistent) context layouts."""
        assert self._buffered == 0, \
            "cannot reset context width with clips still buffered"
        self._ctx_width = None

    def _dispatch(self, tok, ctx, mask, n_real: int) -> None:
        # the dispatch span includes any blocking retires forced by the
        # in-flight cap — the same accounting window the pre-obs
        # dispatch_seconds stopwatch covered
        with self.obs.span("predict.dispatch", instance=self.instance):
            self._dispatch_inner(tok, ctx, mask, n_real)

    def _dispatch_inner(self, tok, ctx, mask, n_real: int) -> None:
        if self._faults is not None:
            # chaos layer: may stall (slow_flush) or raise (device_error)
            # exactly where a real device failure would surface
            self._faults.on_dispatch()
        if self._shards:
            # sharded dispatch contract: every device gets a non-empty,
            # equal shard (bucket_sizes keeps buckets aligned; a pool
            # smaller than the mesh was padded with masked zero rows)
            assert tok.shape[0] >= self._shards \
                and tok.shape[0] % self._shards == 0, \
                (tok.shape[0], self._shards)
        if self._fused:
            # host-side context dedup (~ms per batch): the fused step
            # attends over each row's unique tokens with multiplicity
            # weights instead of all M context rows
            with self.obs.span("predict.dedup", instance=self.instance):
                uniq, counts = std_mod.dedupe_context_tokens(ctx)
            batch = {"rt_idx": jnp.asarray(tok),
                     "ctx_uniq": jnp.asarray(uniq),
                     "ctx_count": jnp.asarray(counts),
                     "clip_mask": jnp.asarray(mask)}
            out = self._predict(self.params, self._serving_plan(), batch)
        elif self._cache is not None:
            batch = {"rt_idx": jnp.asarray(tok),
                     "context_tokens": jnp.asarray(ctx),
                     "clip_mask": jnp.asarray(mask)}
            out = self._predict(self.params, self._cache.table, batch)
        else:
            batch = {"clip_tokens": jnp.asarray(tok),
                     "context_tokens": jnp.asarray(ctx),
                     "clip_mask": jnp.asarray(mask)}
            out = self._predict(self.params, batch)   # async dispatch
        self._pending.append((out, n_real))
        shape = tok.shape[0]
        handle = self._batch_handles.get(shape)
        if handle is None:
            handle = self._fam_batches.labels(instance=self.instance,
                                              shape=shape)
            self._batch_handles[shape] = handle
        handle.inc()
        self._c_pad.inc(shape - n_real)
        while len(self._pending) > self.max_in_flight:
            self._retire()
        self._g_in_flight.set(len(self._pending))

    def _serving_plan(self):
        """Per-table-version cross K/V plan: rebuilt when (and only when)
        the cache table object changes — ``ensure_rows`` growth replaces
        the (immutable) array, and holding the strong reference in
        ``_plan_src`` makes the identity check GC-safe."""
        table = self._cache.table
        if self._plan is None or self._plan_src is not table:
            self._plan = serving_plan_fn(self.cfg)(self.params, table)
            self._plan_src = table
        return self._plan

    def _retire(self) -> None:
        with self.obs.span("predict.retire", instance=self.instance):
            out, n_real = self._pending.popleft()
            out = np.asarray(out)[:n_real]              # blocks this batch
            if self._faults is not None:
                # nan_output chaos: the retired batch comes back
                # non-finite; the service-level guard must catch it
                # before demux
                out = self._faults.corrupt_output(out)
            self._retired.append(out)
            self._c_predicted.inc(n_real)

    def drain(self) -> np.ndarray:
        """Flush the remainder, block on all outstanding batches, and
        return (n_clips,) float32 predictions in submission order."""
        with self.obs.span("predict.drain", instance=self.instance):
            return self._drain_inner()

    def _drain_inner(self) -> np.ndarray:
        if self._buffered:
            n = self._buffered
            tok, ctx, mask = self._take(n)
            bucket = min((b for b in self.buckets if b >= n),
                         default=self.batch_size)
            pad = bucket - n
            if pad:
                # zero rows, not repeats of the last real clip: repeated
                # real rows burn block-encoder FLOPs on phantom work.  A
                # zero token row is all-<PAD>; a zero rt_idx row is the
                # cache's pad slot; a zero mask excludes the row entirely.
                # On a mesh the bucket floor is max(8, n_shards), so a
                # pool smaller than the device count pads up to a full
                # (aligned) shard set instead of dispatching an empty
                # shard; the [:n_real] demux in _retire drops the pads.
                tok = np.concatenate(
                    [tok, np.zeros((pad,) + tok.shape[1:], tok.dtype)])
                ctx = np.concatenate(
                    [ctx, np.zeros((pad,) + ctx.shape[1:], ctx.dtype)])
                mask = np.concatenate(
                    [mask, np.zeros((pad,) + mask.shape[1:], mask.dtype)])
                assert not mask[n:].any(), \
                    "padded remainder rows must be fully masked"
            self._dispatch(tok, ctx, mask, n)
        while self._pending:
            self._retire()
        self._g_in_flight.set(0)
        preds = (np.concatenate(self._retired) if self._retired
                 else np.zeros(0, np.float32))
        # n_predicted accumulates over the backend's lifetime (many
        # flushes); each drain returns exactly the clips added since the
        # previous drain
        assert preds.shape[0] == self.stats.n_predicted - self._drained, \
            "demux must return exactly the real (non-pad) clips"
        self._drained = self.stats.n_predicted
        self._retired = []
        return preds


def _clip_rows(ctx_all: np.ndarray, n_clips: int) -> np.ndarray:
    """One context row per clip: the snapshot before the clip's first
    instruction (the last one for clips past the final snapshot)."""
    return ctx_all[np.minimum(np.arange(n_clips), len(ctx_all) - 1)]


@dataclasses.dataclass
class _Job:
    bench: object                     # Benchmark or (multicore) core label
    offset: int = 0                   # first clip index in the global pool
    n_clips: int = 0
    n_intervals: int = 0
    n_instructions: int = 0
    oracle_cycles: float = 0.0
    oracle_seconds: float = 0.0
    func_seconds: float = 0.0
    # multicore demux: (bench, core) clips land in per-checkpoint
    # segments interleaved across cores, so predictions accumulate
    # segment-by-segment instead of as one contiguous pool slice
    predicted_cycles: float = 0.0
    name: str = ""


@dataclasses.dataclass
class MulticoreSimResult:
    """One multicore benchmark's demuxed (benchmark, core) results.

    ``predicted_cycles`` / ``oracle_cycles`` are the across-core sums —
    total core-cycles of the N-core run; the per-core breakdown is in
    ``cores`` (entries named ``<bench>#c<k>``).
    """

    name: str
    n_cores: int
    cores: List[SimResult]

    @property
    def predicted_cycles(self) -> float:
        return sum(r.predicted_cycles for r in self.cores)

    @property
    def oracle_cycles(self) -> Optional[float]:
        if any(r.oracle_cycles is None for r in self.cores):
            return None
        return sum(r.oracle_cycles for r in self.cores)

    @property
    def n_clips(self) -> int:
        return sum(r.n_clips for r in self.cores)

    @property
    def n_instructions(self) -> int:
        return sum(r.n_instructions for r in self.cores)

    # --- PredictionReport aggregates (analytical-ML fusion path) ---

    @property
    def cycles_ci(self) -> Optional[Tuple[float, float]]:
        """Across-core CI: summed per-core bounds (conservative — the
        per-core draws are independent, so the true interval is
        narrower).  None unless every core ran the fusion path."""
        if any(r.cycles_ci is None for r in self.cores):
            return None
        return (sum(r.cycles_ci[0] for r in self.cores),
                sum(r.cycles_ci[1] for r in self.cores))

    @property
    def clips_predicted(self) -> int:
        return sum(r.clips_predicted for r in self.cores)

    @property
    def clips_extrapolated(self) -> int:
        return sum(r.clips_extrapolated for r in self.cores)


class SimulationEngine:
    """Queue of benchmarks -> functional sims -> one shared clip pool ->
    cached-jit bucketed inference -> demultiplexed ``SimResult``s.

    Construction is config-first: ``SimulationEngine.from_config(params,
    cfg, vocab, EngineConfig(...))`` (or the equivalent ``config=``
    keyword) is the single way every knob — trace scale, batching,
    precision, RT cache, multicore N and the device mesh — reaches the
    engine; ``capsim_simulate``/``capsim_simulate_multicore``, serving
    ``PredictorEngine`` and ``launch/serve.py`` are all thin wrappers
    over it.  A non-empty ``mesh_shape`` shards every predict dispatch
    AND every RT-cache encode pass across the data mesh, bitwise equal
    to the unsharded engine.  ``config.sampling`` switches runs to the
    analytical-ML fusion path: only a stratified sample of each
    benchmark's clips reaches the predictor and the rest extrapolate
    from analytical features with a bootstrap CI (``sampling=None``
    keeps the full-prediction path bitwise).  The pre-PR-6 loose
    keyword signature is retired: extra keywords raise ``TypeError``
    pointing at ``EngineConfig``.
    """

    def __init__(self, params, cfg, vocab: std_mod.Vocab,
                 config: Optional[EngineConfig] = None, *,
                 timing_params: Optional[timing.TimingParams] = None,
                 **legacy):
        reject_legacy_kwargs(legacy, "SimulationEngine")
        config = config or EngineConfig()
        self.config = config
        self.obs = Observability.from_config(config.observability)
        self.instance = self.obs.metrics.next_instance("engine")
        self._c_instructions = self.obs.metrics.counter(
            "capsim_frontend_instructions_total",
            "Instructions functionally simulated.",
            ("instance",)).labels(instance=self.instance)
        self._c_fe_clips = self.obs.metrics.counter(
            "capsim_frontend_clips_total",
            "Clips sliced/tokenized by the front-end.",
            ("instance",)).labels(instance=self.instance)
        self._c_peer_clips = self.obs.metrics.counter(
            "capsim_peer_context_clips_total",
            "Clips fed with the peer-channel context layout.",
            ("instance",)).labels(instance=self.instance)
        if config.precision == "int8":
            # per-channel weight fake-quantization at engine build: the
            # cache, plan and predict step all see the SAME quantized
            # tree, so the bitwise params-identity contract holds within
            # the engine (and the RT store keys on the quantized bytes)
            from repro.core import quant
            with self.obs.span("engine.quantize", instance=self.instance):
                params = quant.quantize_dequant_params(params)
        self.params = params
        self.cfg = pred_mod.inference_config(cfg, config.precision)
        self.vocab = vocab
        # mirror the config's trace-scale fields as attributes (the
        # pre-EngineConfig public surface; internal code reads these too)
        self.interval_size = config.interval_size
        self.warmup = config.warmup
        self.max_checkpoints = config.max_checkpoints
        self.l_min = config.l_min
        self.l_clip = config.l_clip
        self.l_token = config.l_token
        self.batch_size = config.batch_size
        self.use_context = config.use_context
        self.with_oracle = config.with_oracle
        self.timing_params = (timing_params if timing_params is not None
                              else timing.TimingParams())
        self.max_in_flight = config.max_in_flight
        # one fault injector per engine (None without config.faults): the
        # cache and every per-run BatchedPredictor share its RNG stream,
        # so a chaos run's injection schedule is one deterministic
        # sequence across the whole stack
        self._faults = None
        if config.faults:
            from repro.serving.faults import FaultInjector
            self._faults = FaultInjector.from_config(config)
        # one cache per engine: params are pinned at construction, so the
        # table never goes stale; new programs just append unseen rows.
        # The cache shares the engine's mesh: encode passes shard too.
        # With rt_store_dir the cache loads (or later persists) the
        # table under a (params, cfg, l_token, vocab) content key.
        self._rt_cache = (RTCache(self.params, self.cfg, config.l_token,
                                  n_shards=config.n_shards,
                                  store_dir=config.rt_store_dir,
                                  store_extra=vocab.signature(),
                                  fault_injector=self._faults,
                                  obs=self.obs)
                          if config.rt_cache else None)
        self._queue: List[progen.Benchmark] = []
        self.last_stats: Optional[PredictorStats] = None
        self.last_rt_stats = None
        self.frontend_stats = FrontendStats(self.obs, self.instance)

    @classmethod
    def from_config(cls, params, cfg, vocab: std_mod.Vocab,
                    config: Optional[EngineConfig] = None, *,
                    timing_params: Optional[timing.TimingParams] = None
                    ) -> "SimulationEngine":
        """Canonical constructor: every public entry point routes here."""
        return cls(params, cfg, vocab, config,
                   timing_params=timing_params)

    @property
    def rt_cache(self) -> Optional[RTCache]:
        """The engine's RT cache (None on the monolithic path)."""
        return self._rt_cache

    def submit(self, bench: progen.Benchmark) -> None:
        self._queue.append(bench)

    def submit_names(self, names: Sequence[str]) -> None:
        for name in names:
            self.submit(progen.build_benchmark(name))

    def _feed_trace(self, trace, token_table, static_ids,
                    pred: BatchedPredictor, job: _Job,
                    core_id: Optional[int] = None,
                    sink: Optional[list] = None,
                    peer_snapshots: Optional[np.ndarray] = None) -> int:
        """Tokenize + context one interval trace and enqueue its clips —
        the shared interval body of the single-core and multicore paths
        (``core_id=None`` keeps the single-core context layout bit for
        bit).  With ``peer_snapshots`` (core ``core_id``'s whole-machine
        captures from ``run_multicore(..., peer_snapshots=True)``) each
        clip gets the peer-channel layout, ``peer_context_tokens``, the
        same rows the multicore training build packs.  Returns the clip
        count enqueued.

        With ``sink`` (the fusion path) nothing reaches the predictor
        yet: clip tensors land in the sink together with their
        analytical feature rows, and the caller feeds only the
        stratified sample once the job's trace is complete."""
        n = len(trace)
        job.n_intervals += 1
        job.n_instructions += n
        self._c_instructions.inc(n)

        with self.obs.span("engine.tokenize", instance=self.instance):
            if static_ids is not None:
                tok, mask = std_mod.fixed_clip_indices(
                    static_ids, trace.pc, self.l_min, self.l_clip)
            else:
                tok, mask = std_mod.encode_fixed_clips(
                    token_table, trace.pc, self.l_min, self.l_clip)
            n_clips = tok.shape[0]             # slice_fixed partition

        with self.obs.span("engine.context", instance=self.instance):
            if peer_snapshots is None:
                ctx = _clip_rows(ctx_mod.context_tokens_from_matrix(
                    trace.snapshots, self.vocab, core_id=core_id), n_clips)
            else:
                with self.obs.span("engine.peer_context",
                                   instance=self.instance,
                                   args={"clips": n_clips}):
                    ctx = _clip_rows(ctx_mod.peer_context_tokens(
                        trace.snapshots, peer_snapshots, core_id,
                        self.vocab), n_clips)
                self._c_peer_clips.inc(n_clips)

        job.n_clips += n_clips
        self._c_fe_clips.inc(n_clips)
        if sink is not None:
            with self.obs.span("engine.analytical",
                               instance=self.instance):
                feats = analytical.clip_features(trace, self.l_min,
                                                 self.timing_params)
            assert feats.shape[0] == n_clips, \
                "analytical windows must mirror the clip partition"
            sink.append((tok, ctx, mask, feats))
        elif static_ids is not None:
            pred.add_indexed(tok, ctx, mask)
        else:
            pred.add(tok, ctx, mask)
        return n_clips

    def _feed_sample(self, pred: BatchedPredictor, sink: list,
                     job: _Job, job_key: int):
        """Stratify one job's collected clips, select the sample, and
        feed ONLY those rows to the predictor (preserving clip order,
        so cross-benchmark pipelining survives: the device crunches
        this job's sample while the next job's functional sim runs).

        Returns the per-job fusion plan ``(features, strata, sampled)``
        the post-drain demux hands to ``fuse_predictions``."""
        scfg = self.config.sampling
        if sink:
            tok = np.concatenate([s[0] for s in sink])
            ctx = np.concatenate([s[1] for s in sink])
            mask = np.concatenate([s[2] for s in sink])
            feats = np.concatenate([s[3] for s in sink])
        else:
            feats = np.zeros((0, analytical.N_FEATURES), np.float64)
        strata = analytical.stratify(feats, scfg.strata)
        sampled, _ = sampler_mod.stratified_sample(
            strata, scfg.fraction, scfg.min_clips_per_stratum,
            scfg.seed, key=job_key)
        if sampled.shape[0]:
            if self._rt_cache is not None:
                pred.add_indexed(tok[sampled], ctx[sampled],
                                 mask[sampled])
            else:
                pred.add(tok[sampled], ctx[sampled], mask[sampled])
        return feats, strata, sampled

    def _functional(self, bench: progen.Benchmark, pred: BatchedPredictor,
                    job: _Job, sink: Optional[list] = None) -> None:
        """Columnar functional sim + slice + tokenize one benchmark,
        feeding clips straight into the (asynchronously consuming)
        predictor.  Tokens/contexts are bitwise identical to the object
        path (``ClipEncoder`` over ``slice_fixed`` clips).  With
        ``sink`` the clips collect there instead (fusion path)."""
        cprog = bench.compiled()
        token_table = cprog.token_table(self.vocab, self.l_token)
        static_ids = None
        if self._rt_cache is not None:
            # one instruction-encoder pass over n_static rows serves every
            # dynamic clip of this benchmark (and dedupes across programs)
            static_ids = self._rt_cache.ensure_rows(
                token_table,
                keys=cprog.token_row_keys(self.vocab, self.l_token))
        st = progen.fresh_compiled_state(bench)
        with self.obs.span("engine.interpret", instance=self.instance):
            _, st = funcsim.run_compiled(cprog, self.warmup, st)
        n_ckp = min(bench.ckp_num, self.max_checkpoints)
        for _ in range(n_ckp):
            with self.obs.span("engine.interpret",
                               instance=self.instance):
                trace, st = funcsim.run_compiled(
                    cprog, self.interval_size, st,
                    snapshot_every=self.l_min)
            if not len(trace):
                break
            self._feed_trace(trace, token_table, static_ids, pred, job,
                             sink=sink)
            if self.with_oracle:
                with self.obs.span("engine.oracle",
                                   instance=self.instance) as osp:
                    job.oracle_cycles += timing.total_cycles_columnar(
                        trace, self.timing_params)
                job.oracle_seconds += osp.seconds

    def run(self, benches: Optional[Sequence[progen.Benchmark]] = None
            ) -> List[SimResult]:
        """Drain the queue (plus ``benches``) and return one ``SimResult``
        per benchmark, in submission order."""
        jobs = [_Job(b) for b in self._queue]
        self._queue = []
        if benches is not None:
            jobs.extend(_Job(b) for b in benches)
        if self.config.sampling is not None:
            return self._run_sampled(jobs)
        self.frontend_stats = FrontendStats(self.obs, self.instance)
        pred = BatchedPredictor(self.params, self.cfg, config=self.config,
                                rt_cache=self._rt_cache,
                                fault_injector=self._faults, obs=self.obs)
        rt_stats = (self._rt_cache.stats if self._rt_cache is not None
                    else RTCacheStats())
        offset = 0
        for job in jobs:
            job.offset = offset
            d0 = pred.stats.dispatch_seconds
            b0 = rt_stats.build_seconds
            with self.obs.span("engine.job", instance=self.instance,
                               args={"bench": job.bench.name}) as jsp:
                self._functional(job.bench, pred, job)
            # dispatch (and any blocking retire) and the RT-cache build
            # overlap the functional window; subtract both so device
            # predict time isn't counted twice
            job.func_seconds = (jsp.seconds - job.oracle_seconds
                                - (pred.stats.dispatch_seconds - d0)
                                - (rt_stats.build_seconds - b0))
            offset = job.offset + job.n_clips
        preds = pred.drain()
        if self._rt_cache is not None:
            self._rt_cache.persist()          # no-op without a store_dir
        self.last_stats = pred.stats
        self.last_rt_stats = (rt_stats.freeze()
                              if self._rt_cache is not None else None)
        assert preds.shape[0] == offset == pred.stats.n_predicted, \
            "clip accounting mismatch between pool and predictions"

        results = []
        total_clips = max(offset, 1)
        for job in jobs:
            mine = preds[job.offset:job.offset + job.n_clips]
            share = job.n_clips / total_clips
            results.append(SimResult(
                name=job.bench.name,
                n_intervals=job.n_intervals,
                n_instructions=job.n_instructions,
                n_clips=job.n_clips,
                predicted_cycles=float(mine.sum()),
                oracle_cycles=job.oracle_cycles if self.with_oracle
                else None,
                func_seconds=job.func_seconds,
                predict_seconds=pred.stats.predict_seconds * share,
                oracle_seconds=job.oracle_seconds if self.with_oracle
                else None))
        return results

    def simulate(self, bench: progen.Benchmark) -> SimResult:
        """Single-benchmark convenience path (``capsim_simulate``)."""
        return self.run([bench])[0]

    def _run_sampled(self, jobs: List[_Job]) -> List[SimResult]:
        """Fusion path of ``run()``: per benchmark, collect every clip's
        tensors + analytical features, stratify on the analytical cycle
        estimate, run ONLY the stratified sample through the predictor,
        then extrapolate the rest with a ridge residual fit and a
        bootstrap CI (``analytical.fuse_predictions``).

        At ``fraction=1.0`` every clip is "sampled" in original order,
        the fit never runs, and the total is the plain ``float(sum())``
        over the same prediction rows the unsampled path sums — bitwise
        equal by the batch-composition-independence contract."""
        scfg = self.config.sampling
        self.frontend_stats = FrontendStats(self.obs, self.instance)
        pred = BatchedPredictor(self.params, self.cfg, config=self.config,
                                rt_cache=self._rt_cache,
                                fault_injector=self._faults, obs=self.obs)
        rt_stats = (self._rt_cache.stats if self._rt_cache is not None
                    else RTCacheStats())
        plans = []                    # (features, strata, sampled) per job
        offset = 0
        for j, job in enumerate(jobs):
            sink: list = []
            d0 = pred.stats.dispatch_seconds
            b0 = rt_stats.build_seconds
            with self.obs.span("engine.job", instance=self.instance,
                               args={"bench": job.bench.name}) as jsp:
                self._functional(job.bench, pred, job, sink=sink)
                feats, strata, sampled = self._feed_sample(pred, sink,
                                                           job, j)
            job.func_seconds = (jsp.seconds - job.oracle_seconds
                                - (pred.stats.dispatch_seconds - d0)
                                - (rt_stats.build_seconds - b0))
            job.offset = offset
            offset += int(sampled.shape[0])
            plans.append((feats, strata, sampled))
        preds = pred.drain()
        if self._rt_cache is not None:
            self._rt_cache.persist()          # no-op without a store_dir
        self.last_stats = pred.stats
        self.last_rt_stats = (rt_stats.freeze()
                              if self._rt_cache is not None else None)
        assert preds.shape[0] == offset == pred.stats.n_predicted, \
            "clip accounting mismatch between sample and predictions"

        results = []
        total_sampled = max(offset, 1)
        for j, (job, (feats, strata, sampled)) in enumerate(
                zip(jobs, plans)):
            n_samp = int(sampled.shape[0])
            mine = preds[job.offset:job.offset + n_samp]
            rep = analytical.fuse_predictions(
                feats, strata, sampled, mine,
                bootstrap_resamples=scfg.bootstrap_resamples,
                seed=scfg.seed, key=j)
            share = n_samp / total_sampled
            results.append(SimResult(
                name=job.bench.name,
                n_intervals=job.n_intervals,
                n_instructions=job.n_instructions,
                n_clips=job.n_clips,
                predicted_cycles=rep.total_cycles,
                oracle_cycles=job.oracle_cycles if self.with_oracle
                else None,
                func_seconds=job.func_seconds,
                predict_seconds=pred.stats.predict_seconds * share,
                oracle_seconds=job.oracle_seconds if self.with_oracle
                else None,
                cycles_ci=rep.cycles_ci,
                clips_predicted=rep.clips_predicted,
                clips_extrapolated=rep.clips_extrapolated,
                clip_provenance=rep.clip_provenance))
        return results

    # ------------------------------ multicore ------------------------------ #

    def _multicore_interval(self, mb: multicore.MulticoreBenchmark,
                            cprogs, states, quantum: int
                            ) -> multicore.MulticoreTrace:
        """One interval of ``mb`` under the round-robin schedule; with
        ``config.peer_channels`` (and more than one core) the trace also
        carries the whole-machine captures the peer layout needs."""
        with self.obs.span("engine.interpret", instance=self.instance):
            return multicore.run_multicore(
                cprogs, self.interval_size, states,
                snapshot_every=self.l_min, quantum=quantum,
                peer_snapshots=self.config.peer_channels
                and mb.n_cores > 1)

    def run_multicore(self,
                      mbenches: Sequence[multicore.MulticoreBenchmark], *,
                      quantum: Optional[int] = None
                      ) -> List[MulticoreSimResult]:
        """Multicore path: interleaved per-core functional sims ->
        (benchmark, core) clip shards through the SAME pooled
        ``BatchedPredictor`` + shared ``RTCache`` -> demuxed per-core
        ``SimResult``s summed into per-benchmark cycles.

        Clips arrive in per-(core, checkpoint) segments interleaved
        across cores, so demux walks the recorded segment list; per-core
        predicted cycles accumulate one ``float(segment.sum())`` per
        checkpoint — the exact accumulation order the sequential
        reference path (``bench_speed.run_multicore_bench``) mirrors, so
        equality is bitwise, per core and summed.  Each core's context
        matrices carry its ``core_id`` channel
        (``context_tokens_from_matrix(..., core_id=c)``); with
        ``config.peer_channels`` and more than one core they are the
        peer-channel layout instead (M = n_cores x 369: the own block,
        then every other core's block as of the quantum's start).  The
        oracle is ``timing.simulate_multicore`` over the recorded commit
        interleave.
        """
        if quantum is None:
            quantum = (self.config.quantum
                       if self.config.quantum is not None
                       else multicore.DEFAULT_QUANTUM)
        if self.config.sampling is not None:
            return self._run_multicore_sampled(mbenches, quantum)
        self.frontend_stats = FrontendStats(self.obs, self.instance)
        pred = BatchedPredictor(self.params, self.cfg, config=self.config,
                                rt_cache=self._rt_cache,
                                fault_injector=self._faults, obs=self.obs)
        rt_stats = (self._rt_cache.stats if self._rt_cache is not None
                    else RTCacheStats())
        all_jobs: List[List[_Job]] = []
        segments: List[Tuple[_Job, int]] = []
        for mb in mbenches:
            cprogs = mb.compiled()
            token_tables = [cp.token_table(self.vocab, self.l_token)
                            for cp in cprogs]
            static_ids = None
            if self._rt_cache is not None:
                # all cores of one program share identical token tables
                # (immediates collapse to <CONST>), so rows dedupe to one
                # RT-table entry set across the whole benchmark
                static_ids = [
                    self._rt_cache.ensure_rows(
                        tt, keys=cp.token_row_keys(self.vocab,
                                                   self.l_token))
                    for cp, tt in zip(cprogs, token_tables)]
            jobs = [_Job(bench=mb, name=f"{mb.name}#c{c}")
                    for c in range(mb.n_cores)]
            all_jobs.append(jobs)
            states = mb.fresh_states()
            d0 = pred.stats.dispatch_seconds
            b0 = rt_stats.build_seconds
            oracle_s = 0.0
            with self.obs.span("engine.job", instance=self.instance,
                               args={"bench": mb.name}) as jsp:
                if self.warmup:
                    with self.obs.span("engine.interpret",
                                       instance=self.instance):
                        multicore.run_multicore(cprogs, self.warmup,
                                                states, quantum=quantum)
                n_ckp = min(mb.ckp_num, self.max_checkpoints)
                for _ in range(n_ckp):
                    mtrace = self._multicore_interval(mb, cprogs, states,
                                                      quantum)
                    if len(mtrace) == 0:
                        break
                    peers = (mtrace.peer_snapshots
                             or [None] * len(mtrace.cores))
                    for c, trace in enumerate(mtrace.cores):
                        if not len(trace):
                            continue
                        n_clips = self._feed_trace(
                            trace, token_tables[c],
                            static_ids[c] if static_ids is not None
                            else None,
                            pred, jobs[c], core_id=c,
                            peer_snapshots=peers[c])
                        segments.append((jobs[c], n_clips))
                    if self.with_oracle:
                        with self.obs.span("engine.oracle",
                                           instance=self.instance) as osp:
                            totals = timing.total_cycles_multicore(
                                mtrace.cores, mtrace.schedule,
                                self.timing_params)
                        dt = osp.seconds
                        oracle_s += dt
                        for c, cyc in enumerate(totals):
                            jobs[c].oracle_cycles += cyc
                            jobs[c].oracle_seconds += dt / mb.n_cores
            mb_seconds = (jsp.seconds - oracle_s
                          - (pred.stats.dispatch_seconds - d0)
                          - (rt_stats.build_seconds - b0))
            mb_clips = max(sum(j.n_clips for j in jobs), 1)
            for job in jobs:
                job.func_seconds = mb_seconds * (job.n_clips / mb_clips)

        preds = pred.drain()
        if self._rt_cache is not None:
            self._rt_cache.persist()          # no-op without a store_dir
        self.last_stats = pred.stats
        self.last_rt_stats = (rt_stats.freeze()
                              if self._rt_cache is not None else None)
        total = sum(n for _, n in segments)
        assert preds.shape[0] == total == pred.stats.n_predicted, \
            "clip accounting mismatch between shards and predictions"
        off = 0
        for job, n in segments:
            job.predicted_cycles += float(preds[off:off + n].sum())
            off += n

        results = []
        total_clips = max(total, 1)
        for mb, jobs in zip(mbenches, all_jobs):
            cores = [SimResult(
                name=job.name,
                n_intervals=job.n_intervals,
                n_instructions=job.n_instructions,
                n_clips=job.n_clips,
                predicted_cycles=job.predicted_cycles,
                oracle_cycles=job.oracle_cycles if self.with_oracle
                else None,
                func_seconds=job.func_seconds,
                predict_seconds=pred.stats.predict_seconds
                * (job.n_clips / total_clips),
                oracle_seconds=job.oracle_seconds if self.with_oracle
                else None) for job in jobs]
            results.append(MulticoreSimResult(
                name=mb.name, n_cores=mb.n_cores, cores=cores))
        return results

    def _run_multicore_sampled(
            self, mbenches: Sequence[multicore.MulticoreBenchmark],
            quantum: int) -> List[MulticoreSimResult]:
        """Fusion path of ``run_multicore()``: each core's clips (all
        checkpoints) collect in a per-core sink, then the per-core
        stratified sample feeds the pooled predictor in core order.
        One ``fuse_predictions`` per (benchmark, core) job; the job key
        counts flattened jobs so every core draws independently but
        reproducibly."""
        scfg = self.config.sampling
        self.frontend_stats = FrontendStats(self.obs, self.instance)
        pred = BatchedPredictor(self.params, self.cfg, config=self.config,
                                rt_cache=self._rt_cache,
                                fault_injector=self._faults, obs=self.obs)
        rt_stats = (self._rt_cache.stats if self._rt_cache is not None
                    else RTCacheStats())
        all_jobs: List[List[_Job]] = []
        plans = []                 # (job, features, strata, sampled)
        offset = 0
        key = 0
        for mb in mbenches:
            cprogs = mb.compiled()
            token_tables = [cp.token_table(self.vocab, self.l_token)
                            for cp in cprogs]
            static_ids = None
            if self._rt_cache is not None:
                static_ids = [
                    self._rt_cache.ensure_rows(
                        tt, keys=cp.token_row_keys(self.vocab,
                                                   self.l_token))
                    for cp, tt in zip(cprogs, token_tables)]
            jobs = [_Job(bench=mb, name=f"{mb.name}#c{c}")
                    for c in range(mb.n_cores)]
            all_jobs.append(jobs)
            sinks: List[list] = [[] for _ in range(mb.n_cores)]
            states = mb.fresh_states()
            d0 = pred.stats.dispatch_seconds
            b0 = rt_stats.build_seconds
            oracle_s = 0.0
            with self.obs.span("engine.job", instance=self.instance,
                               args={"bench": mb.name}) as jsp:
                if self.warmup:
                    with self.obs.span("engine.interpret",
                                       instance=self.instance):
                        multicore.run_multicore(cprogs, self.warmup,
                                                states, quantum=quantum)
                n_ckp = min(mb.ckp_num, self.max_checkpoints)
                for _ in range(n_ckp):
                    mtrace = self._multicore_interval(mb, cprogs, states,
                                                      quantum)
                    if len(mtrace) == 0:
                        break
                    peers = (mtrace.peer_snapshots
                             or [None] * len(mtrace.cores))
                    for c, trace in enumerate(mtrace.cores):
                        if not len(trace):
                            continue
                        self._feed_trace(
                            trace, token_tables[c],
                            static_ids[c] if static_ids is not None
                            else None,
                            pred, jobs[c], core_id=c, sink=sinks[c],
                            peer_snapshots=peers[c])
                    if self.with_oracle:
                        with self.obs.span("engine.oracle",
                                           instance=self.instance) as osp:
                            totals = timing.total_cycles_multicore(
                                mtrace.cores, mtrace.schedule,
                                self.timing_params)
                        dt = osp.seconds
                        oracle_s += dt
                        for c, cyc in enumerate(totals):
                            jobs[c].oracle_cycles += cyc
                            jobs[c].oracle_seconds += dt / mb.n_cores
                for c, job in enumerate(jobs):
                    feats, strata, sampled = self._feed_sample(
                        pred, sinks[c], job, key)
                    key += 1
                    job.offset = offset
                    offset += int(sampled.shape[0])
                    plans.append((job, feats, strata, sampled))
            mb_seconds = (jsp.seconds - oracle_s
                          - (pred.stats.dispatch_seconds - d0)
                          - (rt_stats.build_seconds - b0))
            mb_clips = max(sum(j.n_clips for j in jobs), 1)
            for job in jobs:
                job.func_seconds = mb_seconds * (job.n_clips / mb_clips)

        preds = pred.drain()
        if self._rt_cache is not None:
            self._rt_cache.persist()          # no-op without a store_dir
        self.last_stats = pred.stats
        self.last_rt_stats = (rt_stats.freeze()
                              if self._rt_cache is not None else None)
        assert preds.shape[0] == offset == pred.stats.n_predicted, \
            "clip accounting mismatch between sample and predictions"

        total_sampled = max(offset, 1)
        reports: Dict[int, Tuple[analytical.PredictionReport, int]] = {}
        for k, (job, feats, strata, sampled) in enumerate(plans):
            n_samp = int(sampled.shape[0])
            mine = preds[job.offset:job.offset + n_samp]
            rep = analytical.fuse_predictions(
                feats, strata, sampled, mine,
                bootstrap_resamples=scfg.bootstrap_resamples,
                seed=scfg.seed, key=k)
            reports[id(job)] = (rep, n_samp)

        results = []
        for mb, jobs in zip(mbenches, all_jobs):
            cores = []
            for job in jobs:
                rep, n_samp = reports[id(job)]
                cores.append(SimResult(
                    name=job.name,
                    n_intervals=job.n_intervals,
                    n_instructions=job.n_instructions,
                    n_clips=job.n_clips,
                    predicted_cycles=rep.total_cycles,
                    oracle_cycles=job.oracle_cycles if self.with_oracle
                    else None,
                    func_seconds=job.func_seconds,
                    predict_seconds=pred.stats.predict_seconds
                    * (n_samp / total_sampled),
                    oracle_seconds=job.oracle_seconds if self.with_oracle
                    else None,
                    cycles_ci=rep.cycles_ci,
                    clips_predicted=rep.clips_predicted,
                    clips_extrapolated=rep.clips_extrapolated,
                    clip_provenance=rep.clip_provenance))
            results.append(MulticoreSimResult(
                name=mb.name, n_cores=mb.n_cores, cores=cores))
        return results
