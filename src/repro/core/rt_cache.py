"""Static-instruction RT cache: the two-level inference split.

An instruction's ideal-execution-time vector RT_i (paper Eq 5-8) depends
only on its *static* standardized tokens — the same static/dynamic split
the columnar IR's ``token_table`` exploits one level down.  The monolithic
``forward`` nevertheless re-runs the 4-layer instruction encoder over all
B x L_clip dynamic rows of every batch.  This cache hoists that work out
of the per-clip loop:

  build   one device pass of ``encode_instructions`` over a program's
          ``n_static`` unique rows (orders of magnitude fewer than the
          dynamic rows a benchmark's trace expands them into),
  serve   every clip batch becomes an ``rt_table[rt_idx]`` gather inside
          the jit'd ``forward_cached`` — device FLOPs per clip drop from
          (instruction encoder + block encoder) to (block encoder only).

The cache is *content-addressed*: rows are keyed by their standardized
token bytes, so it is shared across programs (common instruction shapes
dedupe globally) and serves both the trace engine (whole token tables at
once) and the serving engine (arbitrary tokenized requests, deduped via
``index_clips``).  Row id 0 is reserved for the all-<PAD> row, so masked
clip slots gather a real encoder output and fp32 results stay bitwise
identical to the monolithic path (rows encode independently).

Invalidation: entries are pure functions of (params, cfg numerics, row
bytes).  The cache pins the params it was built with — build a fresh
``RTCache`` (or engine) when params change; new *programs* never
invalidate anything, their unseen rows are simply appended.

Persistence (``store_dir``): the (row bytes -> RT vector) table can be
checkpointed to disk via ``checkpoint/ckpt.py`` under a content key
hashing (params bytes, model config, l_token, extra — by convention the
vocab signature).  A fresh cache with a matching key adopts the stored
table byte-identically instead of re-encoding (a full-scale cold build is
~49 s); ANY key component changing — retrained params, different
numerics, new vocabulary — lands on a different store path, so stale rows
are structurally unservable.  A corrupt or truncated store warns and
falls back to the cold encode.
"""
from __future__ import annotations

import dataclasses
import hashlib
import warnings
from functools import lru_cache
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.core import predictor as pred_mod
from repro.obs import SPAN_SECONDS_TOTAL, Observability

PAD_ROW_ID = 0

# Bump when the persisted layout/semantics change: old stores then fail
# the metadata check and rebuild cold instead of being misread.
RT_STORE_VERSION = 1


def rt_store_key(params, cfg, l_token: Optional[int] = None,
                 extra: str = "") -> str:
    """Content key for the persistent RT store: a hash over the exact
    parameter bytes, the model config repr (numerics/dtype/attn choices
    included), the token-row width, and ``extra`` (the vocab signature by
    convention).  Equal keys => bitwise-equal tables."""
    h = hashlib.sha256()
    flat = ckpt._flatten(params)
    for key in sorted(flat):
        arr = np.asarray(flat[key])
        h.update(key.encode())
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    h.update(repr(cfg).encode())
    h.update(str(l_token).encode())
    h.update(extra.encode())
    return h.hexdigest()[:32]


@lru_cache(maxsize=64)
def rt_encode_fn(cfg):
    """Cached jit'd RT-table build pass: (N, L_token) rows -> (N, E)."""
    return jax.jit(lambda p, rows: pred_mod.encode_instructions(p, rows,
                                                                cfg))


@lru_cache(maxsize=64)
def rt_encode_mesh_fn(cfg, n_shards: int):
    """Sharded twin of ``rt_encode_fn``: the encode pass splits its row
    axis over an ``n_shards``-device data mesh, so a cold table build
    divides by mesh size.  Rows encode independently, so the assembled
    table is byte-identical to the single-device build."""
    from repro.launch.mesh import make_data_mesh
    return jax.jit(pred_mod.sharded_encode_instructions(
        cfg, make_data_mesh(n_shards)))


# XLA CPU matmul results are row-independent of the batch dimension only
# above ~32 rows — below that the backend may pick a different reduction
# order (measured: a d_model=64 encode at 8 or 16 rows differs ~2.6e-6
# from the same rows inside a >=32-row pass).  Keeping every encode pass
# AND every per-device shard of one at >= 32 rows keeps the whole build
# in one numerical equivalence class, so tables are bitwise reproducible
# across flush patterns and mesh sizes.
ENCODE_STABLE_MIN = 32


def encode_bucket(n: int, align: int = 1) -> int:
    """Pad target for an encode pass: next power of two >=
    max(n, ENCODE_STABLE_MIN), bounding compiled shapes to
    ~log2(n_static) variants while staying in the shape-stable kernel
    class.  ``align`` (the mesh shard count x ENCODE_STABLE_MIN) rounds
    the bucket up to a multiple so every device receives an equal-size,
    stable-class row shard."""
    b = ENCODE_STABLE_MIN
    while b < n:
        b *= 2
    if align > 1:
        b = (b + align - 1) // align * align
    return b


class _RTStatsDictMixin:
    @property
    def rows_avoided(self) -> int:
        """Dynamic instruction-encoder rows the gather replaced."""
        return max(self.n_rows_served - self.n_rows_encoded, 0)

    def as_dict(self) -> Dict[str, float]:
        return {"rt_rows_encoded": self.n_rows_encoded,
                "rt_encode_passes": self.n_encode_passes,
                "rt_rows_served": self.n_rows_served,
                "rt_rows_avoided": self.rows_avoided,
                "rt_lookups": self.n_lookups,
                "rt_build_seconds": self.build_seconds,
                "rt_rows_loaded": self.n_rows_loaded,
                "rt_store_load_seconds": self.store_load_seconds}


@dataclasses.dataclass(frozen=True)
class RTCacheStatsSnapshot(_RTStatsDictMixin):
    """Point-in-time copy of an :class:`RTCacheStats` view (what
    ``SimulationEngine.last_rt_stats`` hands out)."""

    n_rows_encoded: int = 0
    n_encode_passes: int = 0
    n_rows_served: int = 0
    n_lookups: int = 0
    build_seconds: float = 0.0
    n_rows_loaded: int = 0
    store_load_seconds: float = 0.0


class RTCacheStats(_RTStatsDictMixin):
    """Live *view* over the obs metrics registry for one cache instance.

    The cache writes counters/gauges/spans into ``repro.obs`` (that is
    the system of record — ``/metrics`` serves the same cells); this
    class keeps the historical attribute surface by reading them back.
    Constructed with no arguments it is an all-zeros stand-in (the
    engine's "no RT cache" placeholder).  ``freeze()`` returns an
    immutable :class:`RTCacheStatsSnapshot`.
    """

    def __init__(self, obs: Optional[Observability] = None,
                 instance: str = ""):
        self._obs = obs
        self._instance = instance

    def _val(self, name: str) -> float:
        if self._obs is None:
            return 0.0
        return self._obs.metrics.value(name, instance=self._instance)

    def _span_s(self, span: str) -> float:
        if self._obs is None:
            return 0.0
        return self._obs.metrics.value(SPAN_SECONDS_TOTAL, span=span,
                                       instance=self._instance)

    @property
    def n_rows_encoded(self) -> int:
        return int(self._val("capsim_rt_rows_encoded_total"))

    @property
    def n_encode_passes(self) -> int:
        return int(self._val("capsim_rt_encode_passes_total"))

    @property
    def n_rows_served(self) -> int:
        return int(self._val("capsim_rt_rows_served_total"))

    @property
    def n_lookups(self) -> int:
        return int(self._val("capsim_rt_lookups_total"))

    @property
    def build_seconds(self) -> float:
        return self._span_s("rt.build")

    @property
    def n_rows_loaded(self) -> int:
        return int(self._val("capsim_rt_rows_loaded"))

    @property
    def store_load_seconds(self) -> float:
        return self._span_s("rt.store_load")

    def freeze(self) -> RTCacheStatsSnapshot:
        return RTCacheStatsSnapshot(
            n_rows_encoded=self.n_rows_encoded,
            n_encode_passes=self.n_encode_passes,
            n_rows_served=self.n_rows_served,
            n_lookups=self.n_lookups,
            build_seconds=self.build_seconds,
            n_rows_loaded=self.n_rows_loaded,
            store_load_seconds=self.store_load_seconds)


class RTCache:
    """Content-addressed map from standardized token rows to rows of a
    device-resident RT table.

    ``ensure_rows`` returns global int32 row ids, encoding unseen rows in
    one bucketed device pass; ``table`` is the (capacity, E) device array
    ``forward_cached`` gathers from.  The table grows by doubling, so jit
    retraces stay bounded; in-flight batches keep referencing the
    (immutable) array version they were dispatched with.
    """

    def __init__(self, params, cfg, l_token: Optional[int] = None, *,
                 capacity: int = 4096, n_shards: int = 0,
                 store_dir: Optional[str] = None, store_extra: str = "",
                 fault_injector=None, obs: Optional[Observability] = None):
        self.params = params
        self.cfg = cfg
        self.l_token = l_token
        self.obs = obs if obs is not None else Observability()
        m = self.obs.metrics
        self.instance = m.next_instance("rt")
        self._c_encoded = m.counter(
            "capsim_rt_rows_encoded_total",
            "Unique static rows run through the instruction encoder.",
            ("instance",)).labels(instance=self.instance)
        self._c_passes = m.counter(
            "capsim_rt_encode_passes_total",
            "Device encode passes (one per new-row flush).",
            ("instance",)).labels(instance=self.instance)
        self._c_served = m.counter(
            "capsim_rt_rows_served_total",
            "Dynamic (unmasked) rows answered by the RT gather.",
            ("instance",)).labels(instance=self.instance)
        self._c_lookups = m.counter(
            "capsim_rt_lookups_total",
            "Rows presented to ensure_rows.",
            ("instance",)).labels(instance=self.instance)
        self._g_loaded = m.gauge(
            "capsim_rt_rows_loaded",
            "Rows adopted from the persistent store (0 after a failed "
            "load).", ("instance",)).labels(instance=self.instance)
        # chaos layer (repro.serving.faults.FaultInjector or None): may
        # corrupt store reads and crash persists on the REAL code paths
        self._faults = fault_injector
        # n_shards = 0: single-device encode passes (the default);
        # n_shards >= 1: encode passes shard their row axis over an
        # n-device data mesh (EngineConfig.mesh_shape) — byte-identical
        # table, build time divided by mesh size
        self.n_shards = n_shards
        self._encode = (rt_encode_mesh_fn(cfg, n_shards) if n_shards
                        else rt_encode_fn(cfg))
        self._index: Dict[bytes, int] = {}
        self._table: Optional[jax.Array] = None
        self._capacity = capacity
        self._n = 0
        self.stats = RTCacheStats(self.obs, self.instance)
        # persistent store: one ckpt directory per content key under
        # store_dir; loaded eagerly so a warm store never cold-encodes
        self._store_path: Optional[Path] = None
        self._persisted_rows = 0
        if store_dir is not None:
            self._store_key = rt_store_key(params, cfg, l_token,
                                           store_extra)
            self._store_path = Path(store_dir) / self._store_key
            self._load_store()

    @property
    def n_rows(self) -> int:
        return self._n

    @property
    def table(self) -> jax.Array:
        assert self._table is not None, "RT cache is empty (no rows ensured)"
        return self._table

    def ensure_rows(self, rows: np.ndarray,
                    keys: Optional[Sequence[bytes]] = None) -> np.ndarray:
        """rows: (k, L_token) int32 standardized rows -> (k,) int32 global
        RT row ids; unseen rows are encoded in one padded device pass.
        ``keys`` (the rows' ``tobytes()``, e.g. a program's memoized
        ``token_row_keys``) skips re-hashing."""
        with self.obs.span("rt.build", instance=self.instance):
            rows = np.ascontiguousarray(rows, dtype=np.int32)
            if self.l_token is None:
                self.l_token = rows.shape[1]
            assert (rows.ndim == 2
                    and rows.shape[1] == self.l_token), rows.shape
            if keys is None:
                keys = [r.tobytes() for r in rows]
            self._c_lookups.inc(rows.shape[0])

            new_rows: List[np.ndarray] = []
            pending: Dict[bytes, int] = {}
            if self._n == 0:                 # reserve the all-<PAD> row
                pad = np.zeros(self.l_token, np.int32)
                pending[pad.tobytes()] = PAD_ROW_ID
                new_rows.append(pad)
            ids = np.empty(rows.shape[0], np.int32)
            index = self._index
            for i, key in enumerate(keys):
                gid = index.get(key)
                if gid is None:
                    gid = pending.get(key)
                    if gid is None:
                        gid = self._n + len(new_rows)
                        pending[key] = gid
                        new_rows.append(rows[i])
                ids[i] = gid
            if new_rows:
                self._flush(np.stack(new_rows), pending)
        return ids

    def record_served(self, n: int) -> None:
        """Count dynamic rows the gather answered (called by the
        predictor's indexed dispatch path)."""
        self._c_served.inc(n)

    def index_clips(self, clip_tokens: np.ndarray) -> np.ndarray:
        """Serving-path adapter: (n, L_clip, L_token) tokenized clips ->
        (n, L_clip) int32 RT row ids.  Dynamic rows are deduped before the
        encoder sees them; all-<PAD> (masked) slots land on row 0."""
        from repro.core.standardize import dedupe_token_rows
        with self.obs.span("rt.index", instance=self.instance):
            n, L, T = clip_tokens.shape
            uniq, inv = dedupe_token_rows(clip_tokens.reshape(n * L, T))
            ids = self.ensure_rows(uniq)
            return ids[inv].reshape(n, L).astype(np.int32)

    def _flush(self, rows: np.ndarray, pending: Dict[bytes, int]) -> None:
        k = rows.shape[0]
        # sharded: every device must get >= ENCODE_STABLE_MIN rows so its
        # local pass stays in the same kernel class as the unsharded one
        align = (self.n_shards * ENCODE_STABLE_MIN if self.n_shards
                 else 1)
        bucket = encode_bucket(k, align)
        if bucket != k:
            rows = np.concatenate(
                [rows, np.zeros((bucket - k, self.l_token), np.int32)])
        rt = self._encode(self.params, jnp.asarray(rows))[:k]
        lo = self._n
        while lo + k > self._capacity:
            self._capacity *= 2
        if self._table is None or self._table.shape[0] < self._capacity:
            table = jnp.zeros((self._capacity, rt.shape[1]), rt.dtype)
            if self._table is not None and lo:
                table = table.at[:lo].set(self._table[:lo])
            self._table = table
        self._table = self._table.at[lo:lo + k].set(rt)
        # build time stays in stats; the wait includes any predict
        # batches already queued ahead of the encode pass on the device
        with self.obs.span("rt.wait", instance=self.instance):
            self._table.block_until_ready()
        self._index.update(pending)
        self._n += k
        self._c_encoded.inc(k)
        self._c_passes.inc()

    # ------------------------------------------------------------------ #
    # Persistent store
    # ------------------------------------------------------------------ #

    def _load_store(self) -> None:
        """Adopt the persisted (rows -> RT vectors) table if a store
        exists under this cache's content key.  Key/version mismatch is
        the *expected* invalidation path (silent clean rebuild); a store
        that matches the key but fails validation — truncated file,
        wrong shapes, non-finite values — warns and cold-encodes."""
        path = self._store_path
        with self.obs.span("rt.store_load", instance=self.instance):
            self._load_store_inner(path)

    def _load_store_inner(self, path: Optional[Path]) -> None:
        try:
            step = ckpt.latest_step(str(path))
            if step is None:
                return
            meta = ckpt.read_manifest(step, str(path)).get("metadata", {})
            if (meta.get("store_key") != self._store_key
                    or meta.get("version") != RT_STORE_VERSION):
                return                           # clean rebuild, no warn
            n, lt, e = (int(meta["n_rows"]), int(meta["l_token"]),
                        int(meta["d_model"]))
            if n < 1 or (self.l_token is not None and lt != self.l_token):
                return
            state = ckpt.restore(
                {"rows": np.zeros((n, lt), np.int32),
                 "table": np.zeros((n, e), np.float32)},
                step, str(path))
            if self._faults is not None:
                # corrupt_rt_read chaos: a read that returned garbage —
                # raising inside this try exercises the real warn +
                # cold-encode fallback below
                self._faults.maybe_raise(
                    "corrupt_rt_read", "injected corrupt RT-store read")
            rows = np.ascontiguousarray(state["rows"])
            table = np.asarray(state["table"])
            if rows.shape != (n, lt) or table.shape != (n, e):
                raise ValueError(
                    f"stored shapes {rows.shape}/{table.shape} != "
                    f"manifest ({n}, {lt})/({n}, {e})")
            if rows.dtype != np.int32:
                raise ValueError(f"stored rows dtype {rows.dtype}")
            if not np.isfinite(table).all():
                raise ValueError("stored table has non-finite values")
            if rows[0].any():
                raise ValueError("stored pad row (id 0) is not all-<PAD>")
            keys = [r.tobytes() for r in rows]
            if len(set(keys)) != n:
                raise ValueError("stored rows are not unique")
            self.l_token = lt
            while self._capacity < n:
                self._capacity *= 2
            self._table = jnp.zeros(
                (self._capacity, e), table.dtype).at[:n].set(
                    jnp.asarray(table))
            self._table.block_until_ready()
            self._index = {k: i for i, k in enumerate(keys)}
            self._n = n
            self._persisted_rows = n
            self._g_loaded.set(n)
        except Exception as exc:                     # noqa: BLE001
            warnings.warn(
                f"RT store at {path} unreadable ({exc!r}); "
                "falling back to cold encode", stacklevel=2)
            self._index = {}
            self._table = None
            self._n = 0
            self._persisted_rows = 0
            self._g_loaded.set(0)
            self.obs.event("rt_store_load_failure", path=str(path),
                           error=repr(exc))

    def persist(self) -> Optional[Path]:
        """Checkpoint the current table under the store key (atomic
        overwrite via ``ckpt.save``).  No-op without a store, on an empty
        cache, or when nothing grew since the last load/persist.  Rows
        are reconstructed from the index keys, so the persisted mapping
        is exactly what ``ensure_rows`` would serve."""
        if (self._store_path is None or self._n == 0
                or self._n == self._persisted_rows):
            return None
        rows = np.zeros((self._n, self.l_token), np.int32)
        for key, gid in self._index.items():
            rows[gid] = np.frombuffer(key, np.int32)
        table = np.asarray(self._table[:self._n])
        meta = {"store_key": self._store_key,
                "version": RT_STORE_VERSION,
                "n_rows": int(self._n),
                "l_token": int(self.l_token),
                "d_model": int(table.shape[1])}
        out = ckpt.save({"rows": rows, "table": table}, 0,
                        str(self._store_path), metadata=meta,
                        pre_publish=(self._faults.crash_hook()
                                     if self._faults is not None
                                     else None))
        self._persisted_rows = self._n
        return out
