"""Sharded checkpointing: atomic save/restore/resume with a manifest.

Layout (one directory per step):

    <dir>/step_000120/
        manifest.json       tree structure, shapes, dtypes, step, metadata
        arr_00000.npy ...   one file per leaf (host-local shard)
    <dir>/LATEST            text file holding the newest complete step

Writes are atomic: arrays land in a writer-unique ``step_N.tmp*``
directory which is renamed only after the manifest is fsync'd, so a
killed writer can never leave a half-checkpoint that restore would pick
up — the crash-restart path in distributed/fault_tolerance.py and the
persistent RT-cache store (core/rt_cache.py) rely on this.  The
``LATEST`` pointer is published the same way (temp file + fsync +
``os.replace``), so a crash mid-write can never leave it truncated.
Concurrent writers racing one step are safe: tmp names embed pid + a
serial so they never collide, and the publish rename retries through
the delete/rename window — last writer wins with no corrupt final dir.

``pre_publish`` (chaos hook) runs right before the final rename — the
worst-case crash point; ``serving/faults.py`` uses it to prove the
previous checkpoint generation survives a mid-persist death.

On a multi-host pod each process saves only its addressable shards
(``host`` / ``n_hosts`` name the files disjointly) and restore re-shards
via device_put against the provided shardings; on this single-process CPU
host that degenerates to whole-array files, but the format is the same.

``CheckpointManager`` adds async saves (overlap serialization with the
next train steps — distributed-optimization trick #3 in DESIGN.md) and
keep-last-K garbage collection.
"""
from __future__ import annotations

import itertools
import json
import os
import re
import shutil
import threading
from pathlib import Path
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

_STEP_DIR = re.compile(r"step_(\d+)$")

# writer-unique tmp suffix serial: two saves in one process (or two
# engine threads sharing an RT store dir) never collide on a tmp path
_TMP_SERIAL = itertools.count()


def _completed_steps(ckpt_dir: Path):
    """Step numbers of *published* checkpoint dirs only — tmp dirs (any
    ``step_N.tmp*`` writer suffix) and stray files never match."""
    out = []
    for d in ckpt_dir.iterdir():
        m = _STEP_DIR.fullmatch(d.name)
        if m and d.is_dir():
            out.append(int(m.group(1)))
    return sorted(out)


def _write_latest(ckpt_dir: Path, step: int, host: int) -> None:
    """Atomic LATEST publish: a crash can truncate the temp file, never
    the pointer itself (the old truncate-then-write left a window where
    a killed writer orphaned every published step)."""
    tmp = ckpt_dir / f"LATEST.tmp{host}-{os.getpid()}-{next(_TMP_SERIAL)}"
    with open(tmp, "w") as f:
        f.write(str(step))
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, ckpt_dir / "LATEST")


def _flatten(tree) -> Dict[str, Any]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out[key] = leaf
    return out


def save(state, step: int, ckpt_dir: str, *, host: int = 0,
         n_hosts: int = 1, metadata: Optional[dict] = None,
         pre_publish: Optional[Callable[[], None]] = None) -> Path:
    ckpt_dir = Path(ckpt_dir)
    final = ckpt_dir / f"step_{step:08d}"
    tmp = ckpt_dir / (f"step_{step:08d}.tmp{host}"
                      f"-{os.getpid()}-{next(_TMP_SERIAL)}")
    tmp.mkdir(parents=True, exist_ok=True)

    try:
        flat = _flatten(state)
        entries = {}
        for i, (key, leaf) in enumerate(sorted(flat.items())):
            arr = np.asarray(leaf)
            fname = f"arr_{i:05d}.h{host}.npy"
            np.save(tmp / fname, arr)
            entries[key] = {"file": fname, "shape": list(arr.shape),
                            "dtype": str(arr.dtype)}
        manifest = {"step": step, "host": host, "n_hosts": n_hosts,
                    "entries": entries, "metadata": metadata or {}}
        mpath = tmp / f"manifest.h{host}.json"
        with open(mpath, "w") as f:
            json.dump(manifest, f, indent=1)
            f.flush()
            os.fsync(f.fileno())

        if pre_publish is not None:
            pre_publish()       # chaos hook: worst-case crash point

        # publish: replace any previous generation of this step.  Two
        # writers racing the same step can interleave rmtree/rename, so
        # retry through the window — last writer wins, and a loser never
        # leaves a half-deleted final dir (rmtree happens on OUR tmp's
        # turn only; the published dir is always a complete rename).  A
        # writer can lose the window once per rival, more often on a
        # loaded host, so the bound is far above any writer count.
        attempts = 64
        for attempt in range(attempts):
            if final.exists():
                shutil.rmtree(final, ignore_errors=True)
            try:
                tmp.rename(final)                        # atomic publish
                break
            except OSError:
                if attempt == attempts - 1:
                    raise
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    _write_latest(ckpt_dir, step, host)
    return final


def latest_step(ckpt_dir: str) -> Optional[int]:
    p = Path(ckpt_dir) / "LATEST"
    if not p.exists():
        return None
    step = int(p.read_text().strip())
    if not (Path(ckpt_dir) / f"step_{step:08d}").exists():
        # LATEST points at a GC'd/missing dir: fall back to scanning the
        # published step dirs (tmp dirs of any writer-suffix shape are
        # excluded by the regex, not by a fragile endswith list)
        steps = _completed_steps(Path(ckpt_dir))
        return steps[-1] if steps else None
    return step


def read_manifest(step: int, ckpt_dir: str, *, host: int = 0) -> dict:
    """Load a step's manifest (entries + metadata) without touching the
    array files — how the RT-cache store validates its content key before
    paying the restore."""
    final = Path(ckpt_dir) / f"step_{step:08d}"
    return json.loads((final / f"manifest.h{host}.json").read_text())


def restore(state_like, step: int, ckpt_dir: str, *, host: int = 0,
            shardings=None):
    """Rebuild the state tree from disk.  ``state_like`` provides the tree
    structure (concrete arrays or ShapeDtypeStructs); ``shardings`` (same
    tree shape, optional) re-shards each leaf via device_put."""
    final = Path(ckpt_dir) / f"step_{step:08d}"
    manifest = json.loads((final / f"manifest.h{host}.json").read_text())
    entries = manifest["entries"]
    flat_keys = sorted(_flatten(state_like))
    assert flat_keys == sorted(entries), (
        f"checkpoint tree mismatch: {set(flat_keys) ^ set(entries)}")

    sh_flat = _flatten(shardings) if shardings is not None else {}
    leaves = {}
    for key in flat_keys:
        arr = np.load(final / entries[key]["file"])
        if key in sh_flat:
            leaves[key] = jax.device_put(arr, sh_flat[key])
        else:
            leaves[key] = jax.numpy.asarray(arr)

    treedef = jax.tree_util.tree_structure(state_like)
    ordered = [leaves[k] for k in
               ("/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                         for p in path)
                for path, _ in jax.tree_util.tree_flatten_with_path(
                    state_like)[0])]
    return jax.tree_util.tree_unflatten(treedef, ordered)


class CheckpointManager:
    """Async, keep-last-K checkpointing for the training loop."""

    def __init__(self, ckpt_dir: str, *, keep: int = 3,
                 async_save: bool = True, host: int = 0, n_hosts: int = 1):
        self.dir = Path(ckpt_dir)
        self.keep = keep
        self.async_save = async_save
        self.host = host
        self.n_hosts = n_hosts
        self._thread: Optional[threading.Thread] = None

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def save(self, state, step: int, metadata: Optional[dict] = None):
        self.wait()                                     # one in flight
        # materialize on host *now* so training can mutate device state
        host_state = jax.tree_util.tree_map(np.asarray, state)

        def _do():
            save(host_state, step, str(self.dir), host=self.host,
                 n_hosts=self.n_hosts, metadata=metadata)
            self._gc()

        if self.async_save:
            self._thread = threading.Thread(target=_do, daemon=True)
            self._thread.start()
        else:
            _do()

    def _gc(self) -> None:
        steps = _completed_steps(self.dir)
        for s in steps[: -self.keep]:
            shutil.rmtree(self.dir / f"step_{s:08d}", ignore_errors=True)

    def restore_latest(self, state_like, shardings=None):
        self.wait()
        step = latest_step(str(self.dir))
        if step is None:
            return None, None
        return restore(state_like, step, str(self.dir), host=self.host,
                       shardings=shardings), step
