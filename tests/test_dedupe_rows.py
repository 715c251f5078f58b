"""``dedupe_token_rows`` on packed integer keys against ``np.unique``.

The serving path (``RTCache.index_clips``) and ``data.dataset.indexed_clips``
rely on ``dedupe_token_rows`` returning exactly what
``np.unique(rows, axis=0, return_inverse=True)`` returns: unique rows in
lexicographic order (so an all-<PAD> row is local id 0) and the inverse
that rebuilds ``rows``.  The packed-key path must match it bit for bit,
dtypes and shapes included, whatever the width and token range.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import predictor
from repro.core.rt_cache import RTCache
from repro.core.standardize import build_vocab, dedupe_token_rows
from repro.isa import progen

WIDTHS = (1, 7, 16, 33)
RANGES = ((0, 3), (0, 512), (0, 2**31 - 1))


def _half_duplicated(rng, k, width, lo, hi):
    """``k`` rows of which about half repeat an earlier one."""
    base = rng.integers(lo, hi, (max(1, (k + 1) // 2), width),
                        dtype=np.int64).astype(np.int32)
    pick = np.concatenate([np.arange(base.shape[0]),
                           rng.integers(0, base.shape[0], k)])[:k]
    return base[rng.permutation(pick)]


def _rows(case, seed=0):
    rng = np.random.default_rng(seed)
    kind = case[0]
    if kind == "range":
        _, width, (lo, hi), k = case
        return _half_duplicated(rng, k, width, lo, hi)
    if kind == "negative":
        _, width = case
        return _half_duplicated(rng, 1_000, width, -2**31, 2**31 - 1)
    if kind == "all_equal":
        _, width = case
        return np.tile(rng.integers(0, 512, (1, width), dtype=np.int32),
                       (500, 1))
    if kind == "zero_row":
        _, width = case
        rows = _half_duplicated(rng, 1_000, width, 1, 512)
        rows[rng.integers(0, 1_000, 50)] = 0
        return rows
    raise ValueError(kind)


CASES = ([("range", w, r, k) for w in WIDTHS for r in RANGES
          for k in (0, 1, 1_000)]
         + [("negative", w) for w in WIDTHS]
         + [("all_equal", w) for w in (1, 16)]
         + [("zero_row", w) for w in (7, 16)])


def _case_id(case):
    return "-".join(f"{p[0]}..{p[1]}" if isinstance(p, tuple) else str(p)
                    for p in case)


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_dedupe_token_rows_equals_np_unique(case):
    rows = _rows(case)
    uniq, inv = dedupe_token_rows(rows)
    ref_uniq, ref_inv = np.unique(rows, axis=0, return_inverse=True)
    ref_inv = ref_inv.reshape(rows.shape[0]).astype(np.int32)
    assert uniq.dtype == np.int32 and inv.dtype == np.int32
    assert uniq.shape == ref_uniq.shape and inv.shape == ref_inv.shape
    assert uniq.flags.c_contiguous
    np.testing.assert_array_equal(uniq, ref_uniq)
    np.testing.assert_array_equal(inv, ref_inv)
    np.testing.assert_array_equal(uniq[inv], rows)
    if (rows == 0).all(axis=1).any():
        assert not uniq[0].any()
        assert (inv[(rows == 0).all(axis=1)] == 0).all()


def test_index_clips_ids_match_np_unique_path():
    """A 40 x 128 x 16 request through ``index_clips`` gets the RT row ids
    (and the cache the same rows, in the same order) that the
    ``np.unique(axis=0)`` dedupe gave a copy of the cache."""
    cfg = get_config("capsim").replace(d_model=32, head_dim=8, d_ff=64,
                                       dtype="float32")
    params = predictor.init_params(cfg, jax.random.PRNGKey(0))
    table = progen.build_benchmark("505.mcf").compiled().token_table(
        build_vocab(), 16)
    rng = np.random.RandomState(0)
    pc = rng.randint(0, table.shape[0], (40, 128))
    mask = rng.uniform(size=(40, 128)) < 0.8
    req = (table[pc] * mask[..., None]).astype(np.int32)  # masked slots PAD

    fast, slow = RTCache(params, cfg, 16), RTCache(params, cfg, 16)
    ids = fast.index_clips(req)
    u, i = np.unique(req.reshape(-1, 16), axis=0, return_inverse=True)
    ref = slow.ensure_rows(u)[i.reshape(-1)].reshape(40, 128)
    assert ids.dtype == np.int32 and ids.shape == (40, 128)
    np.testing.assert_array_equal(ids, ref)
    assert (ids[~mask] == 0).all()
    assert fast.n_rows == slow.n_rows
    np.testing.assert_array_equal(np.asarray(fast.table),
                                  np.asarray(slow.table))
