"""The request a tenant sends the simulation service, and its check.

A *request* is one benchmark interval: the functional trace's clips
(tokenized), each with its context row and mask, whose predicted
runtimes the service sums.  ``validate_request`` checks a request's
whole payload at the submission boundary.  The served path that packs
many requests' clips into shared device batches is
``repro.serving.service.SimulationService``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.core import context as ctx_mod
from repro.core.engine_config import EngineConfig


@dataclasses.dataclass
class Request:
    request_id: int
    clip_tokens: np.ndarray           # (n, l_clip, l_token) int32
    # (n, M) int32 — M is one of the context.context_len layouts
    # (single-core / core-tagged / peer-channel)
    context_tokens: np.ndarray
    clip_mask: np.ndarray             # (n, l_clip) float32


def validate_request(req: Request, config: EngineConfig,
                     expect: Optional[tuple] = None) -> None:
    """Full submission-boundary payload check: ndims, dtypes, and
    internal shape consistency of every array (not just the context
    width).  ``expect=(l_clip, l_token)`` additionally pins the clip
    shape — the ``SimulationService`` pins it to its config, because
    one flush's clips concatenate into shared device batches.  Raises
    ``ValueError`` naming the request and the offending field — a
    malformed tenant payload must never surface as a downstream
    concatenate/jit shape error (or worse, a silently wrong gather)."""
    who = f"Request {req.request_id}"
    tok, ctx, mask = req.clip_tokens, req.context_tokens, req.clip_mask
    if tok.ndim != 3:
        raise ValueError(f"{who}: clip_tokens must be "
                         f"(n, l_clip, l_token), got shape {tok.shape}")
    n = tok.shape[0]
    if expect is not None and tok.shape[1:] != tuple(expect):
        raise ValueError(
            f"{who}: clip_tokens shape {tok.shape} does not match the "
            f"engine's (n, l_clip={expect[0]}, l_token={expect[1]})")
    if not np.issubdtype(tok.dtype, np.integer):
        raise ValueError(f"{who}: clip_tokens dtype {tok.dtype} is not "
                         f"an integer token dtype (expected int32)")
    if ctx.ndim != 2 or ctx.shape[0] != n:
        raise ValueError(
            f"{who}: context_tokens must be (n={n}, M), "
            f"got shape {ctx.shape}")
    if not np.issubdtype(ctx.dtype, np.integer):
        raise ValueError(f"{who}: context_tokens dtype {ctx.dtype} is "
                         f"not an integer token dtype (expected int32)")
    ctx_mod.validate_context_width(ctx.shape[1], who)
    if mask.shape != (n, tok.shape[1]):
        raise ValueError(
            f"{who}: clip_mask shape {mask.shape} does not match "
            f"clip_tokens' (n={n}, l_clip={tok.shape[1]})")
    if not np.issubdtype(mask.dtype, np.floating):
        raise ValueError(f"{who}: clip_mask dtype {mask.dtype} is not a "
                         f"float mask dtype (expected float32)")
