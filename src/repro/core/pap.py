"""Probability-Aware Point pruning (PAP) — paper §3.2 (contribution C2).

After softmax, attention probabilities over the ``N_l × N_p`` sampling
points of one (query, head) sum to 1 and are exponentially peaked; the paper
finds >80 % of them near zero in Deformable DETR and prunes those points,
skipping their grid-sampling and aggregation entirely.

Two executions:
  * ``threshold`` mode — paper-faithful: zero every probability below
    ``pap_threshold`` (exact removal semantics, since the contribution is
    ``prob · sampled_value``); the framework counts the pruned fraction and
    the saved gathers/FLOPs.
  * ``topk`` mode — the TPU-native static-shape realization: keep the
    ``K`` highest-probability points per (query, head) and gather *only*
    those (real gather-traffic and BI/aggregation reduction on SIMD
    hardware). Equals threshold mode whenever K covers all survivors.

Optionally renormalizes surviving probabilities (off by default — the paper
drops mass, it does not renormalize).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class PAPSelection(NamedTuple):
    probs: jnp.ndarray       # (B, Nq, H, K) surviving probabilities (zeros allowed)
    point_idx: jnp.ndarray   # (B, Nq, H, K) int32 index into the L*P point axis
    keep_frac: jnp.ndarray   # scalar — fraction of points kept (paper: ~16%)


def pap_threshold_select(probs: jnp.ndarray, threshold: float) -> PAPSelection:
    """Zero near-zero probabilities; keeps the full L*P axis (K = L*P)."""
    mask = probs > threshold
    kept = jnp.where(mask, probs, 0.0)
    lp = probs.shape[-1]
    idx = jnp.broadcast_to(jnp.arange(lp, dtype=jnp.int32), probs.shape)
    return PAPSelection(probs=kept, point_idx=idx,
                        keep_frac=jnp.mean(mask.astype(jnp.float32)))


def pap_topk_select(probs: jnp.ndarray, k: int,
                    threshold: float = 0.0) -> PAPSelection:
    """Keep the top-K points per (query, head); optional threshold on top."""
    top_p, top_i = jax.lax.top_k(probs, k)                      # (..., K)
    if threshold > 0.0:
        keep = top_p > threshold
        top_p = jnp.where(keep, top_p, 0.0)
        kept_frac = jnp.mean(keep.astype(jnp.float32)) * (k / probs.shape[-1])
    else:
        kept_frac = jnp.asarray(k / probs.shape[-1], dtype=jnp.float32)
    return PAPSelection(probs=top_p, point_idx=top_i.astype(jnp.int32),
                        keep_frac=kept_frac)


def keeps_point_order(mode: str) -> bool:
    """Whether ``pap_select`` in ``mode`` keeps the whole L*P point axis in
    its own order (``point_idx`` is ``arange(L*P)`` broadcast).

    A static fact of the mode: a caller may then index points by their
    position and skip the gather by ``point_idx``."""
    return mode in ("off", "threshold")


def pap_select(probs: jnp.ndarray, mode: str, *, threshold: float, k: int) -> PAPSelection:
    if mode == "off":
        lp = probs.shape[-1]
        idx = jnp.broadcast_to(jnp.arange(lp, dtype=jnp.int32), probs.shape)
        return PAPSelection(probs=probs, point_idx=idx,
                            keep_frac=jnp.asarray(1.0, jnp.float32))
    if mode == "threshold":
        return pap_threshold_select(probs, threshold)
    if mode == "topk":
        return pap_topk_select(probs, k, threshold=0.0)
    raise ValueError(f"unknown PAP mode {mode!r}")
