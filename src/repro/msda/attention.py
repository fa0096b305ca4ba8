"""Planned MSDA execution — the algorithm level of the DEFA dataflow.

Two entry points, one seam:

  * :func:`msda_attention_cached` — the sample-everywhere half: PAP'd
    probabilities, masked sampling-point generation, backend-dispatched
    fused MSGS+aggregation, and (optionally) FWP frequency counting, all
    against a prebuilt :class:`~repro.msda.cache.MSDAValueCache`.
  * :func:`msda_attention` — the legacy monolithic block, now a thin
    build-cache-then-sample wrapper: it builds a fresh cache from
    ``x_flat`` and immediately samples it. Encoder blocks use this (their
    memory changes every block); decoder layers call the cached form
    against ONE shared cache (see ``repro/msda/decoder.py``).

The gather+aggregate step is a registry lookup — backends never leak into
this file.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import fwp as fwp_lib
from repro.core.quant import maybe_fake_quant
from repro.msda import backends as backend_registry
from repro.msda import ordering as ordering_lib
from repro.msda.cache import MSDAValueCache, build_value_cache, project_values
from repro.msda.pipeline import MSDAPipelineState
from repro.msda.plan import MSDAPlan
from repro.msda.sampling import corner_data, generate_points

__all__ = ["msda_attention", "msda_attention_cached", "project_values"]


def msda_attention_cached(
    params: dict,
    plan: MSDAPlan,
    query: jnp.ndarray,                 # (B, Nq, D)
    ref_points: jnp.ndarray,            # (B, Nq, 2) normalized
    cache: MSDAValueCache,              # prebuilt shared value table
    state: Optional[MSDAPipelineState] = None,
    *,
    collect_stats: bool = False,
    update_fwp: bool = True,
) -> Tuple[jnp.ndarray, MSDAPipelineState]:
    """One planned MSDA sampling pass against a prebuilt value cache.

    ``params`` needs the per-layer sampling weights (``attn_w``/``attn_b``,
    ``offs_w``/``offs_b``, ``out_w``/``out_b``) but NOT the value
    projection — that lives in the cache. ``update_fwp=False`` (decoder
    layers, any repeated sampling of one fixed memory) skips the frequency
    count and carries the existing FWP chain link through unchanged: the
    cache's compaction is fixed, so re-deriving a mask per layer would be
    wasted work. Returns (out (B, Nq, D), next state)."""
    cfg = plan.cfg
    b, nq, _ = query.shape
    if state is None:
        state = MSDAPipelineState.initial()
    wq = lambda w: maybe_fake_quant(w, cfg.weight_bits)

    with jax.named_scope("msda/points"):
        # ---- 0. cache-local query ordering (plan policy) --------------------
        # sort queries by reference point so each kernel tile touches a tight
        # slot window, run the whole pass permuted, and invert on the output.
        # Every per-query op below is row-independent, so the result is
        # BIT-IDENTICAL to the unordered pass (tests/test_msda_ordering.py).
        # Raster-only backends (pallas_windowed) derive their tile->window
        # geometry from raster query POSITION, so for them the permutation
        # stays off and ordering is accounting-only (plan.measured_tilewin).
        # Per-layer decoder calls re-derive the permutation here from each
        # layer's own (pre-refinement) reference points — refined refs shift
        # every layer, so no permutation is carried across layers.
        inv_perm = None
        raster_only = backend_registry.backend_info(plan.backend).raster_only
        if plan.query_order != "none" and not raster_only:
            perm, inv_perm = ordering_lib.query_permutation(
                ref_points, plan.level_shapes, plan.query_order)
            query = ordering_lib.permute_queries(query, perm)
            ref_points = ordering_lib.permute_queries(ref_points, perm)

        # ---- 1+2. PAP'd probabilities + masked point generation -------------
        # compact-table geometry rides along with the point geometry: the
        # windowed kernel locates slot windows by searchsorting keep_idx
        sel, pts = generate_points(params, cfg, query, ref_points,
                                   plan.level_shapes, pix2slot=cache.pix2slot,
                                   keep_idx=cache.keep_idx)

    with jax.named_scope("msda/sample"):
        # ---- 3. backend-dispatched fused MSGS + aggregation -----------------
        # the cache rides along as a kwarg: backends that consume build-once
        # artifacts (pallas_decode's pre-staged table) find them there,
        # everyone else ignores it
        backend = backend_registry.get_backend(plan.backend)
        out_h = backend(plan, cache.v, pts, sel.probs,
                        cache=cache).astype(query.dtype)

    with jax.named_scope("msda/out"):
        out = jnp.einsum("bnhk,hkd->bnd", out_h, wq(params["out_w"])) \
            + params["out_b"]
        if inv_perm is not None:
            out = ordering_lib.invert_queries(out, inv_perm)

    with jax.named_scope("msda/fwp"):
        # ---- 4. FWP frequency counting for the NEXT block -------------------
        need_freq = update_fwp and cfg.fwp_mode != "off"
        next_fwp = None if update_fwp else state.fwp
        stats = None
        if need_freq or collect_stats:
            # pruned points don't count
            pt_alive = (sel.probs > 0).astype(jnp.float32)
            # frequency is counted in ORIGINAL pixel space (pre-compaction)
            idx_orig, _, valid_orig = corner_data(pts.x_px, pts.y_px,
                                                  pts.wl, pts.hl, pts.start)
            counted = valid_orig.astype(jnp.float32) * pt_alive[..., None]
            freq = fwp_lib.count_frequency(
                idx_orig.reshape(b, -1), counted.reshape(b, -1), plan.n_in)
            if need_freq:
                next_fwp = fwp_lib.build_fwp_state(
                    freq, plan.level_shapes, k=cfg.fwp_k,
                    mode=cfg.fwp_mode, capacity=cfg.fwp_capacity)
            if collect_stats:
                stats = {
                    "freq": freq,
                    "pap_keep_frac": sel.keep_frac,
                    "point_alive_frac": jnp.mean(pt_alive),
                    "value_rows": cache.n_rows,
                    "cache_table_bytes": cache.table_bytes,
                }
                if update_fwp and next_fwp is not None:
                    stats["fwp_keep_frac"] = \
                        1.0 - fwp_lib.fwp_sparsity(next_fwp)
    return out, state.advance(next_fwp, stats)


def msda_attention(
    params: dict,
    plan: MSDAPlan,
    query: jnp.ndarray,                 # (B, Nq, D)
    ref_points: jnp.ndarray,            # (B, Nq, 2) normalized
    x_flat: jnp.ndarray,                # (B, N_in, D) raw fmap features
    state: Optional[MSDAPipelineState] = None,
    *,
    collect_stats: bool = False,
) -> Tuple[jnp.ndarray, MSDAPipelineState]:
    """One planned MSDA block: build the value cache, then sample it.

    Thin wrapper over :func:`~repro.msda.cache.build_value_cache` +
    :func:`msda_attention_cached` for callers whose memory changes every
    call (encoder blocks). Returns (out (B, Nq, D), next state)."""
    assert x_flat.shape[1] == plan.n_in, (x_flat.shape, plan.n_in)
    if state is None:
        state = MSDAPipelineState.initial()
    cache = build_value_cache(params, plan, x_flat, state)
    return msda_attention_cached(params, plan, query, ref_points, cache,
                                 state, collect_stats=collect_stats)
