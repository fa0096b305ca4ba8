"""Named MSDA execution backends.

Every backend implements one uniform contract:

    backend(plan: MSDAPlan,
            v: (B, N_rows, H, Dh),          # value table (maybe FWP-compacted)
            pts: SamplingPoints,            # (B, Nq, H, K) point geometry
            probs: (B, Nq, H, K),           # PAP-surviving probabilities
            cache=None,                     # MSDAValueCache when sampling a
                                            # prebuilt shared table
            ) -> (B, Nq, H, Dh)             # per-head aggregated samples

so new kernels (sharded, quantized, batched-serving) slot in with a
``@register_backend("name")`` and zero caller changes. Selection happens
once, in ``plan.make_plan`` — never inside the hot path. ``cache`` is
how build-once artifacts (e.g. the persistent decode path's pre-staged
table) reach the kernel without widening the positional contract;
backends that don't consume it ignore it.

  * ``jnp_gather``           — XLA flat-gather oracle path (any hardware).
  * ``pallas_fused``         — whole-table-in-VMEM fused MSGS+aggregation
                               kernel (C6); head-packed 128-lane dispatch
                               when the plan packs ``head_pack`` heads per
                               group.
  * ``pallas_windowed``      — multi-scale-parallel windowed kernel
                               (C3+C5+C7): ONE launch whose grid spans
                               (B x head-group x query-tile), staging
                               only each level's range-narrowed window
                               and accumulating all levels in-kernel.
                               Samples the FWP-compacted table directly
                               through the pix2slot indirection — never
                               densifies.
                               Needs raster-ordered encoder queries
                               (Nq == N_in) and range-narrowing — no
                               decode-shaped launch.
  * ``pallas_decode``        — persistent-cache decode kernel
                               (kernels/msgs_decode.py): samples the
                               shared cache's PRE-STAGED table (laid out
                               once per memory by ``build_value_cache``),
                               grid (B x head-group x query-tile x layer)
                               with the table block indexed by
                               (batch, head-group) only. Decode-shaped
                               launches only (N_q learned queries);
                               differentiable via custom_vjp.

(``pallas_windowed_loop``, the L² launch loop kept one release as the
single-launch kernel's numeric diff target, is retired: the parity matrix
now diffs ``pallas_windowed`` against the ``jnp_gather`` oracle directly.)
"""
from __future__ import annotations

from typing import Callable, Dict, List, NamedTuple

import jax.numpy as jnp

from repro.msda.sampling import SamplingPoints, corner_data, flat_gather_heads

BackendFn = Callable[..., jnp.ndarray]


class BackendInfo(NamedTuple):
    """Static registry metadata the planner (and benchmarks) consult:
    ``raster_only`` backends need raster-ordered encoder queries
    (Nq == N_in); ``decode_only`` backends need a decode-shaped plan
    (N_q learned queries). Neither set => any query geometry."""
    raster_only: bool = False
    decode_only: bool = False


_REGISTRY: Dict[str, BackendFn] = {}
_INFO: Dict[str, BackendInfo] = {}


def register_backend(name: str, *, raster_only: bool = False,
                     decode_only: bool = False):
    """Decorator: register fn under ``name`` in the backend registry."""
    def deco(fn: BackendFn) -> BackendFn:
        _REGISTRY[name] = fn
        _INFO[name] = BackendInfo(raster_only=raster_only,
                                  decode_only=decode_only)
        return fn
    return deco


def get_backend(name: str) -> BackendFn:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"no MSDA backend {name!r}; "
                       f"available: {available_backends()}") from None


def backend_info(name: str) -> BackendInfo:
    """Query-geometry metadata for a registered backend (default-neutral
    for probe backends registered without explicit flags)."""
    return _INFO.get(name, BackendInfo())


def available_backends() -> List[str]:
    return sorted(_REGISTRY)


def candidate_backends(*, decode_shaped: bool) -> List[str]:
    """Registered backends eligible for one query geometry — the
    autotuner's candidate set (and the planner's legal-choice universe):
    decode-shaped launches exclude ``raster_only`` backends, raster
    launches exclude ``decode_only`` ones."""
    out = []
    for name in available_backends():
        info = _INFO[name]
        if decode_shaped and info.raster_only:
            continue
        if not decode_shaped and info.decode_only:
            continue
        out.append(name)
    return out


# --------------------------------------------------------------------------
# jnp_gather — pure-XLA flat gather (runs anywhere, autodiff-friendly)
# --------------------------------------------------------------------------

@register_backend("jnp_gather")
def jnp_gather(plan, v: jnp.ndarray, pts: SamplingPoints,
               probs: jnp.ndarray, cache=None) -> jnp.ndarray:
    b, nq, h, k = probs.shape
    idx, wgt, valid = corner_data(pts.x_px, pts.y_px, pts.wl, pts.hl, pts.start)
    idx = idx.reshape(b, nq, h, k * 4)
    if pts.pix2slot is not None:
        # pixel -> compact-slot remap on the flat (b, nq, h, k*4) index:
        # hoisted out of the 5-D corner broadcast so the oracle path pays
        # one flat gather, not a broadcast remap plus a gather.
        bidx = jnp.arange(b).reshape(b, 1, 1, 1)
        idx = pts.pix2slot[bidx, idx]                    # pruned -> sentinel
    eff_w = wgt * valid.astype(wgt.dtype) * probs[..., None]
    g = flat_gather_heads(v, idx)
    scale = getattr(cache, "scale", None)
    if scale is not None:
        # int8 table: gather the codes, aggregate in compute dtype, and
        # dequantize ONCE after aggregation — exact because the scale is
        # shared across all rows of a channel.
        g = g.astype(probs.dtype)
    out = jnp.sum(g * eff_w.reshape(b, nq, h, k * 4)[..., None], axis=3)
    if scale is not None:
        out = out * scale.astype(out.dtype)       # (B,1,H,Dh) broadcasts
    return out


# --------------------------------------------------------------------------
# pallas_fused — whole value table staged in VMEM, optional head packing
# --------------------------------------------------------------------------

@register_backend("pallas_fused")
def pallas_fused(plan, v: jnp.ndarray, pts: SamplingPoints,
                 probs: jnp.ndarray, cache=None) -> jnp.ndarray:
    from repro.kernels import ops as kernel_ops
    h = v.shape[2]
    scale = getattr(cache, "scale", None)
    if plan.head_pack > 1 and h % plan.head_pack == 0:
        return kernel_ops.msgs_fused_packed(
            v, pts.x_px, pts.y_px, pts.start, pts.wl, pts.hl, probs,
            remap=pts.pix2slot, scale=scale, head_pack=plan.head_pack,
            block_q=plan.block_q)
    return kernel_ops.msgs_fused(
        v, pts.x_px, pts.y_px, pts.start, pts.wl, pts.hl, probs,
        remap=pts.pix2slot, scale=scale, block_q=plan.block_q)


# --------------------------------------------------------------------------
# pallas_windowed — multi-scale-parallel windowed single launch (C3+C5+C7)
# --------------------------------------------------------------------------

def _require_raster(plan, nq: int) -> None:
    assert nq == plan.n_in, (
        "windowed backends need raster-ordered encoder queries "
        f"(Nq={nq} != N_in={plan.n_in}); plan a different backend")
    assert plan.cfg.range_narrow is not None


@register_backend("pallas_windowed", raster_only=True)
def pallas_windowed(plan, v: jnp.ndarray, pts: SamplingPoints,
                    probs: jnp.ndarray, cache=None) -> jnp.ndarray:
    """One Pallas launch across all levels (multi-scale parallelism).

    The grid spans (B x head-group x query-tile); each tile stages all L
    levels' range-narrowed windows and accumulates every level's corners
    in-kernel, so level aggregation is fused instead of materialized as L
    HBM-sized accumulators. Under FWP-compact the window is a slot window
    of the compacted table addressed through ``pix2slot`` — the dense
    (B, N_in, H, Dh) table is never built. Each point's corners go to its
    own level's window, which keeps PAP-topk dynamic point-to-level
    assignment supported."""
    from repro.core import fwp as fwp_lib
    from repro.kernels import ops as kernel_ops
    cfg = plan.cfg
    b, nq, h, k = probs.shape
    _require_raster(plan, nq)

    g = plan.head_pack if (plan.lane_layout == "pack"
                           and h % plan.head_pack == 0) else 1
    caps = None
    if pts.pix2slot is not None:
        assert pts.keep_idx is not None, (
            "FWP-compact windowed execution needs the raster-ordered "
            "keep_idx (slot -> pixel map) threaded through SamplingPoints")
        caps = fwp_lib.level_capacities(plan.level_shapes, cfg.fwp_capacity)
    scale = getattr(cache, "scale", None)
    if scale is not None:
        # windowed kernel wants the scale per head-GROUP, matching its
        # (batch, head-group) grid axes: (B,1,H,Dh) -> (B, H/g, g, Dh)
        dh = v.shape[3]
        scale = scale.reshape(b, h // g, g, dh)
    return kernel_ops.msgs_windowed_msp(
        v, pts.x_px, pts.y_px, pts.lvl_of_pt,
        probs, remap=pts.pix2slot, keep_idx=pts.keep_idx, scale=scale,
        level_shapes=plan.level_shapes, ranges=cfg.range_narrow,
        tile_q=plan.tile_q, head_pack=g, caps=caps)


# --------------------------------------------------------------------------
# pallas_decode — persistent-cache decode kernel (table staged once/memory)
# --------------------------------------------------------------------------

@register_backend("pallas_decode", decode_only=True)
def pallas_decode(plan, v: jnp.ndarray, pts: SamplingPoints,
                  probs: jnp.ndarray, cache=None) -> jnp.ndarray:
    """Decode-shaped sampling against the ONCE-staged value table.

    The decoder's ``build_value_cache`` stages the table into the decode
    launch layout exactly when the plan's backend is this one
    (``MSDAValueCache.staged``); every layer's launch then consumes the
    staged block verbatim — one staging per (batch, head-group) per
    memory, never per layer (spy-tested). A caller without a prebuilt
    cache (parity harnesses, one-shot sampling) pays a per-call staging —
    the fallback keeps the contract uniform, and the staging spy's
    positive control counts exactly those restagings."""
    from repro.kernels import ops as kernel_ops
    staged = getattr(cache, "staged", None)
    if staged is None:
        staged = kernel_ops.stage_decode_table(
            v, pts.pix2slot, head_pack=plan.decode_head_pack,
            scale=getattr(cache, "scale", None))
    return kernel_ops.msgs_decode(
        staged, pts.x_px, pts.y_px, pts.start, pts.wl, pts.hl, probs,
        block_q=plan.block_q)
