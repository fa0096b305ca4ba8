"""MSDAValueCache — build-once, sample-everywhere compacted value tables.

DEFA's architecture level wins not only by multi-scale parallelism but by
**feature-map reusing**: the same (pruned) value table is sampled by many
attention layers, so it should be projected, FWP-compacted, and staged
*once* and then reused. The cache is that staged table plus everything a
backend needs to sample it:

  * ``v``        — the projected, head-laid-out value table
                   (B, N_rows, H, Dh); under ``fwp_mode="compact"`` the
                   table is the compacted slot buffer + zero sentinel row;
  * ``pix2slot`` — the pixel -> compact-slot indirection (None when dense);
  * ``keep_idx`` — the raster-ordered slot -> pixel map the windowed
                   kernel searchsorts for its slot windows (None when dense);
  * ``slot_windows`` — static per-level slot-window extents (compact mode);
  * ``table_bytes`` — staged-bytes accounting per (batch, head-group):
                   the VMEM/HBM cost of staging this table ONCE, the unit
                   the decoder's build-once-vs-rebuild-per-layer comparison
                   is measured in.

Consumers: every encoder block builds its own cache (its memory changes
block to block — only the FWP *compaction* is reused, via the pipeline
state), while the decoder builds ONE cache from the encoder memory and
every decoder layer samples it (``repro/msda/decoder.py``). All backends
keep the existing ``(plan, v, pts, probs)`` contract — the cache simply
carries ``v`` and its geometry between the build and the samples.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import fwp as fwp_lib
from repro.core.quant import (maybe_fake_quant, maybe_fake_quant_with_scale,
                              quant_scale, quantize_table_rows,
                              table_quant_scale)


class MSDAValueCache(NamedTuple):
    """Projected (optionally FWP-compacted) value table + sampling geometry."""
    v: jnp.ndarray                      # (B, N_rows, H, Dh) staged table
    pix2slot: Optional[jnp.ndarray]     # (B, N_in) pixel -> slot (or None)
    keep_idx: Optional[jnp.ndarray]     # (B, cap) slot -> pixel, raster-ordered
    n_rows: int                         # static row count of ``v``
    slot_windows: Tuple[int, ...]       # static per-level slot windows
    #   (compact mode; () when dense) — what a windowed consumer may stage
    table_bytes: int                    # bytes staged per (batch, head-group)
    #   to build this table once: rows x lanes x itemsize (+ the int32
    #   pix2slot indirection in compact mode). This is the ACTUAL built
    #   table (dense when no FWP link exists yet); the static plan-side
    #   estimate that assumes compaction is ``MSDAPlan.cache_table_bytes``.
    #   Surfaced per block via the collect_stats "cache_table_bytes" entry.
    staged: Optional[object] = None     # DecodeStagedTable when the plan's
    #   backend is the persistent decode kernel: ``v`` re-laid-out ONCE
    #   per memory into the decode launch layout (kernels/msgs_decode.py);
    #   every consumer launch then reuses it — one staging per
    #   (batch, head-group) per memory, never per layer.
    scale: Optional[jnp.ndarray] = None  # (B, 1, H, Dh) f32 per-channel
    #   dequant scale when the plan stores the table as int8 codes
    #   (``plan.quantized_table``): ``v`` then holds the codes and every
    #   sampler dequantizes in-register AFTER the bilinear gather. The
    #   scale is shared across all rows of a channel, so it is frozen for
    #   the cache's lifetime — streaming row updates re-quantize against
    #   it (same grid as the surrounding table). None for float tables.


def project_values(params: dict, cfg, x_flat: jnp.ndarray,
                   fwp_state: Optional[fwp_lib.FWPState]):
    """FWP-pruned value projection V = X W^V.

    Returns (v (B, N_rows, H, Dh), pix2slot or None, n_rows)."""
    b = x_flat.shape[0]
    h, dh = cfg.n_heads, cfg.head_dim
    n_in = x_flat.shape[1]
    wq = lambda w: maybe_fake_quant(w, cfg.weight_bits)
    if fwp_state is not None and cfg.fwp_mode == "compact":
        cap = fwp_state.keep_idx.shape[1]
        x_kept = jnp.take_along_axis(x_flat, fwp_state.keep_idx[..., None], axis=1)
        v = jnp.einsum("bnd,dhk->bnhk", x_kept, wq(params["value_w"])) \
            + params["value_b"]
        v = jnp.concatenate([v, jnp.zeros((b, 1, h, dh), v.dtype)], axis=1)
        pix2slot = fwp_state.pix2slot                    # (B, N_in)
        n_rows = cap + 1
    elif fwp_state is not None and cfg.fwp_mode == "mask":
        xm = x_flat * fwp_state.keep_mask[..., None].astype(x_flat.dtype)
        v = jnp.einsum("bnd,dhk->bnhk", xm, wq(params["value_w"])) \
            + params["value_b"]
        # masked pixels must contribute EXACT zero (bias would leak):
        v = v * fwp_state.keep_mask[..., None, None].astype(v.dtype)
        pix2slot = None
        n_rows = n_in
    else:
        v = jnp.einsum("bnd,dhk->bnhk", x_flat, wq(params["value_w"])) \
            + params["value_b"]
        pix2slot = None
        n_rows = n_in
    return maybe_fake_quant(v, cfg.act_bits), pix2slot, n_rows


@jax.named_scope("msda/value")
def build_value_cache(params: dict, plan, x_flat: jnp.ndarray,
                      state=None) -> MSDAValueCache:
    """Build the shared value cache for one memory ``x_flat``.

    ``params`` needs only the value projection (``value_w``/``value_b``);
    ``state`` is the :class:`~repro.msda.pipeline.MSDAPipelineState` whose
    FWP chain link decides the compaction (None / no link => dense table).
    Called ONCE per memory; every sampler (encoder block body, all decoder
    layers) then consumes the result through
    :func:`repro.msda.attention.msda_attention_cached`."""
    # trace-time staging event on the process-wide registry: inside jit
    # this body runs once per compilation, so a flat counter after warmup
    # pins "no path is rebuilding/retracing the cache" globally —
    # complementing each engine's per-registry msda_compiles_total spy
    from repro.obs.metrics import default_registry
    default_registry().counter(
        "msda_cache_build_traces_total",
        "build_value_cache tracings/eager builds (process-wide)"
    ).inc(backend=plan.backend, table_dtype=plan.table_dtype)
    cfg = plan.cfg
    fwp_state = getattr(state, "fwp", None)
    v, pix2slot, n_rows = project_values(params, cfg, x_flat, fwp_state)
    keep_idx = fwp_state.keep_idx if pix2slot is not None else None

    scale = None
    if plan.quantized_table:
        # int8 end-to-end: the dense f32 table never exists past this
        # point — the cache stores codes + per-channel scale, and every
        # backend (gather / fused / decode / windowed) dequantizes
        # in-register after the bilinear corner gather. The sentinel row
        # is exact zero (code 0). Scale is per-channel over the rows
        # axis, so aggregation-then-dequant equals per-corner dequant.
        scale = table_quant_scale(v)
        v = quantize_table_rows(v, scale)

    table_bytes = plan.table_bytes_for_rows(
        n_rows, with_indirection=pix2slot is not None)
    slot_windows: Tuple[int, ...] = ()
    if pix2slot is not None:
        # geometry for windowed consumers of a compact cache (the raster
        # kernel derives its own via WindowGeometry; a decode-shaped
        # windowed kernel — ROADMAP — would stage these per level). The
        # bound excludes the zero sentinel row: it is addressable but
        # never part of a level's slot range.
        caps = fwp_lib.level_capacities(plan.level_shapes, cfg.fwp_capacity)
        slot_windows = tuple(min(int(c), n_rows - 1) for c in caps)

    staged = None
    if plan.backend == "pallas_decode":
        # The plan-keyed staging decision: lay the table out in the decode
        # launch layout ONCE, here, per memory — every consumer layer's
        # launch reuses the staged block (kernels/msgs_decode.py). Routed
        # through the module attribute so the staging-spy tests can count
        # stagings per memory.
        from repro.kernels import msgs_decode as msgs_decode_kernel
        staged = msgs_decode_kernel.stage_decode_table(
            v, pix2slot, head_pack=plan.decode_head_pack, scale=scale)
    return MSDAValueCache(v=v, pix2slot=pix2slot, keep_idx=keep_idx,
                          n_rows=n_rows, slot_windows=slot_windows,
                          table_bytes=table_bytes, staged=staged,
                          scale=scale)


# --------------------------------------------------------------------------
# Incremental (streaming) row updates — temporal feature-map reuse
# --------------------------------------------------------------------------

def cache_act_scale(cache: MSDAValueCache, cfg) -> Optional[jnp.ndarray]:
    """The frozen activation-quant scale of a built cache.

    ``project_values`` fake-quants the table per-tensor; the scale it
    used is recoverable from the built table (the max-magnitude element
    quantizes onto the grid's endpoint, so ``quant_scale`` of the staged
    values reproduces it up to float rounding). Streaming row updates
    re-quantize against THIS scale so partial updates stay on the same
    grid as the surrounding table (see ``fake_quant_with_scale``)."""
    if cfg.act_bits is None or cfg.act_bits <= 0:
        return None
    v = cache.v
    if cache.scale is not None:
        # int8 table: the act-quant grid lives in value space, not code
        # space — recover it from the dequantized view. The per-channel
        # amax survives quantization exactly (the amax element maps onto
        # the code grid's endpoint), so this reproduces the build scale.
        v = v.astype(cache.scale.dtype) * cache.scale
    return quant_scale(v, cfg.act_bits)


def project_cache_rows(params: dict, cfg, x_flat: jnp.ndarray,
                       pix_idx: jnp.ndarray,
                       keep_mask: Optional[jnp.ndarray] = None,
                       act_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Value-project a PIXEL-ROW subset of one memory.

    ``pix_idx`` (B, U) selects the pixels whose table rows are being
    refreshed (a changed tile's kept pixels); returns (B, U, H, Dh) rows
    computed exactly like the corresponding rows of a full
    :func:`project_values` build: same weight fake-quant, same bias,
    mask-mode zeroing via ``keep_mask``, and activation fake-quant
    against the FROZEN ``act_scale`` (partial updates must share the full
    build's quantization grid). jit-safe — every input is an array."""
    wq = lambda w: maybe_fake_quant(w, cfg.weight_bits)
    x_rows = jnp.take_along_axis(x_flat, pix_idx[..., None], axis=1)
    if keep_mask is not None:                        # fwp_mode == "mask"
        m_rows = jnp.take_along_axis(keep_mask, pix_idx, axis=1)
        x_rows = x_rows * m_rows[..., None].astype(x_rows.dtype)
    rows = jnp.einsum("bnd,dhk->bnhk", x_rows, wq(params["value_w"])) \
        + params["value_b"]
    if keep_mask is not None:
        rows = rows * m_rows[..., None, None].astype(rows.dtype)
    return maybe_fake_quant_with_scale(rows, cfg.act_bits, act_scale)


def scatter_table_rows(v: jnp.ndarray, slot_idx: jnp.ndarray,
                       rows: jnp.ndarray) -> jnp.ndarray:
    """Scatter (B, U, H, Dh) rows into the (B, N_rows, H, Dh) table.

    Dtypes must match exactly: an int8 table takes int8 CODES (quantized
    against the cache's frozen scale), never raw float rows — a silent
    cast here would scatter garbage onto the code grid."""
    if rows.dtype != v.dtype:
        raise TypeError(
            f"scatter_table_rows: rows dtype {rows.dtype} != table dtype "
            f"{v.dtype}; quantize rows against the cache's frozen scale "
            f"before scattering into an int8 table")
    bidx = jnp.arange(v.shape[0])[:, None]
    return v.at[bidx, slot_idx].set(rows)


def update_value_cache_rows(params: dict, plan, cache: MSDAValueCache,
                            x_flat: jnp.ndarray, slot_idx: jnp.ndarray,
                            act_scale: Optional[jnp.ndarray] = None,
                            keep_mask: Optional[jnp.ndarray] = None,
                            refresh: Optional[jnp.ndarray] = None,
                            ) -> Tuple[MSDAValueCache, int]:
    """In-place (functional) tile update of a built value cache.

    Re-projects the ``slot_idx`` (B, U) table rows from the NEW memory
    ``x_flat`` and scatters them into ``cache.v`` — and, when the plan
    staged the decode layout, into ``cache.staged`` via
    ``update_staged_rows`` — leaving the keep geometry (``pix2slot`` /
    ``keep_idx`` / ``slot_windows``) untouched: a tile update never
    changes WHICH pixels hold slots, only their values (keep transitions
    rebuild instead). Returns ``(cache', staged_bytes_delta)`` where the
    delta is the per-(batch, head-group) bytes this partial restage
    actually moved — ``U`` rows under the plan's lane layout, with NO
    pix2slot restage — the unit the streaming rebuild-vs-incremental
    comparison is measured in (vs ``cache.table_bytes`` for a full
    build). ``refresh`` (B, U) bool, when given, marks the rows to
    re-project; the others are written back unchanged, bit for bit."""
    cfg = plan.cfg
    u = slot_idx.shape[1]
    if cache.keep_idx is not None:                   # compact: slot -> pixel
        pix_idx = jnp.take_along_axis(cache.keep_idx, slot_idx, axis=1)
    else:                                            # dense/mask: slot == pixel
        pix_idx = slot_idx
    rows = project_cache_rows(params, cfg, x_flat, pix_idx,
                              keep_mask=keep_mask, act_scale=act_scale)
    if cache.scale is not None:
        # int8 end-to-end: re-quantize the refreshed rows against the
        # cache's FROZEN per-channel scale and scatter the codes — the
        # dense f32 table is never materialized mid-stream.
        rows = quantize_table_rows(rows, cache.scale)
    if refresh is not None:
        bidx = jnp.arange(cache.v.shape[0])[:, None]
        rows = jnp.where(refresh[..., None, None], rows,
                         cache.v[bidx, slot_idx])
    v = scatter_table_rows(cache.v, slot_idx, rows)
    staged = cache.staged
    if staged is not None:
        from repro.kernels import msgs_decode as msgs_decode_kernel
        staged = msgs_decode_kernel.update_staged_rows(staged, slot_idx, rows)
    delta_bytes = plan.table_bytes_for_rows(u, with_indirection=False)
    return cache._replace(v=v, staged=staged), delta_bytes
