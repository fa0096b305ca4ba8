"""Pallas TPU kernels: windowed MSGS — fmap reuse via bounded ranges (C3+C7).

DEFA bounds sampling offsets per level (range-narrowing) so only a bounded
window of the fmap around a query tile's reference points can ever be
touched; neighbouring tiles' windows overlap and the overlap is reused
on-chip (paper Fig. 4).

``msgs_windowed_msp_pallas`` — the **multi-scale-parallel** kernel (paper
C5 at the launch level): ONE ``pallas_call`` whose grid spans

    (batch x head-group x query-tile)

with all L sampled levels served inside each grid step. At a tile's
first step the L range-narrowed level windows are copied by DMA into one
VMEM buffer, each at its own static extent, so the big level's window
never inflates the small levels' staging (a level axis in the grid would
force one uniform window extent on every level). The L levels' corners
accumulate into one register accumulator and the output block is
written once — cross-level aggregation is fused in-kernel instead of
materialized as L HBM-sized accumulators, and the co-resident level
windows are the VMEM analogue of DEFA's inter-level parallel PE groups.
The corner rows are computed outside the kernel and made window-local
(a corner outside its level's window contributes nothing); the kernel
loads them as in the fused kernel (``msgs_fused.accumulate_tile``).
The kernel is **FWP-compact-native**: when the value table is compacted,
each level window is a *slot* window of the compact table (slots are
raster-ordered per level, so a pixel window maps to one contiguous slot
range located by ``searchsorted(keep_idx, window_start)`` and bounded
statically by ``min(window_pixels, level_capacity)``), and corners are
routed to slots through ``pix2slot`` outside the kernel — the densified
(B, N_in, H, Dh) table is never built. The window starts ride in as a
scalar-prefetch argument that addresses the DMAs.

(The first generation — ``msgs_windowed_pallas``, one launch per
(query-level x sampled-level) pair — served its one release as the
``pallas_windowed_loop`` numeric diff target and is deleted; the parity
suite now diffs the multi-scale-parallel kernel against the ``jnp_gather``
oracle directly.)

The per-tile windows above derive from raster query POSITION (tile t
covers queries [t*tile, (t+1)*tile) of the raster encoder order), which
is why the backend registers ``raster_only=True``: cache-local query
ordering (``repro/msda/ordering.py``) must not permute the queries fed
to this kernel, and the attention pass gates it to the identity path.
The ordering layer's measured per-tile accounting
(``plan.with_measured_tile_window``) uses the same window geometry to
size what a permutation-aware decode tile would stage.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.msgs_fused import (_pad_axis, accumulate_tile,
                                     corner_operands, fold_rows, query_tile,
                                     stage_words, unplane, vmem_limit,
                                     word_planes)


# ==========================================================================
# Static window geometry for the multi-scale-parallel kernel
# ==========================================================================

class WindowGeometry(NamedTuple):
    """Static (numpy) per-(tile, sampled-level) window plan.

    Tiles partition the *padded* raster query axis level by level (tiles
    never straddle a query-level boundary, so every tile has one static
    reference-row span). All arrays are host-side numpy: the geometry is
    resolved once per (level_shapes, ranges, tile_q) and closed over by
    the jit'd kernel wrapper."""
    level_shapes: Tuple[Tuple[int, int], ...]
    level_starts: Tuple[int, ...]     # flat start of each level
    tile_q: int                       # uniform query-tile size
    n_tiles: int                      # total tiles across query levels
    nq_padded: int                    # tile_q * n_tiles
    pad_offsets: Tuple[int, ...]      # per query level: start in padded axis
    tile_qlevel: np.ndarray           # (T,) query level of each tile
    pix_lo: np.ndarray                # (T, L) natural flat-pixel window start
    win_pix: np.ndarray               # (T, L) pixel-window size (rows * w_l)
    w_pix_levels: Tuple[int, ...]     # per sampled level: staged pixel
    #   window (max over tiles) — the static BlockSpec extent of level l
    pstart: np.ndarray                # (T, L) pix_lo clipped per level so a
    #   w_pix_levels[l] window always stays inside the flat table
    n_in: int

    def slot_windows(self, caps: Sequence[int]) -> Tuple[int, ...]:
        """Per-level compact-table slot windows: a pixel window of
        ``w_pix_levels[l]`` pixels holds at most ``min(that, cap_l)``
        slots (slots are raster-ordered per level)."""
        return tuple(min(w, int(c))
                     for w, c in zip(self.w_pix_levels, caps))

    def staged_bytes(self, lanes: int, itemsize: int,
                     caps: Optional[Sequence[int]] = None) -> int:
        """Value-window VMEM staged per grid step (all L level windows
        are co-resident). With ``caps`` (FWP-compact): the slot windows
        of the compacted table plus the int32 ``pix2slot`` slices. The
        single source of truth for plan accounting and benchmarks."""
        if caps is None:
            return sum(self.w_pix_levels) * lanes * itemsize
        return (sum(self.slot_windows(caps)) * lanes * itemsize
                + sum(self.w_pix_levels) * 4)


@functools.lru_cache(maxsize=64)
def window_geometry(level_shapes: Tuple[Tuple[int, int], ...],
                    ranges: Tuple[float, ...],
                    tile_q: int) -> WindowGeometry:
    """Resolve the static window plan.

    For tile t (query level ql, reference rows [qr0, qr1]) sampling level
    sl, the touched rows are bounded by the pixel-centre reference mapping
    y = (r + 0.5) / h_ql * h_sl - 0.5 plus the range-narrowing bound
    R_sl, one bilinear-corner row, and one row of quantization margin.

    Note the static extents are maxima over ALL tiles: a coarse query
    level's tile spans many of its rows, so its references cover most of
    the image and its fine-level windows approach the whole level. The
    fine (large) query levels hold the vast majority of tiles and keep
    tight windows; under FWP-compact every extent is additionally
    capacity-bounded via :meth:`WindowGeometry.slot_windows`."""
    starts = np.concatenate(
        [[0], np.cumsum([h * w for h, w in level_shapes])[:-1]]).astype(np.int64)
    n_in = int(sum(h * w for h, w in level_shapes))
    n_l = len(level_shapes)

    tiles = []                       # (ql, first query row, last query row)
    pad_offsets = []
    off = 0
    for ql, (h, w) in enumerate(level_shapes):
        pad_offsets.append(off)
        n = h * w
        for i in range(0, n, tile_q):
            qr0 = i // w
            qr1 = (min(i + tile_q, n) - 1) // w
            tiles.append((ql, qr0, qr1))
        off += tile_q * math.ceil(n / tile_q)
    n_tiles = len(tiles)

    pix_lo = np.zeros((n_tiles, n_l), np.int64)
    win_pix = np.zeros((n_tiles, n_l), np.int64)
    for t, (ql, qr0, qr1) in enumerate(tiles):
        h_ql = level_shapes[ql][0]
        for sl, (h_sl, w_sl) in enumerate(level_shapes):
            r_bound = float(ranges[sl])
            ymin = (qr0 + 0.5) / h_ql * h_sl - 0.5 - r_bound - 1.0
            ymax = (qr1 + 0.5) / h_ql * h_sl - 0.5 + r_bound + 1.0
            r0 = max(0, int(math.floor(ymin)))
            r1 = min(h_sl - 1, int(math.floor(ymax)) + 1)
            pix_lo[t, sl] = starts[sl] + r0 * w_sl
            win_pix[t, sl] = (r1 - r0 + 1) * w_sl
    w_pix_levels = tuple(int(w) for w in win_pix.max(axis=0))
    pstart = np.stack(
        [np.clip(pix_lo[:, l], 0, n_in - w_pix_levels[l])
         for l in range(n_l)], axis=1)
    return WindowGeometry(
        level_shapes=level_shapes, level_starts=tuple(int(s) for s in starts),
        tile_q=tile_q, n_tiles=n_tiles,
        nq_padded=tile_q * n_tiles, pad_offsets=tuple(pad_offsets),
        tile_qlevel=np.asarray([t[0] for t in tiles], np.int64),
        pix_lo=pix_lo, win_pix=win_pix, w_pix_levels=w_pix_levels,
        pstart=pstart.astype(np.int32), n_in=n_in)


def repack_queries(geo: WindowGeometry, arr: jnp.ndarray,
                   fill=0) -> jnp.ndarray:
    """Re-lay a raster-ordered (B, Nq, ...) per-query array into the
    tile-packed padded layout (B, nq_padded, ...)."""
    parts = []
    for ql, (h, w) in enumerate(geo.level_shapes):
        n = h * w
        seg = arr[:, geo.level_starts[ql]:geo.level_starts[ql] + n]
        pad = geo.tile_q * math.ceil(n / geo.tile_q) - n
        if pad:
            widths = [(0, 0), (0, pad)] + [(0, 0)] * (arr.ndim - 2)
            seg = jnp.pad(seg, widths, constant_values=fill)
        parts.append(seg)
    return jnp.concatenate(parts, axis=1)


def unpack_queries(geo: WindowGeometry, arr: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`repack_queries` (drops the per-level padding)."""
    parts = []
    for ql, (h, w) in enumerate(geo.level_shapes):
        off = geo.pad_offsets[ql]
        parts.append(arr[:, off:off + h * w])
    return jnp.concatenate(parts, axis=1)


# ==========================================================================
# Multi-scale-parallel windowed kernel (single launch, fused aggregation)
# ==========================================================================

def _window_plan(geo: WindowGeometry, w_rows_v: Tuple[int, ...],
                 fold: int) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Per-level staged window extents and their offsets in the one VMEM
    window buffer, both in folded rows (:func:`fold_rows`) and multiples
    of 8: a window starts on an 8-row tile boundary at or below its first
    row, so it holds up to ``8·fold - 1`` rows of lead-in on top of the
    ``w_rows_v[l]`` rows it must cover."""
    ext = tuple(-(-(8 + -(-w // fold)) // 8) * 8 for w in w_rows_v)
    off = tuple(int(o) for o in np.cumsum((0,) + ext[:-1]))
    return ext, off


@functools.partial(jax.jit, static_argnames=(
    "level_shapes", "ranges", "tile_q", "head_pack", "caps", "interpret"))
def msgs_windowed_msp_pallas(
    v: jnp.ndarray,          # (B, N_rows, H, Dh) value table (maybe compact)
    x_px: jnp.ndarray,       # (B, Nq, H, K) absolute pixel x in own level
    y_px: jnp.ndarray,       # (B, Nq, H, K)
    lvl_of_pt: jnp.ndarray,  # (B, Nq, H, K) int32 level index per point
    probs: jnp.ndarray,      # (B, Nq, H, K)
    remap: Optional[jnp.ndarray] = None,      # (B, N_in) pix -> slot
    keep_idx: Optional[jnp.ndarray] = None,   # (B, cap) slot -> pix, sorted
    scale: Optional[jnp.ndarray] = None,      # (B, n_groups, G, Dh) f32
    *,
    level_shapes: Tuple[Tuple[int, int], ...],
    ranges: Tuple[float, ...],               # per-level |offset| bound (px)
    tile_q: int = 128,
    head_pack: int = 1,
    caps: Optional[Tuple[int, ...]] = None,  # compact per-level capacities
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-launch multi-scale-parallel windowed MSGS + fused aggregation.

    Grid (B, H/G, tile, sub-tile). At the first sub-tile of a tile the L
    level windows of the (batch, head-group) table are copied by DMA into
    one VMEM buffer, where they stay for the tile's sub-tiles; the corner
    loads then run as in the fused kernel (:func:`accumulate_tile`), with
    corner rows made window-local outside the kernel. A corner outside its
    level's window contributes nothing.

    Queries must be raster-ordered encoder queries (Nq == N_in). Returns
    (B, Nq, H, Dh). ``remap``/``keep_idx``/``caps`` together enable the
    FWP-compact-native path (v is the compacted table + sentinel row)."""
    b, n_rows, h, dh = v.shape
    nq = x_px.shape[1]
    k = x_px.shape[-1]
    use_remap = remap is not None
    assert h % head_pack == 0, (h, head_pack)
    g = head_pack
    ng = h // g
    m = 4 * k

    geo = window_geometry(level_shapes, ranges, tile_q)
    assert nq == geo.n_in, (nq, geo.n_in)
    n_l = len(level_shapes)
    n_t = geo.n_tiles

    if use_remap:
        # Window of the COMPACT table: first slot at-or-after the pixel
        # window start (slots are raster-ordered per level), clipped so
        # the static per-level slot window always fits the table.
        # Clipping only moves the start down, which keeps every kept
        # slot of the pixel window covered.
        w_rows_v = tuple(min(w, n_rows) for w in (
            geo.slot_windows(caps) if caps is not None else geo.w_pix_levels))
        pix_lo = jnp.asarray(geo.pix_lo.reshape(-1), jnp.int32)
        vstart = jax.vmap(lambda ki: jnp.searchsorted(ki, pix_lo))(keep_idx)
        vstart = vstart.reshape(b, n_t, n_l)
        hi = jnp.asarray([n_rows - wv for wv in w_rows_v], jnp.int32)
        vstart = jnp.clip(vstart, 0, hi[None, None, :]).astype(jnp.int32)
    else:
        w_rows_v = geo.w_pix_levels
        vstart = jnp.broadcast_to(jnp.asarray(geo.pstart, jnp.int32),
                                  (b, n_t, n_l))  # pixel == row space

    vg = v.reshape(b, n_rows, ng, g, dh).transpose(0, 2, 1, 3, 4)
    words, dh_p = stage_words(vg)                    # (B, NG, N_rows, W)
    n_w = words.shape[-1]
    tab = fold_rows(words)
    lanes = tab.shape[-1]
    fold = lanes // n_w
    ext, off = _window_plan(geo, w_rows_v, fold)
    n_f = tab.shape[2] + (-tab.shape[2]) % 8
    n_f = max(n_f, max(ext))
    tab = _pad_axis(tab, 2, n_f - tab.shape[2])
    # window starts in folded rows: 8-aligned, clipped into the table
    ext_a = jnp.asarray(ext, jnp.int32)
    fstart = jnp.minimum((vstart // fold) // 8 * 8, n_f - ext_a)

    # corner rows and weights outside the kernel, made window-local
    pack = lambda a, fill=0: repack_queries(geo, a, fill=fill)
    lvl = pack(lvl_of_pt, -1)                 # padding matches no level
    lvl_c = jnp.clip(lvl, 0, n_l - 1)
    level_arr = lambda vals: jnp.asarray(np.asarray(vals, np.int32))[lvl_c]
    idx, w = corner_operands(
        pack(x_px), pack(y_px), level_arr(geo.level_starts),
        level_arr([w_ for _, w_ in level_shapes]),
        level_arr([h_ for h_, _ in level_shapes]), pack(probs),
        remap)                                # (B, Nq_p, H, M)
    lvl4 = jnp.repeat(lvl, 4, axis=-1)
    lvl4c = jnp.clip(lvl4, 0, n_l - 1)
    tile_of = jnp.asarray(np.arange(geo.nq_padded) // geo.tile_q, np.int32)
    fs = fstart[jnp.arange(b)[:, None, None, None],
                tile_of[None, :, None, None], lvl4c]
    span = ext_a[lvl4c] * fold
    local = idx - fs * fold
    inside = (lvl4 >= 0) & (local >= 0) & (local < span)
    idx = jnp.asarray(off, jnp.int32)[lvl4c] * fold \
        + jnp.clip(local, 0, span - 1)
    w = jnp.where(inside, w, 0.0)

    tq = math.gcd(geo.tile_q, query_tile(geo.tile_q, g, m))
    n_s = geo.tile_q // tq

    def lay(a):      # (B, Nq_p, H, M) -> flat (B, NG, Nq_p, G, M)
        return a.reshape(b, geo.nq_padded, ng, g, m).transpose(
            0, 2, 1, 3, 4).reshape(-1)

    blk = tq * g * m
    n_sub = geo.nq_padded // tq

    def corner_block(bi, gi, ti, si, fs_ref):
        return ((bi * ng + gi) * n_sub + ti * n_s + si,)

    cspec = pl.BlockSpec((blk,), corner_block, memory_space=pltpu.SMEM)
    ospec = pl.BlockSpec(
        (1, 1, word_planes(v.dtype), tq, lanes),
        lambda bi, gi, ti, si, fs_ref: (bi, gi, 0, ti * n_s + si, 0))
    p = word_planes(v.dtype)

    def kernel(fs_ref, idx_ref, w_ref, tab_hbm, o_ref, win, sems):
        bi, gi, ti = pl.program_id(0), pl.program_id(1), pl.program_id(2)

        @pl.when(pl.program_id(3) == 0)
        def _stage():
            copies = []
            for l in range(n_l):
                r0 = pl.multiple_of(fs_ref[(bi * n_t + ti) * n_l + l], 8)
                cp = pltpu.make_async_copy(
                    tab_hbm.at[bi, gi, pl.ds(r0, ext[l])],
                    win.at[pl.ds(off[l], ext[l])], sems.at[l])
                cp.start()
                copies.append(cp)
            for cp in copies:
                cp.wait()

        accumulate_tile(idx_ref, w_ref, win, o_ref, tq=tq, head_pack=g,
                        n_corners=m, row_words=n_w, dtype=v.dtype)

    name = "msgs_windowed_msp" + ("_int8" if scale is not None else "")
    win_rows = sum(ext)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, ng, n_t, n_s),
            in_specs=[cspec, cspec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=ospec,
            scratch_shapes=[pltpu.VMEM((win_rows, lanes), jnp.uint32),
                            pltpu.SemaphoreType.DMA((n_l,))]),
        out_shape=jax.ShapeDtypeStruct((b, ng, p, geo.nq_padded, lanes),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(win_rows * lanes * 2,
                                        p * tq * lanes * 4)),
        interpret=interpret, name=name,
    )(fstart.reshape(-1), lay(idx), lay(w), tab)
    out = unplane(out[..., :n_w], g, dh_p, dh)      # (B, Nq_p, H, Dh)
    out = unpack_queries(geo, out)
    if scale is not None:
        # int8 codes aggregate exactly in f32; the per-channel scale is
        # shared by every row, so it multiplies once after aggregation
        s = scale.reshape(b, 1, h, dh).astype(jnp.float32)
        return (out * s).astype(probs.dtype)
    return out.astype(v.dtype)
