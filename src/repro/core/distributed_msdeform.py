"""Distributed MSDeformAttn: band sharding + bounded halo exchange (§Perf
hillclimb 3 — the beyond-paper scaling of DEFA's range-narrowing insight).

The paper's level-wise range-narrowing (C3) bounds every sampling offset to
±R_l pixels; on the ASIC that bounds the on-chip window (C7). At pod scale
the same bound turns distribution of the encoder from "all-gather the whole
multi-scale fmap" into a 2-neighbour halo exchange:

  * every model-axis rank owns one horizontal BAND of the image — the same
    normalized y-interval of every pyramid level (queries AND value rows);
  * the value projection V = X·W^V runs band-locally (1/TP of the pixels);
  * each rank ppermutes its top/bottom halo_l = ceil(R_l)+2 value rows to
    its neighbours — range-narrowing guarantees every bilinear corner of a
    band's queries lands inside band ± halo;
  * sampling + aggregation are then fully rank-local.

Per-layer communication: 2·Σ_l halo_l·W_l·D bytes (independent of image
height and batch-per-rank query count) versus Σ_l H_l·W_l·D for the
all-gather a naive query-sharded encoder needs.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.msdeform_attn import MSDeformAttnConfig
from repro.core.quant import maybe_fake_quant
from repro.msda.sampling import corner_data, select_points


def band_layout(level_shapes, n_bands: int, ranges):
    """Per-level padded band geometry: (rows_per_band_l, halo_l)."""
    rows, halos = [], []
    for li, (h, w) in enumerate(level_shapes):
        rb = int(np.ceil(h / n_bands))
        halos.append(int(np.ceil(ranges[li])) + 2)
        rows.append(rb)
    return rows, halos


def pad_levels_to_bands(x_flat, level_shapes, n_bands: int):
    """Pad each level's rows to n_bands*rows_per_band and re-flatten.

    x_flat: (B, N_in, D) -> (B, N_pad, D), plus padded level shapes."""
    b, _, d = x_flat.shape
    rows, _ = band_layout(level_shapes, n_bands, [0] * len(level_shapes))
    pieces, padded_shapes = [], []
    start = 0
    for (h, w), rb in zip(level_shapes, rows):
        seg = x_flat[:, start:start + h * w].reshape(b, h, w, d)
        hp = rb * n_bands
        seg = jnp.pad(seg, ((0, 0), (0, hp - h), (0, 0), (0, 0)))
        pieces.append(seg.reshape(b, hp * w, d))
        padded_shapes.append((hp, w))
        start += h * w
    return jnp.concatenate(pieces, axis=1), tuple(padded_shapes)


def _band_slices(padded_shapes, n_bands):
    """Flat index ranges of ONE band across levels (band-local layout)."""
    locs = []
    start = 0
    for (hp, w) in padded_shapes:
        rb = hp // n_bands
        locs.append((start, rb, w))
        start += rb * w
    return locs, start                 # per-level (band start, rows, W), band size


def msdeform_attn_banded(
    params: dict,
    cfg: MSDeformAttnConfig,
    query: jnp.ndarray,                 # (B, N_pad, D) — padded, band-ordered
    ref_points: jnp.ndarray,            # (B, N_pad, 2)
    x_flat: jnp.ndarray,                # (B, N_pad, D) padded pyramid
    padded_shapes: Sequence[Tuple[int, int]],
    mesh: Mesh,
    axis: str = "model",
    batch_axes: Tuple[str, ...] = (),
):
    """Band-sharded MSDeformAttn. Requires cfg.range_narrow set (the bound
    IS what makes the halo finite). Returns (B, N_pad, D).

    The flat layout here is BAND-MAJOR: for band r, its rows of level 0,
    then its rows of level 1, ... (callers reorder with band_reorder)."""
    assert cfg.range_narrow is not None, "halo exchange needs range-narrowing"
    n_bands = mesh.shape[axis]
    h, l, p_pts, dh = cfg.n_heads, cfg.n_levels, cfg.n_points, cfg.head_dim
    rows, halos = band_layout(
        [(hp, w) for hp, w in padded_shapes], 1, cfg.range_narrow)
    locs, band_n = _band_slices(padded_shapes, n_bands)

    def body(prm, q_b, ref_b, x_b):
        rank = jax.lax.axis_index(axis)
        b, nq_b, d = q_b.shape
        wq = lambda w_: maybe_fake_quant(w_, cfg.weight_bits)

        # --- band-local value projection (1/TP of the pixels) -------------
        v = jnp.einsum("bnd,dhk->bnhk", x_b, wq(prm["value_w"])) \
            + prm["value_b"]
        v = maybe_fake_quant(v, cfg.act_bits)

        # --- halo exchange per level (2-neighbour ppermute) ----------------
        up = [(i, (i - 1) % n_bands) for i in range(n_bands)]
        down = [(i, (i + 1) % n_bands) for i in range(n_bands)]
        v_locals = []                 # (window (B,rows,W,H,Dh), gathered?)
        for li, ((hp, w_l), (st, rb, _)) in enumerate(zip(padded_shapes, locs)):
            hal = int(np.ceil(cfg.range_narrow[li])) + 2
            seg = jax.lax.dynamic_slice_in_dim(v, st, rb * w_l, axis=1)
            seg = seg.reshape(b, rb, w_l, h, dh)
            if hal >= rb:
                # band thinner than the sampling radius: a 1-hop halo can't
                # cover it — replicate this (small) level via all-gather
                vfull = jax.lax.all_gather(seg, axis, axis=1, tiled=True)
                v_locals.append((vfull, True))
                continue
            top, bot = seg[:, :hal], seg[:, -hal:]
            # halo ABOVE band j = band j-1's BOTTOM rows (bottoms sent down);
            # halo BELOW band j = band j+1's TOP rows (tops sent up).
            from_above = jax.lax.ppermute(bot, axis, down)
            from_below = jax.lax.ppermute(top, axis, up)
            # first/last band: zero halo beyond the image (wrap is masked out
            # by the validity check, but zero it for exactness)
            from_above = jnp.where(rank == 0, 0.0, from_above)
            from_below = jnp.where(rank == n_bands - 1, 0.0, from_below)
            v_locals.append((jnp.concatenate(
                [from_above, seg, from_below], axis=1), False))

        # --- sampling-point generation (PAP-aware, shared with msda) -------
        sel, offs_k, lvl_of_pt = select_points(prm, cfg, q_b)

        # --- per-level local gather + Eq.4 BI + aggregation ----------------
        out_h = jnp.zeros((b, nq_b, h, dh), q_b.dtype)
        for li, ((hp, w_l), (st, rb, _)) in enumerate(zip(padded_shapes, locs)):
            hal = int(np.ceil(cfg.range_narrow[li])) + 2
            window, gathered = v_locals[li]
            vloc = window.reshape(b, -1, h, dh)              # rows*(W) flat
            n_rows_loc = window.shape[1]
            on_lvl = (lvl_of_pt == li)
            wl_f = jnp.asarray(w_l, q_b.dtype)
            hp_f = jnp.asarray(hp, q_b.dtype)
            x_px = ref_b[:, :, None, None, 0] * wl_f + offs_k[..., 0] - 0.5
            y_px = ref_b[:, :, None, None, 1] * hp_f + offs_k[..., 1] - 0.5
            # band-local row coordinates (halo offset added); gathered levels
            # use global coordinates directly
            if gathered:
                y_loc = y_px
            else:
                y_loc = y_px - rank * rb + hal
            ones = jnp.ones_like(lvl_of_pt)
            idx, wgt, valid = corner_data(
                x_px, y_loc, ones * w_l, ones * n_rows_loc,
                jnp.zeros_like(ones))
            # validity in GLOBAL image coords. Built as a stacked mask, not
            # per-corner .at[].set(): the boolean scatter miscompiles under
            # shard_map on multi-device CPU (silently corrupts one corner).
            yg = jnp.floor(y_px)
            extra = jnp.stack([((yg + dy) >= 0) & ((yg + dy) < hp)
                               for dy in (0, 0, 1, 1)], axis=-1)
            valid = valid & extra
            eff_w = wgt * valid.astype(wgt.dtype) \
                * (sel.probs * on_lvl.astype(wgt.dtype))[..., None]
            k_pts = idx.shape[3]
            vv = vloc.transpose(0, 2, 1, 3).reshape(b * h, -1, dh)
            ii = idx.transpose(0, 2, 1, 3, 4).reshape(b * h, -1)
            g = jnp.take_along_axis(vv, ii[..., None], axis=1, mode="clip")
            g = g.reshape(b, h, nq_b, k_pts, 4, dh).transpose(0, 2, 1, 3, 4, 5)
            out_h = out_h + jnp.sum(
                g * eff_w[..., None], axis=(3, 4)).astype(out_h.dtype)

        out = jnp.einsum("bnhk,hkd->bnd", out_h, wq(prm["out_w"])) \
            + prm["out_b"]
        return out

    bspec = (batch_axes if len(batch_axes) != 1 else batch_axes[0]) \
        if batch_axes else None
    in_specs = (P(), P(bspec, axis, None), P(bspec, axis, None),
                P(bspec, axis, None))
    out_specs = P(bspec, axis, None)
    fn = jax.shard_map(body, mesh=mesh, axis_names=set(mesh.axis_names),
                       in_specs=in_specs, out_specs=out_specs,
                       check_vma=False)
    return fn(params, query, ref_points, x_flat)


def band_reorder(flat_padded: jnp.ndarray, padded_shapes, n_bands: int):
    """Level-major padded layout -> band-major layout (and inverse perm)."""
    perm = []
    starts = np.concatenate(
        [[0], np.cumsum([hp * w for hp, w in padded_shapes])[:-1]])
    for r in range(n_bands):
        for (hp, w), st in zip(padded_shapes, starts):
            rb = hp // n_bands
            base = st + r * rb * w
            perm.extend(range(base, base + rb * w))
    perm = np.asarray(perm)
    inv = np.argsort(perm)
    return flat_padded[:, perm], perm, inv
