"""Shared sampling-point machinery for every MSDA backend.

One place computes, for each (batch, query, head, point):

  * the PAP-surviving attention probabilities and point indices,
  * the range-narrowed, fake-quantized offsets,
  * the per-point level geometry (flat start, width, height) and the
    absolute pixel coordinates in the point's own level.

Backends then only differ in HOW they gather + bilinearly combine the
value rows (``repro/msda/backends.py``); the distributed banded path
reuses ``select_points`` and applies its own band-local geometry.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fwp as fwp_lib
from repro.core import pap as pap_lib
from repro.core.quant import maybe_fake_quant


class SamplingPoints(NamedTuple):
    """Backend-agnostic sampling geometry. All point arrays (B, Nq, H, K)."""
    x_px: jnp.ndarray        # absolute pixel x in the point's own level
    y_px: jnp.ndarray
    start: jnp.ndarray       # int32 flat start of the point's level
    wl: jnp.ndarray          # int32 level width per point
    hl: jnp.ndarray          # int32 level height per point
    lvl_of_pt: jnp.ndarray   # int32 level index per point
    pix2slot: Optional[jnp.ndarray]   # (B, N_pix) FWP-compact indirection
    keep_idx: Optional[jnp.ndarray] = None   # (B, cap) slot -> pixel map,
    #   raster-ordered per level; the windowed kernel searchsorts it to
    #   locate the compact slot window of a pixel window (no densify)


def level_meta(level_shapes: Sequence[Tuple[int, int]]):
    """Static per-level int32 numpy arrays: flat starts, widths, heights;
    total N_in."""
    starts, n_in = fwp_lib.level_starts(level_shapes)
    ws = np.asarray([w for _, w in level_shapes], np.int32)
    hs = np.asarray([h for h, _ in level_shapes], np.int32)
    return starts, ws, hs, n_in


def corner_data(x_px, y_px, wl, hl, start):
    """Per-point corner indices/weights/validity in the flat fmap.

    x_px,y_px,wl,hl,start: (...,) arrays (wl/hl/start already per-point).
    Returns idx (..., 4) int32, wgt (..., 4), valid (..., 4)."""
    x0 = jnp.floor(x_px)
    y0 = jnp.floor(y_px)
    t1 = x_px - x0
    t0 = y_px - y0
    corners = []
    for dy in (0, 1):
        for dx in (0, 1):
            cx = x0 + dx
            cy = y0 + dy
            valid = ((cx >= 0) & (cx < wl) & (cy >= 0) & (cy < hl))
            cxc = jnp.clip(cx, 0, wl - 1).astype(jnp.int32)
            cyc = jnp.clip(cy, 0, hl - 1).astype(jnp.int32)
            idx = start + cyc * wl + cxc
            w = (t1 if dx else (1 - t1)) * (t0 if dy else (1 - t0))
            corners.append((idx, w, valid))
    idx = jnp.stack([c[0] for c in corners], axis=-1)
    wgt = jnp.stack([c[1] for c in corners], axis=-1)
    valid = jnp.stack([c[2] for c in corners], axis=-1)
    return idx, wgt, valid


def flat_gather_heads(v: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """v: (B, N, H, Dh); idx: (B, Nq, H, M) -> (B, Nq, H, M, Dh)."""
    b, n, h, dh = v.shape
    _, nq, _, m = idx.shape
    vv = v.transpose(0, 2, 1, 3).reshape(b * h, n, dh)
    ii = idx.transpose(0, 2, 1, 3).reshape(b * h, nq * m)
    g = jnp.take_along_axis(vv, ii[..., None], axis=1)
    return g.reshape(b, h, nq, m, dh).transpose(0, 2, 1, 3, 4)


class _PointLevels(NamedTuple):
    """Per-point lookups of per-level tables for one selection.

    ``lvl_of_pt`` is None when the selection keeps the whole L*P axis in
    its own order: point k then lies on level k // P, a static pattern,
    and a lookup is a broadcast constant rather than a gather (on the TPU
    an XLA gather of every point costs milliseconds)."""
    lvl_of_pt: Optional[jnp.ndarray]    # (B, Nq, H, K) int32, or None
    n_levels: int
    n_points: int
    shape: Tuple[int, ...]               # (B, Nq, H, K)

    def take(self, table, dtype=None) -> jnp.ndarray:
        """``table`` (L,) -> its entry for each point's level, (B,Nq,H,K)."""
        if len(table) != self.n_levels:
            raise ValueError(f"{len(table)} per-level values for a config "
                             f"of {self.n_levels} levels")
        if self.lvl_of_pt is None:
            per_point = np.repeat(np.asarray(table), self.n_points)
            return jnp.broadcast_to(jnp.asarray(per_point, dtype), self.shape)
        return jnp.take(jnp.asarray(table, dtype), self.lvl_of_pt)

    def index(self) -> jnp.ndarray:
        """Each point's level, (B, Nq, H, K) int32."""
        if self.lvl_of_pt is None:
            return self.take(np.arange(self.n_levels, dtype=np.int32))
        return self.lvl_of_pt


def _select_points(params: dict, cfg, query: jnp.ndarray):
    """:func:`select_points`, with the levels as a :class:`_PointLevels`."""
    b, nq, _ = query.shape
    h, p, lp = cfg.n_heads, cfg.n_points, cfg.n_lp
    wq = lambda w: maybe_fake_quant(w, cfg.weight_bits)
    identity = pap_lib.keeps_point_order(cfg.pap_mode)
    # trace-time event (process-wide registry): which path each traced
    # MSDA call took
    from repro.obs.metrics import default_registry
    default_registry().counter(
        "msda_point_select_traces_total",
        "select_points tracings by point path (identity | gather)"
    ).inc(path="identity" if identity else "gather")

    logits = jnp.einsum("bnd,dhk->bnhk", query, wq(params["attn_w"])) \
        + params["attn_b"]
    probs = jax.nn.softmax(logits, axis=-1)
    probs = maybe_fake_quant(probs, cfg.act_bits)
    sel = pap_lib.pap_select(probs, cfg.pap_mode,
                             threshold=cfg.pap_threshold, k=cfg.pap_keep)

    offs = jnp.einsum("bnd,dhk->bnhk", query, wq(params["offs_w"])) \
        + params["offs_b"]
    offs = offs.reshape(b, nq, h, lp, 2)
    if identity:
        offs_k = offs
        levels = _PointLevels(None, cfg.n_levels, p, sel.point_idx.shape)
    else:
        offs_k = jnp.take_along_axis(
            offs, sel.point_idx[..., None].astype(jnp.int32), axis=3)
        levels = _PointLevels((sel.point_idx // p).astype(jnp.int32),
                              cfg.n_levels, p, sel.point_idx.shape)
    if cfg.range_narrow is not None:
        bounds = levels.take(cfg.range_narrow, query.dtype)
        offs_k = jnp.clip(offs_k, -bounds[..., None], bounds[..., None])
    offs_k = maybe_fake_quant(offs_k, cfg.act_bits)     # INT12 BI datapath input
    return sel, offs_k, levels


def select_points(params: dict, cfg, query: jnp.ndarray):
    """PAP selection + masked offset generation (pre-geometry).

    Returns (sel: PAPSelection, offs_k (B,Nq,H,K,2) range-narrowed &
    quantized, lvl_of_pt (B,Nq,H,K) int32). Shared by the planned
    execution and the distributed banded path. Where the PAP mode keeps
    every point in order (``pap.keeps_point_order``), the offsets and
    levels come from the point axis's structure, with no gather."""
    sel, offs_k, levels = _select_points(params, cfg, query)
    return sel, offs_k, levels.index()


def generate_points(params: dict, cfg, query: jnp.ndarray,
                    ref_points: jnp.ndarray,
                    level_shapes: Sequence[Tuple[int, int]],
                    pix2slot: Optional[jnp.ndarray] = None,
                    keep_idx: Optional[jnp.ndarray] = None):
    """Full point generation: PAP + offsets + flat-level geometry.

    Returns (sel: PAPSelection, pts: SamplingPoints)."""
    starts, ws, hs, _ = level_meta(level_shapes)
    sel, offs_k, levels = _select_points(params, cfg, query)
    wl = levels.take(ws)
    hl = levels.take(hs)
    st = levels.take(starts)
    # float32 coordinates whatever the compute dtype: in bf16 a pixel
    # coordinate on a 128-wide level is off by up to half a pixel
    f32 = jnp.float32
    # (B, Nq, 1, 1, 2): a trailing integer index is then a slice, where
    # one mixed with new axes lowers to a gather
    refs = ref_points.astype(f32)[:, :, None, None, :]
    offs = offs_k.astype(f32)
    x_px = refs[..., 0] * wl.astype(f32) + offs[..., 0] - 0.5
    y_px = refs[..., 1] * hl.astype(f32) + offs[..., 1] - 0.5
    pts = SamplingPoints(x_px=x_px, y_px=y_px, start=st, wl=wl, hl=hl,
                         lvl_of_pt=levels.index(), pix2slot=pix2slot,
                         keep_idx=keep_idx)
    return sel, pts
